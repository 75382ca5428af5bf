"""Runner ``serve``: ``launch.run_serve`` on a thread of this process,
answered through ``GatewayClient``, with the load generator in front.

The mix file decides the loop:

``open``    arrivals on a fixed schedule (independent users).  Every
            latency is taken from the time a request was DUE, not from
            when it was sent, and how late the generator ran is printed.
``closed``  N callers, each sends its next request when its completion
            has returned (RL rollout clients of a generation service).

One driver thread sends and receives (plus the client's own receive
thread and the server's pump thread): load from one process with few
threads.  Before the gateway takes traffic the engine's prefill
programs are warmed on the serving thread itself (``warm_engine``), so
that nothing compiles in the window.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np


class _Rec:
    __slots__ = ("req", "due", "sent", "first", "last", "done", "chunks",
                 "final", "error", "measured", "counted")

    def __init__(self, req, due: float, measured: bool):
        self.req, self.due, self.measured = req, due, measured
        self.sent = self.first = self.last = self.done = None
        self.chunks: List[np.ndarray] = []
        self.final = None
        self.error = None
        self.counted = 0      # tokens of this request counted in window


def _pow2_buckets(lo: int, hi: int, cap: int) -> List[int]:
    """The engine's bucket values (next power of two, at most ``cap``)
    that spans from ``lo`` to ``hi`` can fall into."""
    out, b = set(), 1
    while b < lo:
        b *= 2
    while True:
        out.add(min(b, cap))
        if b >= hi or b >= cap:
            break
        b *= 2
    return sorted(out)


def warm_engine(engine, mix: dict, vocab: int, seed: int, gen) -> dict:
    """On the serving thread, before the gateway serves.  Counts as
    set-up.

    The engine compiles one prefill program per (wave-size bucket,
    prompt-span bucket) pair, and every pair the mix can reach is
    warmed (12-24 s each on the chip, PERF.md section 6: what a mix
    costs in set-up follows from how many pairs its lengths reach):

    1. the mix's shared prefixes go into the prefix cache, all in one
       wave (what a standing service has after its first minute);
    2. one wave of distinct random prompts for every wave-size bucket up
       to ``warm_max_wave`` x every span bucket the private parts of
       the prompts fall into, plus the decode segment."""
    rs = np.random.default_rng([int(seed), 0x3A93])
    slots, max_prompt = engine.slots, engine.cfg.max_prompt_len
    page = engine.cfg.page_size
    budget = int(mix.get("warm_budget", 2))
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    rid, t0 = 1 << 40, time.perf_counter()
    per_wave = []

    def drain(label):
        t = time.perf_counter()
        while engine.pending:
            engine.step()
        per_wave.append([*label, round(time.perf_counter() - t, 2)])

    prefixes = gen.shared_prefixes(mix, seed, vocab)
    if prefixes:
        for pre in prefixes:
            engine.submit(rid, np.concatenate(
                [pre, rs.integers(2, vocab, size=lo, dtype=np.int32)]),
                budget=budget)
            rid += 1
        drain(("prefixes", len(prefixes)))
        # a prefix that is not a whole number of pages leaves its tail
        # to be prefilled with the private part
        tail = len(prefixes[0]) % page
        lo, hi = lo + tail, hi + tail
    elif hi > page:
        lo = 1      # a finished twin's whole pages can shorten a span
    spans = _pow2_buckets(max(16, lo), min(hi, max_prompt), max_prompt)
    waves = _pow2_buckets(1, min(int(mix.get("warm_max_wave", slots)),
                                 slots), slots)
    for nb in waves:
        for span in spans:
            for _ in range(nb):
                engine.submit(rid, rs.integers(2, vocab, size=span,
                                               dtype=np.int32),
                              budget=budget)
                rid += 1
            drain((nb, span))
    return {"prefill_waves_warmed": len(per_wave), "per_wave_s": per_wave,
            "wave_buckets": waves, "span_buckets": spans,
            "engine_warm_s": round(time.perf_counter() - t0, 2)}


class _Driver:
    """Sends requests and takes their events, on one thread."""

    def __init__(self, ctx, client, engine, window, tracer, watch, mix):
        self.ctx, self.client, self.engine = ctx, client, engine
        self.window, self.tracer, self.watch, self.mix = \
            window, tracer, watch, mix
        self.recs: Dict[int, _Rec] = {}
        self.tokens_in_window = 0
        self.compiles_open = self.compiles_close = None
        self.stats_close: Optional[dict] = None
        self.completions = 0
        self.trace_after = float(mix.get("trace_after_seconds", 2.0))
        self.trace_seconds = float(mix.get("trace_seconds", 3.0))
        self.trace_tokens = 0

    # -- clockwork ---------------------------------------------------------
    def tick(self, now: float) -> None:
        w = self.window
        if w.start is None or now < w.start:
            return
        if self.compiles_open is None:
            self.compiles_open = self.watch.snapshot()
            # telemetry of the window only; in-flight marks survive
            self.engine.reset_server_stats()
        if self.tracer.enabled and self.tracer.t_stop is None:
            if not self.tracer.active and now >= w.start + self.trace_after:
                self.tracer.start()
            elif self.tracer.active and \
                    now >= self.tracer.t_start + self.trace_seconds:
                # in the background: this thread is the load generator
                self.tracer.stop(background=True)
        if now >= w.end and self.compiles_close is None:
            self.compiles_close = self.watch.snapshot()
            self.stats_close = dict(self.engine.server_stats())

    def send(self, req, due: float, measured: bool) -> _Rec:
        rec = _Rec(req, due, measured)
        rec.sent = time.perf_counter()
        rid = self.client.submit(req.prompt, budget=req.budget)
        self.recs[rid] = rec
        return rec

    def take(self, ev, t: float) -> Optional[_Rec]:
        """Book one stream event; returns the record when it is done."""
        rec = self.recs.get(ev.req_id)
        if rec is None:
            return None
        if ev.restarted:            # preempted: what was streamed is void
            rec.chunks = []
            self.tokens_in_window -= rec.counted
            rec.counted = 0
        if ev.tokens.size:
            rec.chunks.append(ev.tokens)
            if rec.first is None:
                rec.first = t
            rec.last = t
            if self.window.contains(t):
                self.tokens_in_window += int(ev.tokens.size)
                rec.counted += int(ev.tokens.size)
            if self.tracer.active:
                self.trace_tokens += int(ev.tokens.size)
        if ev.done:
            rec.done = t
            rec.final, rec.error = ev.completed, ev.error
            self.completions += 1
            return rec
        return None

    # -- the two loops -----------------------------------------------------
    def open_loop(self, reqs: list) -> None:
        mix, w = self.mix, self.window
        t0 = time.perf_counter() + 0.05
        w.start = t0 + float(mix.get("warm_seconds", 0.0))
        hard_stop = w.end + float(mix["drain_seconds"])
        i, out = 0, 0
        while True:
            now = time.perf_counter()
            self.tick(now)
            while i < len(reqs) and t0 + reqs[i].due_s <= now:
                self.send(reqs[i], t0 + reqs[i].due_s, reqs[i].measured)
                i += 1
                out += 1
            if (i == len(reqs) and out == 0 and now >= w.end) \
                    or now > hard_stop:
                break
            nxt = t0 + reqs[i].due_s if i < len(reqs) else now + 0.02
            with self.tracer.annotate("driver_wait"):
                ev = self.client.next_event(
                    timeout=min(0.02, max(0.0005, nxt - now)))
            if ev is not None and self.take(ev, time.perf_counter()):
                out -= 1
        self.tick(max(time.perf_counter(), w.end))

    def closed_loop(self, stream) -> None:
        mix, w = self.mix, self.window
        warm_completions = int(mix["warm_completions"])
        hard_stop = None
        out = 0
        for _ in range(int(mix["callers"])):
            self.send(next(stream), time.perf_counter(), False)
            out += 1
        while out:
            now = time.perf_counter()
            if w.start is None and self.completions >= warm_completions:
                w.start = now
                hard_stop = w.end + float(mix["drain_seconds"])
            self.tick(now)
            if hard_stop is not None and now > hard_stop:
                break
            with self.tracer.annotate("driver_wait"):
                ev = self.client.next_event(timeout=0.02)
            if ev is None:
                continue
            t = time.perf_counter()
            if self.take(ev, t) is None:
                continue
            out -= 1
            if w.start is None or t < w.end:
                # the caller's next request, at once
                self.send(next(stream), t, w.start is not None)
                out += 1
        self.tick(max(time.perf_counter(), w.end))


class Server:
    """``launch.run_serve`` on a thread, its engine warmed, a client
    connected.  ``stop()`` takes the server's own stop path."""

    def __init__(self, ctx, tracer):
        from orion_tpu import launch
        from orion_tpu.config import GRPOConfig, load_config
        from orion_tpu.orchestration.gateway import GatewayClient

        h = ctx.lib("harness")
        mix = ctx.traffic
        if mix.get("kind") != "requests":
            raise h.BenchFailure(f"runner serve needs a request mix, got "
                                 f"{mix.get('kind')!r}")
        self.cfg = load_config(GRPOConfig, cli_args=[
            *ctx.config["launch"], "rollout.engine=continuous",
            *mix["engine"], f"seed={h.seed31(ctx.seed)}"])
        self.vocab = int(self.cfg.model.vocab_size)
        self._stop, ready = threading.Event(), threading.Event()
        self.box: dict = {}
        self.waves: List[tuple] = []     # (requests, longest span) per wave

        def on_ready(gw):
            engine = gw.engines[0]
            self.box["warm"] = warm_engine(engine, mix, self.vocab, ctx.seed,
                                          ctx.lib("traffic_gen"))
            # the benchmark's own host spans around the calls into the
            # serving layers (traced runs only), for labelling idle gaps
            tracer.wrap(gw, "step", "gateway_pump")
            tracer.wrap(engine, "step", "engine_step")
            for attr in ("_prefill_wave", "_harvest_pending",
                         "_extend_running", "_emit_stream_chunks"):
                tracer.wrap(engine, attr)
            tracer.wrap(engine.sched, "admit", "sched_admit")
            # which (wave size, prompt span) pairs the traffic forms: a
            # pair outside the warmed buckets compiles inside the window
            real_activate = engine._activate

            def activate(entries, rng):
                self.waves.append((len(entries), max(
                    len(e["ids"]) - e["off"] for e in entries.values())))
                return real_activate(entries, rng)

            engine._activate = activate
            self.box["gw"] = gw
            ready.set()

        def serve():
            try:
                self.box["stats"] = launch.run_serve(
                    self.cfg, port=0, stop=self._stop, on_ready=on_ready)
            except BaseException as e:     # surfaced on the main thread
                self.box["error"] = e
            finally:
                ready.set()

        self.thread = threading.Thread(target=serve, name="bench-serve")
        t_build = time.perf_counter()
        self.thread.start()
        ready.wait()
        if "error" in self.box:
            self.thread.join()
            raise self.box["error"]
        self.gw = self.box["gw"]
        self.engine = self.gw.engines[0]
        self.warmed = self.box["warm"]
        h.note(phase="engine_ready", slots=self.engine.slots,
               pages=self.engine.num_pages,
               segment_len=self.engine.segment_len,
               build_and_warm_s=round(time.perf_counter() - t_build, 2),
               **self.box["warm"])
        self.client = GatewayClient(self.gw.port)

    def stop(self, h) -> dict:
        """Close the client, stop the server; its gateway's counters."""
        try:
            self.client.close()
        finally:
            self._stop.set()
            self.thread.join(timeout=120.0)
        if self.thread.is_alive():
            raise h.BenchFailure("the server's stop path did not return")
        if "error" in self.box:
            raise self.box["error"]
        stats = {k: v for k, v in (self.box.get("stats") or {}).items()
                 if isinstance(v, (int, float))}
        self.box.clear()
        self.gw = None
        return stats


def run(ctx) -> dict:
    import jax

    from orion_tpu.ops.pallas import interpret_mode

    h = ctx.lib("harness")
    gen = ctx.lib("traffic_gen")
    mix = ctx.traffic
    window = h.Window(ctx.t_process_start, ctx.seconds)
    tracer = h.Tracer(ctx.trace, ctx.out_dir + "/trace")
    watch = h.CompileWatch()
    server = None
    try:
        server = Server(ctx, tracer)
        engine, cfg, vocab = server.engine, server.cfg, server.vocab
        driver = _Driver(ctx, server.client, engine, window, tracer, watch,
                         mix)
        if mix["loop"] == "open":
            driver.open_loop(gen.open_schedule(mix, ctx.seed, ctx.seconds,
                                               vocab))
        elif mix["loop"] == "closed":
            driver.closed_loop(gen.closed_stream(mix, ctx.seed, vocab))
        else:
            raise h.BenchFailure(f"unknown loop {mix['loop']!r}")
    finally:
        tracer.stop()
        tracer.wait()
        try:
            gateway_stats = server.stop(h) if server is not None else {}
        finally:
            watch.close()

    # -- requests of the window --------------------------------------------
    eos = engine.eos
    measured = [r for r in driver.recs.values() if r.measured]
    why: List[str] = []
    failed = 0
    for r in measured:
        ok = r.final is not None and r.error is None
        if ok:
            toks = r.final.tokens
            ended = len(toks) == r.req.budget or \
                (eos is not None and len(toks) and toks[-1] == eos)
            got = np.concatenate(r.chunks) if r.chunks else toks[:0]
            if not ended:
                why.append("a request ended short of its budget without EOS")
                ok = False
            elif not np.array_equal(got, toks):
                why.append("streamed chunks differ from the final completion")
                ok = False
        if not ok:
            failed += 1
            driver.tokens_in_window -= r.counted   # counts no tokens
    if not measured:
        raise h.BenchFailure("no request fell into the window")

    # -- end-to-end metrics ------------------------------------------------
    miss_ms = 1e3 * (ctx.seconds + float(mix["drain_seconds"]))
    good = [r for r in measured if r.final is not None and r.error is None
            and r.first is not None]
    ttft = [1e3 * (r.first - r.due) for r in good]
    tpot = [1e3 * (r.last - r.first) / (len(r.final.tokens) - 1)
            for r in good if len(r.final.tokens) > 1]
    lag = [1e3 * (r.sent - r.due) for r in measured]
    end_to_end = {
        "gen_tokens_per_s": driver.tokens_in_window / ctx.seconds,
        "ttft_p95_ms": h.percentile(ttft + [miss_ms] * failed, 95),
        "tpot_p95_ms": h.percentile(tpot + [miss_ms] * failed, 95)
        if tpot or failed else miss_ms,
    }

    # -- correct: invariants, then the reference ---------------------------
    in_window = watch.between(driver.compiles_open or {},
                              driver.compiles_close or {})
    if in_window:
        why.append(f"compiled inside the window: {in_window}")
    with engine._ctx():
        decode_text = engine._jit_segment.lower(
            engine._params, engine._pools, jax.numpy.asarray(engine._bt),
            engine._state, engine._rng,
            n_steps=engine.segment_len).as_text()
    kernel_calls = decode_text.count("tpu_custom_call")
    if ctx.require_kernels and kernel_calls < 1:
        why.append("no tpu_custom_call in the lowered decode segment")
    if ctx.require_kernels and interpret_mode():
        why.append("interpret_mode() is true")
    # free the KV pool before the reference takes its float32 layers
    params = engine._params
    engine._pools = engine._state = None
    rs = np.random.RandomState(h.seed31(ctx.seed))
    cap = int(mix.get("reference_max_tokens", 2048))
    pool = [r for r in good if np.array_equal(
        np.concatenate(r.chunks), r.final.tokens)
        and len(r.req.prompt) + len(r.final.tokens) <= cap]
    picks = [pool[i] for i in rs.choice(len(pool), size=min(2, len(pool)),
                                        replace=False)] if pool else []
    ref = ctx.lib(ctx.config["reference_check"]).check_served(
        ctx, params, [(r.req.prompt, r.final.tokens, r.final.logprobs)
                      for r in picks], int8_kv=bool(cfg.rollout.quantize_kv))
    if not ref["ok"]:
        why.append(f"reference disagreement: {ref}")

    stats = driver.stats_close or {}
    prompt_tokens = sum(len(r.req.prompt) for r in measured)
    counters = {
        "server_stats": stats,
        "page_size": int(cfg.rollout.page_size),
        "slots": int(engine.slots), "segment_len": int(engine.segment_len),
        "prompt_tokens_measured": prompt_tokens,
        "trace_tokens": driver.trace_tokens,
        "requests_measured": len(measured),
    }
    info = {
        "requests_total": len(driver.recs), "measured": len(measured),
        "failed": failed, "tokens_in_window": driver.tokens_in_window,
        "send_lag_ms_median": h.percentile(lag, 50),
        "send_lag_ms_max": max(lag),
        "ttft_ms_p50": h.percentile(ttft, 50) if ttft else None,
        "tpot_ms_p50": h.percentile(tpot, 50) if tpot else None,
        "completed_per_s": len(good) / ctx.seconds,
        "offered_per_s": len(measured) / ctx.seconds,
        "all_end_to_end": end_to_end,
        "compiles_in_window": in_window,
        "prefill_waves": len(server.waves),
        "largest_wave": max((n for n, _ in server.waves), default=0),
        "waves_over_warmed": sum(
            n > max(server.warmed["wave_buckets"]) for n, _ in server.waves),
        "kernel_calls_in_decode": kernel_calls, "reference": ref,
        "gateway_stats": gateway_stats,
        "engine": {k: stats.get(k) for k in (
            "queue_wait_s_p95", "ttft_s_p95", "prefix_cached_pages",
            "preempted_requests", "shed_requests", "page_occupancy_mean",
            "page_occupancy_count", "requests_finished")},
    }
    return {
        "correct": not why, "why_incorrect": sorted(set(why)),
        "attempted": len(measured), "failed": failed, "window": window,
        "end_to_end": end_to_end, "counters": counters, "tracer": tracer,
        "info": info,
    }
