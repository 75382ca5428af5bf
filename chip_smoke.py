"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the full Pythia-1B shape (16 layers, random weights from a seed):

  1. trainer: ``orion_tpu.launch.main(["ppo", ...])`` at the ppo1b
     shape, the benchmark's ``ppo1b-sync`` job (shared backbone, remat,
     scanned layers, bf16 Adam moments, int8 rollout weights + KV,
     B=48, mb=16, P=256, T=128), one warm-up iteration + 3 steady ones;
  2. server: ``orion_tpu.launch.run_serve`` on a thread of the SAME
     process, answered through ``GatewayClient``: two passes (warm-up,
     steady) of 8 ragged streamed requests, two sharing a prefix.

and checks what came out (finite loss, params moved, every request
finished with its budget or EOS, the flash / paged-decode kernels are
IN the lowered programs, nothing compiled inside a steady window).

``--chips 4`` runs ONLY the cross-chip paths: the PPO update on an
fsdp=2 x tensor=2 mesh against the same seed on one of the four
devices, then the async rollout/learner device split.

One process touches JAX: this one.  It needs a TPU; without one it
exits non-zero and prints no result.  The numbers it prints are phase
walls of a smoke, NOT benchmark records.  Last stdout line:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import List, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Shape:
    """What a phase runs at.  ``FULL`` is the only shape ``main`` ever
    uses; the CPU rehearsal test passes its own tiny one."""
    model: List[str]                 # launch CLI keys selecting the model
    batch: int                       # rollout_batch_size
    minibatch: int
    prompt_len: int
    new_tokens: int
    serve_slots: int
    serve_page_size: int
    # (prompt_len, budget) per served request; requests 0 and 1 share
    # their first ``serve_shared`` prompt tokens.
    serve_requests: List[Tuple[int, int]]
    serve_shared: int
    # the kernels must be IN the programs (False only in the CPU
    # rehearsal, where Pallas runs interpreted / the XLA twin serves)
    require_kernels: bool = True


FULL = Shape(
    model=["model_preset=pythia_1b"],
    batch=48, minibatch=16, prompt_len=256, new_tokens=128,
    serve_slots=32, serve_page_size=64,
    serve_requests=[(320, 64), (384, 48), (32, 16), (512, 128),
                    (97, 33), (200, 100), (64, 128), (450, 20)],
    serve_shared=256)

# --chips 4 compares the first update of the sharded run with the same
# seed on one device.  The two runs share init, prompts and program but
# not reduction order (tensor-sharded bf16 matmuls, int8 rollout
# weights): sampled tokens flip, the completions diverge, and the two
# updates do NOT see the same batch.  So the comparison is statistical:
#
# - loss.  At random init it is ~ vf_coef * 0.5 * mean(GAE^2): the
#   policy term cancels over the minibatches (whitened advantages), and
#   GAE sums the random value head's TD errors over ~1/(1-lambda) = 20
#   tokens.  A mean of squares of 20-token-correlated sums over B*T =
#   6144 tokens has ~300 effective samples, i.e. a relative sampling
#   spread of sqrt(2/300) ~ 8%; first chip run: 11.9% apart.  3 sigma:
LOSS_RTOL = 0.25
# - entropy.  The mean policy entropy under the TRAINING graph barely
#   depends on which tokens were sampled (random init: ~log V each),
#   but a wrong head split, reduction or vocab-parallel softmax in the
#   sharded forward moves it at once.  This is the tight check:
ENTROPY_RTOL = 0.01
# Exact agreement of the sharded kernels is a CPU-mesh test matter
# (tests/test_pallas_flash.py, test_continuous_sharded.py); the chip
# run shows the absence of gross faults.


CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                "/jax/compilation_cache/cache_misses": 0}


def watch_jax() -> None:
    """Count persistent-cache hits and entries written (jax fires
    ``cache_misses`` where it writes one)."""
    import jax.monitoring

    def on_event(name, **kw):
        if name in CACHE_EVENTS:
            CACHE_EVENTS[name] += 1

    jax.monitoring.register_event_listener(on_event)


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def require_tpu(want_count: int) -> dict:
    """The device as JAX reports it, or exit non-zero.  The ONLY place
    the platform is judged (the CPU rehearsal steers this function)."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        fail(f"needs a TPU; jax found {dev}")
    if dev["count"] != want_count:
        fail(f"needs {want_count} chip(s); jax found {dev}")
    return dev


def device_memory(key: str) -> List[int]:
    """``memory_stats()[key]`` of every device (0 where unreported)."""
    import jax

    return [int((d.memory_stats() or {}).get(key, 0))
            for d in jax.devices()]


def compiled_since(sentinel, before: dict) -> dict:
    """{function: compiles} the sentinel saw after ``before`` (a copy
    of its counts)."""
    return {k: n - before.get(k, 0) for k, n in sentinel.counts.items()
            if n > before.get(k, 0)}


class _Recorder:
    """Wraps a jitted function; keeps the abstract signature of its
    first call so the program can be lowered again afterwards."""

    def __init__(self, fn):
        self.fn, self.spec = fn, None

    def __call__(self, *args, **kw):
        if self.spec is None:
            import jax

            # host arrays and uncommitted ones (a plain device_put)
            # follow the committed arguments
            self.spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                    if getattr(x, "committed", False) else None),
                (args, kw))
        return self.fn(*args, **kw)

    def lowered_text(self) -> str:
        args, kw = self.spec
        return self.fn.lower(*args, **kw).as_text()


class _MarkedIter:
    """Prompt iterator that stamps (clock, compiles so far) at every
    ``next`` — i.e. at the start of every training iteration."""

    def __init__(self, it, sentinel, marks):
        self._it, self._sentinel, self._marks = it, sentinel, marks

    def __iter__(self):
        return self

    def __next__(self):
        self._marks.append((time.perf_counter(),
                            dict(self._sentinel.counts)))
        return next(self._it)

    def __getattr__(self, name):  # state()/load_state() for checkpoints
        return getattr(self._it, name)


def trainer_args(shape: Shape, iterations: int, extra=()) -> List[str]:
    seq = shape.prompt_len + shape.new_tokens
    return ["ppo", *shape.model,
            f"model.max_seq_len={1 << (seq - 1).bit_length()}",
            "model.remat=true", "model.scan_layers=true",
            "share_backbone=true", "ref_param_dtype=bfloat16",
            "optimizer.learning_rate=1e-6",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"rollout.max_prompt_len={shape.prompt_len}",
            f"rollout.max_new_tokens={shape.new_tokens}",
            "rollout.quantize_weights=true", "rollout.quantize_kv=true",
            "rollout.temperature=1.0",
            f"rollout_batch_size={shape.batch}",
            f"minibatch_size={shape.minibatch}",
            "num_epochs=1", "kl_coef=0.05",
            "data.dataset=synthetic", "reward=length", "seed=0",
            f"total_iterations={iterations}", *extra]


def run_launch(argv: List[str], n_devices: int = 0) -> dict:
    """``launch.main(argv)`` with the smoke's instruments attached: the
    trainer (and async orchestrator) it builds are kept for inspection,
    the update program's signature is recorded, iteration starts are
    stamped.  ``n_devices`` > 0 builds the mesh on the first n devices
    instead of all of them.  Returns everything the checks need; the
    caller drops it (``release``) before the next phase needs the
    memory."""
    from unittest import mock

    import jax
    import numpy as np

    import orion_tpu.orchestration as orchestration
    from orion_tpu import launch
    from orion_tpu.analysis.runtime_guards import RecompileSentinel

    sentinel = RecompileSentinel(budget=10 ** 9).install()
    marks: list = []
    kept: dict = {}
    real_build_trainer = launch.build_trainer
    real_prompt_iterator = launch.build_prompt_iterator
    real_make_mesh = launch.make_mesh
    real_split_devices = orchestration.split_devices

    def small_leaves(params):
        return [np.asarray(x) for x in jax.tree.leaves(params)
                if x.size <= 1 << 16]

    def build_trainer(algo, cfg, mesh, tokenizer):
        trainer = real_build_trainer(algo, cfg, mesh, tokenizer)
        trainer._jit_epochs = _Recorder(trainer._jit_epochs)
        kept.update(trainer=trainer, mesh=mesh,
                    before=small_leaves(trainer.state.params))
        return trainer

    def build_prompt_iterator(*a, **k):
        return _MarkedIter(real_prompt_iterator(*a, **k), sentinel, marks)

    def make_mesh(cfg, devices=None):
        if devices is None and n_devices:
            devices = jax.devices()[:n_devices]
        return real_make_mesh(cfg, devices=devices)

    def split_devices(devices, n_rollout):
        return real_split_devices(devices[:n_devices or None], n_rollout)

    class Orchestrator(orchestration.AsyncOrchestrator):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            kept["orch"] = self

    t0 = time.perf_counter()
    try:
        with mock.patch.object(launch, "build_trainer", build_trainer), \
                mock.patch.object(launch, "build_prompt_iterator",
                                  build_prompt_iterator), \
                mock.patch.object(launch, "make_mesh", make_mesh), \
                mock.patch.object(orchestration, "AsyncOrchestrator",
                                  Orchestrator), \
                mock.patch.object(orchestration, "split_devices",
                                  split_devices):
            hist = launch.main(argv)
        jax.block_until_ready(kept["trainer"].state.params)
    finally:
        sentinel.uninstall()
    t1 = time.perf_counter()
    trainer = kept["trainer"]
    after = small_leaves(trainer.state.params)
    delta = max(float(np.max(np.abs(a - b)))
                for a, b in zip(after, kept["before"]))
    with kept["mesh"]:
        update_text = trainer._jit_epochs.lowered_text()
    steady_from = marks[1] if len(marks) > 1 else (t1, sentinel.counts)
    steady = compiled_since(sentinel, steady_from[1])
    return {
        "hist": list(hist), "kept": kept, "param_delta": delta,
        "flash_calls_in_update": update_text.count("tpu_custom_call"),
        "warmup_s": steady_from[0] - t0, "steady_s": t1 - steady_from[0],
        "compiles_warmup": sum(steady_from[1].values()),
        "compiles_steady": steady,
    }


def release(run: dict) -> None:
    """Drop a finished phase's device buffers and programs."""
    import jax

    run.pop("kept", None)
    gc.collect()
    jax.clear_caches()
    gc.collect()


def check_training(run: dict, iterations: int, shape: Shape,
                   name: str) -> dict:
    hist = run["hist"]
    if len(hist) != iterations:
        fail(f"{name}: {len(hist)} metric rows for {iterations} iterations")
    losses = [float(h["loss"]) for h in hist]
    if not all(math.isfinite(x) for x in losses):
        fail(f"{name}: non-finite loss {losses}")
    if not run["param_delta"] > 0.0:
        fail(f"{name}: params unchanged after {iterations} updates")
    if shape.require_kernels and run["flash_calls_in_update"] < 1:
        fail(f"{name}: no tpu_custom_call in the lowered update — the "
             "flash kernel is not in the program")
    if run["compiles_steady"]:
        fail(f"{name}: compiles inside the steady window: "
             f"{run['compiles_steady']}")
    return {
        "phase": name, "iterations": iterations, "loss": losses,
        "param_delta": run["param_delta"],
        "flash_calls_in_update": run["flash_calls_in_update"],
        "warmup_and_compile_s": round(run["warmup_s"], 2),
        "steady_s": round(run["steady_s"], 2),
        # each row's account of its wall (trainers/base.py,
        # _finalize_iteration)
        **{f"rows_{k}": [round(h[k], 4) for h in hist]
           for k in ("iter_s", "fetch_wait_s", "fetch_copy_s",
                     "host_cpu_s", "host_gc_s")},
        "compiles_warmup": run["compiles_warmup"],
        "compiles_steady": run["compiles_steady"],
        "peak_hbm_bytes": device_memory("peak_bytes_in_use"),
        "note": "smoke walls, not a benchmark",
    }


# ---------------------------------------------------------------------------
# phase 1: trainer
# ---------------------------------------------------------------------------

def phase_trainer(shape: Shape) -> None:
    iterations = 4  # 1 warm-up + 3 steady
    run = run_launch(trainer_args(shape, iterations))
    emit(**check_training(run, iterations, shape, "trainer"))
    release(run)


# ---------------------------------------------------------------------------
# phase 2: server
# ---------------------------------------------------------------------------

def serve_pass(client, shape: Shape, vocab: int, seed: int, eos) -> dict:
    """Submit the request mix one by one — each after the previous one
    produced its first chunk, so every admission wave holds exactly one
    new request and both passes compile the same prefill programs —
    then drain.  Request 0 is awaited to its END before request 1 (its
    prefix twin) goes in: prompt pages graduate into the prefix cache
    when their request finishes.  Returns per-request facts."""
    import numpy as np

    rs = np.random.RandomState(seed)
    shared = rs.randint(2, vocab, shape.serve_shared).astype(np.int32)
    budgets, chunks, finals, started = {}, {}, {}, set()

    def pump(until, timeout=600.0):
        deadline = time.monotonic() + timeout
        while not until():
            if time.monotonic() > deadline:
                fail("server: timed out waiting for stream events")
            ev = client.next_event(timeout=1.0)
            if ev is None:
                continue
            if ev.restarted:
                chunks[ev.req_id] = []
            if ev.tokens.size:
                chunks.setdefault(ev.req_id, []).append(ev.tokens)
                started.add(ev.req_id)
            if ev.done:
                started.add(ev.req_id)
                finals[ev.req_id] = ev

    for i, (plen, budget) in enumerate(shape.serve_requests):
        ids = rs.randint(2, vocab, plen).astype(np.int32)
        if i < 2:
            ids[:shape.serve_shared] = shared
        rid = client.submit(ids, budget=budget)
        budgets[rid] = budget
        pump(lambda: rid in (finals if i == 0 else started))
    pump(lambda: len(finals) == len(budgets))

    multi_chunk = 0
    for rid, ev in finals.items():
        if ev.error is not None:
            fail(f"server: request {rid} ended with error {ev.error!r}")
        toks = ev.completed.tokens
        ended = len(toks) == budgets[rid] or \
            (eos is not None and len(toks) and toks[-1] == eos)
        if not ended:
            fail(f"server: request {rid} returned {len(toks)} tokens of "
                 f"a budget of {budgets[rid]} without EOS")
        got = np.concatenate(chunks.get(rid) or [toks[:0]])
        if not np.array_equal(got, toks):
            fail(f"server: request {rid}'s streamed chunks differ from "
                 "its final completion")
        multi_chunk += len(chunks.get(rid, ())) > 1
    if multi_chunk < 1:
        fail("server: no request was delivered in more than one chunk")
    return {"completed": len(finals),
            "tokens": int(sum(len(e.completed.tokens)
                              for e in finals.values()))}


def phase_server(shape: Shape) -> None:
    import jax

    from orion_tpu import launch
    from orion_tpu.analysis.runtime_guards import RecompileSentinel
    from orion_tpu.config import GRPOConfig, load_config
    from orion_tpu.ops.pallas import interpret_mode
    from orion_tpu.orchestration.gateway import GatewayClient

    max_prompt = max(p for p, _ in shape.serve_requests)
    max_budget = max(b for _, b in shape.serve_requests)
    cfg = load_config(GRPOConfig, cli_args=[
        *shape.model, "rollout.engine=continuous",
        f"rollout.max_batch_size={shape.serve_slots}",
        f"rollout.page_size={shape.serve_page_size}",
        f"rollout.max_prompt_len={max_prompt}",
        f"rollout.max_new_tokens={max_budget}",
        "rollout.quantize_weights=true", "rollout.quantize_kv=true",
        "rollout.temperature=1.0", "seed=0"])
    sentinel = RecompileSentinel(budget=10 ** 9).install()
    stop, ready, box = threading.Event(), threading.Event(), {}

    def serve():
        try:
            box["stats"] = launch.run_serve(
                cfg, port=0, stop=stop,
                on_ready=lambda gw: (box.update(gw=gw), ready.set()))
        except BaseException as e:  # surfaced by the main thread below
            box["error"] = e
        finally:
            ready.set()

    t0 = time.perf_counter()
    th = threading.Thread(target=serve, name="chip-smoke-serve")
    th.start()
    try:
        ready.wait()
        if "error" in box:
            raise box["error"]
        gw = box["gw"]
        engine = gw.engines[0]
        client = GatewayClient(gw.port)
        eos = engine.eos
        warm = serve_pass(client, shape, cfg.model.vocab_size, 1, eos)
        t1 = time.perf_counter()
        compiles_warm = dict(sentinel.counts)
        steady = serve_pass(client, shape, cfg.model.vocab_size, 2, eos)
        t2 = time.perf_counter()
        compiles_steady = compiled_since(sentinel, compiles_warm)
        client.close()
    finally:
        stop.set()
        th.join(timeout=120.0)
        sentinel.uninstall()
    if th.is_alive():
        fail("server: the stop path did not drain and return")
    if "error" in box:
        raise box["error"]
    t3 = time.perf_counter()

    stats = engine.server_stats()
    if not stats["prefix_cached_pages"] > 0:
        fail("server: prefix_cached_pages == 0 though two requests "
             "shared a prefix")
    # The decode program the engine ran, lowered again from its own
    # live arguments (lowering donates nothing).
    with engine._ctx():
        decode_text = engine._jit_segment.lower(
            engine._params, engine._pools, jax.numpy.asarray(engine._bt),
            engine._state, engine._rng,
            n_steps=engine.segment_len).as_text()
    paged_calls = decode_text.count("tpu_custom_call")
    if shape.require_kernels and paged_calls < 1:
        fail("server: no tpu_custom_call in the lowered decode segment — "
             "the paged decode kernel is not in the program")
    if shape.require_kernels and interpret_mode():
        fail("interpret_mode() is true on the chip")
    if compiles_steady:
        fail(f"server: compiles inside the steady pass: {compiles_steady}")
    emit(phase="server", requests_completed=warm["completed"]
         + steady["completed"], tokens_out=warm["tokens"]
         + steady["tokens"],
         prefix_cached_pages=stats["prefix_cached_pages"],
         paged_calls_in_decode=paged_calls,
         scheduler=scheduler_name(engine.sched),
         interpret_mode=interpret_mode(),
         harvest_lag=engine._harvest_lag,
         slots=engine.slots, pages=engine.num_pages,
         build_and_warmup_s=round(t1 - t0, 2),
         steady_pass_s=round(t2 - t1, 2), drain_s=round(t3 - t2, 2),
         compiles_warmup=sum(compiles_warm.values()),
         compiles_steady=compiles_steady,
         gateway_stats={k: v for k, v in (box.get("stats") or {}).items()
                        if isinstance(v, (int, float))},
         peak_hbm_bytes=device_memory("peak_bytes_in_use"),
         note="smoke walls, not a benchmark")
    box.clear()
    del engine, gw
    release({})


def scheduler_name(sched) -> str:
    return ("PyScheduler" if type(sched).__name__ == "PyScheduler"
            else "native")


# ---------------------------------------------------------------------------
# --chips 4: the cross-chip paths only
# ---------------------------------------------------------------------------

def scalars(row: dict) -> dict:
    """The float stats of one metrics row."""
    return {k: float(v) for k, v in row.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def check_spread(name: str) -> List[int]:
    """Every device holds something and none holds most of it — code
    that has only seen fake devices may put everything on device 0."""
    used = device_memory("bytes_in_use")
    if min(used) <= 0:
        fail(f"{name}: a device holds nothing: bytes_in_use={used}")
    if max(used) > 0.5 * sum(used):
        fail(f"{name}: one device holds more than half: "
             f"bytes_in_use={used}")
    return used


def phase_four_chip(shape: Shape) -> None:
    mesh = ["mesh.data=1", "mesh.fsdp=2", "mesh.tensor=2"]
    # (a) FSDP x TP sharded PPO, 2 iterations
    run = run_launch(trainer_args(shape, 2, mesh), n_devices=4)
    row = check_training(run, 2, shape, "sharded_fsdp2_tp2")
    row["bytes_in_use"] = check_spread("sharded_fsdp2_tp2")
    first_sharded = scalars(run["hist"][0])
    emit(**row)
    release(run)

    # (b) the same seed and batch on ONE of the four devices
    one = ["mesh.data=1", "mesh.fsdp=1", "mesh.tensor=1"]
    run = run_launch(trainer_args(shape, 1, one), n_devices=1)
    hist = run["hist"]
    if len(hist) != 1 or not math.isfinite(float(hist[0]["loss"])):
        fail(f"single_device: bad history {hist}")
    first_single = scalars(hist[0])
    release(run)

    def rel(key):
        a, b = first_sharded[key], first_single[key]
        return abs(a - b) / max(abs(b), 1e-12)

    emit(phase="sharded_vs_single", rel_diff=rel("loss"), rtol=LOSS_RTOL,
         entropy_rel_diff=rel("entropy"), entropy_rtol=ENTROPY_RTOL,
         first_update_sharded=first_sharded,
         first_update_single=first_single)
    if not rel("loss") <= LOSS_RTOL:
        fail(f"first-update loss differs: sharded {first_sharded['loss']} "
             f"vs single {first_single['loss']} (rel {rel('loss'):.3g} > "
             f"{LOSS_RTOL})")
    if not rel("entropy") <= ENTROPY_RTOL:
        fail(f"first-update entropy differs: sharded "
             f"{first_sharded['entropy']} vs single "
             f"{first_single['entropy']} (rel {rel('entropy'):.3g} > "
             f"{ENTROPY_RTOL})")

    # (c) async rollout/learner split: 2 rollout + 2 learner devices
    run = run_launch(trainer_args(
        shape, 3, ["async_mode=true", "rollout_devices=2",
                   "async_staleness=1"]), n_devices=4)
    hist, kept = run["hist"], run["kept"]
    stale = [int(h["staleness"]) for h in hist]
    if len(hist) != 3 or not all(0 <= s <= 1 for s in stale):
        fail(f"async: staleness {stale} outside [0, 1] over "
             f"{len(hist)} iterations")
    if not all(math.isfinite(float(h["loss"])) for h in hist):
        fail(f"async: non-finite loss in {hist}")
    import jax

    def devices_of(tree):
        out = set()
        for x in jax.tree.leaves(tree):
            out |= set(x.devices())
        return out

    train_devs = devices_of(kept["trainer"].state.params)
    roll_devs = devices_of(kept["orch"]._rollout_params)
    if train_devs & roll_devs or len(train_devs) != 2 or \
            len(roll_devs) != 2:
        fail(f"async: rollout {sorted(map(str, roll_devs))} and learner "
             f"{sorted(map(str, train_devs))} device sets are not a "
             "disjoint 2 + 2 split")
    emit(phase="async_split", iterations=3, staleness=stale,
         loss=[float(h["loss"]) for h in hist],
         rollout_devices=sorted(d.id for d in roll_devs),
         learner_devices=sorted(d.id for d in train_devs),
         wall_s=round(run["warmup_s"] + run["steady_s"], 2),
         peak_hbm_bytes=device_memory("peak_bytes_in_use"),
         note="smoke walls, not a benchmark")
    release(run)


# ---------------------------------------------------------------------------

def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def prepare() -> dict:
    """Before JAX is touched: build the native scheduler from source as
    git has it (a stale .so/.fail must not travel with the tree), and
    place the compile cache."""
    shutil.rmtree(os.path.join(REPO, "orion_tpu", "runtime", "native",
                               "_build"), ignore_errors=True)
    from orion_tpu.runtime import scheduler
    from orion_tpu.utils.platform import enable_compile_cache

    native = scheduler.native_available()
    if not native:
        print("[chip_smoke] WARNING: the native scheduler did not build "
              f"({scheduler.last_build_error}); PyScheduler will serve",
              file=sys.stderr, flush=True)
    cache_dir = enable_compile_cache()
    return {"native_scheduler_built": native,
            "native_build_error": scheduler.last_build_error,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": cache_entries(cache_dir)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    setup = prepare()
    watch_jax()
    dev = require_tpu(args.chips)
    emit(phase="setup", device=dev, **setup)
    if args.chips == 4:
        phase_four_chip(FULL)
    else:
        phase_trainer(FULL)
        phase_server(FULL)
    emit(phase="done", total_s=round(time.perf_counter() - t0, 2),
         compile_cache_hits=CACHE_EVENTS["/jax/compilation_cache/cache_hits"],
         compile_cache_misses=CACHE_EVENTS[
             "/jax/compilation_cache/cache_misses"],
         compile_cache_entries_at_end=cache_entries(
             setup["compile_cache_dir"]))
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
