"""Find the knee of an open-loop cell once, by one sweep in one process.

    python3 benchmarks/sweep.py --workload <cell> --start 4 --steps 8 \
        [--factor 1.25] [--seconds 20] [--seed 1]

Takes the cell's context from ``run.py`` and its server from the
``serve`` runner (same configuration, same engine keys, same warm-up),
then offers the cell's own mix at rising rates, ``--factor`` apart: at
each rate the mix's ``warm_seconds`` of traffic, then ``--seconds``
measured, then the drain.  The knee is the highest rate whose completed requests per second
(completions inside the window) stay at or above 0.95 of the offered
rate (requests due inside the window).  The cell's rate, written by hand
into its traffic file, is 0.8 x the knee rounded to two figures.

Not part of a run of the benchmark: it prints a table, one JSON line per
rate, and the knee as the last line.  Needs the TPU, as ``run.py`` does.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as bench  # noqa: E402


def main(argv=None, rehearsal=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    h, gen = bench.lib("harness"), bench.lib("traffic_gen")
    ctx, serve = bench.context(args.workload, args.seed, args.seconds,
                               False, T0, manifest={}, rehearsal=rehearsal)
    mix = ctx.traffic
    if mix.get("loop") != "open":
        raise h.BenchFailure("only an open-loop mix has a knee")
    tracer = h.Tracer(False, "")
    watch = h.CompileWatch()
    server = serve.Server(ctx, tracer)
    knee, rate, misses = None, args.start, 0
    try:
        for _ in range(args.steps):
            at = dict(mix, rate_per_s=rate)
            window = h.Window(T0, args.seconds)
            driver = serve._Driver(ctx, server.client, server.engine,
                                   window, tracer, watch, at)
            driver.open_loop(gen.open_schedule(at, args.seed, args.seconds,
                                               server.vocab))
            recs = list(driver.recs.values())
            due = [r for r in recs if r.measured]
            done_in = [r for r in recs if r.done is not None
                       and r.error is None and window.contains(r.done)]
            good = [r for r in due if r.final is not None and r.first]
            ttft = [1e3 * (r.first - r.due) for r in good]
            tpot = [1e3 * (r.last - r.first) / (len(r.final.tokens) - 1)
                    for r in good if len(r.final.tokens) > 1]
            offered = len(due) / args.seconds
            completed = len(done_in) / args.seconds
            sustained = completed >= 0.95 * offered
            compiled = watch.between(driver.compiles_open or {},
                                     driver.compiles_close or {})
            h.note(rate_per_s=rate, offered_per_s=offered,
                   completed_per_s=completed, sustained=sustained,
                   unfinished=len(due) - len(good),
                   tokens_per_s=driver.tokens_in_window / args.seconds,
                   ttft_p50_ms=h.percentile(ttft, 50) if ttft else None,
                   ttft_p95_ms=h.percentile(ttft, 95) if ttft else None,
                   tpot_p50_ms=h.percentile(tpot, 50) if tpot else None,
                   tpot_p95_ms=h.percentile(tpot, 95) if tpot else None,
                   send_lag_ms_max=max(1e3 * (r.sent - r.due) for r in due),
                   queue_wait_p95_ms=1e3 * (driver.stats_close or {}).get(
                       "queue_wait_s_p95", 0.0),
                   preempted=(driver.stats_close or {}).get(
                       "preempted_requests"),
                   compiled_in_window=compiled,
                   memory_peak_bytes=h.memory_peak_bytes())
            if sustained:
                knee, misses = rate, 0
            else:
                misses += 1
                if misses >= 2:       # two rates in a row fell behind
                    break
            rate *= args.factor
    finally:
        server.stop(h)
        watch.close()
    h.note(knee_per_s=knee, cell_rate_per_s=None if knee is None
           else float(f"{0.8 * knee:.2g}"))


if __name__ == "__main__":
    main()
