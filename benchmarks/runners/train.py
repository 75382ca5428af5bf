"""Runner ``train``: one training job through ``orion_tpu.launch.main``.

The job file (``traffic/<job>.json``) gives the algorithm and every
launch key; the configuration file gives the model's keys.  The runner
adds only ``seed=`` (from ``--seed``) and a horizon that never ends:
the job is stopped at the first iteration boundary after the window
through the program's own graceful path (a ``resilience.preemption``
request made from the prompt iterator), so no option enters the
program.

Iteration boundaries are stamped at ``next(prompt_iter)``: in steady
state iteration i ends where i+1 starts.  ``train_samples_per_s`` is
the samples of the whole iterations between the first and the last
boundary inside the window over the time between those two stamps.
"""

from __future__ import annotations

import math
import time
from typing import List


class _Recorder:
    """Wraps a jitted function; keeps the abstract signature of its
    first call so the program can be lowered again afterwards."""

    def __init__(self, fn):
        self.fn, self.spec = fn, None

    def __call__(self, *args, **kw):
        if self.spec is None:
            import jax

            self.spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                    if getattr(x, "committed", False) else None),
                (args, kw))
        return self.fn(*args, **kw)

    def lowered_text(self) -> str:
        args, kw = self.spec
        return self.fn.lower(*args, **kw).as_text()


class _WindowIter:
    """The prompt iterator with the run's clockwork on it: stamps every
    ``next`` (the start of an iteration), opens the window after the
    warm-up iterations, starts and stops the profiler over a few steady
    iterations of a traced run, and asks the program to stop at the
    first boundary after the window."""

    def __init__(self, it, run):
        self._it, self._run = it, run

    def __iter__(self):
        return self

    def __next__(self):
        self._run.boundary()
        return next(self._it)

    def __getattr__(self, name):   # state()/load_state() of the real one
        return getattr(self._it, name)


class _Run:
    def __init__(self, ctx, window, tracer, watch):
        job = ctx.traffic
        self.window, self.tracer, self.watch = window, tracer, watch
        self.warm = int(job["warmup_iterations"])
        self.trace_from = self.warm + int(job["trace_after_iterations"])
        self.trace_to = self.trace_from + int(job["trace_iterations"])
        self.marks: List[float] = []
        self.compiles_at_open = None
        self.compiles_at_close = None
        self.stop_requested = False

    def boundary(self) -> None:
        from orion_tpu.resilience.preemption import install_handler

        now = time.perf_counter()
        i = len(self.marks)
        self.marks.append(now)
        if i == self.warm:
            self.window.start = now
            self.compiles_at_open = self.watch.snapshot()
        if i == self.trace_from:
            self.tracer.start()
        elif i == self.trace_to:
            self.tracer.stop()
        if (self.window.start is not None and now >= self.window.end
                and not self.stop_requested
                and (not self.tracer.enabled or i >= self.trace_to)):
            self.compiles_at_close = self.watch.snapshot()
            self.stop_requested = True
            install_handler(register_signals=False).request()


def run(ctx) -> dict:
    from unittest import mock

    import jax
    import numpy as np

    import orion_tpu.orchestration as orchestration
    from orion_tpu import launch
    from orion_tpu.ops.pallas import interpret_mode
    from orion_tpu.resilience.preemption import clear_handler

    h = ctx.lib("harness")
    job = ctx.traffic
    if job.get("kind") != "train_job":
        raise h.BenchFailure(f"runner train needs a train_job, got "
                             f"{job.get('kind')!r}")
    window = h.Window(ctx.t_process_start, ctx.seconds)
    tracer = h.Tracer(ctx.trace, ctx.out_dir + "/trace")
    watch = h.CompileWatch()
    run_ = _Run(ctx, window, tracer, watch)
    kept: dict = {}
    real_build_trainer = launch.build_trainer
    real_prompt_iterator = launch.build_prompt_iterator

    def small_leaves(params):
        return [np.asarray(x) for x in jax.tree.leaves(params)
                if x.size <= 1 << 16]

    def build_trainer(algo, cfg, mesh, tokenizer):
        trainer = real_build_trainer(algo, cfg, mesh, tokenizer)
        trainer._jit_epochs = _Recorder(trainer._jit_epochs)
        # the benchmark's own host spans around the calls into the
        # trainer's phases (traced runs only), for labelling idle gaps
        for attr in ("make_experience", "update_epochs", "sync_weights",
                     "generate", "build_experience"):
            tracer.wrap(trainer, attr)
        kept.update(trainer=trainer, mesh=mesh,
                    before=small_leaves(trainer.state.params))
        return trainer

    def build_prompt_iterator(*a, **k):
        return _WindowIter(real_prompt_iterator(*a, **k), run_)

    class Orchestrator(orchestration.AsyncOrchestrator):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            kept["orch"] = self

    argv = [job["algo"], *ctx.config["launch"], *job["launch"],
            f"seed={h.seed31(ctx.seed)}", "total_iterations=1000000000"]
    t_launch = time.perf_counter()
    try:
        with mock.patch.object(launch, "build_trainer", build_trainer), \
                mock.patch.object(launch, "build_prompt_iterator",
                                  build_prompt_iterator), \
                mock.patch.object(orchestration, "AsyncOrchestrator",
                                  Orchestrator):
            hist = list(launch.main(argv))
        jax.block_until_ready(kept["trainer"].state.params)
    finally:
        tracer.stop()
        watch.close()
        clear_handler()
    t_done = time.perf_counter()
    trainer = kept["trainer"]
    if window.start is None or not run_.stop_requested:
        raise h.BenchFailure("the job ended before the window did")

    # -- the end-to-end metric -------------------------------------------
    inside = [t for t in run_.marks if window.start <= t <= window.end]
    n_iter = len(inside) - 1
    if n_iter < 1:
        raise h.BenchFailure(
            f"no whole iteration inside a window of {ctx.seconds}s")
    per_iter = int(job["samples_per_iteration"])
    if per_iter != trainer.cfg.rollout_batch_size * getattr(
            trainer.cfg, "group_size", 1):
        raise h.BenchFailure(
            f"samples_per_iteration={per_iter} is not what the job runs")
    samples_per_s = per_iter * n_iter / (inside[-1] - inside[0])

    # -- attempted / failed ----------------------------------------------
    first = run_.warm
    rows = hist[first:first + n_iter]
    failed = sum(not math.isfinite(float(r["loss"])) for r in rows) \
        + (n_iter - len(rows))

    # -- correct: invariants, then the reference -------------------------
    why = []
    if not all(math.isfinite(float(r["loss"])) for r in hist):
        why.append("non-finite loss")
    after = small_leaves(trainer.state.params)
    delta = max(float(np.max(np.abs(a - b)))
                for a, b in zip(after, kept["before"]))
    if not delta > 0.0:
        why.append("parameters did not move")
    in_window = watch.between(run_.compiles_at_open, run_.compiles_at_close)
    if in_window:
        why.append(f"compiled inside the window: {in_window}")
    with kept["mesh"]:
        update_text = trainer._jit_epochs.lowered_text()
    kernel_calls = update_text.count("tpu_custom_call")
    if ctx.require_kernels and kernel_calls < 1:
        why.append("no tpu_custom_call in the lowered update program")
    if ctx.require_kernels and interpret_mode():
        why.append("interpret_mode() is true")
    stale = [int(r["staleness"]) for r in hist if "staleness" in r]
    split = None
    if "orch" in kept:
        def devices_of(tree):
            out = set()
            for x in jax.tree.leaves(tree):
                out |= set(x.devices())
            return out

        train_devs = devices_of(trainer.state.params)
        roll_devs = devices_of(kept["orch"]._rollout_params)
        split = [sorted(d.id for d in roll_devs),
                 sorted(d.id for d in train_devs)]
        want = int(job.get("rollout_devices", 0))
        if train_devs & roll_devs or (want and len(roll_devs) != want):
            why.append(f"rollout and learner devices not disjoint: {split}")
        bound = int(job.get("max_staleness", 1))
        if not all(0 <= s <= bound for s in stale):
            why.append(f"staleness outside [0, {bound}]: {stale}")
    ref = ctx.lib(ctx.config["reference_check"]).check_trainer(ctx, trainer, kept["mesh"])
    if not ref["ok"]:
        why.append(f"reference disagreement: {ref}")

    counters = {
        "samples_per_iteration": per_iter,
        "iterations_in_window": n_iter,
        "prompt_len": int(job["prompt_len"]),
        "new_tokens": int(job["new_tokens"]),
        "num_epochs": int(job.get("num_epochs", 1)),
        "staleness": stale,
        "model": ctx.config,
        "chips": ctx.cell["chips"],
        "device_kind": ctx.device["kind"],
    }
    info = {
        "iterations_total": len(hist), "iterations_in_window": n_iter,
        "iteration_s_in_window": [round(b - a, 4) for a, b in
                                  zip(inside, inside[1:])],
        "rows_samples_per_sec": [round(float(r["samples_per_sec"]), 3)
                                 for r in rows],
        "loss_first_last": [float(hist[0]["loss"]), float(hist[-1]["loss"])],
        "param_delta": delta, "kernel_calls_in_update": kernel_calls,
        "compiles_before_window": sum(run_.compiles_at_open.values()),
        "compiles_in_window": in_window, "reference": ref,
        "device_split": split, "staleness": stale,
        "launch_to_done_s": round(t_done - t_launch, 2),
        "after_window_s": round(t_done - window.end, 2),
    }
    return {
        "correct": not why, "why_incorrect": why,
        "attempted": n_iter, "failed": failed, "window": window,
        "end_to_end": {"train_samples_per_s": samples_per_s},
        "counters": counters, "tracer": tracer, "info": info,
    }
