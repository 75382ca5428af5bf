"""What both runners share: the device check, the compile cache, the
compile counter, device memory, percentiles, and the lines printed
before the result.

Nothing here names a cell, a configuration, a mix, a runner or a
per-layer metric.  Copied in spirit from ``chip_smoke.py`` (the smoke
stays as it is; the benchmark imports nothing from it).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                "/jax/compilation_cache/cache_misses": 0}


class BenchFailure(SystemExit):
    """Exit non-zero with no result line."""

    def __init__(self, msg: str):
        print(f"[bench] FAILED: {msg}", file=sys.stderr, flush=True)
        super().__init__(1)


def note(**row) -> None:
    """One JSON line of side information, printed BEFORE the result
    line (sweep tables, walls, cache hits): never the last line."""
    print(json.dumps(row, default=float), flush=True)


def seed31(seed: int) -> int:
    """The program's ``seed=`` key feeds ``jax.random.key`` and
    ``numpy.random.RandomState``; fold any whole number into 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def prepare() -> dict:
    """Before JAX is touched: the native scheduler is built once into
    the checkout (not rebuilt by each run) and the compile cache is
    placed where ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed
    ``<checkout>/.jax_cache``."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from orion_tpu.runtime import scheduler
    from orion_tpu.utils.platform import enable_compile_cache

    native = scheduler.native_available()
    cache_dir = enable_compile_cache()
    n = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    return {"native_scheduler": bool(native), "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": n}


def watch_jax() -> None:
    """Count persistent-cache hits and misses; keep the per-compile
    chatter of ``jax_log_compiles`` off stderr."""
    import logging

    import jax.monitoring

    def on_event(name, **kw):
        if name in CACHE_EVENTS:
            CACHE_EVENTS[name] += 1

    jax.monitoring.register_event_listener(on_event)

    class NoCompileChatter(logging.Filter):
        def filter(self, record):
            return not record.getMessage().startswith(
                ("Finished ", "Compiling ", "Persistent compilation cache"))

    log = logging.getLogger("jax")
    if not log.handlers:
        log.addHandler(logging.StreamHandler())
    for handler in log.handlers:
        handler.addFilter(NoCompileChatter())
    log.propagate = False


def require_device(chips: int) -> dict:
    """The device as JAX reports it.  Not a TPU, or another count than
    the cell asks for: exit non-zero, no result.  The ONLY place the
    platform is judged (the CPU rehearsal in tests/bench steers it)."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise BenchFailure(f"needs a TPU; jax found {dev}")
    if dev["count"] != chips:
        raise BenchFailure(f"the cell needs {chips} chip(s); jax found {dev}")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileWatch:
    """``RecompileSentinel`` with snapshots: what compiled between two
    points of a run."""

    def __init__(self):
        from orion_tpu.analysis.runtime_guards import RecompileSentinel

        self.sentinel = RecompileSentinel(budget=10 ** 9).install()

    def snapshot(self) -> Dict[str, int]:
        return dict(self.sentinel.counts)

    @staticmethod
    def between(before: Dict[str, int], after: Dict[str, int]) -> dict:
        return {k: n - before.get(k, 0) for k, n in after.items()
                if n > before.get(k, 0)}

    def close(self) -> None:
        self.sentinel.uninstall()


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list: the
    smallest value with at least q% of the values at or below it."""
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])


class Window:
    """The measured window on the host clock (``time.perf_counter``)."""

    def __init__(self, t_process_start: float, seconds: float):
        self.t_process_start = t_process_start
        self.seconds = float(seconds)
        self.start: Optional[float] = None

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def contains(self, t: float) -> bool:
        return self.start is not None and self.start <= t < self.end

    @property
    def setup_s(self) -> float:
        return self.start - self.t_process_start


class Tracer:
    """``jax.profiler`` over a short steady part of the window.  Only
    on in a ``--trace 1`` run.  ``start``/``stop`` are called by the
    runner on its own thread; the trace lands under ``out_dir``."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled, self.out_dir = bool(enabled), out_dir
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.active = False

    def start(self) -> None:
        if not self.enabled or self.active or self.t_stop is not None:
            return
        import shutil

        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        # Python's own function-call tracer is off: it records every
        # call of every thread, slows the host it shares with the load
        # generator, and the benchmark's spans label the gaps instead.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.active = True
        self.t_start = time.perf_counter()
        # the traced window, as a span on the profiler's own clock
        self._window_span = jax.profiler.TraceAnnotation("bench_window")
        self._window_span.__enter__()

    def stop(self, background: bool = False) -> None:
        """Close the traced window and stop the profiler.  Stopping
        writes the whole trace out and takes tens of seconds: a runner
        whose thread must go on serving passes ``background=True`` and
        calls :meth:`wait` before it reads the trace."""
        if not self.active:
            return
        import threading

        import jax

        self._window_span.__exit__(None, None, None)
        self.t_stop = time.perf_counter()
        self.active = False
        if background:
            self._stopper = threading.Thread(
                target=jax.profiler.stop_trace, name="bench-stop-trace")
            self._stopper.start()
        else:
            jax.profiler.stop_trace()

    def wait(self) -> None:
        stopper = getattr(self, "_stopper", None)
        if stopper is not None:
            stopper.join()
            self._stopper = None

    def annotate(self, name: str):
        """A host span on the profiler's own clock (a no-op context
        when tracing is off)."""
        if not self.enabled:
            import contextlib

            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def wrap(self, obj, attr: str, label: Optional[str] = None) -> None:
        """Put a host span around ``obj.attr(...)`` — the benchmark's
        own span around a call into a layer of the program.  Only in a
        traced run; a missing attribute is left alone."""
        fn = getattr(obj, attr, None)
        if not self.enabled or fn is None:
            return
        import functools

        import jax

        name = label or attr.lstrip("_")

        @functools.wraps(fn)
        def spanned(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)

        setattr(obj, attr, spanned)

    def xplane_path(self) -> Optional[str]:
        import glob

        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None
