"""orion_tpu.obs: distributed span tracing, request-lifecycle
telemetry, and a crash flight recorder (ISSUE 9; SURVEY.md §5).

The async-RLHF pitch lives or dies on *where the time goes* — rollout
vs. update vs. weight sync vs. queue wait — across threads AND
processes.  This package is the instrumentation layer the rest of the
tree reports through:

- :mod:`trace` — ``span("rollout.generate")`` context managers over a
  lock-free per-process ring buffer, exportable as Chrome
  ``trace_event`` JSON (open in Perfetto next to the xplane dumps);
  trace ids propagate across the pool via the ORTP frame header so one
  trace stitches submit → worker-generate → TRAJ → consume → update.
- :mod:`telemetry` — per-request lifecycle clocks + histograms
  (queue wait, TTFT, tok/s, prefix-hit ratio, page occupancy) for the
  continuous engine, summarized as p50/p95/p99 through
  :class:`~orion_tpu.utils.metrics.MetricsWriter`.
- :mod:`flightrec` — the last ``ring_size`` events dumped to
  ``<log_dir>/flightrec-<ts>.json`` on unhandled exception,
  degradation-ladder transitions, or SIGUSR1.
- :mod:`gcwatch` — the interpreter's garbage collections: counted per
  thread always (the metrics row's ``host_gc_s``), a ``host.gc`` span
  when they are the expensive kind.
- :mod:`compilewatch` — every trace, lowering, compile and cache load
  by program, from one pair of ``jax.monitoring`` listeners: counted
  per thread always (the metrics row's ``compile_s`` / ``compiles`` /
  ``cache_misses``), ``compile.*`` ring events, and with the ``setup.*``
  phases the one ``setup`` row that says where set-up went.

Module-global convenience mirrors ``resilience.inject``: one process
tracer + one flight recorder, armed by ``TrainConfig.obs``
(``obs.trace`` / ``obs.ring_size`` / ``obs.flight_recorder``) at
trainer construction, released by ``trainer.close()``.  Everything is
host code; the one thing taken from jax is ``jax.profiler``'s
``TraceAnnotation`` (:mod:`trace`), through which every span also lies
on the profiler's clock beside the device events whenever a profiler
session records.  With tracing off and no session a span costs about
half a microsecond (measured: :mod:`trace`'s docstring).
"""

from __future__ import annotations

# first: its first statements stamp the start of the process's set-up
from orion_tpu.obs.compilewatch import CompileWatch, SetupAccount

import logging
import os
from typing import Any, Dict, Optional

from orion_tpu.obs.flightrec import FlightRecorder  # noqa: F401
from orion_tpu.obs.gcwatch import GcWatch
from orion_tpu.obs.telemetry import (  # noqa: F401
    RequestTelemetry,
    TokenBucket,
)
from orion_tpu.obs.trace import (  # noqa: F401
    Span,
    Tracer,
    merge_chrome_traces,
)
from orion_tpu.utils.metrics import Counter, Histogram  # noqa: F401

#: The always-present fallback: disabled, 1-slot ring.  Every call
#: site can use the module-level helpers unconditionally.
_DEFAULT = Tracer(ring_size=1, enabled=False)
_TRACER: Tracer = _DEFAULT
_FLIGHT: Optional[FlightRecorder] = None


# ---------------------------------------------------------------------------
# process-global tracer
# ---------------------------------------------------------------------------


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install ``tracer`` (None restores the disabled default).
    Returns the previous tracer so scoped installs can restore it."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer if tracer is not None else _DEFAULT
    return prev


def configure(enabled: bool = True, ring_size: int = 4096,
              pid: Optional[int] = None,
              name: Optional[str] = None) -> Tracer:
    """Build + install the process tracer; returns it."""
    tracer = Tracer(ring_size=ring_size, enabled=enabled, pid=pid,
                    name=name)
    set_tracer(tracer)
    return tracer


def span(name: str, **attrs):
    """Scoped span on the process tracer (no-op singleton when
    tracing is off and no profiler session records)."""
    return _TRACER.span(name, **attrs)


def timed(name: str, **attrs) -> Span:
    """A span that always measures (``.duration``) and records only
    when tracing is on — THE replacement for naked ``time.*`` deltas
    in library code (analysis rule ``naked-timer``)."""
    return _TRACER.timed(name, **attrs)


def instant(name: str, parent: int = 0, **attrs) -> None:
    _TRACER.instant(name, parent=parent, **attrs)


# ---------------------------------------------------------------------------
# the process's one gc.callbacks hook
# ---------------------------------------------------------------------------

_GC = GcWatch(get_tracer)


def install_gc_watch():
    """Count this process's garbage collections (and make the
    generation-2 ones ``host.gc`` spans) for as long as the returned
    handle is held: ``handle.uninstall()`` is idempotent, several
    holders share ONE hook, the last to let go removes it
    (:mod:`gcwatch`)."""
    return _GC.install()


def gc_totals():
    """``(count, seconds)`` of the collections the calling thread has
    run under the hook, all generations: a clock to take differences
    of; it stands still while no hook is installed."""
    return _GC.totals()


# ---------------------------------------------------------------------------
# the process's one pair of jax.monitoring listeners, and its set-up
# ---------------------------------------------------------------------------

_COMPILE = CompileWatch(get_tracer)
_SETUP = SetupAccount(_COMPILE, get_tracer)


def install_compile_watch(observer=None):
    """Account for this process's traces, lowerings, compiles and cache
    loads for as long as the returned handle is held (also a context
    manager): ``handle.uninstall()`` is idempotent, several holders
    share ONE pair of listeners, the last to let go unregisters them;
    ``observer(kind, program name)`` is called after every event while
    this hold lasts (:mod:`compilewatch`)."""
    return _COMPILE.install(observer)


def compile_totals():
    """The calling thread's compile clock (``programs``, ``trace_s``,
    ``lower_s``, ``compile_s``, ``load_s``, ``hits``, ``misses``;
    ``.seconds`` their sum): a clock to take differences of, like
    :func:`gc_totals`."""
    return _COMPILE.totals()


def compile_programs():
    """By program name, process-wide and complete
    (:meth:`CompileWatch.programs`)."""
    return _COMPILE.programs()


def compile_events():
    """The kept events, bounded (:meth:`CompileWatch.events`)."""
    return _COMPILE.events()


def setup_phase(name: str, **attrs):
    """``timed(name)`` as a phase of set-up: the account keeps it until
    the ``setup`` row is written (:class:`SetupAccount`)."""
    return _SETUP.phase(name, **attrs)


def setup_begin() -> None:
    """A job starts in this process (``launch.main``)."""
    _SETUP.begin()


def setup_imported() -> None:
    """The entry point's imports are done: closes ``setup.import``."""
    _SETUP.imported()


def setup_row(begins, steady) -> dict:
    """The ``setup`` row (:meth:`SetupAccount.row`)."""
    return _SETUP.row(begins, steady)


# ---------------------------------------------------------------------------
# process-global flight recorder
# ---------------------------------------------------------------------------


def install_flight_recorder(rec: Optional[FlightRecorder]
                            ) -> Optional[FlightRecorder]:
    """Install ``rec`` as the process flight recorder (None clears).
    Returns the previous recorder."""
    global _FLIGHT
    prev = _FLIGHT
    _FLIGHT = rec
    return prev


def current_flight_recorder() -> Optional[FlightRecorder]:
    return _FLIGHT


def flight_dump(reason: str, extra: Optional[Dict[str, Any]] = None
                ) -> Optional[str]:
    """Dump the ring if a recorder is installed; no-op (None)
    otherwise.  NEVER raises — a failing dump must not turn a
    degradation into a crash."""
    rec = _FLIGHT
    if rec is None:
        return None
    try:
        return rec.dump(reason, extra)
    except Exception:  # pragma: no cover - disk-full style failures
        logging.getLogger(__name__).exception(
            "flight recorder dump failed (reason=%s)", reason)
        return None


# ---------------------------------------------------------------------------
# config wiring (TrainConfig.obs)
# ---------------------------------------------------------------------------


class ObsSession:
    """Handle returned by :func:`install_from_config`: restores the
    previous tracer/recorder on :meth:`uninstall` (idempotent), so
    sweep scripts constructing many trainers don't accumulate
    process-global hooks — same contract as the recompile sentinel."""

    def __init__(self, tracer: Tracer, prev_tracer: Tracer,
                 recorder: Optional[FlightRecorder],
                 prev_recorder: Optional[FlightRecorder],
                 directory: Optional[str] = None):
        self.tracer = tracer
        self.recorder = recorder
        self.directory = directory
        self.spans_path: Optional[str] = None
        self._prev_tracer = prev_tracer
        self._prev_recorder = prev_recorder
        self._live = True

    def uninstall(self) -> None:
        """Also the normal end of a run: the spans kept in the ring are
        written out once, as ``<directory>/spans-<pid>.json`` (Chrome
        trace_event JSON, opens in Perfetto; ``merge_chrome_traces``
        joins several processes' files)."""
        if not self._live:
            return
        self._live = False
        if self.directory:
            path = os.path.join(self.directory,
                                f"spans-{self.tracer.pid}.json")
            try:
                self.spans_path = self.tracer.export_chrome(path)
            except OSError:
                # a full disk must not turn a finished run into a crash
                logging.getLogger(__name__).exception(
                    "could not write %s", path)
        if self.recorder is not None:
            self.recorder.uninstall()
            install_flight_recorder(self._prev_recorder)
        set_tracer(self._prev_tracer)


def install_from_config(cfg) -> Optional[ObsSession]:
    """Arm tracing + the flight recorder from ``TrainConfig.obs``.

    Returns None (nothing installed) unless ``cfg.obs.trace`` is on.
    The recorder and the span file written when the session ends
    (:meth:`ObsSession.uninstall`) need a directory: ``obs.trace_dir``
    or, by default, ``cfg.log_dir`` (the metrics dir — they land next
    to metrics.jsonl).
    """
    obs_cfg = getattr(cfg, "obs", None)
    if obs_cfg is None or not obs_cfg.trace:
        return None
    tracer = Tracer(ring_size=obs_cfg.ring_size, enabled=True)
    prev_tracer = set_tracer(tracer)
    recorder = prev_recorder = None
    directory = obs_cfg.trace_dir or getattr(cfg, "log_dir", None)
    if obs_cfg.flight_recorder and directory:
        recorder = FlightRecorder(directory, tracer=tracer).install()
        prev_recorder = install_flight_recorder(recorder)
    return ObsSession(tracer, prev_tracer, recorder, prev_recorder,
                      directory)
