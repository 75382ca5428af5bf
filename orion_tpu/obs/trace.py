"""Span/event tracing core (ISSUE 9 tentpole, SURVEY.md §5 tracing).

Design constraints, in order:

1. **Near-zero cost when off.**  ``Tracer.span`` on a disabled tracer,
   with no profiler session recording, returns one shared no-op
   singleton — no allocation, no clock read, one atomic load
   (``TraceAnnotation.is_enabled``) — so the serving hot loop and the
   wire protocol can be instrumented unconditionally (<1% budget,
   enforced by
   tests/test_obs.py::test_disabled_tracing_overhead_budget).
2. **Lock-free recording.**  Events land in a fixed-size per-process
   ring: the write cursor is an ``itertools.count`` (``next()`` is
   atomic under the GIL) and each slot stores ``(index, event)``, so
   readers reconstruct write order without ever taking a lock and a
   wedged reader can never stall a producer thread.
3. **Cross-process stitchable.**  Every event carries
   (trace_id, span_id, parent_id); ``adopt_trace`` lets a worker
   process take the learner's trace id (it rides the ORTP frame
   header — see orchestration/remote.py), so one trace id spans the
   whole pool and ``merge_chrome_traces`` produces a single
   Perfetto-loadable timeline with the learner and every worker as
   separate process tracks.
4. **One timeline with the device.**  Every span also opens a
   ``jax.profiler.TraceAnnotation`` of the same name (attributes as its
   keyword arguments) whenever a profiler session is recording — the
   benchmark's ``--trace 1``, the trainer's ``profile_dir`` window, an
   operator's ``jax.profiler.start_trace`` — so the program's spans lie
   on the ``/host:CPU`` plane of the same xplane as the device events,
   on the profiler's clock, nested as the program nests them, whether
   or not the ring (``obs.trace``) is on.  The program asks the
   profiler itself (``TraceAnnotation.is_enabled()``, the ``TraceMe``
   atomic): no option, no environment variable, no call from outside.
   The ring is the operator's record across processes; the annotation
   is what shares the device's clock.  A span that opens before a
   session starts, or closes after it stops, is not in the xplane.

5. **Wall is not work.**  A span that measures reads the thread's CPU
   clock (``time.thread_time``) beside the monotonic one, at both ends:
   ``Span.cpu`` beside ``Span.duration``, ``"cpu"`` on the ring event
   (``tdur`` in the Chrome export), ``cpu_us`` on the profiler
   annotation.  A span that waits on the device, sleeps or sits in a
   dispatch that blocks burns none; a garbage collection or a spinning
   wait burns all of its wall; wall that is neither a named wait nor
   CPU is time the thread was off the processor (ISSUE 36).

What a span costs (this repo's CPU sandbox, python 3.12, jax 0.9.0,
best of three loops of 2 x 10^5 ``with t.span("x", a=1): pass`` in the
thread's CPU time, less an empty call; the commit before the CPU clock
in brackets, same loop, same machine): tracer off and no session
0.375 µs [0.374] — the ``with`` statement and the keyword dict; the
atomic load is 0.02-0.04 µs of it, and no clock is read; ``timed``
2.2 µs [1.4] (it always reads both clocks: a ``thread_time`` read is a
system call, 0.4 µs, a ``monotonic`` read 0.13 µs); with the ring on
4.0 µs [3.3]; under a recording session one ``TraceMe`` more (1.9 µs in
PR 25's loop).  tests/test_obs.py holds the off path under 1 µs of the
thread's CPU time.

Timestamps are dual: Chrome ``ts`` uses the wall clock (epoch µs) so
independently-dumped processes align on one timeline; durations come
from the monotonic clock (immune to NTP steps).  This package is the
one place in the tree allowed to read raw clocks for timing — the
``naked-timer`` analysis rule routes everyone else through spans.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation as _Annotation

#: True while a profiler session records host events (one atomic load).
_profiling = _Annotation.is_enabled

__all__ = ["Span", "Tracer", "merge_chrome_traces"]

_SPAN_IDS = itertools.count(1)


def _gen_trace_id() -> int:
    """63-bit random trace id.  os.urandom, not a seeded PRNG: forked
    worker processes must not share a stream and mint colliding ids."""
    return (int.from_bytes(os.urandom(8), "little") & ((1 << 63) - 1)) or 1


class _NullSpan:
    """The shared disabled-path span: no clock reads, no allocation.
    ``duration``/``elapsed`` report 0.0 (and there is no ``cpu``) —
    callers that need a real measurement even with tracing off use
    :meth:`Tracer.timed`."""

    __slots__ = ()
    duration = 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def elapsed(self) -> float:
        return 0.0

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Span:
    """One timed scope.  Context manager; nesting is tracked per
    thread, so a child span's ``parent_id`` is the innermost open span
    on the same thread.  ``record=False`` (from :meth:`Tracer.timed`
    on a disabled tracer) still measures — the duration feeds metrics
    rows — but touches neither the ring nor the context stack.  Either
    way the span is also a profiler annotation while a session records
    (module docstring, point 4), and measures on two clocks: ``duration``
    on the monotonic one, ``cpu`` on the thread's own (point 5)."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "duration", "cpu", "_tracer", "_record", "_t0", "_c0",
                 "_wall", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any],
                 record: bool):
        self.name = name
        self.attrs = attrs
        self._tracer = tracer
        self._record = record
        self.trace_id = 0
        self.span_id = 0
        self.parent_id = 0
        self.duration = 0.0
        self.cpu = 0.0
        self._t0 = 0.0
        self._c0 = 0.0
        self._wall = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        if _profiling():
            self._ann = _Annotation(self.name, **self.attrs)
            self._ann.__enter__()
        if self._record:
            stack = self._tracer._stack()
            self.trace_id = self._tracer.trace_id
            self.span_id = next(_SPAN_IDS)
            self.parent_id = stack[-1].span_id if stack else 0
            stack.append(self)
        self._wall = time.time()
        self._t0 = time.monotonic()
        self._c0 = time.thread_time()   # inside the wall: cpu <= duration
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.cpu = time.thread_time() - self._c0
        self.duration = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.set_metadata(cpu_us=round(self.cpu * 1e6))
            self._ann.__exit__(exc_type, exc, tb)
        if self._record:
            stack = self._tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            attrs = self.attrs
            if exc_type is not None:
                attrs = dict(attrs, error=exc_type.__name__)
            self._tracer._emit({
                "name": self.name, "ph": "X", "wall": self._wall,
                "dur": self.duration, "cpu": self.cpu,
                "trace": self.trace_id,
                "span": self.span_id, "parent": self.parent_id,
                "tid": threading.get_ident() & 0x7FFFFFFF,
                "attrs": attrs,
            })
        return False

    def elapsed(self) -> float:
        """Monotonic seconds since ``__enter__`` — mid-span laps for
        metrics that split one scope into phases."""
        return time.monotonic() - self._t0

    @property
    def start(self) -> float:
        """Monotonic stamp of ``__enter__``: differences between the
        ``start``/``end`` of spans are differences on one clock."""
        return self._t0

    @property
    def end(self) -> float:
        """Monotonic stamp of ``__exit__`` (``start + duration``)."""
        return self._t0 + self.duration

    @property
    def cpu_start(self) -> float:
        """The thread's CPU clock at ``__enter__``: the difference
        between the ``cpu_start`` of two spans OF ONE THREAD is the CPU
        time that thread burnt between them."""
        return self._c0

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (counts, bytes):
        added to the ring event and to the profiler annotation."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)


class Tracer:
    """Per-process span/event recorder over a lock-free ring buffer.

    One (module-global) instance per process is the normal shape —
    ``orion_tpu.obs.configure`` installs it; tests that stand in for
    several processes inside one interpreter construct extra instances
    with distinct ``pid`` overrides so the merged Chrome trace keeps
    separate process tracks.
    """

    def __init__(self, ring_size: int = 4096, enabled: bool = True,
                 pid: Optional[int] = None, name: Optional[str] = None):
        if ring_size < 1:
            raise ValueError(f"ring_size must be >= 1, got {ring_size}")
        self.enabled = bool(enabled)
        self.ring_size = int(ring_size)
        self._ring: List[Optional[Tuple[int, dict]]] = [None] * ring_size
        self._cursor = itertools.count()
        self.pid = os.getpid() if pid is None else int(pid)
        self.name = name or f"pid-{self.pid}"
        self.trace_id = _gen_trace_id()
        self._local = threading.local()

    # -- recording -------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _emit(self, ev: dict) -> None:
        i = next(self._cursor)  # atomic under the GIL: no lock
        self._ring[i % self.ring_size] = (i, ev)

    def span(self, name: str, **attrs) -> Any:
        """Recorded timed scope.  Disabled and no profiler session
        recording: the shared no-op singleton (identity-stable: the
        overhead test asserts it).  Disabled under a recording session:
        a span that is only the profiler's annotation."""
        if self.enabled:
            return Span(self, name, attrs, record=True)
        if _profiling():
            return Span(self, name, attrs, record=False)
        return _NULL_SPAN

    def timed(self, name: str, **attrs) -> Span:
        """A span that ALWAYS measures (``.duration``/``.elapsed``,
        ``.cpu``) and records only when enabled — for durations that
        feed metrics rows regardless of tracing."""
        return Span(self, name, attrs, record=self.enabled)

    def instant(self, name: str, parent: int = 0, **attrs) -> None:
        """Point event at the current trace/span context.  ``parent``
        links to a REMOTE span id (cross-process causality — the TRAJ
        consume event names the worker's generate span)."""
        if not self.enabled:
            return
        stack = self._stack()
        self._emit({
            "name": name, "ph": "i", "wall": time.time(), "dur": 0.0,
            "trace": self.trace_id,
            "span": stack[-1].span_id if stack else 0,
            "parent": parent,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "attrs": attrs,
        })

    def record_closed(self, name: str, start: float, duration: float,
                      cpu: float = 0.0, tid: Optional[int] = None,
                      **attrs) -> None:
        """A span measured elsewhere, written with its own monotonic
        ``start`` and ``duration``: a compile event (jax reports it when
        it ends) or a set-up phase that closed before the ring was
        there (``obs/compilewatch.py``).  Its parent is the span open on
        this thread now if that one was open at ``start``, else none."""
        if not self.enabled:
            return
        stack = self._stack()
        parent = stack[-1] if stack and stack[-1].start <= start else None
        self._emit({
            "name": name, "ph": "X",
            "wall": time.time() - (time.monotonic() - start),
            "dur": duration, "cpu": cpu, "trace": self.trace_id,
            "span": next(_SPAN_IDS),
            "parent": parent.span_id if parent is not None else 0,
            "tid": (threading.get_ident() if tid is None else tid)
            & 0x7FFFFFFF,
            "attrs": attrs,
        })

    # -- cross-process context ------------------------------------------
    def adopt_trace(self, trace_id: int) -> None:
        """Take a remote originator's trace id as ours (worker side of
        the pool protocol): every later root span stitches into the
        learner's trace."""
        if trace_id:
            self.trace_id = int(trace_id)

    def context(self) -> Tuple[int, int]:
        """(trace_id, current span id) for stamping outgoing frames;
        (0, 0) when disabled so the wire bytes are stable."""
        if not self.enabled:
            return (0, 0)
        stack = self._stack()
        return (self.trace_id, stack[-1].span_id if stack else 0)

    # -- readout ---------------------------------------------------------
    def events(self) -> List[dict]:
        """Snapshot of the ring in write order (the last
        ``ring_size`` events).  Lock-free: a slot overwritten mid-scan
        just surfaces the newer event."""
        entries = [e for e in list(self._ring) if e is not None]
        entries.sort(key=lambda pair: pair[0])
        return [ev for _, ev in entries]

    def chrome_events(self) -> List[dict]:
        """Events as Chrome ``trace_event`` dicts (Perfetto-loadable).
        ``ts`` is wall-clock µs so independently dumped processes line
        up on one timeline."""
        out = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": self.name},
        }]
        for ev in self.events():
            e = {
                "name": ev["name"], "ph": ev["ph"], "cat": "orion",
                "ts": ev["wall"] * 1e6, "pid": self.pid, "tid": ev["tid"],
                "args": {"trace_id": str(ev["trace"]),
                         "span_id": str(ev["span"]),
                         "parent_id": str(ev["parent"]),
                         **ev["attrs"]},
            }
            if ev["ph"] == "X":
                e["dur"] = ev["dur"] * 1e6
                e["tdur"] = ev["cpu"] * 1e6   # thread-clock duration
            else:
                e["s"] = "t"  # thread-scoped instant
            out.append(e)
        return out

    def export_chrome(self, path: str) -> str:
        """Write the ring as a Chrome/Perfetto trace JSON file."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms",
               "otherData": {"process": self.name,
                             "trace_id": str(self.trace_id)}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def merge_chrome_traces(paths: Sequence[str], out_path: str) -> str:
    """Concatenate per-process Chrome trace files into ONE
    Perfetto-loadable timeline.  Events keep their pids, so each
    process stays a separate track; a shared trace_id in ``args`` is
    what ties them into one logical trace."""
    events: List[dict] = []
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        events.extend(doc.get("traceEvents", doc if isinstance(doc, list)
                              else []))
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return out_path
