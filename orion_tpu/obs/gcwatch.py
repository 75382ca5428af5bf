"""The interpreter's garbage collections as part of the host's account
(ISSUE 36): counted always, a span when they are the expensive kind.

One ``gc.callbacks`` hook for the process (:class:`GcWatch`; the
trainer installs it beside its ``obs`` session and ``close()`` releases
it).  A collection runs on the thread whose allocation crossed the
threshold and stops that thread for its length, so the count is kept
per thread: :meth:`GcWatch.stats` gives the CALLING thread's
collections by generation — count, total seconds, longest pause — as
running totals, like a clock: whoever wants "since the last read" keeps
the last reading and subtracts (the trainer loop does, at the stamps
where it reads its other two clocks).  That costs two reads of the
monotonic clock a collection.

A generation-2 collection walks every tracked object of the process.
It is also a span ``host.gc`` (``gen``, ``collected``, ``cpu_us``),
opened at the callback's ``start`` and closed at its ``stop`` on the
thread that collected: on the ring when the ring is on, a profiler
annotation while a session records, nested in whatever span the
collection interrupted.  Young collections are tens an iteration and
tens of microseconds each: counted, not spans.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Callable, List, Tuple

__all__ = ["GcWatch"]

GENERATIONS = 3
SPAN_GENERATION = 2


class _Installed:
    """One user's hold on the hook (the ``ObsSession`` contract:
    ``uninstall`` is idempotent and leaves ``gc.callbacks`` as this
    user found it once the last hold goes)."""

    def __init__(self, watch: "GcWatch"):
        self._watch = watch

    def uninstall(self) -> None:
        watch, self._watch = self._watch, None
        if watch is not None:
            watch._release()


class GcWatch:
    """``tracer_of()`` is the process tracer at the time of a
    collection (``obs.get_tracer``): the hook outlives sessions."""

    def __init__(self, tracer_of: Callable[[], "object"]):
        self._tracer_of = tracer_of
        self._local = threading.local()
        self._holds = 0
        # the collection in flight: collections never overlap (the
        # interpreter runs one at a time, callbacks included)
        self._t0 = None
        self._span = None

    # -- installation ----------------------------------------------------
    def install(self) -> _Installed:
        if self._holds == 0:
            gc.callbacks.append(self._on_gc)
        self._holds += 1
        return _Installed(self)

    def _release(self) -> None:
        self._holds -= 1
        if self._holds == 0:
            gc.callbacks.remove(self._on_gc)
            # released from a finalizer inside a collection: its stop
            # will not be seen
            span, self._span, self._t0 = self._span, None, None
            if span is not None:
                span.__exit__(None, None, None)

    # -- the hook --------------------------------------------------------
    def _mine(self) -> List[list]:
        st = getattr(self._local, "stats", None)
        if st is None:
            st = self._local.stats = [[0, 0.0, 0.0]
                                      for _ in range(GENERATIONS)]
        return st

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info["generation"] >= SPAN_GENERATION:
                # the no-op singleton unless the ring is on or a
                # profiler session records
                self._span = self._tracer_of().span(
                    "host.gc", gen=info["generation"])
                self._span.__enter__()
            self._t0 = time.monotonic()
            return
        if self._t0 is None:      # installed inside this collection
            return
        pause = time.monotonic() - self._t0
        span, self._span, self._t0 = self._span, None, None
        if span is not None:
            span.set(collected=info["collected"])
            span.__exit__(None, None, None)
        row = self._mine()[info["generation"]]
        row[0] += 1
        row[1] += pause
        row[2] = max(row[2], pause)

    # -- readout ---------------------------------------------------------
    def stats(self) -> List[Tuple[int, float, float]]:
        """``(count, total seconds, longest pause)`` by generation of
        the collections the calling thread has run while the hook was
        installed."""
        return [tuple(row) for row in self._mine()]

    def totals(self) -> Tuple[int, float]:
        """``(count, seconds)`` over all generations: the calling
        thread's collector clock."""
        st = self._mine()
        return sum(r[0] for r in st), sum(r[1] for r in st)
