"""The program's own spans, read from the ``/host:`` planes of the
xplane file a traced run already wrote.

``orion_tpu.obs`` opens a ``jax.profiler.TraceAnnotation`` for every
``obs.span`` / ``obs.timed`` while a profiler session records, so the
spans of the trainer loop and of the serving engine lie on the
profiler's clock beside the device events, one line per host thread,
nested as the program nests them.  This file turns them into numbers
for the ``program_span`` readers in ``layer_metrics/``; it iterates no
device event and names no span, cell or metric.

What counts as a span: a host event whose name is a lower-case dotted
identifier (``train.iteration``, ``rollout.fetch``, ``update``, and the
harness's own ``bench_window`` and wrappers) and that carries no
``hlo_module`` stat.  jax's and XLA's own host events never match
(``PjitFunction(f)``, ``np.asarray(jax.Array)``, ``$profiler.py:91
start_trace``, ``ThreadpoolListener::...``); XLA:CPU operations that a
rehearsal on the CPU runs inline on the calling thread (``fusion.3``)
would, and are told apart by that stat.

A span's **self time** is its duration less what its child spans on the
same thread cover (choosing-metrics guide, section 4).  Everything is
clipped to the ``bench_window`` span (the harness opens it right after
``start_trace`` and closes it right before ``stop_trace``); statistics
per parent use only parents that lie WHOLLY inside it.  A span that was
open when the session started, or still open when it stopped, is not in
the file at all: a ``TraceMe`` records only what both opens and closes
while a session records.

Times are nanoseconds on the profiler's clock inside this file and
seconds in what it returns.
"""

from __future__ import annotations

import os
import re
import time
from statistics import median  # noqa: F401  (the readers' median)
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench_window"
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")
NOT_A_SPAN_STAT = "hlo_module"


class Span(NamedTuple):
    thread: int          # index into HostSpans.threads
    name: str
    start: float         # ns
    end: float           # ns
    self_ns: float       # duration less the child spans on the thread
    parent: int          # index into the thread's spans, -1 at the top
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def nest(events: list) -> List[Tuple[float, int]]:
    """(self time ns, parent index) of each event of ONE thread.
    ``events``: ``[name, start_ns, dur_ns, stats]`` sorted by start,
    longer first on a tie.  Events of one thread are properly nested
    (they are scopes of one call stack)."""
    out = [[e[2], -1] for e in events]
    stack: List[int] = []
    for i, (_, start, dur, _) in enumerate(events):
        while stack and start >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][0] -= dur
            out[i][1] = stack[-1]
        stack.append(i)
    return [(max(0.0, s), p) for s, p in out]


class HostSpans:
    """The spans of every host thread of one trace.  ``threads`` is a
    list of ``(label, [Span])``, spans in start order; the label is
    ``<plane>/<line index>:<line name>`` because thread lines share
    names (every Python thread's line is called ``python3``)."""

    def __init__(self, lines: List[Tuple[str, list]]):
        self.threads: List[Tuple[str, List[Span]]] = []
        lo, hi = float("inf"), float("-inf")
        window = None
        for t, (label, events) in enumerate(lines):
            events = sorted(events, key=lambda e: (e[1], -e[2]))
            spans = [Span(t, e[0], e[1], e[1] + e[2], s, p, e[3])
                     for e, (s, p) in zip(events, nest(events))]
            self.threads.append((label, spans))
            for sp in spans:
                if sp.name == WINDOW_SPAN and window is None:
                    window = (sp.start, sp.end)
                lo, hi = min(lo, sp.start), max(hi, sp.end)
        self.lo, self.hi = window if window else (lo, hi)
        self.n_spans = sum(len(s) for _, s in self.threads)

    # -- per name and thread ---------------------------------------------
    def by_name(self) -> Dict[str, Dict[str, dict]]:
        """``{name: {thread label: {count, total_s, self_s}}}``, every
        span clipped to the window (self time clipped with it)."""
        out: Dict[str, Dict[str, dict]] = {}
        for label, spans in self.threads:
            clipped = [[sp.name, max(sp.start, self.lo),
                        min(sp.end, self.hi) - max(sp.start, self.lo), None]
                       for sp in spans
                       if min(sp.end, self.hi) > max(sp.start, self.lo)]
            for (name, _, dur, _), (self_ns, _) in zip(clipped,
                                                       nest(clipped)):
                row = out.setdefault(name, {}).setdefault(
                    label, {"count": 0, "total_s": 0.0, "self_s": 0.0})
                row["count"] += 1
                row["total_s"] += dur / 1e9
                row["self_s"] += self_ns / 1e9
        return out

    # -- per parent -------------------------------------------------------
    def whole(self, name: str) -> List[Span]:
        """The spans called ``name`` that lie wholly inside the window,
        in time order."""
        found = [sp for _, spans in self.threads for sp in spans
                 if sp.name == name and sp.start >= self.lo
                 and sp.end <= self.hi]
        return sorted(found, key=lambda sp: sp.start)

    def inside(self, parent: Span, names: Iterable[str],
               less: Iterable[str] = ()) -> float:
        """Seconds that the spans called one of ``names`` take inside
        ``parent`` on its thread (descendants at any depth), less the
        spans called one of ``less`` nested inside them.  (By name, not
        by self time: while the harness's wrappers sit between a span
        of the program and its work, the span's self time is what the
        wrapper left over.)"""
        names, less = set(names), set(less)
        total = 0.0
        taken: List[Span] = []
        for sp in self.threads[parent.thread][1]:
            if sp.start >= parent.end:
                break
            if sp is parent or sp.start < parent.start \
                    or sp.end > parent.end:
                continue
            if sp.name in names:
                total += sp.dur
                taken.append(sp)
            elif sp.name in less and any(
                    m.start <= sp.start and sp.end <= m.end for m in taken):
                total -= sp.dur
        return total / 1e9

    def before(self, parent: Span, name: str) -> float:
        """Seconds of the span called ``name`` that ends last before
        ``parent`` starts on its thread, if no other span called
        ``parent.name`` lies between the two; else 0."""
        best: Optional[Span] = None
        fence = float("-inf")
        for sp in self.threads[parent.thread][1]:
            if sp.start >= parent.start:
                break
            if sp.name == parent.name:
                fence = max(fence, sp.end)
            elif sp.name == name and sp.end <= parent.start:
                best = sp
        if best is None or best.start < fence:
            return 0.0
        return best.dur / 1e9

    def per_parent(self, parent: str, names: Iterable[str],
                   less: Iterable[str] = ()) -> List[float]:
        """One number per whole ``parent`` span: :meth:`inside` of it."""
        names, less = tuple(names), tuple(less)
        return [self.inside(p, names, less) for p in self.whole(parent)]


def from_planes(planes: list) -> HostSpans:
    """From ``trace_reduce.load``-style planes (``{"name", "lines":
    [{"name", "events": [[name, start_ns, dur_ns, stats]]}]}``): how the
    tests hand in a small recorded fixture."""
    lines = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for i, line in enumerate(plane["lines"]):
            events = [e for e in line["events"]
                      if SPAN_NAME.match(e[0])
                      and NOT_A_SPAN_STAT not in (e[3] or {})]
            if events:
                lines.append((f"{plane['name']}/{i}:{line['name']}", events))
    return HostSpans(lines)


def read_planes(path: str) -> list:
    """The ``/host:`` planes of an xplane file as plain Python; only
    events that are spans are kept, with their stats."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if not SPAN_NAME.match(e.name):
                    continue
                stats = {k: (v if isinstance(v, (int, float)) else str(v)[:120])
                         for k, v in e.stats}
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


_LOADED: Dict[tuple, HostSpans] = {}


def _key(path: str) -> tuple:
    st = os.stat(path)
    return (os.path.abspath(path), st.st_size, st.st_mtime_ns)


def load(path: str) -> HostSpans:
    """Parsed once per file however many readers ask (keyed by path,
    size and modification time); ``load_s`` is what the parse cost."""
    key = _key(path)
    if key not in _LOADED:
        _LOADED.clear()
        t0 = time.perf_counter()
        spans = from_planes(read_planes(path))
        spans.load_s = time.perf_counter() - t0
        _LOADED[key] = spans
    return _LOADED[key]


def of_run(ctx) -> Optional[HostSpans]:
    """The spans of the traced run ``ctx`` describes, or None where it
    left no xplane file.  The call that parses prints what that cost."""
    h = ctx.lib("harness")
    path = h.Tracer(True, ctx.out_dir + "/trace").xplane_path()
    if path is None:
        return None
    parsed = _key(path) not in _LOADED
    spans = load(path)
    if parsed:
        h.note(phase="host_spans", load_s=round(spans.load_s, 3),
               spans=spans.n_spans, threads=len(spans.threads))
    return spans
