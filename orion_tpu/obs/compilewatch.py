"""Where set-up goes (ISSUE 51): every trace, lowering, compile and
cache load by program, and the account that closes on the seconds
between the package's import and the first steady iteration.

One pair of ``jax.monitoring`` listeners for the process
(:class:`CompileWatch`; ``launch.main`` takes the first hold, the
trainer its own beside the collector's, the last ``uninstall()``
unregisters both and leaves ``jax.monitoring`` as found).  jax reports a
duration when a piece of work ENDS, on the thread that did it:
``jaxpr_trace_duration`` (``fun_name=f``), ``jaxpr_to_mlir_module_
duration`` and ``backend_compile_duration`` (``fun_name=jit(f)``); where the
persistent cache is asked, ``compile_requests_use_cache``, then
``cache_hits`` (and ``compile_time_saved_sec``) or, where a miss is
written, ``cache_misses`` fire on the same thread just before the
backend event of the program they belong to: a backend compile's
outcome is ``hit``, ``miss`` (asked, not found) or ``off`` (not asked).  The listener takes
``time.monotonic()`` as the end and end - duration as the start (the
clock :class:`~orion_tpu.obs.trace.Span` stamps with), and keeps

- per thread, running totals read like a clock (:meth:`totals`, the
  twin of ``GcWatch.totals``): whoever wants "since the last read" keeps
  the last reading and subtracts;
- per program name, process-wide and complete (:meth:`programs`); the
  name as ``RecompileSentinel.counts`` keys it (``jit(f)`` -> ``f``);
- the events themselves, bounded (:meth:`events`): every lowering and
  backend compile, a trace from :data:`TRACE_EVENT_MIN_S` up (traces are
  the many: most are eager primitives'), :data:`MAX_EVENTS` of them and a
  count of what was dropped.  A kept event is also a ring event
  ``compile.trace`` / ``compile.lower`` / ``compile.backend`` (``fun``,
  ``cache``) when ``obs.trace`` is on.

Events NEST: a program's trace holds the traces of the jitted functions
it calls, and an eager operation met while tracing lowers and compiles
inside that trace.  So a thread's seconds are the union of its events'
intervals and each kind's share is SELF time (an interval less what lies
inside it); the totals are self times, and a phase's seconds in its
compiles are the difference of two readings of the clock.

:class:`SetupAccount` keeps the ``setup.*`` phases (``obs.setup_phase``:
an ``obs.timed`` span whose name, stamps, CPU seconds and compile
seconds are kept until set-up is over) and builds the one ``setup`` row
the trainer writes at its first steady iteration.  The row's parts
(each phase's self time, the four kinds of compile seconds,
``warm_run_s``, ``unaccounted_s``) sum to ``total_s``.

What it costs (this repo's CPU sandbox, python 3.12, jax 0.9.0; the
thread's CPU clock, best of five loops, less an empty loop): a read of
:meth:`totals`, the one thing a steady iteration does here, 0.39 us; a
listener call 2.5-2.6 us an event (3.5-4.5 us with ``obs.trace`` on, the
ring event included), so a tiny PPO job's 2 200 events cost 6 ms and a
cell's few thousand 10-20 ms of 47-137 s.  No listener runs while
nothing compiles.  A recording profiler session adds nothing to a
listener call (2.5 us with Python's own call tracer off, as the
benchmark's traced run has it; 7.0 us with jax's default
``python_tracer_level``, which slows every Python call alike): the
events are no annotations, jax's own ``TraceMe`` s mark the compiles
there.
"""

from __future__ import annotations

import time

# The first statement the package executes (orion_tpu/__init__.py
# imports obs before anything else, obs/__init__.py this module): the
# origin of the process's set-up, on Span's two clocks.
T0 = time.monotonic()
C0 = time.thread_time()

import bisect  # noqa: E402
import contextlib  # noqa: E402
import logging  # noqa: E402
import threading  # noqa: E402
from array import array  # noqa: E402
from typing import Callable, Dict, List, NamedTuple, Optional  # noqa: E402

__all__ = ["CompileWatch", "CompileTotals", "SetupAccount"]

_THREAD0 = threading.get_ident()

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# In the order jax fires them before a backend event: the persistent
# cache is asked (a miss unless a hit follows; on this jax
# ``cache_misses`` itself fires only where an entry is WRITTEN, i.e. for
# a miss of a second or more), it hit, an entry is written.
_OUTCOMES = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
             "/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}

#: A trace event shorter than this is summed and not kept as an event.
TRACE_EVENT_MIN_S = 0.010
#: Events kept; what comes after is counted in ``dropped``.
MAX_EVENTS = 4096
#: jax writes a cache entry only for a compile of this length or more:
#: a miss under it is by rule, a miss over it in a warm process is not.
SLOW_MISS_S = 1.0
#: Closed intervals of a thread that a later one may still hold.
_OPEN_CAP = 1 << 16
_MAX_PHASES = 64


class CompileTotals(NamedTuple):
    """A thread's compile clock: counts, and SELF seconds by kind
    (``compile_s`` a backend compile that missed the cache or ran with
    it off, ``load_s`` one that hit: retrieval, deserialisation, load
    onto the device)."""
    programs: int = 0
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0
    load_s: float = 0.0
    hits: int = 0
    misses: int = 0

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s + self.load_s

    def since(self, before: "CompileTotals") -> "CompileTotals":
        return CompileTotals(*(b - a for a, b in zip(before, self)))


def program_name(fun_name: str) -> str:
    """``jit(f)`` -> ``f``: the lowering and the backend compile come as
    the former, the trace of the same program as the latter."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class _Installed:
    """One user's hold on the listeners (the ``ObsSession`` contract:
    ``uninstall`` is idempotent; the last hold to go unregisters)."""

    def __init__(self, watch: "CompileWatch", observer):
        self._watch, self._observer = watch, observer

    def uninstall(self) -> None:
        watch, self._watch = self._watch, None
        if watch is not None:
            watch._release(self._observer)

    def __enter__(self) -> "_Installed":
        return self

    def __exit__(self, *exc) -> bool:
        self.uninstall()
        return False


class _Thread(threading.local):
    """One thread's clock, the cache outcome waiting for its backend
    compile, and the closed intervals no later one has claimed yet
    (ascending and disjoint: what a parent that closes later holds)."""

    def __init__(self):
        self.totals = [0, 0.0, 0.0, 0.0, 0.0, 0, 0]
        self.cache: Optional[str] = None
        self.saved = 0.0
        self.starts = array("d")
        self.durs = array("d")


class CompileWatch:
    """``tracer_of()`` is the process tracer at the time of an event
    (``obs.get_tracer``): the listeners outlive sessions."""

    def __init__(self, tracer_of: Callable[[], "object"]):
        self._tracer_of = tracer_of
        self._local = _Thread()
        self._lock = threading.Lock()
        self._holds = 0
        self._programs: Dict[str, dict] = {}
        self._events: List[dict] = []
        # a tuple replaced whole under the lock: a listener's read of it
        # is one atomic load
        self._observers: tuple = ()
        self.dropped = 0
        self.errors = 0

    # -- installation ----------------------------------------------------
    def install(self, observer: Optional[Callable[[str, str], None]]
                = None) -> _Installed:
        """``observer(kind, program name)`` is called on the compiling
        thread after every event for as long as this hold lasts
        (``RecompileSentinel`` counts and warns from there).  The
        first hold starts the bounded records anew: what an earlier
        holder's events left of the cap is not this one's to inherit."""
        with self._lock:
            if observer is not None:
                self._observers += (observer,)
            if self._holds == 0:
                import jax.monitoring as monitoring

                self._events, self.dropped = [], 0
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                monitoring.register_event_listener(self._on_event)
            self._holds += 1
        return _Installed(self, observer)

    def _release(self, observer) -> None:
        with self._lock:
            if observer is not None:
                left = list(self._observers)
                left.remove(observer)
                self._observers = tuple(left)
            self._holds -= 1
            if self._holds:
                return
            import jax.monitoring as monitoring

            for unregister, fn in (
                    (monitoring.unregister_event_duration_listener,
                     self._on_duration),
                    (monitoring.unregister_event_listener, self._on_event)):
                try:
                    unregister(fn)
                except (AssertionError, ValueError):
                    pass    # someone cleared jax's lists meanwhile

    # -- the listeners: they never raise (a failing one fails a compile) --
    def _on_event(self, event: str, **kw) -> None:
        outcome = _OUTCOMES.get(event)
        if outcome is not None:
            self._local.cache = outcome

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            if event == _SAVED:
                self._local.saved = duration
            return
        try:
            name = self._record(kind, str(kw.get("fun_name", "?")),
                                time.monotonic(), float(duration))
            seen = self._observers  # orion: ignore[lock-discipline] a tuple replaced whole, never mutated
            for observer in seen:
                observer(kind, name)
        except Exception:
            self.errors += 1
            if self.errors == 1:
                logging.getLogger(__name__).exception(
                    "compile watch: the listener failed on a %s event "
                    "(logged once; CompileWatch.errors counts)", kind)

    def _record(self, kind: str, fun: str, end: float,
                duration: float) -> str:
        st = self._local
        start = end - duration
        # what closed inside this interval and belongs to no other
        i = bisect.bisect_left(st.starts, start)
        inside = sum(st.durs[i:])
        del st.starts[i:], st.durs[i:]
        st.starts.append(start)
        st.durs.append(duration)
        if len(st.starts) > _OPEN_CAP:
            del st.starts[:_OPEN_CAP // 2], st.durs[:_OPEN_CAP // 2]
        self_s = max(0.0, duration - inside)
        tot, cache, saved = st.totals, None, 0.0
        if kind == "trace":
            tot[1] += self_s
        elif kind == "lower":
            tot[2] += self_s
        else:
            cache, st.cache = st.cache or "off", None
            saved, st.saved = st.saved, 0.0
            tot[0] += 1
            if cache == "hit":
                tot[4] += self_s
                tot[5] += 1
            else:
                tot[3] += self_s
                tot[6] += cache == "miss"
        name = program_name(fun)
        keep = kind != "trace" or duration >= TRACE_EVENT_MIN_S
        ev = None
        with self._lock:
            p = self._programs.get(name)
            if p is None:
                p = self._programs[name] = {
                    "count": 0, "traces": 0, "lowers": 0, "trace_s": 0.0,
                    "lower_s": 0.0, "backend_s": 0.0, "self_s": 0.0,
                    "saved_s": 0.0, "hit": 0, "miss": 0, "off": 0}
            p["self_s"] += self_s
            if kind == "backend":
                p["count"] += 1
                p["backend_s"] += duration
                p["saved_s"] += saved
                p[cache] += 1
            else:
                p[kind + "s"] += 1
                p[kind + "_s"] += duration
            if keep and len(self._events) < MAX_EVENTS:
                ev = {"fun": name, "kind": kind, "start": start, "end": end,
                      "thread": threading.get_ident(), "cache": cache,
                      "ringed": False}
                self._events.append(ev)
            elif keep:
                self.dropped += 1
        if ev is not None:
            ev["ringed"] = _ring(self._tracer_of(), ev)
        return name

    # -- readout ---------------------------------------------------------
    def totals(self) -> CompileTotals:
        """The calling thread's clock: it stands still while nothing
        compiles and while no hold is taken."""
        return CompileTotals(*self._local.totals)

    def programs(self) -> Dict[str, dict]:
        """By program name, every event since the first hold, whatever
        :meth:`events` dropped or left out: ``count`` (backend
        compiles), ``traces``, ``lowers``, ``trace_s`` / ``lower_s`` /
        ``backend_s`` as jax reports them (a program's trace holds its
        callees' traces), ``self_s`` (all three kinds less what lay
        inside: these sum to the threads' clocks), the cache outcomes
        ``hit`` / ``miss`` / ``off`` of its backend compiles, and
        ``saved_s`` (``compile_time_saved_sec`` behind its hits)."""
        with self._lock:
            return {name: dict(p) for name, p in self._programs.items()}

    def events(self) -> List[dict]:
        """The kept events in the order they closed: ``fun``, ``kind``
        (``trace`` / ``lower`` / ``backend``), ``start``, ``end``
        (monotonic), ``thread``, ``cache`` (a backend event's ``hit`` /
        ``miss`` / ``off``), ``ringed``."""
        with self._lock:
            return list(self._events)

    def slow_misses(self, since: float = 0.0) -> List[dict]:
        """The kept backend compiles of :data:`SLOW_MISS_S` or more that
        missed the persistent cache and started at or after ``since``."""
        return [{"fun": e["fun"], "backend_s": e["end"] - e["start"]}
                for e in self.events()
                if e["cache"] == "miss" and e["start"] >= since
                and e["end"] - e["start"] >= SLOW_MISS_S]


def _ring(tracer, ev: dict) -> bool:
    """A compile event as a ring event with its own start and duration;
    False where the ring is off."""
    if not tracer.enabled:
        return False
    attrs = {"fun": ev["fun"]}
    if ev["cache"] is not None:
        attrs["cache"] = ev["cache"]
    tracer.record_closed("compile." + ev["kind"], ev["start"],
                         ev["end"] - ev["start"], tid=ev["thread"], **attrs)
    return True


class SetupAccount:
    """The ``setup.*`` phases of the job that is starting, and the one
    row that says where its set-up went.  A process's first job counts
    from the package's import (:data:`T0`); a later one in the same
    process from its ``begin()``, or from its first phase.  Set-up is
    one thread's: the thread that builds the trainer runs its loop."""

    def __init__(self, watch: CompileWatch, tracer_of):
        self._watch = watch
        self._tracer_of = tracer_of
        self._fresh = True
        self._origin: Optional[float] = T0
        self._totals0 = CompileTotals()
        self._programs0: Dict[str, dict] = {}
        self._phases: List[dict] = []
        self._open: List[dict] = []

    def _reset(self, origin: Optional[float]) -> None:
        self._fresh = False
        self._origin = origin
        self._totals0 = self._watch.totals()
        self._programs0 = self._watch.programs()
        self._phases = []

    def begin(self) -> None:
        """``launch.main``: a job starts.  What an earlier job of this
        process left behind is dropped."""
        if self._fresh:
            self._fresh = False
        else:
            self._reset(time.monotonic())

    def imported(self) -> None:
        """The end of ``launch.py``'s import block: ``setup.import`` is
        two stamps and no span, for it starts before there is one."""
        if not self._fresh or self._phases:
            return
        cpu = time.thread_time() - C0 \
            if threading.get_ident() == _THREAD0 else 0.0
        self._close({"name": "setup.import", "inner_s": 0.0}, T0,
                    time.monotonic(), cpu, self._watch.totals().seconds,
                    ringed=False)

    def _close(self, rec: dict, start: float, end: float, cpu: float,
               compile_s: float, ringed: bool) -> None:
        own = (end - start) - compile_s     # children included
        rec.update(start=start, end=end, cpu_s=cpu, compile_s=compile_s,
                   self_s=own - rec.pop("inner_s"), ringed=ringed)
        if self._open:
            self._open[-1]["inner_s"] += own
        rec["top"] = not self._open
        self._phases.append(rec)
        del self._phases[:-_MAX_PHASES]

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        """``obs.timed(name)`` (ring, annotation and CPU clock come with
        it) that the account keeps."""
        before = self._watch.totals()
        if self._origin is None:
            self._reset(time.monotonic())
        rec = {"name": name, "inner_s": 0.0}
        self._open.append(rec)
        sp = self._tracer_of().timed(name, **attrs)
        try:
            with sp:
                yield sp
        finally:
            self._open.pop()
            self._close(rec, sp.start, sp.end, sp.cpu,
                        self._watch.totals().seconds - before.seconds,
                        ringed=sp._record)

    def row(self, begins: List[tuple], steady: tuple) -> dict:
        """The ``setup`` row, once set-up is over, on the thread that
        ran it.  ``begins``: the ``(monotonic start, CompileTotals)`` of
        the first iterations (at most eight are reported), ``steady``
        that of the first iteration that compiled nothing.  Phases and
        compile events that closed before the ring was there are
        written into it now; the account starts anew."""
        first, end = begins[0], steady
        if self._origin is None:
            self._origin, self._totals0 = first[0], first[1]
        origin = self._origin
        tot = end[1].since(self._totals0)
        phases: Dict[str, dict] = {}
        in_loop = 0.0
        for p in self._phases:
            if p["end"] > end[0]:
                continue
            acc = phases.setdefault(p["name"], {
                "at_s": p["start"] - origin, "wall_s": 0.0, "s": 0.0,
                "compile_s": 0.0, "cpu_s": 0.0})
            acc["wall_s"] += p["end"] - p["start"]
            acc["s"] += p["self_s"]
            acc["compile_s"] += p["compile_s"]
            acc["cpu_s"] += p["cpu_s"]
            if p["top"] and p["start"] >= first[0]:
                in_loop += p["end"] - p["start"] - p["compile_s"]
        total = end[0] - origin
        warm_run = (end[0] - first[0]) \
            - end[1].since(first[1]).seconds - in_loop
        parts = sum(p["s"] for p in phases.values()) + tot.seconds + warm_run
        row = {
            "setup": 1, "total_s": total, "phases": phases,
            "trace_s": tot.trace_s, "lower_s": tot.lower_s,
            "compile_s": tot.compile_s, "load_s": tot.load_s,
            "programs": tot.programs, "hits": tot.hits,
            "misses": tot.misses, "warm_run_s": warm_run,
            "unaccounted_s": total - parts,
            "iteration_starts_s": [b[0] - origin for b in begins[:8]],
            "top": self._top(), "missed": self._watch.slow_misses(origin),
            "events_dropped": self._watch.dropped,
        }
        self._replay(origin)
        self._reset(None)
        return row

    def _top(self, n: int = 10) -> List[dict]:
        """The ``n`` programs with the most seconds since the origin."""
        out = []
        for name, p in self._watch.programs().items():
            was = self._programs0.get(name, {})
            d = {k: v - was.get(k, 0) for k, v in p.items()}
            s = d["trace_s"] + d["lower_s"] + d["backend_s"]
            if s > 0:
                out.append((s, {
                    "fun": name, "count": d["count"],
                    "trace_s": d["trace_s"], "lower_s": d["lower_s"],
                    "backend_s": d["backend_s"],
                    "cache": {k: d[k] for k in ("hit", "miss", "off")
                              if d[k]}}))
        out.sort(key=lambda pair: -pair[0])
        return [d for _, d in out[:n]]

    def _replay(self, origin: float) -> None:
        tracer = self._tracer_of()
        if not tracer.enabled:
            return
        for p in self._phases:
            if not p["ringed"]:
                tracer.record_closed(p["name"], p["start"],
                                     p["end"] - p["start"], cpu=p["cpu_s"])
        for ev in self._watch.events():
            if not ev["ringed"] and ev["start"] >= origin:
                ev["ringed"] = _ring(tracer, ev)
