"""From the profiler's ``.xplane.pb`` to the numbers the per-layer
readers use.

Two steps, so that the second can be checked on a small recorded trace
(``tests/bench/fixtures``):

``load(path)``   the xplane file as plain Python: planes -> lines ->
                 events ``[name, start_ns, duration_ns, stats]``, read
                 with ``jax.profiler.ProfileData`` and nothing else.
``reduce(planes, n_chips)``   busy and idle time of the device, device
                 time by program (XLA module) and by operation (self
                 time: an operation's time less the operations nested
                 in it), the share inside custom calls (Pallas kernels
                 are ``tpu_custom_call``), the top operations, and the
                 idle gaps labelled by the host span that covered them.

What a TPU trace looks like (first chip trace, PR 24, jax 0.9.0): one
plane ``/device:TPU:<i>`` per chip with the lines ``XLA Modules`` (one
event per program execution, named ``<module>(<fingerprint>)``),
``XLA Ops`` (one event per HLO operation, nested where an operation
contains others, e.g. a ``while``; the event's NAME is the whole HLO
instruction text, ``%fusion.657 = bf16[16,384,2048]{...} fusion(...)``,
and it carries no category or scope stat), ``Async XLA Ops`` and
``Steps`` (not used).  A Pallas kernel is an instruction with the
opcode ``custom-call`` and ``custom_call_target="tpu_custom_call"``; its
instruction name comes from the traced function (``%attn.42``), not
from a kernel name, so the flash forward, the flash backward and the
paged decode kernels cannot be told apart by name today.  Host threads
are lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans are
events of the line ``python3`` there, on the same clock.  The traced
window is the span ``bench_window`` that the harness opens right after
``start_trace`` and closes right before ``stop_trace``.  An execution
that was running when the trace started is recorded from the trace's
start, so per-execution times are MEDIANS, not means.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_window"
SHORT_GAP_NS = 20e3     # idle under 20 us is between two operations
DEVICE_LINES = (OPS_LINE, MODULES_LINE)
OPCODE = re.compile(r"[\]\}\)] ([a-z][\w\-]*)\(")
RESULT = re.compile(r" = \(?([a-z]+[0-9]*\[[0-9,]*\])")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
# host events that are the profiler's or the runtime's own bookkeeping
# and say nothing about what the host was doing
HOST_NOISE = re.compile(
    r"^(ThreadpoolListener|TaskDispatcher|\$|bench_window$)")


def load(path: str, keep_stats: Tuple[str, ...] = ()) -> list:
    """The xplane file as plain Python.  Device events need no stats (a
    TPU event's name is the whole instruction); ``keep_stats`` names the
    stats to keep on host events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            is_ops = device and line.name == OPS_LINE
            events = []
            for e in line.events:
                stats = {}
                if not device and keep_stats:
                    for k, v in e.stats:
                        if k in keep_stats:
                            stats[k] = v if isinstance(v, (int, float)) \
                                else str(v)[:120]
                events.append([op_label(e.name) if is_ops else e.name[:120],
                               float(e.start_ns), float(e.duration_ns),
                               stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_label(hlo_text: str) -> str:
    """``%fusion.657 = bf16[16,384,2048]{...} fusion(...)`` ->
    ``fusion.657 fusion bf16[16,384,2048]``; a custom call also gets its
    target: ``attn.42 custom-call:tpu_custom_call f32[16,8,384,256]``.
    Text that is not an HLO instruction is kept (cut to 120)."""
    if " = " not in hlo_text:
        return hlo_text[:120]
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    op = OPCODE.search(hlo_text)
    opcode = op.group(1) if op else "?"
    if opcode == "custom-call":
        target = TARGET.search(hlo_text)
        opcode += ":" + (target.group(1) if target else "?")
    shape = RESULT.search(hlo_text)
    return f"{name} {opcode} {shape.group(1) if shape else ''}".strip()


def union_s(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length (ns) of the union of [start, end) intervals and the
    merged intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(events: list) -> List[float]:
    """Self time (ns) of each event of one line: its duration less the
    events nested inside it.  ``events`` sorted by start."""
    out = [e[2] for e in events]
    stack: List[int] = []
    for i, (_, start, dur, _) in enumerate(events):
        while stack and start >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]] -= dur
        stack.append(i)
    return [max(0.0, x) for x in out]


def _median(xs: list) -> float:
    xs = sorted(xs)
    n = len(xs)
    return 0.5 * (xs[(n - 1) // 2] + xs[n // 2]) if n else 0.0


def program_name(event_name: str) -> str:
    """``jit__epochs_fn(123456789)`` -> ``jit__epochs_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_kind(label: str) -> str:
    """The opcode of an operation's label; every custom call is one
    kind, ``custom_call``."""
    parts = label.split(" ")
    if len(parts) < 2:
        return "other"
    return "custom_call" if parts[1].startswith("custom-call") \
        else parts[1]


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for name, start, dur, stats in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s, stats])
    return out


def find_window(planes: list) -> Tuple[float, float]:
    """The ``bench_window`` span; else the extent of everything."""
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
                lo, hi = min(lo, start), max(hi, start + dur)
    return lo, hi


def host_spans(planes: list, lo: float, hi: float) -> list:
    """[(start, end, label)] of host events long enough to explain a
    gap, labelled ``<event> @<thread>``."""
    spans = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            thread = line["name"].split("/")[0]
            for name, start, dur, _ in line["events"]:
                if dur < 20e3 or HOST_NOISE.match(name):
                    continue
                if start + dur <= lo or start >= hi:
                    continue
                spans.append((start, start + dur, f"{name} @{thread}"))
    return spans


def label_gap(gap: Tuple[float, float], spans: list) -> str:
    """The shortest host span that covers at least half of the gap,
    else the one that overlaps it most."""
    g0, g1 = gap
    best_cover, best_overlap = None, None
    for s, e, label in spans:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        if ov >= 0.5 * (g1 - g0):
            if best_cover is None or e - s < best_cover[0]:
                best_cover = (e - s, label)
        if best_overlap is None or ov > best_overlap[0]:
            best_overlap = (ov, label)
    if best_cover:
        return best_cover[1]
    return best_overlap[1] if best_overlap else "no host span"


def device_streams(planes: list) -> list:
    """[(device id, operation events, program events)], one per device
    plane of the trace."""
    streams = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            streams.append((m.group(2), lines.get(OPS_LINE, []),
                            lines.get(MODULES_LINE, [])))
    return streams


def reduce(planes: list, n_chips: int = 1, streams: list = None) -> dict:
    """``streams`` defaults to the trace's device planes, and a trace
    without one is an error: nothing here reads host events as the
    device's.  (tests/bench hand in streams of their own making for
    traces recorded on the CPU.)"""
    lo, hi = find_window(planes)
    window_s = (hi - lo) / 1e9
    devices: Dict[str, dict] = {}
    if streams is None:
        streams = device_streams(planes)
    if not streams:
        raise ValueError("the trace has no /device:TPU plane: " + ", ".join(
            p["name"] for p in planes))
    for dev_id, all_ops, all_mods in streams:
        ops = _clip(sorted(all_ops, key=lambda e: e[1]), lo, hi)
        mods = _clip(all_mods, lo, hi)
        # program statistics from executions that lie WHOLLY inside the
        # window: a clipped one would bias the mean duration
        whole = [e for e in all_mods if e[1] >= lo and e[1] + e[2] <= hi]
        busy_ns, merged = union_s([(s, s + d) for _, s, d, _ in ops]
                                  or [(s, s + d) for _, s, d, _ in mods])
        selfs = self_times(ops)
        by_op: Dict[str, float] = {}
        by_kind: Dict[str, float] = {}
        for (name, _, _, _), st in zip(ops, selfs):
            by_op[name] = by_op.get(name, 0.0) + st
            kind = op_kind(name)
            by_kind[kind] = by_kind.get(kind, 0.0) + st
        by_program: Dict[str, dict] = {}
        for name, start, dur, _ in whole:
            p = by_program.setdefault(program_name(name),
                                      {"durs": [], "starts": []})
            p["durs"].append(dur / 1e9)
            p["starts"].append((start - lo) / 1e9)
        for p in by_program.values():
            starts = sorted(p.pop("starts"))
            durs = p.pop("durs")
            gaps = [b - a for a, b in zip(starts, starts[1:])]
            p.update(s=sum(durs), runs=len(durs), median_s=_median(durs),
                     period_s=_median(gaps) if gaps else None)
        gaps, cursor = [], lo
        for s, e in merged:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            gaps.append((cursor, hi))
        devices[dev_id] = {
            "busy_s": busy_ns / 1e9, "by_program": by_program,
            "by_op_s": {k: v / 1e9 for k, v in by_op.items()},
            "by_kind_s": {k: v / 1e9 for k, v in by_kind.items()},
            "gaps": gaps, "n_ops": len(ops),
        }

    used = sorted(devices, key=int)[:max(1, n_chips)]
    n = max(1, len(used))

    def mean_of(key: str) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for d in used:
            for k, v in devices[d][key].items():
                out[k] = out.get(k, 0.0) + v / n
        return out

    by_program: Dict[str, dict] = {}
    for d in used:
        for k, v in devices[d]["by_program"].items():
            p = by_program.setdefault(
                k, {"s": 0.0, "runs": 0.0, "median_s": 0.0,
                    "period_s": v["period_s"]})
            p["s"] += v["s"] / n
            p["runs"] += v["runs"] / n
            p["median_s"] += v["median_s"] / n
    by_op = mean_of("by_op_s")
    by_kind = mean_of("by_kind_s")
    busy_s = sum(devices[d]["busy_s"] for d in used) / n if used else 0.0

    spans = sorted(host_spans(planes, lo, hi))
    idle: Dict[str, float] = {}
    longest = []
    for d in used:
        active: list = []
        nxt = 0
        for g in devices[d]["gaps"]:            # in time order
            if g[1] - g[0] < SHORT_GAP_NS:
                label = "between operations (<20us)"
            else:
                while nxt < len(spans) and spans[nxt][0] < g[1]:
                    active.append(spans[nxt])
                    nxt += 1
                active = [sp for sp in active if sp[1] > g[0]]
                label = label_gap(g, active)
            idle[label] = idle.get(label, 0.0) + (g[1] - g[0]) / 1e9 / n
            longest.append(((g[1] - g[0]) / 1e9, label))
    longest.sort(reverse=True)

    def top(d: Dict[str, float], k: int = 10) -> list:
        return [[name, s] for name, s in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    return {
        "window_s": window_s, "busy_s": busy_s, "chips": len(used),
        "idle_s": max(0.0, window_s - busy_s),
        "by_program": by_program, "by_kind_s": by_kind,
        "custom_call_s": by_kind.get("custom_call", 0.0),
        "top_ops": top(by_op, 40),
        "longest_gaps": [[label, s] for s, label in longest[:20]],
        "per_device_busy_s": {d: devices[d]["busy_s"] for d in used},
        "breakdown": {"device_ops": top(by_op, 10),
                      "idle_gaps": top(idle, 10)},
    }


def programs(trace: dict, pattern: str) -> Tuple[float, float]:
    """(device seconds, executions) of the programs whose name matches
    ``pattern``, from executions wholly inside the traced window."""
    s = runs = 0.0
    for name, p in trace["by_program"].items():
        if re.search(pattern, name):
            s += p["s"]
            runs += p["runs"]
    return s, runs


def program(trace: dict, pattern: str):
    """The one program matching ``pattern`` that took most device time:
    ``{"s", "runs", "median_s", "period_s"}``, or None."""
    found = [p for name, p in trace["by_program"].items()
             if re.search(pattern, name)]
    return max(found, key=lambda p: p["s"]) if found else None


def reduce_file(path: str, n_chips: int = 1) -> dict:
    return reduce(load(path), n_chips)
