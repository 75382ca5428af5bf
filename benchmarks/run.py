"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It finds everything by
name: ``cells/<cell>.json`` names a configuration
(``configs/<config>.json``), a traffic mix or job
(``traffic/<mix>.json``) and a runner (``runners/<runner>.py``);
``BENCHMARK.json`` says which metrics the cell reports, and each
per-layer metric is read by ``layer_metrics/<metric>.py`` or, where
several cells' metrics are read the same way, by the file named after
the part before the first dot (``device_idle_pct.train`` ->
``layer_metrics/device_idle_pct.py``).  There is no list of cells,
configurations, mixes, runners or metrics in code.

Without a TPU, or with another chip count than the cell's, it exits
non-zero and prints no result.  The last line of standard output is the
one JSON object of the contract: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  Everything else
(walls, cache hits, the end-to-end reading of a traced run) is on
earlier lines.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
_LIBS: Dict[str, Any] = {}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def lib(name: str):
    """A module of the benchmark's own directory, by file name."""
    if name not in _LIBS:
        _LIBS[name] = load_module(os.path.join(HERE, name + ".py"),
                                  "orionbench_" + name)
    return _LIBS[name]


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise lib("harness").BenchFailure(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Rehearsal:
    """How tests/bench rehearse a runner on the CPU: a tiny
    configuration and mix handed over in Python, the device check
    steered, a manifest that lists cells the real one does not hold yet,
    and the tests' own reading of a trace recorded without a device.
    There is no way to ask for this from the command line."""
    config: Optional[dict] = None
    traffic: Optional[dict] = None
    device: Optional[dict] = None      # reported instead of judging jax's
    require_kernels: bool = False
    manifest: Optional[dict] = None
    reduce_trace: Optional[Callable[[str, int], dict]] = None


@dataclasses.dataclass
class Context:
    name: str
    cell: dict
    config: dict
    traffic: dict
    manifest: dict
    seed: int
    seconds: float
    trace: bool
    t_process_start: float
    out_dir: str
    device: dict
    require_kernels: bool = True

    def lib(self, name: str):
        return lib(name)


def metrics_of(manifest: dict, group: str, cell: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader_of(metric: str):
    """The per-layer reader of ``metric``: the file of that name, else
    the file named after the part before the first dot."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(HERE, "layer_metrics", stem + ".py")
        if os.path.isfile(path):
            return load_module(path, "orionbench_metric_"
                               + stem.replace(".", "_"))
    raise lib("harness").BenchFailure(f"no reader for {metric!r}")


def context(workload: str, seed: int, seconds: float, trace: bool, t0: float,
            manifest: dict, rehearsal: Optional[Rehearsal] = None):
    """The cell's files, found by name; the device checked; the compile
    cache placed.  Returns (Context, the runner's module)."""
    h = lib("harness")
    cell = load_json("cells", workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearsal is not None:
        config = rehearsal.config or config
        traffic = rehearsal.traffic or traffic
    runner = load_module(os.path.join(HERE, "runners",
                                      cell["runner"] + ".py"),
                         "orionbench_runner_" + cell["runner"])
    setup = h.prepare()
    if rehearsal is None:
        h.watch_jax()
    if rehearsal is not None and rehearsal.device is not None:
        device = dict(rehearsal.device, count=cell["chips"])
    else:
        device = h.require_device(cell["chips"])
    h.note(phase="setup", cell=workload, device=device, **setup)
    return Context(
        name=workload, cell=cell, config=config, traffic=traffic,
        manifest=manifest, seed=seed, seconds=seconds, trace=trace,
        t_process_start=t0,
        out_dir=os.path.join(REPO, "chiprun_out", "bench", workload),
        device=device,
        require_kernels=(rehearsal is None or rehearsal.require_kernels)
    ), runner


def main(argv=None, rehearsal: Optional[Rehearsal] = None,
         t_process_start: Optional[float] = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = T_PROCESS_START if t_process_start is None else t_process_start
    h = lib("harness")

    manifest = rehearsal.manifest if rehearsal is not None else None
    if manifest is None:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise h.BenchFailure(f"{args.workload!r} is not a cell of "
                             "BENCHMARK.json")
    ctx, runner = context(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0, manifest, rehearsal)
    cell, device = ctx.cell, ctx.device
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise h.BenchFailure(
                f"cells/{args.workload}.json and BENCHMARK.json disagree "
                f"on {key!r}: {cell[key]!r} vs {entry[key]!r}")
    res = runner.run(ctx)

    end_to_end = dict(res["end_to_end"])
    end_to_end["setup_s"] = res["window"].setup_s
    e2e = {}
    for m in metrics_of(manifest, "end_to_end", args.workload):
        if m["name"] not in end_to_end:
            raise h.BenchFailure(f"runner {cell['runner']!r} reported no "
                                 f"{m['name']!r}")
        e2e[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=h.memory_peak_bytes())
    line: Dict[str, Any] = {
        "correct": bool(res["correct"]), "attempted": int(res["attempted"]),
        "failed": int(res["failed"])}
    h.note(phase="result_detail", cell=args.workload,
           end_to_end={k: v["value"] for k, v in e2e.items()},
           why_incorrect=res.get("why_incorrect", []),
           info=res.get("info", {}),
           compile_cache_hits=h.CACHE_EVENTS[
               "/jax/compilation_cache/cache_hits"],
           compile_cache_misses=h.CACHE_EVENTS[
               "/jax/compilation_cache/cache_misses"])

    if not ctx.trace:
        line["metrics"] = e2e
    else:
        xplane = res["tracer"].xplane_path()
        if xplane is None:
            raise h.BenchFailure("the traced run left no xplane file")
        reduce_trace = lib("trace_reduce").reduce_file
        if rehearsal is not None and rehearsal.reduce_trace is not None:
            reduce_trace = rehearsal.reduce_trace
        reduced = reduce_trace(xplane, cell["chips"])
        with open(os.path.join(ctx.out_dir, "trace_reduced.json"), "w") as f:
            json.dump(reduced, f, indent=1, default=float)
        per_layer = {}
        for m in metrics_of(manifest, "per_layer", args.workload):
            value = reader_of(m["name"]).read(
                reduced, res.get("counters", {}), ctx)
            if value is not None:
                per_layer[m["name"]] = {"value": float(value),
                                        "unit": m["unit"]}
        if not reduced["busy_s"] > 0:
            raise h.BenchFailure("no operation ran on the device in the "
                                 "traced window")
        line["metrics"] = per_layer
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
    line["device"] = device
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
