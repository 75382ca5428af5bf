"""Shared by the tests in this directory: loads ``benchmarks/run.py`` by
path and builds the tiny configuration, job and mixes of the CPU
rehearsals.  The tiny shapes exist only here: the benchmark's command
line cannot ask for them."""

import copy
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
# The device the rehearsals REPORT (steered, never judged): a kind that
# peaks.json knows, so the MFU reader has a peak to divide by.  Nothing a
# rehearsal prints is a measurement.
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite"}


def load(path, name):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def run_module():
    return load(os.path.join(BENCH, "run.py"), "orionbench_run")


def lib(name):
    return run_module().lib(name)


def read_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# The serve runner's cells are not proven on the chip yet, so
# BENCHMARK.json does not hold them; the runner and its readers are
# rehearsed all the same, under the entries they would have there.
_LAYER = "rollout, continuous (rollout/continuous.py, runtime/scheduler.py)"
UNPROVEN = {
    "workloads": ["serve1b-arrivals", "gen7b-closed"],
    "end_to_end": [("ttft_p95_ms", "ms", ["serve1b-arrivals"]),
                   ("tpot_p95_ms", "ms", ["serve1b-arrivals"]),
                   ("gen_tokens_per_s", "tokens/s", ["gen7b-closed"])],
    "per_layer": [
        ("queue_wait_p95_ms.serve", "ms", "program_counter",
         "serving edge (orchestration/gateway.py)", "ttft_p95_ms"),
        ("prefix_hit_pct.serve", "%", "program_counter", _LAYER,
         "ttft_p95_ms"),
        ("decode_step_ms.serve", "ms", "device_trace", _LAYER,
         "tpot_p95_ms"),
        ("device_idle_pct.serve", "%", "device_trace", "device",
         "tpot_p95_ms"),
        ("decode_step_ms.gen", "ms", "device_trace", _LAYER,
         "gen_tokens_per_s"),
        ("useful_row_pct.gen", "%", "program_counter", _LAYER,
         "gen_tokens_per_s"),
        ("prefill_share_pct.gen", "%", "device_trace",
         "model (models/transformer.py)", "gen_tokens_per_s"),
        ("custom_call_pct.gen", "%", "device_trace", "kernels (ops/pallas)",
         "gen_tokens_per_s"),
        ("device_idle_pct.gen", "%", "device_trace", "device",
         "gen_tokens_per_s"),
    ],
}


def manifest_with_unproven():
    m = manifest()
    have = {x["name"] for g in ("workloads", "end_to_end", "per_layer")
            for x in m[g]}
    for name in UNPROVEN["workloads"]:
        if name not in have:
            cell = read_json("cells", name + ".json")
            m["workloads"].append({k: cell[k] for k in (
                "name", "config", "traffic", "chips", "why")})
    for name, unit, cells in UNPROVEN["end_to_end"]:
        if name not in have:
            m["end_to_end"].append({
                "name": name, "unit": unit, "better": "lower", "bound": 0.1,
                "source": "host_clock", "workloads": cells})
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for name, unit, source, layer, moves in UNPROVEN["per_layer"]:
        if name not in have:
            m["per_layer"].append({
                "name": name, "unit": unit, "better": "lower",
                "source": source, "layer": layer, "moves": moves,
                "workloads": e2e[moves]["workloads"]})
    return m


def host_ops_as_device(planes):
    """For a trace recorded on the CPU backend, which has no device
    plane: XLA's CPU operations are host events that carry an
    ``hlo_module`` stat.  Here, and only here, they stand in as a
    device's operations, with one pseudo-execution per (module, run_id)
    as its programs."""
    ops, runs = [], {}
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                mod = ev[3].get("hlo_module")
                if mod is None:
                    continue
                ops.append(ev)
                key = (mod, ev[3].get("run_id"))
                a, b = runs.get(key, (ev[1], ev[1] + ev[2]))
                runs[key] = (min(a, ev[1]), max(b, ev[1] + ev[2]))
    mods = [[f"{mod}", a, b - a, {}] for (mod, _), (a, b) in runs.items()]
    return [("0", ops, sorted(mods, key=lambda e: e[1]))]


def reduce_cpu_trace(path, n_chips):
    tr = lib("trace_reduce")
    planes = tr.load(path, keep_stats=("hlo_module", "run_id"))
    return tr.reduce(planes, n_chips, streams=host_ops_as_device(planes))


def tiny_config(cell):
    cfg = read_json("configs", read_json("cells", cell + ".json")["config"]
                    + ".json")
    return dict(
        cfg, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, vocab_size=260,
        launch=["model_preset=tiny", "model.arch=neox",
                "model.num_kv_heads=4", "model.rotary_pct=0.25",
                "model.use_parallel_residual=true", "model.attn_bias=true",
                "model.mlp_bias=true", "model.max_seq_len=128",
                # float32 activations: the reference check's error model
                # counts bfloat16 roundings through 12-16 wide layers and
                # is off twofold at 2 layers of 64 (16 tokens compared)
                "model.dtype=float32"])


def tiny_traffic(cell):
    mix = copy.deepcopy(read_json(
        "traffic", read_json("cells", cell + ".json")["traffic"] + ".json"))
    engine = ["rollout.max_batch_size=4", "rollout.page_size=4",
              "rollout.segment_len=4", "rollout.quantize_weights=true",
              "rollout.quantize_kv=true", "rollout.temperature=1.0"]
    if mix["kind"] == "train_job":
        shaped = ("rollout.max_", "rollout_batch", "minibatch",
                  "model.max_seq")
        mix["launch"] = [k for k in mix["launch"]
                         if not k.startswith(shaped)] + [
            "rollout.max_prompt_len=16", "rollout.max_new_tokens=8",
            "rollout_batch_size=4", "minibatch_size=2",
            "model.max_seq_len=32"]
        mix.update(samples_per_iteration=4, prompt_len=16, new_tokens=8,
                   warmup_iterations=2, trace_after_iterations=1,
                   trace_iterations=2)
    elif mix["loop"] == "open":
        mix.update(
            rate_per_s=8.0, warm_seconds=0.5, drain_seconds=10.0,
            prefix={"count": 3, "tokens": 16, "zipf_s": 1.0},
            prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 32},
            budget={"median": 8, "sigma": 0.8, "min": 2, "max": 24},
            # a pool large enough that no shared prefix is ever evicted:
            # an evicted one would be prefilled whole again, in a span
            # bucket the warm-up (rightly) did not cover
            engine=engine + ["rollout.max_prompt_len=48",
                             "rollout.max_new_tokens=24",
                             "rollout.num_pages=512"],
            trace_after_seconds=0.3, trace_seconds=0.8)
    else:
        mix.update(
            callers=4, group=2, pool_groups=16, warm_completions=6,
            drain_seconds=10.0,
            prompt={"median": 12, "sigma": 0.6, "min": 4, "max": 32},
            budget={"median": 16, "sigma": 1.0, "min": 2, "max": 60},
            engine=engine + ["rollout.max_prompt_len=32",
                             "rollout.max_new_tokens=60"],
            trace_after_seconds=0.3, trace_seconds=0.8)
    return mix


def rehearse(cell, seconds=2.0, trace=0, seed=3000000019):
    """One run of ``cell``'s runner at the tiny shape on the CPU.
    Returns the dict of the last line."""
    import time

    run = run_module()
    return run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        rehearsal=run.Rehearsal(config=tiny_config(cell),
                                traffic=tiny_traffic(cell),
                                device=dict(FAKE_DEVICE),
                                manifest=manifest_with_unproven(),
                                reduce_trace=reduce_cpu_trace),
        t_process_start=time.perf_counter())
