"""``benchmarks/trace_reduce.py`` on a small recorded trace.

``fixtures/ppo1b_trace_slice.json.gz`` is 0.58 s cut from the first chip
trace of ``ppo1b-sync`` (TPU v5e, PR 24), in the plain form ``load``
gives: one policy+value forward and one reference forward whole, the
head of an update program clipped by the window, the glue programs
between them, and the host threads with the harness's spans.  The
reduction's busy time, self times and program statistics are checked
against independent arithmetic here; ``load`` itself is checked on a
trace recorded by the test on the CPU."""

import gzip
import json
import os

import numpy as np
import pytest

import bench_rehearsal as br

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "ppo1b_trace_slice.json.gz")


@pytest.fixture(scope="module")
def planes():
    with gzip.open(FIXTURE) as f:
        return json.loads(f.read())


@pytest.fixture(scope="module")
def tr():
    return br.lib("trace_reduce")


def _device_ops(planes):
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    return next(ln["events"] for ln in dev["lines"]
                if ln["name"] == "XLA Ops")


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 1 << 20


def test_busy_and_idle_against_a_raster(planes, tr):
    r = tr.reduce(planes, 1)
    lo, hi = tr.find_window(planes)
    assert r["window_s"] == pytest.approx(0.58)
    # independent: paint every operation onto a 1 us raster
    n = int(round((hi - lo) / 1e3))
    busy = np.zeros(n + 1, bool)
    for _, start, dur, _ in _device_ops(planes):
        a = int(np.floor((max(start, lo) - lo) / 1e3))
        b = int(np.ceil((min(start + dur, hi) - lo) / 1e3))
        if b > a:
            busy[a:b] = True
    raster_s = busy[:n].sum() * 1e-6
    assert r["busy_s"] == pytest.approx(raster_s, rel=2e-3)
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"])
    assert 0.97 < r["busy_s"] / r["window_s"] < 0.99   # 97.8% busy
    # self times of all operations add up to the busy time
    assert sum(r["by_kind_s"].values()) == pytest.approx(r["busy_s"],
                                                         rel=1e-6)


def test_programs_whole_executions_only(planes, tr):
    r = tr.reduce(planes, 1)
    prog = r["by_program"]
    assert prog["jit__lp_values_fwd"]["median_s"] == pytest.approx(
        0.27233747)
    assert prog["jit__logprobs_fn"]["median_s"] == pytest.approx(
        0.264880636)
    assert prog["jit__lp_values_fwd"]["runs"] == 1
    # the update program starts inside the slice and runs past its end:
    # not a whole execution, so it gives no statistics
    assert "jit__epochs_fn" not in prog
    assert tr.program(r, r"_epochs_fn") is None
    s, runs = tr.programs(r, r"_lp_values_fwd|_logprobs_fn")
    assert runs == 2 and s == pytest.approx(0.27233747 + 0.264880636)


def test_custom_calls_are_the_pallas_kernels(planes, tr):
    r = tr.reduce(planes, 1)
    # custom calls contain no other operation: self time = duration
    lo, hi = tr.find_window(planes)
    want = sum(min(s + d, hi) - max(s, lo)
               for name, s, d, _ in _device_ops(planes)
               if tr.op_kind(name) == "custom_call") / 1e9
    assert r["custom_call_s"] == pytest.approx(want, rel=1e-6)
    assert 0.07 < r["custom_call_s"] / r["busy_s"] < 0.08
    labels = [n for n, _ in r["top_ops"] if "custom-call" in n]
    assert labels and all("tpu_custom_call" in n for n in labels)


def test_breakdown_shape_and_gap_labels(planes, tr):
    r = tr.reduce(planes, 1)
    b = r["breakdown"]
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("fusion.18")
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the longest idle stretch sits under the host's blocking fetch
    assert b["idle_gaps"][0][0].startswith("np.asarray(jax.Array)")
    assert sum(s for _, s in r["longest_gaps"]) <= r["idle_s"] + 1e-9


def test_self_times_and_union(tr):
    ev = [["outer", 0.0, 100.0, {}], ["a", 10.0, 20.0, {}],
          ["b", 40.0, 30.0, {}], ["b1", 45.0, 5.0, {}],
          ["next", 100.0, 10.0, {}]]
    assert tr.self_times(ev) == [50.0, 20.0, 25.0, 5.0, 10.0]
    total, merged = tr.union_s([(0, 10), (5, 20), (30, 40), (40, 41)])
    assert total == 31 and merged == [[0, 20], [30, 41]]


def test_op_label_and_kind(tr):
    text = ('%attn.42 = (f32[16,8,384,256]{3,2,1,0:T(8,128)}, f32[16,8]{1,0}) '
            'custom-call(bf16[16,8,384,256]{3,2,1,0} %fusion.9), '
            'custom_call_target="tpu_custom_call", operand_layout')
    label = tr.op_label(text)
    assert label == "attn.42 custom-call:tpu_custom_call f32[16,8,384,256]"
    assert tr.op_kind(label) == "custom_call"
    fused = ('%fusion.657 = bf16[16,384,2048]{2,1,0:T(8,128)(2,1)S(1)} '
             'fusion(bf16[16,384,8192]{2,1,0} %gte.1, u16[8]{0} '
             '%custom-call.3), kind=kOutput, calls=%fused_computation.295')
    # an operand that IS a custom call does not make the fusion one
    assert tr.op_kind(tr.op_label(fused)) == "fusion"
    assert tr.op_label(fused) == "fusion.657 fusion bf16[16,384,2048]"
    assert tr.program_name("jit__epochs_fn(9655771210514051792)") == \
        "jit__epochs_fn"


def test_load_reads_a_trace_recorded_here(tmp_path, tr):
    """``load`` on a real ``.xplane.pb`` (the CPU backend's): the
    harness's window span is found."""
    import glob

    import jax
    import jax.numpy as jnp

    h = br.lib("harness")
    tracer = h.Tracer(True, str(tmp_path / "trace"))
    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    tracer.start()
    with tracer.annotate("layer_call"):
        for _ in range(3):
            f(jnp.ones((64, 64))).block_until_ready()
    tracer.stop()
    path = tracer.xplane_path()
    assert path in glob.glob(str(tmp_path / "trace/plugins/profile/*/*.pb"))
    loaded = tr.load(path, keep_stats=("hlo_module", "run_id"))
    lo, hi = tr.find_window(loaded)
    assert 0 < (hi - lo) / 1e9 < 30
    names = {e[0] for p in loaded for ln in p["lines"] for e in ln["events"]}
    assert {"bench_window", "layer_call"} <= names
    # no device plane: the reduction refuses, it does not read host
    # events as the device's; the tests' stand-in is handed in
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        tr.reduce(loaded, 1)
    r = tr.reduce(loaded, 1, streams=br.host_ops_as_device(loaded))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any("lambda" in k for k in r["by_program"])
    assert br.reduce_cpu_trace(path, 1)["busy_s"] == r["busy_s"]
