"""CPU rehearsal of ``chip_smoke.py`` so the script cannot rot between
chip runs: its phases at ``ModelConfig.tiny`` through the same entry
points (``launch.main``, ``launch.run_serve`` + ``GatewayClient``), with
the device check steered HERE (the script itself has no switch for it),
plus the two ways it must fail: no TPU, and a phase that raises.

Kernel presence (``tpu_custom_call`` in the lowered programs) cannot
hold on the CPU harness — ``Shape.require_kernels=False`` is the one
difference from the chip run; tests/test_chip_compile.py covers the
kernels through the TPU compiler instead.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = cs.Shape(
    model=["model_preset=tiny"], batch=4, minibatch=2, prompt_len=16,
    new_tokens=8, serve_slots=4, serve_page_size=4,
    serve_requests=[(20, 20), (24, 6), (5, 4), (32, 40), (9, 5),
                    (14, 20), (7, 36), (30, 3)],
    serve_shared=16, require_kernels=False)
FAKE_DEV = {"platform": "tpu", "kind": "steered-by-test", "count": 0}


@pytest.fixture
def steered(monkeypatch):
    """main() with the tiny shape, no device judgement, no rebuild of
    the native scheduler (other xdist workers are using the .so) and
    no process-wide logging/monitoring hooks left behind."""
    monkeypatch.setattr(cs, "FULL", TINY)
    monkeypatch.setattr(cs, "watch_jax", lambda: None)
    monkeypatch.setattr(cs, "require_tpu",
                        lambda n: dict(FAKE_DEV, count=n))
    monkeypatch.setattr(cs, "prepare", lambda: {
        "compile_cache_dir": os.path.join(REPO, ".jax_cache")})


def _rows(capsys):
    out = capsys.readouterr().out
    return [json.loads(ln) for ln in out.splitlines()
            if ln.startswith("{")]


def test_one_chip_phases_rehearsal(steered, capsys):
    cs.main([])
    rows = _rows(capsys)
    assert rows[-1] == {"ok": True, "device": dict(FAKE_DEV, count=1)}
    by_phase = {r["phase"]: r for r in rows[:-1]}
    tr, sv = by_phase["trainer"], by_phase["server"]
    assert tr["iterations"] == 4 and tr["compiles_steady"] == {}
    assert tr["param_delta"] > 0
    assert sv["requests_completed"] == 2 * len(TINY.serve_requests)
    assert sv["prefix_cached_pages"] > 0
    assert sv["compiles_steady"] == {}
    assert sv["scheduler"] in ("native", "PyScheduler")


def test_four_chip_phases_rehearsal(steered, monkeypatch, capsys):
    """--chips 4 runs the cross-chip paths and NO one-chip phase; the
    first four of the harness's eight virtual devices stand in."""
    monkeypatch.setattr(cs, "check_spread", lambda name: [])
    monkeypatch.setattr(cs, "phase_trainer", None)   # must not be called
    monkeypatch.setattr(cs, "phase_server", None)
    cs.main(["--chips", "4"])
    rows = _rows(capsys)
    assert rows[-1] == {"ok": True, "device": dict(FAKE_DEV, count=4)}
    phases = [r["phase"] for r in rows[:-1]]
    assert phases == ["setup", "sharded_fsdp2_tp2", "sharded_vs_single",
                      "async_split", "done"]
    by_phase = {r["phase"]: r for r in rows[:-1]}
    assert by_phase["sharded_fsdp2_tp2"]["compiles_steady"] == {}
    assert by_phase["sharded_vs_single"]["rel_diff"] <= cs.LOSS_RTOL
    assert all(0 <= s <= 1 for s in by_phase["async_split"]["staleness"])
    assert not (set(by_phase["async_split"]["rollout_devices"])
                & set(by_phase["async_split"]["learner_devices"]))


def test_no_tpu_exits_nonzero_without_result(capsys):
    """What ``JAX_PLATFORMS=cpu python chip_smoke.py`` does."""
    with pytest.raises(SystemExit) as e:
        cs.require_tpu(1)
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_raising_phase_fails_the_smoke(steered, monkeypatch, capsys):
    def boom(shape):
        raise RuntimeError("phase made to raise")

    monkeypatch.setattr(cs, "phase_trainer", boom)
    with pytest.raises(RuntimeError, match="made to raise"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out
