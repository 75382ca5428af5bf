"""The small-step expert layer's kernel (ops/pallas/experts_step.py),
interpreted on the CPU, against the einsum form it stands beside
(ops/moe.py::experts_dense), the rule that chooses between them, and the
rollout's count of the stacks its steps read."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import moe
from orion_tpu.ops.pallas import experts_step as es

D, I, K = 128, 256, 2


def _operands(act, H, T, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    parts = moe.ACTIVATIONS[act][1]

    def normal(*shape, scale=1.0):
        return jnp.asarray(rs.standard_normal(shape) * scale, dtype)

    return (normal(T, D), normal(H, D, parts * I, scale=0.1),
            normal(H, I, D, scale=0.1),
            jnp.asarray(rs.uniform(0.1, 1.0, size=(T, K)), jnp.float32))


def _routing(name, H, T, rs):
    """local [T, K] and the held experts some row selects."""
    if name == "every_expert_hit":      # as far as T * K pairs can
        local = (np.arange(T * K) % H).reshape(T, K)
    elif name == "one_hit":
        local = np.full((T, K), H // 2)
        local[:, 1:] = H + 1            # the other choice is held elsewhere
    elif name == "none_hit":
        local = np.where(rs.uniform(size=(T, K)) < 0.5, -3, H + 2)
    elif name == "hits_at_the_ends":
        local = np.where(rs.uniform(size=(T, K)) < 0.5, 0, H - 1)
        local[0] = (0, H - 1)
    else:
        assert name == "outside_and_masked"
        # other chips' experts (negative, or past the held ones) and
        # masked tokens (``local == H``) beside a few held ones
        local = rs.randint(-H, 2 * H, size=(T, K))
        local[rs.uniform(size=T) < 0.4] = H
    hit = sorted({int(e) for e in local.reshape(-1) if 0 <= e < H})
    return jnp.asarray(local, jnp.int32), hit


def _poisoned(w, hit):
    """NaN throughout every expert's stack that no row selected."""
    unhit = np.ones(w.shape[0], bool)
    unhit[hit] = False
    return jnp.where(jnp.asarray(unhit)[:, None, None], jnp.nan, w)


ROUTINGS = ["every_expert_hit", "one_hit", "none_hit", "hits_at_the_ends",
            "outside_and_masked"]


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("T", [1, 8, 32, 64])
@pytest.mark.parametrize("H", [8, 16])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_kernel_equals_the_einsum_form_and_reads_no_unhit_stack(
        act, H, T, routing, monkeypatch):
    """To float32 re-association; two tiles of the width an expert, so
    that a step past the last hit expert has a tile to go wrong in.  The
    kernel runs on stacks whose unhit experts are NaN throughout: a NaN
    times a gate of 0 is NaN, so a skip that multiplied would show."""
    monkeypatch.setattr(es, "TILE_COLUMNS", 128)
    assert es.width_tile(I) == 128
    rs = np.random.RandomState(H * 100 + T)
    x, w_up, w_down, gates = _operands(act, H, T)
    local, hit = _routing(routing, H, T, rs)
    read, n_hit = es.hits(local, H)
    assert int(n_hit[0]) == len(hit)
    assert list(np.asarray(read)) == (hit + [hit[-1]] * (H - len(hit))
                                      if hit else [0] * H)
    want = moe.experts_dense(x, w_up, w_down, local, gates, act)
    got = es.experts_step(x, _poisoned(w_up, hit), _poisoned(w_down, hit),
                          local, gates, act)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if not hit:
        assert not np.any(np.asarray(got))


@pytest.mark.parametrize("n_hit", [0, 1, 3, 8])
def test_a_step_past_the_last_hit_names_the_resident_block(n_hit):
    """Both grid axes: the last hit expert's LAST tile, not tile ``f`` of
    it (which would copy the expert's tiles again, once an unhit
    expert)."""
    H, n_tiles = 8, 3
    steps = [(i, f) for i in range(H) for f in range(n_tiles)]
    read = [min(i, max(n_hit - 1, 0)) for i in range(H)]
    blocks = [(read[i], int(es.tile_at(i, f, n_hit, n_tiles)))
              for i, f in steps]
    assert blocks[:n_hit * n_tiles] == steps[:n_hit * n_tiles]
    resident = blocks[max(n_hit * n_tiles - 1, 0)]
    assert resident == (read[-1], n_tiles - 1)
    assert set(blocks[n_hit * n_tiles:]) <= {resident}


@pytest.mark.parametrize("T,routing", [(8, "outside_and_masked"),
                                       (64, "every_expert_hit")])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_bfloat16_lies_no_further_from_float32_than_the_einsum_form(
        act, T, routing):
    """Products in bfloat16 with float32 sums, the activation's result
    rounded to bfloat16 before the second product, the gate in float32:
    against the float32 result the kernel is at least as near as the
    einsum form, which rounds each expert's term before it weights it.
    Also at 64 rows with every held stack hit, where the kernel stands
    in for the einsum form on its rate alone (``ppo-lfm2-ep4-sync``)."""
    H = 8
    x, w_up, w_down, gates = _operands(act, H, T, jnp.bfloat16)
    local, hit = _routing(routing, H, T, np.random.RandomState(3))
    assert routing != "every_expert_hit" or hit == list(range(H))
    exact = moe.experts_dense(*(a.astype(jnp.float32)
                                for a in (x, w_up, w_down)),
                              local, gates, act)
    err = [float(jnp.max(jnp.abs(f(x, w_up, w_down, local, gates, act)
                                 .astype(jnp.float32) - exact)))
           for f in (es.experts_step, moe.experts_dense)]
    assert err[0] <= 1.25 * err[1] + 1e-3, err
    assert err[0] < 0.05 * float(jnp.max(jnp.abs(exact)))


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_gradients_are_the_einsum_forms(act):
    """The ``custom_vjp``'s backward is ``experts_dense``'s."""
    H, T = 8, 8
    x, w_up, w_down, gates = _operands(act, H, T)
    local, _ = _routing("outside_and_masked", H, T, np.random.RandomState(5))
    cot = jnp.asarray(np.random.RandomState(6).standard_normal((T, D)),
                      jnp.float32)

    def loss(f):
        return lambda x, w_up, w_down, gates: jnp.sum(
            f(x, w_up, w_down, local, gates, act) * cot)

    got = jax.grad(loss(es.experts_step), argnums=(0, 1, 2, 3))(
        x, w_up, w_down, gates)
    want = jax.grad(loss(moe.experts_dense), argnums=(0, 1, 2, 3))(
        x, w_up, w_down, gates)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# cell -> (rows a decode step, top k, of E experts, their width, the form
# on one TPU device): the expected share of held stacks a step needs is
# 1 - (1 - k / E) ** rows; a step takes the kernel where that is under
# STEP_MAX_READ_SHARE or it carries at most STEP_KERNEL_MAX_ROWS rows
CELLS = {
    "ppo-keye-dsa-ep8-sync": (8, 8, 128, 768, "kernel"),          # 0.40
    "ppo-kimi-linear-ep32-sync": (32, 8, 256, 1024, "kernel"),    # 0.64
    "ppo-mellum2-ep8-sync": (8, 8, 64, 896, "kernel"),            # 0.66
    "ppo-nemotron-h-tp4-sync": (32, 22, 512, 2688, "kernel"),     # 0.75
    "ppo-kanana-ep8-sync": (32, 6, 128, 768, "kernel"),           # 0.78
    # every stack hit, and few enough rows that the kernel reads them
    # faster than the einsum form (PR 57)
    "ppo-lfm2-ep4-sync": (64, 4, 32, 1792, "kernel"),             # 0.9998
    "64 rows that select every expert": (64, 8, 8, 1024, "kernel"),   # 1.0
    "one row more than that": (65, 4, 32, 1792, ""),              # 0.9998
    "ppo-sdar-ep8-sync, the block's first forward": (256, 8, 128, 768, ""),
    "ppo-sdar-ep8-sync, the other three": (128, 8, 128, 768, ""),
    # over 64 rows the share alone decides, as before PR 57
    "128 rows that expect to skip a third": (128, 1, 128, 768, "kernel"),
    "256 rows just over the bound": (256, 1, 111, 768, ""),       # 0.9014
    "256 rows just under it": (256, 1, 112, 768, "kernel"),       # 0.8993
    "a training step": (16384, 8, 128, 768, ""),
    # its tiles would not be whole lanes
    "tiny_deepseek_v3, two rows": (2, 3, 8, 48, ""),
    "64 rows of a width that is not whole lanes": (64, 4, 32, 1800, ""),
}


@pytest.fixture
def on_tpu(monkeypatch):
    import orion_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_at_each_cell_on_one_tpu_device(cell, on_tpu):
    *shapes, form = CELLS[cell]
    assert moe.step_form(*shapes) == form


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_on_the_cpu_and_on_a_mesh_is_the_einsum_form(
        cell, monkeypatch):
    """Off the TPU a kernel would run interpreted; over several devices
    GSPMD partitions the einsums and cannot partition a Mosaic kernel."""
    from jax.sharding import Mesh

    import orion_tpu.ops.pallas as pallas

    *shapes, form = CELLS[cell]
    assert moe.step_form(*shapes) == ""                     # the CPU
    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")
    with Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "expert")):
        assert moe.step_form(*shapes) == ""
    with Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "expert")):
        assert moe.step_form(*shapes) == form


def test_the_expected_share_is_the_closed_form():
    assert moe.step_read_share(8, 8, 128) == pytest.approx(0.4033, abs=1e-4)
    assert moe.step_read_share(64, 4, 32) == pytest.approx(0.9998, abs=1e-4)
    assert moe.step_read_share(1, 8, 64) == pytest.approx(1 / 8)


def test_the_rollout_counts_the_stacks_its_steps_read(tmp_path, monkeypatch):
    """A tiny expert model through ``launch.main`` with the trace
    believing it is for one TPU device (the kernel runs interpreted):
    ``moe_step_read_pct`` on ``rollout.fetch`` and in the metrics row is
    the count, over the same steps and layers, of the held experts that
    a row's ``moe_selected`` names; tokens and logprobs are the einsum
    form's."""
    from orion_tpu import launch
    from orion_tpu.ops import indexer
    from orion_tpu.rollout.engine import RolloutEngine

    monkeypatch.setattr(indexer, "select_form", lambda: "kernel")
    calls = []
    generate = RolloutEngine.generate

    def spy(self, ids, lens, rng, params=None, **kw):
        out = generate(self, ids, lens, rng, params=params, **kw)
        # (the update donates the parameters)
        calls.append((self, ids, lens, rng, jax.tree.map(jnp.copy, params),
                      out))
        return out

    monkeypatch.setattr(RolloutEngine, "generate", spy)
    launch.main([
        "ppo", "model_preset=tiny_deepseek_v3", "share_backbone=true",
        "model.dtype=float32", "model.moe_intermediate_size=128",
        "rollout.max_new_tokens=12",
        "rollout.max_prompt_len=20", "model.max_seq_len=32",
        "rollout_batch_size=2", "minibatch_size=2", "num_epochs=1",
        "data.dataset=synthetic", "reward=length", "total_iterations=1",
        "obs.trace=true", f"log_dir={tmp_path}"])
    eng, ids, lens, rng, params, out = calls[0]
    mc = eng.model_cfg
    B, T = out.completions.shape
    assert moe.step_form(B, mc.num_experts_per_tok, mc.n_routed_experts,
                         mc.moe_intermediate_size)

    # step t of the loop (t = 1 ..) feeds the token at position
    # ``prompt_len + t - 1``: its selections, from one forward over the
    # finished sequences
    host = out.to_host()
    steps = int(host.expert_stacks[1]) // (
        eng._expert_layers * mc.experts_held)
    assert 0 < steps <= T - 1
    L = host.sequences.shape[1]
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    _, sowed = eng.model.apply({"params": params}, host.sequences, positions,
                               mutable=["intermediates"])
    from orion_tpu.models.transformer import sown

    selected = np.concatenate([
        np.asarray(s).reshape(-1, B, L, mc.num_experts_per_tok)
        for s in sown(sowed, "moe_selected")])
    assert selected.shape[0] == eng._expert_layers
    read = 0
    for layer in selected:
        for t in range(1, steps + 1):
            local = layer[np.arange(B), host.prompt_lens + t - 1] \
                - mc.expert_offset
            read += len({int(e) for e in local.reshape(-1)
                         if 0 <= e < mc.experts_held})
    assert int(host.expert_stacks[0]) == read
    pct = 100.0 * read / int(host.expert_stacks[1])
    assert 0 < pct < 100

    with open(tmp_path / f"spans-{os.getpid()}.json") as f:
        events = json.load(f)["traceEvents"]
    (fetch,) = [e["args"] for e in events if e["name"] == "rollout.fetch"]
    assert fetch["moe_step_read_pct"] == pytest.approx(pct)
    (final,) = [e["args"] for e in events if e["name"] == "stats.finalize"]
    assert final["moe_step_read_pct"] == pytest.approx(pct)
    with open(tmp_path / "metrics.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if "moe_step_read_pct" in r]
    assert row["moe_step_read_pct"] == pytest.approx(pct)
    assert "moe_load_max" in row

    # the einsum form on the same weights, prompts and key
    monkeypatch.setattr(indexer, "select_form", lambda: "jnp")
    plain = RolloutEngine(eng.model, mc, eng.cfg, eng.eos_token_id,
                          eng.pad_token_id)
    want = generate(plain, ids, lens, rng, params=params).to_host()
    assert list(want.expert_stacks) == [1, 1]       # every stack, uncounted
    np.testing.assert_array_equal(host.completions, want.completions)
    np.testing.assert_allclose(host.logprobs, want.logprobs, atol=2e-5)
    np.testing.assert_allclose(host.policy_logprobs, want.policy_logprobs,
                               atol=2e-5)


def test_a_model_without_the_expert_layer_reports_nothing():
    from orion_tpu.trainers.base import step_read_pct

    assert step_read_pct(None) == {}
    assert step_read_pct(np.array([1, 1])) == {"moe_step_read_pct": 100.0}
    assert step_read_pct(np.array([63, 112])) == {
        "moe_step_read_pct": pytest.approx(56.25)}
    assert step_read_pct(np.array([0, 0])) == {"moe_step_read_pct": 0.0}
