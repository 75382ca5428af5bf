"""The expert layer's combine kernel (ops/pallas/moe_combine.py),
interpreted on the CPU, against the scatter-add it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import moe
from orion_tpu.ops.pallas import moe_combine as mc


def _sorted_pairs(local, n_held, block):
    """What ops/moe.py::experts_grouped hands on: the pairs sorted by
    expert (stable), in whole blocks."""
    n_pairs = local.size
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).reshape(n_pairs)
    _, order = jax.lax.sort_key_val(key, jnp.arange(n_pairs, dtype=jnp.int32))
    return jnp.pad(order, (0, -n_pairs % block)), int(jnp.sum(held))


def _distinct(rs, T, k, n_all):
    """[T, k]: k distinct experts of ``n_all`` a token, as a top-k gives."""
    return np.argsort(rs.uniform(size=(T, n_all)), axis=1)[:, :k]


# name -> (T, k, D, held, experts, block, tile bytes (None: from the
# shapes), dtype of y, gated, carried sum, routing)
CASES = {
    "one_block": (256, 2, 128, 4, 8, 512, None, "f32", True, False, "topk"),
    "range_cut_by_a_block": (256, 2, 128, 4, 4, 128, None, "f32", True, False,
                             "topk"),
    # every pair on held expert 1: 640 rows in five blocks, a run longer
    # than a chunk of 128 rows and than a tile of 256 tokens
    "all_on_one_held": (640, 1, 128, 3, 3, 128, 256 * 128 * 4, "f32", True,
                        False, "one"),
    "token_holds_several": (128, 4, 128, 4, 4, 256, None, "f32", True, False,
                            "topk"),
    "held_expert_without_a_pair": (256, 2, 128, 4, 8, 256, None, "f32", True,
                                   False, "skip2"),
    "tokens_routed_nowhere": (256, 2, 128, 4, 8, 256, None, "f32", True,
                              False, "masked"),
    "tile_without_a_held_pair": (512, 2, 128, 4, 8, 256, 128 * 128 * 4,
                                 "f32", True, True, "first_tile_only"),
    "tokens_no_multiple_of_a_tile": (200, 2, 128, 4, 8, 128, 128 * 128 * 4,
                                     "f32", True, True, "topk"),
    "latent_1024_wide_8_held": (256, 2, 1024, 8, 16, 256, None, "f32", True,
                                False, "topk"),
    "no_gate": (256, 2, 128, 4, 8, 128, None, "f32", False, False, "topk"),
    "carried_sum": (256, 2, 128, 4, 8, 128, None, "f32", True, True, "topk"),
    "bf16_rows_copied_to_the_bit": (256, 2, 128, 4, 8, 128, None, "bf16",
                                    False, False, "topk"),
    "bf16_rows_gated": (256, 2, 128, 4, 8, 128, None, "bf16", True, True,
                        "topk"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_combine_equals_the_scatter_add(name, monkeypatch):
    """``carry + zeros.at[tok].add(y * gate)`` over every block of the
    sorted pairs, to float32 re-association (a token's at most k terms
    come in another order); rows of bf16 are copied by the 0/1 product
    to the bit."""
    T, k, D, H, E, block, tile_bytes, dt, gated, carried, routing = CASES[name]
    if tile_bytes:
        monkeypatch.setattr(mc, "TILE_BYTES", tile_bytes)
    rs = np.random.RandomState(sorted(CASES).index(name))
    local = _distinct(rs, T, k, E)
    if routing == "one":
        local[:] = 1
    if routing == "skip2":                      # nobody selects expert 2
        local = np.where(local == 2, E + 3, local)
    if routing == "masked":                     # ops/moe.py: masked -> H
        local[rs.uniform(size=T) < 0.4] = H
    if routing == "first_tile_only":
        local[128:] = -1
    local = jnp.asarray(local, jnp.int32)
    gates = jnp.asarray(rs.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    order, n_held = _sorted_pairs(local, H, block)
    n_blocks = order.shape[0] // block
    assert n_blocks >= (5 if routing == "one" else 1)
    ydt = jnp.float32 if dt == "f32" else jnp.bfloat16
    y = jnp.asarray(rs.normal(size=(order.shape[0], D)), ydt)
    # the grouped product's rows are zero where no held expert computed
    y = jnp.where(jnp.arange(order.shape[0])[:, None] < n_held, y, 0)
    carry = jnp.asarray(rs.normal(size=(T, D)), jnp.float32) if carried \
        else jnp.zeros((T, D), jnp.float32)

    tm = mc.token_tile(T, D)
    if name == "tokens_no_multiple_of_a_tile":
        assert T % tm
    starts, ends = mc.ranges(local, H, tm)
    g = moe._gate_of(local, gates, H) if gated else None
    got, want = carry, carry
    for b in range(n_blocks):
        pair = order[b * block:(b + 1) * block]
        tok = pair // k
        rows = y[b * block:(b + 1) * block]
        gate = jnp.take(gates.reshape(-1), pair)[:, None] if gated else 1.0
        want = want.at[tok].add(rows.astype(jnp.float32) * gate)
        items, n_real = mc.work_items(starts, ends, b, block)
        assert items[0].shape == (mc.n_items(*starts.shape, block),)
        assert int(n_real) <= items[0].shape[0]
        tile, _, _, lo, hi = (np.asarray(i) for i in items)
        assert np.all(np.diff(tile) >= 0)           # ordered by tile
        assert set(tile[:int(n_real)]) == set(range(starts.shape[0]))
        assert not np.any((hi > lo)[int(n_real):])  # the rest is masked
        got = mc.moe_combine(got, rows, tok, items, g)
    if name == "tile_without_a_held_pair":
        np.testing.assert_array_equal(got[128:], carry[128:])
    if dt == "bf16" and not gated:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    work = moe.combine_work(local, H, D, block)
    assert int(work[1]) == n_held


@pytest.mark.parametrize("routing", ["topk", "all_on_one_held"])
def test_grouped_equals_dense_value_and_gradients(routing):
    """``experts_grouped`` through the kernel against every held expert
    on every token, value and all four gradients, at the sizes of
    tests/test_deepseek_v3.py::test_dropless, on routings that keep the
    kernel's contract (a token holds an expert at most once)."""
    rs = np.random.RandomState(2)
    T, k, D, I, H, E = 1024, 4, 16, 8, 3, 8
    block = 1024
    x = jnp.asarray(rs.normal(size=(T, D)), jnp.float32)
    w_gu = jnp.asarray(rs.normal(size=(H, D, 2 * I)) * 0.3, jnp.float32)
    w_d = jnp.asarray(rs.normal(size=(H, I, D)) * 0.3, jnp.float32)
    gates = jnp.asarray(rs.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(T, D)), jnp.float32)
    local = _distinct(rs, T, k, E)
    if routing == "all_on_one_held":    # each token's first choice, alone
        local[:, 0], local[:, 1:] = 1, H
    local = jnp.asarray(local, jnp.int32)

    def run(fn, *static):
        def out_and_loss(x, w_gu, w_d, gates):
            out = fn(x, w_gu, w_d, local, gates, *static)
            return jnp.sum(out * cot), out
        (_, out), grads = jax.value_and_grad(
            out_and_loss, argnums=(0, 1, 2, 3), has_aux=True)(
                x, w_gu, w_d, gates)
        return (out,) + grads

    want = run(moe.experts_dense)
    got = run(moe.experts_grouped, block)
    for name, g, w in zip(("out", "d_x", "d_w_gate_up", "d_w_down",
                           "d_gates"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(g, w, atol=1e-5 * scale, rtol=0,
                                   err_msg=name)


def test_counters_of_the_combine_reach_the_stats():
    """``moe_combine_items`` is the most items a layer call took and
    ``moe_combine_fill`` the rows placed over the rows the items'
    products span; the dense form sows nothing and both read 0."""
    from orion_tpu.trainers.base import moe_load_stats

    loads = [jnp.asarray([[3, 5], [4, 4]], jnp.int32)]
    none = moe_load_stats(loads, 16, 0)
    assert float(none["moe_combine_items"]) == 0
    assert float(none["moe_combine_fill"]) == 0
    stats = moe_load_stats(loads, 16, 256,
                           [jnp.asarray([[4, 128], [2, 192]], jnp.int32)])
    assert float(stats["moe_combine_items"]) == 4
    np.testing.assert_allclose(float(stats["moe_combine_fill"]),
                               (128 + 192) / (6 * 128))
