"""The faults ``reference_check_keye_dsa`` must catch, each planted in
the program at the tiny size and shown turning ``correct`` false; the
program itself passes, on two seeds.  In a file of its own so that the
suite's workers share the work (``--dist loadfile``).  The program
computes in float32 here (its selections are then the reference's, key
for key, so that a planted selection is the only thing that can move
them); the lower-precision plants round to fp8, the nearest precision
below the bfloat16 the cell states.  Nothing printed here is a
measurement."""

import numpy as np
import pytest

import bench_rehearsal as br
from test_bench_keye_dsa import tiny_shape
from test_bench_kimi_linear_faults import P, T, _Ctx, _Model, _Trainer


def _fp8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _fp8_attention(params):
    """The main attention's four products from fp8 weights."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: _fp8(x) if any(
            getattr(k, "key", "") in ("q_proj", "k_proj", "v_proj", "o_proj")
            for k in path) else x, params)


def _fp8_head(params):
    """The logits from an fp8 head."""
    params = dict(params)
    params["lm_head"] = {"kernel": _fp8(params["lm_head"]["kernel"])}
    return params


def _plant_selection(monkeypatch, how):
    """Both forms of the program's selection (whole sequences, one step)
    replaced: ``window`` keeps the last ``topk`` keys of every query;
    ``approximate`` is a top-k that never sees every fourth key, as a
    partial reduction over bins loses candidates."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops import indexer

    scores_of = indexer.index_scores

    def rescored(qi, ki, w, q_positions, kv_chunk):
        s = scores_of(qi, ki, w, q_positions, kv_chunk)
        slots = jnp.arange(s.shape[-1])
        if how == "window":
            # the score of a valid key is its slot: the latest win
            return jnp.where(s > -jnp.inf,
                             slots[None, None, :].astype(s.dtype), s)
        hidden = (slots % 4 == 3) & (
            slots[None, None, :] < q_positions[:, :, None] - 8)
        return jnp.where(hidden, -jnp.inf, s)

    monkeypatch.setattr(indexer, "index_scores", rescored)


FAULTS = ["none", "window_selection", "approximate_topk",
          "attention_in_fp8", "head_in_fp8"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("keye_dsa", experts_held=4, expert_offset=2,
                           vocab_size=260, max_seq_len=P + T,
                           dtype="float32")
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    kw = {}
    if fault in ("window_selection", "approximate_topk"):
        _plant_selection(monkeypatch, fault.split("_")[0])
    elif fault == "attention_in_fp8":
        kw["params_fault"] = _fp8_attention
    elif fault == "head_in_fp8":
        kw["params_fault"] = _fp8_head
    trainer = _Trainer(cfg, _Model(Transformer(cfg), **kw), params)
    chk = br.lib("reference_check_keye_dsa")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    assert all(v["tokens"] == 2 * T for v in verdicts)
    if fault == "none":
        assert all(v["ok"] and all(v["parts"].values())
                   for v in verdicts), verdicts
        assert all(v["selection_overlap"] == 1.0 for v in verdicts)
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] > T for v in verdicts)
        assert all(v["max_abs_diff"] < 1e-4 for v in verdicts)
        return
    assert not any(v["ok"] for v in verdicts), verdicts
    parts = verdicts[0]["parts"]
    if fault == "window_selection":
        # the reference, given the program's selections, follows them:
        # (a) agrees; its own selections do not, and the control that
        # must differ does not
        assert parts["a_given_selections"]
        assert not parts["b_own_selections"]
        assert not parts["d_wrong_selection_fails"]
        assert verdicts[0]["selection_overlap"] < 0.5
    elif fault == "approximate_topk":
        assert parts["a_given_selections"]
        assert not parts["b_own_selections"]
        assert 0.5 < verdicts[0]["selection_overlap"] \
            < verdicts[0]["selection_overlap_limit"]
    else:
        # one precision below what the job states fails (a), by a
        # comparison of logprobs
        assert not parts["a_given_selections"]
        assert verdicts[0]["mean_abs_diff"] > verdicts[0]["mean_tolerance"]
