"""The faults ``reference_check_nemotron_h`` must catch, each planted in
the program at the tiny size (share 1 of 2 of the heads, experts 4-7 of
8) and shown turning ``correct`` false; the program itself passes, on two
seeds.  In a file of its own so that the suite's workers share the work
(``--dist loadfile``).  The lower-precision plant rounds the weights to
fp8, the nearest precision below the bfloat16 the cell states.  Nothing
printed here is a measurement."""

import dataclasses

import numpy as np
import pytest

import bench_rehearsal as br
from test_bench_kimi_linear_faults import T, _Ctx, _fp8, _Model, _Trainer
from test_bench_nemotron_h import tiny_shape


def _plant(monkeypatch, fault):
    """The recurrence's two forms, or the experts' activation, with a
    fault in them."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops import mamba2, moe

    step, chunked = mamba2.mamba2_step, mamba2.mamba2_chunked
    if fault == "state_in_bfloat16":
        def rounded(S):
            # not a pair of converts: the TPU's compiler removes those
            return jax.lax.reduce_precision(S, exponent_bits=8,
                                            mantissa_bits=7)

        def bf16_step(x, dt, A, B, C, D, state):
            y, S = step(x, dt, A, B, C, D, rounded(state))
            return y, rounded(S)

        def bf16_chunked(*a, **kw):
            y, S = chunked(*a, **kw)
            return y, rounded(S)

        monkeypatch.setattr(mamba2, "mamba2_step", bf16_step)
        monkeypatch.setattr(mamba2, "mamba2_chunked", bf16_chunked)
    elif fault == "b_and_c_a_head_at_a_time":
        def by_head(t, H):      # head h reads group h % G, its "own"
            return jnp.take(t, jnp.arange(H) % t.shape[-2], axis=-2)

        def bad_step(x, dt, A, B, C, D, state):
            H = x.shape[-2]
            return step(x, dt, A, by_head(B, H), by_head(C, H), D, state)

        def bad_chunked(x, dt, A, B, C, D, *a, **kw):
            H = x.shape[-2]
            return chunked(x, dt, A, by_head(B, H), by_head(C, H), D, *a,
                           **kw)

        monkeypatch.setattr(mamba2, "mamba2_step", bad_step)
        monkeypatch.setattr(mamba2, "mamba2_chunked", bad_chunked)
    else:
        wrong = {"the_square_skipped": jax.nn.relu,
                 "experts_ran_a_swiglu": lambda h: jax.nn.silu(h) * h}[fault]
        monkeypatch.setitem(moe.ACTIVATIONS, "relu2", (wrong, 1))


FAULTS = ["none", "lower_precision", "state_in_bfloat16",
          "the_square_skipped", "experts_ran_a_swiglu",
          "b_and_c_a_head_at_a_time", "one_expert_fewer"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    # float32: the selection is then the reference's, expert for expert,
    # and a planted fault the only thing that moves a logprob
    cfg = dataclasses.replace(
        ModelConfig.tiny("nemotron_h", dtype="float32", vocab_size=260,
                         max_seq_len=128 + T),
        head_share=(1, 2), experts_held=4, expert_offset=4)
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    program_cfg, kw = cfg, {}
    if fault == "lower_precision":
        kw["params_fault"] = _fp8
    elif fault == "one_expert_fewer":
        program_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif fault != "none":
        _plant(monkeypatch, fault)
    trainer = _Trainer(cfg, _Model(Transformer(program_cfg), **kw), params)
    chk = br.lib("reference_check_nemotron_h")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    if fault == "one_expert_fewer":
        assert not any(v["ok"] for v in verdicts)
        assert all("selects 2 experts" in v["why"] for v in verdicts)
        return
    assert all(v["tokens"] == 2 * T for v in verdicts)
    others = ("relu", "silu_gate", "interleaved")
    if fault == "none":
        assert all(v["ok"] for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] > T for v in verdicts)
        assert all(v["state_float32_share"] > 0.9 for v in verdicts)
        assert all(v["first_sequence_mean_abs_diff"]
                   < v[o + "_mean_abs_diff"] for v in verdicts
                   for o in others), verdicts
        return
    assert not any(v["ok"] for v in verdicts), verdicts
    closer = {"the_square_skipped": "relu",
              "experts_ran_a_swiglu": "silu_gate",
              "b_and_c_a_head_at_a_time": "interleaved"}
    if fault == "state_in_bfloat16":
        # one more rounding among a layer's dozens: the logprobs agree,
        # the state's own bits do not
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   and v["decode_mean_abs_diff"] <= v["decode_mean_tolerance"]
                   for v in verdicts), verdicts
        assert all(v["state_float32_share"] == 0.0 for v in verdicts)
    elif fault in closer:
        # (d): the program lies closer to the model it is not
        assert all(v[closer[fault] + "_mean_abs_diff"]
                   < v["first_sequence_mean_abs_diff"] for v in verdicts), \
            verdicts
    else:
        # by a comparison of logprobs, not by a side condition
        assert all(v["mean_abs_diff"] > v["mean_tolerance"]
                   or v["max_abs_diff"] > v["max_tolerance"]
                   or v["decode_mean_abs_diff"] > v["decode_mean_tolerance"]
                   for v in verdicts), verdicts
