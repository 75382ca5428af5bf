"""The faults ``reference_check_lfm2`` must catch, each planted in the
program at the tiny size (experts 4-7 of 8) and shown turning ``correct``
false; the program itself passes, on two seeds.  In a file of its own so
that the suite's workers share the work (``--dist loadfile``).  The
lower-precision plant rounds the weights to fp8, the nearest precision
below the bfloat16 the cell states.  Nothing printed here is a
measurement."""

import copy
import dataclasses

import numpy as np
import pytest

import bench_rehearsal as br
from test_bench_kimi_linear_faults import T, _Ctx, _fp8, _Model, _Trainer
from test_bench_lfm2 import tiny_shape


def _taps_reversed(params):
    params = copy.deepcopy(params)
    for layer in params.values():
        attn = layer.get("attn", {}) if isinstance(layer, dict) else {}
        if "conv_weight" in attn:
            attn["conv_weight"] = attn["conv_weight"][::-1]
    return params


def _plant(monkeypatch, fault):
    """The mixers, or the router, with a fault in them."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models import transformer
    from orion_tpu.ops import moe

    if fault == "handover_dropped":
        handover = transformer._conv_handover
        monkeypatch.setattr(
            transformer, "_conv_handover",
            lambda *a: jnp.zeros_like(handover(*a)))
    elif fault == "handover_misplaced":
        # the rows behind the PADDED length, not each row's real one
        handover = transformer._conv_handover
        monkeypatch.setattr(
            transformer, "_conv_handover",
            lambda ext, token_mask, taps: handover(ext, None, taps))
    elif fault == "conv_in_bfloat16":
        def rounded(t):
            # not a pair of converts: the TPU's compiler removes those
            return jax.lax.reduce_precision(t, exponent_bits=8,
                                            mantissa_bits=7)

        def bf16_conv(ext, w_conv, L):
            out = 0.0
            for j in range(w_conv.shape[0]):
                out = rounded(out + rounded(
                    ext[:, j:j + L].astype(jnp.float32) * rounded(w_conv[j])))
            return out

        monkeypatch.setattr(transformer, "_short_conv", bf16_conv)
    elif fault == "c_gate_omitted":
        class NoC:
            """``jax.numpy`` as models/transformer.py sees it (the
            reference splits b | c | z by the same function), its
            three-way split, ShortConv's alone, giving c = 1."""

            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def split(t, parts, axis=0):
                out = jnp.split(t, parts, axis=axis)
                if parts == 3:
                    out[1] = jnp.ones_like(out[1])
                return out

        monkeypatch.setattr(transformer, "jnp", NoC())
    elif fault == "bias_in_the_gates":
        def leaky(z, router_kernel, bias, k, scale):
            scores = jax.nn.sigmoid(jnp.dot(
                z.astype(jnp.float32), router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) + bias[None, :]
            chosen, idx = jax.lax.top_k(scores, k)
            return idx.astype(jnp.int32), scale * chosen / jnp.sum(
                chosen, axis=-1, keepdims=True)

        monkeypatch.setattr(moe, "sigmoid_topk_route", leaky)
    elif fault == "bias_left_out_of_the_selection":
        route = moe.sigmoid_topk_route
        monkeypatch.setattr(
            moe, "sigmoid_topk_route",
            lambda z, kernel, bias, k, scale: route(
                z, kernel, jnp.zeros_like(bias), k, scale))
    elif fault == "rotary_off":
        monkeypatch.setattr(transformer, "apply_rotary",
                            lambda q, k, *a: (q, k))
    elif fault == "qk_norm_off":
        monkeypatch.setattr(transformer, "mixer_spec",
                            lambda cfg, kind: (transformer.MIXERS[kind], {}))
    else:
        raise ValueError(fault)


FAULTS = ["none", "lower_precision", "handover_dropped",
          "handover_misplaced", "taps_reversed", "c_gate_omitted",
          "bias_in_the_gates", "bias_left_out_of_the_selection",
          "rotary_off", "qk_norm_off",
          "conv_in_bfloat16", "one_expert_fewer"]


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
        ModelConfig.tiny("lfm2_moe", dtype="float32", vocab_size=260,
                         max_seq_len=128 + T),
        experts_held=4, expert_offset=4)
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    # the convolution's projections sixfold: at this width (64) their
    # outputs are 0.16 and the mixer's a sixtieth of the stream; at the
    # published one they are 0.9 and the first layer's mixer IS the
    # stream (16 times the embedding).  A bias tenfold, so that it
    # decides selections and would move a gate
    for layer in params.values():
        attn = layer.get("attn", {}) if isinstance(layer, dict) else {}
        mlp = layer.get("mlp", {}) if isinstance(layer, dict) else {}
        for name in ("in_proj", "out_proj"):
            if name in attn:
                attn[name]["kernel"] = 6.0 * attn[name]["kernel"]
        if "e_score_correction_bias" in mlp:
            mlp["e_score_correction_bias"] = \
                10.0 * mlp["e_score_correction_bias"]
    program_cfg, kw = cfg, {}
    if fault == "lower_precision":
        kw["params_fault"] = _fp8
    elif fault == "taps_reversed":
        kw["params_fault"] = _taps_reversed
    elif fault == "one_expert_fewer":
        program_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif fault != "none":
        _plant(monkeypatch, fault)
    trainer = _Trainer(cfg, _Model(Transformer(program_cfg), **kw), params)
    chk = br.lib("reference_check_lfm2")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    if fault == "one_expert_fewer":
        assert not any(v["ok"] for v in verdicts)
        assert all("selects 1 experts" in v["why"] for v in verdicts)
        return
    assert all(v["tokens"] == 2 * T for v in verdicts)
    others = [name + "_mean_abs_diff" for name in chk.VARIANTS]
    if fault == "none":
        assert all(v["ok"] for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] > T for v in verdicts)
        assert all(v["conv_float32_share"] == 1.0 for v in verdicts)
        assert all(v["handover_tokens"] == 4
                   and v["handover_max_abs_diff"] < 1e-4 for v in verdicts)
        assert all(v["first_sequence_mean_abs_diff"] < v[o]
                   for v in verdicts for o in others), verdicts
        assert all(v["bias_selection_share"] == 1.0
                   and v["bias_gates_published_diff"]
                   < 0.01 * v["bias_gates_biased_diff"] for v in verdicts)
        return
    assert not any(v["ok"] for v in verdicts), verdicts
    closer = {"taps_reversed": "taps_reversed", "c_gate_omitted": "no_c_gate",
              "rotary_off": "no_rotary", "qk_norm_off": "no_qk_norm"}
    if fault == "conv_in_bfloat16":
        # three more roundings among a layer's dozens: the logprobs
        # agree, the convolution's own bits do not
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   and v["decode_mean_abs_diff"] <= v["decode_mean_tolerance"]
                   and v["handover_max_abs_diff"] <= v["handover_tolerance"]
                   for v in verdicts), verdicts
        assert all(v["conv_float32_share"] == 0.0 for v in verdicts)
    elif fault.startswith("handover"):
        # (c): the training forward hands nothing over and agrees; the
        # first generated tokens do not, and the mean over all hides it
        # where one row alone is touched
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   for v in verdicts), verdicts
        assert all(v["handover_max_abs_diff"] > v["handover_tolerance"]
                   for v in verdicts), verdicts
    elif fault in ("bias_in_the_gates", "bias_left_out_of_the_selection"):
        # (d): seen at the layer, with the bias tenfold
        leaked = fault == "bias_in_the_gates"
        assert all((v["bias_gates_biased_diff"]
                    < v["bias_gates_published_diff"]) == leaked
                   and (v["bias_selection_share"] < 0.5) != leaked
                   for v in verdicts), verdicts
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
