"""The faults ``reference_check_mellum2`` must catch (ISSUE 53, Tentpole
5), each planted in the program at the tiny size (float32, experts 4-7
of 8, a window of 8 keys) and shown turning ``correct`` false; the
program itself passes, on two seeds.  In a file of its own so that the
suite's workers share the work.  The lower-precision plant rounds the
weights to fp8, the nearest precision below the bfloat16 the cell
states.  Nothing printed here is a measurement."""

import dataclasses

import numpy as np
import pytest

import bench_rehearsal as br
from test_bench_kimi_linear_faults import T, _Ctx, _fp8, _Model, _Trainer
from test_bench_mellum2 import tiny_shape


def _plant(monkeypatch, fault):
    """The mixers, the rotary table or the router with a fault in them."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models import transformer
    from orion_tpu.ops import moe, rotary

    Window, Full = transformer.WindowAttention, transformer.Attention

    def window_of(n):
        return staticmethod(lambda cfg: n(cfg.sliding_window))

    if fault == "window_ignored":
        monkeypatch.setattr(Window, "window", window_of(lambda w: 10 ** 6))
    elif fault == "window_on_full":
        monkeypatch.setattr(Full, "window", window_of(lambda w: w))
    elif fault == "window_one_key_fewer":
        monkeypatch.setattr(Window, "window", window_of(lambda w: w - 1))
    elif fault == "window_one_key_more":
        monkeypatch.setattr(Window, "window", window_of(lambda w: w + 1))
    elif fault == "ring_keeps_a_key_too_long":
        # the training forward is right; the ring holds position t - w
        # until t + 1 overwrites it
        monkeypatch.setattr(Window, "ring_slots", classmethod(
            lambda cls, cfg, slots: min(cfg.sliding_window + 1, slots)))
    elif fault == "yarn_on_sliding":
        monkeypatch.setattr(Window, "layer_type", "full_attention")
    elif fault == "default_on_full":
        monkeypatch.setattr(Full, "layer_type", "sliding_attention")
    elif fault == "attention_factor_left_out":
        table = rotary.rope_table
        monkeypatch.setattr(rotary, "rope_table",
                            lambda *a: (table(*a)[0], 1.0))
    elif fault in ("router_softmax_in_bfloat16", "gates_not_normalised"):
        def route(z, router_kernel, k, scale):
            logits = jnp.dot(z.astype(jnp.float32),
                             router_kernel.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if fault == "router_softmax_in_bfloat16":
                logits = logits.astype(jnp.bfloat16)
            probs = jax.nn.softmax(logits, axis=-1).astype(jnp.float32)
            chosen, idx = jax.lax.top_k(probs, k)
            if fault == "router_softmax_in_bfloat16":
                chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
            return idx.astype(jnp.int32), scale * chosen

        monkeypatch.setattr(moe, "softmax_topk_route", route)
    else:
        raise ValueError(fault)


FAULTS = ["none", "lower_precision", "window_ignored", "window_on_full",
          "window_one_key_fewer", "window_one_key_more",
          "ring_keeps_a_key_too_long", "yarn_on_sliding", "default_on_full",
          "attention_factor_left_out", "router_softmax_in_bfloat16",
          "gates_not_normalised", "one_expert_fewer"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    # float32: the selection is then the reference's, expert for expert,
    # and a planted fault the only thing that moves a logprob
    # (one period S S S F: every fault shows in it, at half the compiles)
    cfg = dataclasses.replace(
        ModelConfig.tiny("mellum", dtype="float32", vocab_size=260,
                         max_seq_len=128 + T, num_layers=4),
        experts_held=4, expert_offset=4)
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    # queries and keys sixfold: at this width (64) the scores are near
    # zero and the attention uniform whatever is rotated; at the
    # published one their spread is about 1
    for layer in params.values():
        attn = layer.get("attn", {}) if isinstance(layer, dict) else {}
        for name in ("q_norm", "k_norm"):
            if name in attn:
                attn[name]["scale"] = 2.5 * attn[name]["scale"]
    program_cfg, kw = cfg, {}
    if fault == "lower_precision":
        kw["params_fault"] = _fp8
    elif fault == "one_expert_fewer":
        program_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif fault != "none":
        _plant(monkeypatch, fault)
    trainer = _Trainer(cfg, _Model(Transformer(program_cfg), **kw), params)
    chk = br.lib("reference_check_mellum2")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    if fault == "one_expert_fewer":
        assert not any(v["ok"] for v in verdicts)
        assert all("selects 1 experts" in v["why"] for v in verdicts)
        return
    assert all(v["tokens"] == 2 * T for v in verdicts)
    others = [name + "_mean_abs_diff" for name in chk.VARIANTS]
    edges = [kind + "_" + path + "_edge_keys" for kind in ("sliding", "full")
             for path in ("forward", "decode")]
    if fault == "none":
        assert all(v["ok"] and all(v["parts"].values())
                   for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["max_abs_diff"] < 1e-4 and v["decode_tokens"] > T
                   and v["decode_max_abs_diff"] < 1e-4 for v in verdicts)
        assert all(v[e] < 1e-3 for v in verdicts for e in edges), verdicts
        assert all(v["router_float32_share"] == 1.0 for v in verdicts)
        assert all(v["first_sequence_mean_abs_diff"] < 0.01 * v[o]
                   for v in verdicts for o in others), verdicts
        return
    assert not any(v["ok"] for v in verdicts), verdicts
    failed = [{k for k, ok in v["parts"].items() if not ok}
              for v in verdicts]
    closer = {"window_ignored": "window_ignored",
              "window_on_full": "window_on_full",
              "yarn_on_sliding": "yarn_on_sliding",
              "default_on_full": "default_on_full",
              "attention_factor_left_out": "no_attention_factor",
              "gates_not_normalised": "gates_unnormalised"}
    if fault in closer:
        # (c): the program lies closer to the model it is not
        assert all("c_this_model" in f for f in failed), verdicts
        assert all(v[closer[fault] + "_mean_abs_diff"]
                   < v["first_sequence_mean_abs_diff"] for v in verdicts), \
            verdicts
    if fault in ("window_ignored", "window_one_key_fewer",
                 "window_one_key_more"):
        # (d): key for key, in the whole-sequence forward and the ring
        assert all("d_window_edge" in f for f in failed), verdicts
        assert all(v["sliding_forward_edge_keys"] > 0.8
                   and v["sliding_decode_edge_keys"] > 0.8
                   and v["full_forward_edge_keys"] < 1e-3
                   for v in verdicts), verdicts
    elif fault == "window_on_full":
        assert all(v["full_forward_edge_keys"] > 0.8
                   and v["sliding_forward_edge_keys"] < 1e-3
                   for v in verdicts), verdicts
    elif fault == "ring_keeps_a_key_too_long":
        # the training forward agrees; the ring alone holds one key more
        assert all(v["parts"]["a_training_forward"]
                   and v["parts"]["c_this_model"] for v in verdicts), verdicts
        assert all(v["sliding_forward_edge_keys"] < 1e-3
                   and v["sliding_decode_edge_keys"] > 0.8
                   for v in verdicts), verdicts
    elif fault == "router_softmax_in_bfloat16":
        # a gate moves by 2^-9 of itself: the logprobs agree within the
        # limits, the router's own bits do not
        assert all(f == {"e_router_float32"} for f in failed), verdicts
        assert all(v["router_float32_share"] < 0.1 for v in verdicts)
    elif fault == "lower_precision":
        # by a comparison of logprobs, not by a side condition
        assert all(v["mean_abs_diff"] > v["mean_tolerance"]
                   or v["max_abs_diff"] > v["max_tolerance"]
                   for v in verdicts), verdicts
