"""The plain reference against the program's ``Transformer`` at
``ModelConfig.tiny("neox")`` in float32: the two are independent
implementations of GPT-NeoX and must agree to float32 rounding, both in
the plain and in the int8-quantised parameter layout the serving
engine holds."""

import numpy as np
import pytest

import bench_rehearsal as br


class _Ctx:
    """What reference_check reads of a run's context."""

    def __init__(self, config):
        self.config = config

    def lib(self, name):
        return br.lib(name)


@pytest.fixture(scope="module")
def tiny():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models import Transformer, init_params

    cfg = ModelConfig.tiny("neox", dtype="float32", rotary_pct=0.25,
                           use_parallel_residual=True, attn_bias=True,
                           mlp_bias=True)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(7), cfg)
    # biases and norm offsets are zero at init: move them, or a
    # reference that drops one would still agree
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(8), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
              for x, k in zip(leaves, keys)]
    params = jax.tree.unflatten(tree, leaves)
    shape = {"hidden_size": cfg.hidden_size,
             "num_attention_heads": cfg.num_heads,
             "num_hidden_layers": cfg.num_layers,
             "rotary_pct": cfg.rotary_pct, "rotary_emb_base": cfg.rope_theta,
             "layer_norm_eps": cfg.layernorm_eps,
             "use_parallel_residual": True, "vocab_size": cfg.vocab_size}
    return cfg, model, params, shape


def _system_logprobs(model, params, ids):
    import jax
    import jax.numpy as jnp

    pos = jnp.arange(len(ids))[None, :]
    logits, _ = model.apply({"params": params}, jnp.asarray(ids)[None, :],
                            pos)
    logp = jax.nn.log_softmax(logits[0, :-1], axis=-1)
    return np.asarray(jnp.take_along_axis(
        logp, jnp.asarray(ids)[1:, None], axis=-1)[:, 0])


@pytest.mark.parametrize("parallel", [True, False])
def test_reference_agrees_with_transformer(tiny, parallel):
    import dataclasses

    from orion_tpu.models import Transformer

    cfg, _, params, shape = tiny
    cfg = dataclasses.replace(cfg, use_parallel_residual=parallel)
    shape = dict(shape, use_parallel_residual=parallel)
    ids = np.random.RandomState(0).randint(2, cfg.vocab_size, 24
                                           ).astype(np.int32)
    want = _system_logprobs(Transformer(cfg), params, ids)
    got = br.lib("reference_check").reference_logprobs(_Ctx(shape), params,
                                                      ids)
    # float32 on both sides, different operation order: 1e-4 on logprobs
    # near -5.5 is float32 rounding through 2 layers, no more
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_reference_whole_forward_equals_layerwise(tiny):
    """``reference.forward`` (the whole thing at once) and the
    layer-at-a-time path the checks use are the same function."""
    import jax.numpy as jnp

    cfg, _, params, shape = tiny
    ref, chk = br.lib("reference"), br.lib("reference_check")
    ids = np.random.RandomState(1).randint(2, cfg.vocab_size, 16
                                           ).astype(np.int32)
    weights = {
        "embed": params["embed"]["embedding"],
        "layers": [chk._layer_weights(params[f"layers_{i}"])
                   for i in range(cfg.num_layers)],
        "lnf_g": params["final_norm"]["scale"],
        "lnf_b": params["final_norm"]["bias"],
        "w_head": params["lm_head"]["kernel"]}
    whole = ref.next_token_logprobs(
        ref.forward(weights, jnp.asarray(ids), shape), jnp.asarray(ids))
    stepwise = chk.reference_logprobs(_Ctx(shape), params, ids)
    np.testing.assert_allclose(np.asarray(whole), stepwise, atol=1e-5)


def test_reference_reads_the_engines_int8_layout(tiny):
    """Quantised kernels reach the reference dequantised: against the
    program's own quantised model in float32 the agreement is again
    float32 rounding, so int8 error is NOT inside the serve tolerance."""
    import dataclasses

    from orion_tpu.models import Transformer
    from orion_tpu.ops.quant import quantize_params_int8

    cfg, _, params, shape = tiny
    qparams = quantize_params_int8(params)
    qmodel = Transformer(dataclasses.replace(cfg, quantize_dense=True))
    ids = np.random.RandomState(2).randint(2, cfg.vocab_size, 20
                                           ).astype(np.int32)
    want = _system_logprobs(qmodel, qparams, ids)
    got = br.lib("reference_check").reference_logprobs(_Ctx(shape), qparams,
                                                      ids)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    plain = br.lib("reference_check").reference_logprobs(_Ctx(shape),
                                                        params, ids)
    assert np.max(np.abs(plain - got)) > 1e-4     # int8 is visible


def test_verdict_needs_both_the_worst_and_the_mean():
    chk = br.lib("reference_check")
    rms = 0.01          # mean <= 1.5 * 0.798 * rms, worst <= 6 * rms
    assert chk._verdict([np.full(10, 0.008)], rms)["ok"]
    assert not chk._verdict([np.array([0.0, 0.07])], rms)["ok"]
    assert not chk._verdict([np.full(10, 0.013)], rms)["ok"]    # a shift
    assert not chk._verdict([], rms)["ok"]
    assert not chk._verdict([np.array([np.nan])], rms)["ok"]


def test_error_model_numbers():
    """The bound is worked out from what the program rounds: at the 1B
    widths (16 layers, logits' spread 0.9) an RMS of 0.0100; an int8 KV
    cache adds little behind hundreds of keys and much behind few."""
    chk = br.lib("reference_check")
    assert chk.predicted_rms(0.9, 16) == pytest.approx(0.0101, abs=1e-4)
    assert chk.predicted_rms(0.9, 16, 320) < 1.03 * chk.predicted_rms(0.9, 16)
    assert chk.predicted_rms(0.9, 16, 4) > 1.9 * chk.predicted_rms(0.9, 16)
    # twice the roundings' size (one mantissa bit fewer) fails the mean
    d = np.abs(np.random.RandomState(0).normal(0, 0.02, 256))
    assert not chk._verdict([d], chk.predicted_rms(0.9, 16))["ok"]
    assert chk._verdict([d / 2], chk.predicted_rms(0.9, 16))["ok"]


def test_bf16_forward_is_inside_the_model_and_a_wrong_page_is_not(tiny):
    """The program's forward in bfloat16 agrees with the float32
    reference within the error model; the same sequence with its first 4
    tokens (a page of the rehearsals' engine) exchanged does not."""
    import dataclasses

    from orion_tpu.models import Transformer

    import jax

    from orion_tpu.models import init_params

    cfg, model, _, shape = tiny
    chk = br.lib("reference_check")
    # weights as a run has them: the seeded init, not the fixture's
    # perturbed ones (the model is about such weights)
    params = init_params(model, jax.random.key(9), cfg)
    bf16 = Transformer(dataclasses.replace(cfg, dtype="bfloat16"))
    rs = np.random.RandomState(3)
    diffs, wrong, spreads = [], [], []
    for _ in range(4):
        ids = rs.randint(2, cfg.vocab_size, 64).astype(np.int32)
        want, spread = chk.reference_logprobs(_Ctx(shape), params, ids, True)
        diffs.append(np.abs(_system_logprobs(bf16, params, ids) - want)[8:])
        other = ids.copy()
        other[:4] = rs.randint(2, cfg.vocab_size, 4)
        wrong.append(np.abs(chk.reference_logprobs(_Ctx(shape), params, other)
                            - want)[8:])
        spreads.append(spread)
    rms = chk.predicted_rms(max(spreads), cfg.num_layers)
    assert chk._verdict(diffs, rms)["ok"], chk._verdict(diffs, rms)
    assert not chk._verdict(wrong, rms)["ok"]
