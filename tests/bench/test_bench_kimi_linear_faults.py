"""The faults ``reference_check_kimi_linear`` must catch, each planted in
the program at the tiny size (bfloat16, as the cell computes) and shown
failing; the program itself passes.  In a file of its own so that the
suite's workers share the work (``--dist loadfile``).  Nothing printed
here is a measurement."""

import copy
import dataclasses

import numpy as np
import pytest

import bench_rehearsal as br
from test_bench_kimi_linear import _tiny_shape

# -- the check and the faults it must catch ---------------------------------

class _Model:
    """The program's model with a fault between what it is given and
    what it computes with."""

    def __init__(self, model, params_fault=None, unmasked_prefill=False):
        self.model = model
        self.params_fault = params_fault or (lambda p: p)
        self.unmasked_prefill = unmasked_prefill

    def apply(self, variables, *a, **k):
        if self.unmasked_prefill and len(a) > 2 and a[2] is not None:
            k = {key: v for key, v in k.items() if key != "token_mask"}
        return self.model.apply(
            {"params": self.params_fault(variables["params"])}, *a, **k)


class _Trainer:
    """What ``check_trainer`` uses of a trainer."""

    def __init__(self, cfg, model, params):
        import types

        import jax

        from orion_tpu.config import RolloutConfig
        from orion_tpu.rollout.engine import RolloutEngine
        from orion_tpu.trainers.base import BaseTrainer

        self.cfg = types.SimpleNamespace(model=cfg)
        self.model = model
        self.state = types.SimpleNamespace(params=params)
        self._policy_apply = types.MethodType(BaseTrainer._policy_apply, self)
        self._windowed_forward = types.MethodType(
            BaseTrainer._windowed_forward, self)
        self._jit_logprobs = jax.jit(
            types.MethodType(BaseTrainer._logprobs_fn, self),
            static_argnames=("max_new",))
        self.engine = RolloutEngine(model, cfg, RolloutConfig(
            max_prompt_len=P, max_new_tokens=T, temperature=1.0))

    def generate(self, prompt_ids, prompt_lens, rng):
        return self.engine.generate(prompt_ids, prompt_lens, rng,
                                    params=self.state.params)


# prompts longer than a chunk of the delta rule, so that a state crosses
# a chunk boundary in prefill and in the training forward
P, T = 80, 48


class _Ctx:
    def __init__(self, config, seed):
        self.config, self.seed = config, seed
        self.traffic = {"prompt_len": P, "new_tokens": T,
                        "samples_per_iteration": 4}
        self.cell = {"chips": 1}

    lib = staticmethod(br.lib)


def _fp8(params):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params)


def _sharper_attention(params):
    """The latent layer's queries and keys eightfold: at the tiny size
    its scores are near zero and its attention uniform whatever is
    rotated; at the published widths their spread is 0.6."""
    params = copy.deepcopy(params)
    for layer in params.values():
        attn = layer.get("attn", {}) if isinstance(layer, dict) else {}
        if "kv_b_proj" in attn:
            for name in ("q_proj", "kv_a_proj_with_mqa"):
                attn[name]["kernel"] = 8.0 * attn[name]["kernel"]
    return params


def _no_convolution(params):
    """The keys' convolution skipped: the current token's tap alone."""
    import jax.numpy as jnp

    params = copy.deepcopy(params)
    for layer in params.values():
        attn = layer.get("attn", {}) if isinstance(layer, dict) else {}
        if "k_conv" in attn:
            w = attn["k_conv"]
            attn["k_conv"] = jnp.zeros_like(w).at[-1].set(1.0)
    return params


def _plant(monkeypatch, fault):
    """The delta rule's two forms with a fault in both."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops import kda

    step, chunked, chunk = kda.kda_step, kda.kda_chunked, kda._chunk
    if fault == "state_in_bfloat16":
        def rounded(S):
            # not a pair of converts: the TPU's compiler removes those
            return jax.lax.reduce_precision(S, exponent_bits=8,
                                            mantissa_bits=7)

        def bf16_step(q, k, v, g, beta, state):
            o, S = step(q, k, v, g, beta, rounded(state))
            return o, rounded(S)

        def bf16_chunk(S, *a):
            S, o = chunk(rounded(S), *a)
            return rounded(S), o

        monkeypatch.setattr(kda, "kda_step", bf16_step)
        monkeypatch.setattr(kda, "_chunk", bf16_chunk)
        return
    change = {"decay_dropped": lambda g, beta: (jnp.zeros_like(g), beta),
              "beta_one": lambda g, beta: (g, jnp.where(
                  beta > 0, 1.0, beta))}[fault]

    def bad_step(q, k, v, g, beta, state):
        return step(q, k, v, *change(g, beta), state)

    def bad_chunked(q, k, v, g, beta, *a, **kw):
        return chunked(q, k, v, *change(g, beta), *a, **kw)

    monkeypatch.setattr(kda, "kda_step", bad_step)
    monkeypatch.setattr(kda, "kda_chunked", bad_chunked)


# ISSUE 32's seven, the nearest precision below, and none
FAULTS = ["none", "lower_precision", "state_in_bfloat16", "decay_dropped",
          "beta_one", "a_convolution_skipped",
          "padding_not_masked_in_prefill", "rotary_on_the_latent_layer",
          "one_expert_fewer"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    # bfloat16 as the cell computes; half the experts held
    cfg = ModelConfig.tiny("kimi_linear", experts_held=4, expert_offset=2,
                           vocab_size=260, max_seq_len=P + T)
    params = _sharper_attention(
        init_params(Transformer(cfg), jax.random.key(21), cfg))
    program_cfg, kw = cfg, {}
    if fault == "lower_precision":
        kw["params_fault"] = _fp8    # the nearest precision below bfloat16
    elif fault in ("state_in_bfloat16", "decay_dropped", "beta_one"):
        _plant(monkeypatch, fault)
    elif fault == "a_convolution_skipped":
        kw["params_fault"] = _no_convolution
    elif fault == "padding_not_masked_in_prefill":
        kw["unmasked_prefill"] = True
    elif fault == "rotary_on_the_latent_layer":
        program_cfg = dataclasses.replace(cfg, mla_use_nope=False)
    elif fault == "one_expert_fewer":
        program_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    trainer = _Trainer(cfg, _Model(Transformer(program_cfg), **kw), params)
    chk = br.lib("reference_check_kimi_linear")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    # two seeds for the program, one for a fault
    verdicts = [chk.check_trainer(_Ctx(_tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    if fault == "one_expert_fewer":
        assert not any(v["ok"] for v in verdicts)
        assert all("selects 1 experts" in v["why"] for v in verdicts)
        return
    assert all(v["tokens"] == 2 * T for v in verdicts)
    logprobs_agree = all(
        v["mean_abs_diff"] <= v["mean_tolerance"]
        and v["max_abs_diff"] <= v["max_tolerance"]
        and v["decode_mean_abs_diff"] <= v["decode_mean_tolerance"]
        for v in verdicts)
    if fault == "none":
        assert all(v["ok"] for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] > T for v in verdicts)
        assert all(v["state_float32_share"] > 0.9 for v in verdicts)
        assert all(v["mean_abs_diff"] < v["rotated_mean_abs_diff"]
                   for v in verdicts), verdicts
    elif fault == "state_in_bfloat16":
        # one more rounding among a layer's fifty: the logprobs agree,
        # the state's own bits do not
        assert not any(v["ok"] for v in verdicts), verdicts
        assert logprobs_agree, verdicts
        assert all(v["state_float32_share"] == 0.0 for v in verdicts)
    elif fault == "rotary_on_the_latent_layer":
        # closer to the reference WITH the rotation than to the model's
        assert not any(v["ok"] for v in verdicts), verdicts
        assert all(v["rotated_mean_abs_diff"] < v["mean_abs_diff"]
                   for v in verdicts), verdicts
    elif fault == "padding_not_masked_in_prefill":
        # the training forward masks on its own path: the rollout shows it
        assert not any(v["ok"] for v in verdicts), verdicts
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   and v["decode_mean_abs_diff"] > v["decode_mean_tolerance"]
                   for v in verdicts), verdicts
    else:
        assert not any(v["ok"] for v in verdicts), verdicts
        # by a comparison of logprobs, not by a side condition
        assert all(v["mean_abs_diff"] > v["mean_tolerance"]
                   or v["max_abs_diff"] > v["max_tolerance"]
                   or v["decode_mean_abs_diff"] > v["decode_mean_tolerance"]
                   for v in verdicts), verdicts
