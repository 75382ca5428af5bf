"""Rollout engine tests: HF greedy parity, train-graph logprob parity,
EOS early-exit, ragged prompts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.ops.logprobs import completion_logprobs
from orion_tpu.rollout import RolloutEngine

torch = pytest.importorskip("torch")


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    return cfg, model, params


def _engine(cfg, model, temperature=0.0, eos=None, **kw):
    rcfg = RolloutConfig(temperature=temperature, max_new_tokens=8, **kw)
    return RolloutEngine(model, cfg, rcfg, eos_token_id=eos)


def test_greedy_matches_hf_generate():
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg).eval()

    from orion_tpu.models.hf_loader import config_from_hf, convert_hf_state_dict

    cfg = config_from_hf(hf.config)
    cfg.dtype = "float32"
    params = convert_hf_state_dict(hf.state_dict(), cfg)
    model = Transformer(cfg)

    ids = np.random.RandomState(0).randint(0, 128, (2, 7))
    with torch.no_grad():
        hf_out = hf.generate(
            torch.tensor(ids), max_new_tokens=8, do_sample=False,
            eos_token_id=None, pad_token_id=0)
    eng = _engine(cfg, model)
    eng.load_weights(params)
    res = eng.generate(jnp.asarray(ids), jnp.full((2,), 7, jnp.int32),
                       jax.random.key(1))
    np.testing.assert_array_equal(
        np.asarray(res.completions), hf_out[:, 7:].numpy())
    # packed sequences reproduce prompt + completion contiguously
    np.testing.assert_array_equal(
        np.asarray(res.sequences[:, :15]), hf_out.numpy())


def test_rollout_logprobs_match_train_graph(tiny_setup):
    """The trainer/sampler parity contract (SURVEY.md §4): engine
    logprobs at temperature=1 equal recomputation under the full
    training forward."""
    cfg, model, params = tiny_setup
    eng = _engine(cfg, model, temperature=1.0)
    eng.load_weights(params)

    B, P = 3, 6
    ids = jax.random.randint(jax.random.key(2), (B, P), 1, cfg.vocab_size)
    lens = jnp.array([6, 4, 5], jnp.int32)
    res = eng.generate(ids, lens, jax.random.key(3))

    positions = jnp.broadcast_to(jnp.arange(res.sequences.shape[1]),
                                 res.sequences.shape)
    logits, _ = model.apply({"params": params}, res.sequences, positions)
    train_lp = completion_logprobs(logits, res.sequences, lens, 8)
    mask = np.asarray(res.completion_mask)
    np.testing.assert_allclose(
        np.asarray(train_lp) * mask, np.asarray(res.logprobs) * mask,
        rtol=1e-4, atol=1e-5)


def test_eos_early_exit(tiny_setup):
    cfg, model, params = tiny_setup
    eng = _engine(cfg, model)
    eng.load_weights(params)
    ids = jax.random.randint(jax.random.key(4), (2, 5), 1, cfg.vocab_size)
    lens = jnp.full((2,), 5, jnp.int32)
    res = eng.generate(ids, lens, jax.random.key(5))
    # pick the token generated at step 2 of row 0 as the EOS and rerun
    eos = int(res.completions[0, 2])
    eng2 = _engine(cfg, model, eos=eos)
    eng2.load_weights(params)
    res2 = eng2.generate(ids, lens, jax.random.key(5))
    assert int(res2.completion_lens[0]) == 3  # tokens 0,1,2 (EOS included)
    assert np.asarray(res2.completions)[0, 3:].tolist() == [0] * 5
    assert np.asarray(res2.completion_mask)[0].tolist() == [1, 1, 1, 0, 0, 0, 0, 0]
    # logprobs after EOS are zeroed
    assert np.asarray(res2.logprobs)[0, 3:].tolist() == [0.0] * 5


def test_ragged_prompts_match_unpadded(tiny_setup):
    cfg, model, params = tiny_setup
    eng = _engine(cfg, model)
    eng.load_weights(params)
    rng = np.random.RandomState(1)
    a = rng.randint(1, cfg.vocab_size, (1, 4))
    b = rng.randint(1, cfg.vocab_size, (1, 7))

    padded = np.zeros((2, 7), np.int32)
    padded[0, :4] = a
    padded[1] = b
    res = eng.generate(jnp.asarray(padded), jnp.array([4, 7], jnp.int32),
                       jax.random.key(6))
    res_a = eng.generate(jnp.asarray(a), jnp.array([4], jnp.int32),
                         jax.random.key(7))
    res_b = eng.generate(jnp.asarray(b), jnp.array([7], jnp.int32),
                         jax.random.key(8))
    np.testing.assert_array_equal(np.asarray(res.completions[0]),
                                  np.asarray(res_a.completions[0]))
    np.testing.assert_array_equal(np.asarray(res.completions[1]),
                                  np.asarray(res_b.completions[0]))


def test_windowed_logprobs_match_full(tiny_setup):
    """completion-window logits (r3 perf path) are numerically identical
    to the full-logits oracle, ragged prompt lengths included."""
    from orion_tpu.ops.logprobs import (completion_logprobs,
                                        completion_window_positions,
                                        windowed_completion_logprobs)

    cfg, model, params = tiny_setup
    rng = np.random.RandomState(3)
    B, L, T = 3, 12, 5
    seqs = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, L)), jnp.int32)
    lens = jnp.asarray([3, 7, 5], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    logits, _ = model.apply({"params": params}, seqs, positions)
    full = completion_logprobs(logits, seqs, lens, T)

    widx = completion_window_positions(lens, T, L)
    logits_w, _ = model.apply({"params": params}, seqs, positions,
                              logits_positions=widx)
    win = windowed_completion_logprobs(logits_w, seqs, lens, T)
    np.testing.assert_allclose(np.asarray(win), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


def test_cache_length_rounds_to_multiple_of_8(tiny_setup):
    """init_cache pads the cache axis to a multiple of 8 (Mosaic tile
    legality — the r5 on-chip sub-8 block failure), and generation at
    an unlucky max_prompt+max_new (30+25=55 -> 56) is unaffected: the
    padded tail is masked by the slot==position causal rule."""
    from orion_tpu.models.transformer import init_cache, make_decode_twin

    cfg, model, params = tiny_setup
    _, dcfg = make_decode_twin(model, cfg)
    cache = init_cache(dcfg, 2, 55, dtype=jnp.float32)
    leaf = cache[0]["k"] if isinstance(cache, list) else cache["k"]
    assert leaf.shape[1] == 56

    rcfg = RolloutConfig(temperature=0.0, max_prompt_len=30,
                         max_new_tokens=25)
    eng = RolloutEngine(model, cfg, rcfg, eos_token_id=None)
    eng.load_weights(params)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(1, cfg.vocab_size, (2, 30)), jnp.int32)
    lens = jnp.asarray([30, 17], jnp.int32)
    res = eng.generate(ids, lens, jax.random.key(1),
                       max_new_tokens=25)
    assert res.completions.shape == (2, 25)
    assert np.isfinite(np.asarray(res.policy_logprobs)).all()


@pytest.mark.parametrize("arch", ["tiny", "tiny_deepseek_v3"])
def test_fixed_batch_engine_refuses_speculative_k(arch):
    """One decode path here: speculative decoding is the continuous
    engine's, and the refusal says so — also for the latent-attention
    block, whose prompt padding a second decode path once routed
    through the experts."""
    cfg = (ModelConfig.tiny(dtype="float32") if arch == "tiny"
           else ModelConfig.tiny("deepseek_v3", dtype="float32"))
    with pytest.raises(ValueError, match="rollout.engine=continuous"):
        RolloutEngine(Transformer(cfg), cfg,
                      RolloutConfig(speculative_k=4), eos_token_id=None)
