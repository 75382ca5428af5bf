"""The block-diffusion rollout commits a block inside the next block's
first denoising forward (``rollout/engine.py::_generate_blocks``): the
fused engine against a plain loop written here, five forwards a block
(``denoising_steps`` and a commit of its own), on the same draws; a
planted fault (the riding rows' cache write dropped) that the comparison
catches; the forwards the program runs and their rows; the counters of
``rollout.dispatch`` against hand counts; and that no other model's
step grows.  Tiny ``sdar_moe`` preset, float32, the CPU."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import transformer
from orion_tpu.models.transformer import (Transformer, block_decode_attrs,
                                          cache_slots, init_cache,
                                          init_params, make_decode_twin,
                                          prep_decode_params)
from orion_tpu.ops.sampling import bar_token, sample_tokens
from orion_tpu.rollout.engine import RolloutEngine

P = 8
# shorter than a block, on a block boundary, len % 4 = 1 and 3
LENS = np.asarray([2, 8, 5, 7], np.int32)
STOP = 77


def _model(stop=None):
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(7), cfg)
    if stop is not None:
        # a head that favours one id: some row reveals it inside a block
        head = params["lm_head"]["kernel"]
        params = {**params, "lm_head": {"kernel": head.at[:, stop].set(
            head[:, stop] + 0.45)}}
    return cfg, model, params


def _prompts(seed, P=P):
    rs = np.random.RandomState(seed)
    return np.where(np.arange(P)[None] < LENS[:, None],
                    rs.randint(4, 255, (len(LENS), P)), 0).astype(np.int32)


def _engine(cfg, model, T, stop=None, P=P):
    return RolloutEngine(
        model, cfg, RolloutConfig(max_prompt_len=P, max_new_tokens=T,
                                  temperature=1.0), eos_token_id=stop)


def _five_forwards_a_block(cfg, model, params, prompts, lens, rng, T, stop):
    """The rollout as it was before the commit rode along: per block
    ``denoising_steps`` forwards of its ``block_length`` rows, each
    followed by a draw and a reveal, then one more forward of the
    block's final tokens, whose only product is their keys and values.
    A Python loop over blocks, numpy bookkeeping, one key a denoising
    forward split off the carried one."""
    twin, tcfg = make_decode_twin(model, cfg)
    weights = {"params": prep_decode_params(params, cfg, False)}
    forward = jax.jit(lambda z, pos, cache: twin.apply(weights, z, pos, cache))
    B = len(lens)
    Bd, S, mask_id = cfg.block_length, cfg.denoising_steps, cfg.mask_id
    blocks = cfg.blocks_spanned(T)
    slots = P + blocks * Bd
    at = np.broadcast_to(np.arange(P, dtype=np.int32), (B, P))
    _, cache = twin.apply(
        weights, jnp.asarray(prompts), jnp.asarray(at),
        init_cache(tcfg, B, slots, dtype=jnp.float32),
        logits_positions=jnp.zeros((B, 1), jnp.int32),
        token_mask=jnp.asarray(at < lens[:, None]))
    seq = np.zeros((B, slots), np.int32)
    seq[:, :P] = np.where(at < lens[:, None], prompts, 0)
    lp, plp = (np.zeros((B, slots), np.float32) for _ in range(2))
    step = np.full((B, slots), S, np.int32)
    done, comp_len = np.zeros(B, bool), np.zeros(B, np.int32)
    rows = np.arange(B)[:, None]
    for i in range(blocks):
        if done.all():
            break
        pos = ((lens // Bd + i)[:, None] * Bd + np.arange(Bd)).astype(np.int32)
        new = (pos >= lens[:, None]) & (pos < (lens + T)[:, None])
        z = np.where(pos < lens[:, None], seq[rows, pos], mask_id)
        live = new & ~done[:, None]
        masked = live.copy()
        for s in range(S):
            logits, cache = forward(jnp.asarray(z), jnp.asarray(pos), cache)
            rng, sub = jax.random.split(rng)
            cand, l, pl = (np.asarray(t).reshape(B, Bd) for t in sample_tokens(
                sub, bar_token(logits, mask_id).reshape(B * Bd, -1),
                temperature=1.0))
            conf = np.where(masked, np.asarray(jnp.exp(l)), -1.0)
            for b in range(B):
                # the most probable masked ones, the lower position first
                for j in np.argsort(-conf[b], kind="stable")[:Bd // S]:
                    if conf[b, j] >= 0.0:
                        z[b, j], masked[b, j] = cand[b, j], False
                        if live[b, j]:
                            p = pos[b, j]
                            lp[b, p], plp[b, p], step[b, p] = \
                                l[b, j], pl[b, j], s
        _, cache = forward(jnp.asarray(z), jnp.asarray(pos), cache)  # commit
        for b in range(B):
            if done[b]:
                continue
            seq[b, pos[b][live[b]]] = z[b][live[b]]
            hit = [j for j in range(Bd)
                   if live[b, j] and stop is not None and z[b, j] == stop]
            upto = pos[b, hit[0]] + 1 if hit \
                else min(pos[b, -1] + 1, lens[b] + T)
            comp_len[b] = upto - lens[b]
            done[b] = bool(hit) or pos[b, -1] + 1 >= lens[b] + T
    window = lens[:, None] + np.arange(T)[None]
    real = np.arange(T)[None] < comp_len[:, None]
    take = partial(np.take_along_axis, indices=window, axis=1)
    return {"sequences": seq[:, :P + T], "completion_lens": comp_len,
            "completions": np.where(real, take(seq), 0),
            "logprobs": np.where(real, take(lp), 0.0),
            "policy_logprobs": np.where(real, take(plp), 0.0),
            "reveal_step": take(step)}


# T = 10 and 7 are no multiples of the block; (8, STOP): a row stops early
@pytest.mark.parametrize("T,stop", [(10, None), (7, None), (8, STOP),
                                    (13, STOP)])
def test_the_fused_engine_equals_five_forwards_a_block(T, stop):
    cfg, model, params = _model(stop)
    prompts = _prompts(T)
    key = jax.random.key(21)
    got = _engine(cfg, model, T, stop).generate(
        jnp.asarray(prompts), jnp.asarray(LENS), key, params=params).to_host()
    want = _five_forwards_a_block(cfg, model, params, prompts, LENS, key, T,
                                  stop)
    for name in ("sequences", "completions", "completion_lens",
                 "reveal_step"):
        np.testing.assert_array_equal(getattr(got, name), want[name], name)
    for name in ("logprobs", "policy_logprobs"):
        np.testing.assert_allclose(getattr(got, name), want[name], atol=1e-5,
                                   err_msg=name)
    assert np.any(got.logprobs != 0.0)
    if stop is not None:
        assert np.any(got.completion_lens < T), \
            "no row met the stop token: the case tests nothing"


def test_a_dropped_write_of_the_riding_rows_changes_the_next_blocks_logits(
        monkeypatch):
    """The planted fault: the riding rows' keys and values never reach
    the cache, so the next block's queries read what the block's last
    denoising forward left (its state before the last reveal).  The
    first block a row generates has no block riding and stays; the
    log-probabilities of the block after a full one move."""
    cfg, model, params = _model()
    Bd, T = cfg.block_length, 12
    # padded to 10: a prefill of two blocks' length would be a step too
    prompts, key = _prompts(3, P=10), jax.random.key(5)

    def run():
        return _engine(cfg, model, T, P=10).generate(
            jnp.asarray(prompts), jnp.asarray(LENS), key,
            params=params).to_host()

    right = run()
    writer = transformer._cache_writer

    def faulty(positions, B, L, step=False):
        if not (step and L == 2 * Bd):
            return writer(positions, B, L, step)
        own = writer(positions[:, Bd:], B, Bd, step)
        return lambda cache, new: own(cache, new[:, Bd:])

    monkeypatch.setattr(transformer, "_cache_writer", faulty)
    wrong = run()
    moved = np.abs(wrong.policy_logprobs - right.policy_logprobs)
    for b, n in enumerate(Bd - LENS % Bd):   # new positions of a first block
        assert np.all(moved[b, :n] < 1e-6)
    # the row whose prompt ends on a boundary: every block of it is full,
    # so its last denoising forward saw a masked position (a partial
    # block is final a forward early and its stale keys are the final ones)
    b = int(np.flatnonzero(LENS % Bd == 0)[0])
    upto = LENS[b] + 2 * Bd
    np.testing.assert_array_equal(wrong.sequences[b, :upto],
                                  right.sequences[b, :upto])
    assert np.max(moved[b, Bd:2 * Bd]) > 1e-4        # of the same tokens
    assert np.any(wrong.sequences != right.sequences)


def test_two_blocks_in_one_forward_are_the_commit_and_the_next_forward():
    """The model alone: after a block's forward on a stale state, ONE
    forward of ``[final tokens ; the next block]`` gives the next block
    the logits that a commit forward and then its own forward give, and
    other ones than a forward without the commit."""
    cfg, model, params = _model()
    Bd = cfg.block_length
    rs = np.random.RandomState(0)
    B, slots = 2, 24
    ids = jnp.asarray(rs.randint(4, 255, (B, 8)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (B, 8))
    apply = partial(model.apply, {"params": params})
    _, cache = apply(ids, pos, init_cache(cfg, B, slots))
    at = 8 + jnp.broadcast_to(jnp.arange(Bd, dtype=jnp.int32), (B, Bd))
    stale = jnp.full((B, Bd), cfg.mask_id, jnp.int32)
    final = jnp.asarray(rs.randint(4, 255, (B, Bd)), jnp.int32)
    _, cache = apply(stale, at, cache)
    _, committed = apply(final, at, cache)
    want, want_cache = apply(stale, at + Bd, committed)
    skipped, _ = apply(stale, at + Bd, cache)
    got, got_cache = apply(
        jnp.concatenate([final, stale], 1),
        jnp.concatenate([at, at + Bd], 1), cache,
        logits_positions=jnp.broadcast_to(Bd + jnp.arange(Bd), (B, Bd)))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.max(jnp.abs(skipped - want))) > 1e-3


class _Counting:
    """A decode twin whose ``apply`` reports, when the program RUNS, the
    rows a sequence of every forward."""

    def __init__(self, twin, seen):
        self.twin, self.seen = twin, seen

    def apply(self, variables, ids, *args, **kw):
        jax.debug.callback(partial(self.seen.append, ids.shape[1]),
                           ordered=True)
        return self.twin.apply(variables, ids, *args, **kw)


@pytest.mark.parametrize("T", [10, 16])
def test_the_program_runs_blocks_times_steps_forwards(T):
    """``blocks * denoising_steps`` forwards after prefill, none beside:
    a block's first of twice ``block_length`` rows a sequence (the block
    before rides; the first block's riding rows commit nothing) and its
    others of ``block_length``; what ``rollout.dispatch`` says of them."""
    cfg, model, params = _model()
    Bd, S = cfg.block_length, cfg.denoising_steps
    engine = _engine(cfg, model, T)
    seen = []
    engine._decode_model = _Counting(engine._decode_model, seen)
    out = engine.generate(jnp.asarray(_prompts(1)), jnp.asarray(LENS),
                          jax.random.key(2), params=params)
    jax.block_until_ready(out.sequences)
    jax.effects_barrier()
    attrs = engine.dispatch_attrs((len(LENS), P), LENS, params)
    blocks = attrs["blocks"]
    assert blocks == max((n % Bd + T - 1) // Bd + 1 for n in LENS)
    assert seen[0] == P and len(seen) == 1 + attrs["denoise_forwards"]
    assert seen[1:] == ([2 * Bd] + [Bd] * (S - 1)) * blocks
    assert attrs["denoise_forwards"] == blocks * S
    assert attrs["commit_rows"] == (blocks - 1) * Bd


def test_the_counters_of_a_small_shape_by_hand():
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32")
    assert (cfg.block_length, cfg.denoising_steps) == (4, 4)
    got = block_decode_attrs(cfg, [5, 8], 24, 10)
    # both rows span 3 blocks: positions 4 .. 15 and 8 .. 19
    assert got["blocks"] == 3
    assert got["denoise_forwards"] == 12 and got["commit_rows"] == 8
    # a query at p has the (p // 4 + 1) * 4 keys through its block's end
    prefill = (4 * 4 + 8) + (4 * 4 + 4 * 8)
    blocks_5 = [4 * 8, 4 * 12, 4 * 16]           # one forward of each block
    blocks_8 = [4 * 12, 4 * 16, 4 * 20]
    denoise = 4 * (sum(blocks_5) + sum(blocks_8))
    commit = sum(blocks_5[:-1]) + sum(blocks_8[:-1])     # all but the last
    assert got["decode_pairs"] == prefill + denoise + commit == 1608
    assert got["kv_step_slots"] == 24.0          # a cache of one prefix


def test_the_counters_at_the_cells_sizes():
    """``ppo-sdar-ep8-sync``: prompts of 128-256 tokens and 512 new ones
    span 129 blocks: 516 forwards where 645 were, 512 riding rows."""
    cfg = dataclasses.replace(ModelConfig.sdar_30b_a3b(), num_layers=6,
                              experts_held=16, vocab_size=18992,
                              max_seq_len=1024)
    lens = np.random.RandomState(0).randint(128, 257, 32)
    lens[0] = 255                                # a row that spans 129
    T = 512
    slots = cache_slots(256 + cfg.blocks_spanned(T) * cfg.block_length)
    got = block_decode_attrs(cfg, lens, slots, T)
    assert got["blocks"] == 129 and got["denoise_forwards"] == 516
    assert got["commit_rows"] == 512
    assert got["denoise_forwards"] * cfg.block_length + got["commit_rows"] \
        == 129 * 5 * 4 - 4                       # the last commit is gone
    assert 128 <= got["kv_step_slots"] <= slots


@pytest.mark.parametrize("arch,L,steps", [
    ("llama", 1, True), ("llama", 2, False), ("llama", 4, False),
    ("llama", 8, False), ("sdar_moe", 4, True), ("sdar_moe", 8, True),
    ("sdar_moe", 12, False)])
def test_only_a_block_diffusion_model_steps_more_than_a_token(
        arch, L, steps, monkeypatch):
    """A forward of L rows against a cache goes through the decode
    loop's step (``prefix_step``) for one token, and for one block or
    two of a block-diffusion model; for any other model L > 1 is a
    prefill chunk as it was."""
    cfg = ModelConfig.tiny(arch, dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    calls = []
    real = transformer.prefix_step

    def spy(positions, Lmax, fn):
        calls.append(positions.shape)
        return real(positions, Lmax, fn)

    monkeypatch.setattr(transformer, "prefix_step", spy)
    ids = jnp.ones((2, L), jnp.int32)
    pos = 4 + jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (2, L))
    logits, _ = model.apply({"params": params}, ids, pos,
                            init_cache(cfg, 2, 24))
    assert logits.shape[:2] == (2, L)
    assert bool(calls) == steps
    assert all(shape == (2, L) for shape in calls)
