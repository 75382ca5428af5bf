"""The comparison with ``reference_mellum2`` that decides ``correct`` for
a ``mellum`` configuration (the chip's share of it: some of the routed
experts and a slice of the vocabulary).

Shaped like ``reference_check_keye_dsa.py`` (the same block but for the
indexer: pre-norm grouped-query attention with per-head q/k norms over
softmax-routed experts) and ``reference_check_lfm2.py`` (paired variants
and probes of the program's own modules).  This file knows how the
program lays out its parameters: a block ``layers_<i>`` (``layers_<a>to
<b>`` for a stretch stacked by ``scan_layers``: the runs S S S | F | S S
S | F) holds ``input_norm`` + ``attn/{q,k,v,o}_proj, q_norm, k_norm`` and
``post_attn_norm`` + ``mlp/{router, experts_gate_up_proj,
experts_down_proj}``; ``final_norm``, ``lm_head``.  The reference is
handed one layer at a time, as float32, its attention in blocks of
``Q_BLOCK`` queries so that no [heads, S, S] array exists at the timed
length.  The router's 8 of 64 experts are a discrete choice made from
bf16 inputs: the reference FOLLOWS the program's (sown as
``moe_selected``), as Keye's check does.  Logits and log-probabilities
are compared, never sampled ids.  Each limit stands beside its reason.

(a) **The training forward** (``_jit_logprobs``: the windowed and the
    full flash kernels, both rotary tables, the grouped expert product)
    on 2 seeded sequences of the timed length (8192: the answer's 1024
    tokens are compared, every one of whose sliding queries sees an
    eighth or less of its causal keys).  Every compared token is held to
    ``reference_check``'s error model (its form, unit rounding, slack
    and sigmas imported: mean within ``SLACK sqrt(2 / pi)`` of the RMS,
    worst within ``WORST_SIGMAS`` RMS; a logprob's RMS error is
    ``sigma_z sqrt(layers R + 3) U_BF16``) with ``ROUNDINGS_MELLUM`` =
    36 roundings a block: ``ROUNDINGS_KEYE``, kept because this IS that
    block without the indexer (the same roundings in the same places at
    hidden 2304 for 2048 and experts of 896 for 768; calibrated in
    ``reference_check_dsv3`` on a bf16 forward of the pre-norm expert
    block).  What bf16 weights and activations against float32 read on
    the chip, and what fp8 weights read (the nearest precision below: 16
    times the rounding, far outside), are in PERF.md section 6 (PR 53).
(b) **The rollout**: the engine's policy logprobs of one rollout of the
    timed shape, a full-length prompt and one of five sevenths of it in
    one right-padded batch (7168 and 5120 tokens at the cell's sizes),
    so that prefill's ``token_mask``, the ring's hand-over of each row's
    LAST real tokens and the full cache's real lengths are inside
    ``correct``, then ``new_tokens`` one-token steps through BOTH caches
    (``dense_step`` over the ring under ``reach`` and over the full
    cache), against the reference's teacher-forced logprobs of what it
    sampled: a ring slot written or read one position off puts another
    key's value under a query.  Mean alone, within ``DECODE_SLACK`` of
    (a)'s mean limit (the engine sows no selection, so a step that
    selects another expert than the followed forward is off by that
    expert's output: ``reference_check_dsv3``'s point 3).
(c) **Which model the program computes**, paired over the first
    sequence's tokens so that the roundings common to both cancel: the
    program must lie closer to the reference than to the reference
    computed as what the model is NOT (``VARIANTS``): the window ignored
    on the sliding layers; the window applied on the full layers too;
    YaRN's table on the sliding layers; the default table on the full
    ones; ``attention_factor`` left out; the gates not normalised.
(d) **The window's edge, key for key**, looked at where one key can be
    seen: a late query's 1024th key is a thousandth of its attention and
    no logprob shows it, so the program's own mixers of both kinds
    (``models.transformer.mixer_spec``) are run on weights that make the
    attention uniform (``q = 0``) and the values the position's residue
    mod ``head_dim``: the output then COUNTS the keys a query saw,
    residue by residue, and one key more, fewer or other reads about
    1 in ``*_edge_keys`` (the distance in units of one key's weight: 8
    / 9 at a window of 8 keys, 0.999 at 1024) where rounding reads under
    0.1 (limit ``EDGE_KEYS``).  Whole sequences (the flash kernels' mask and tile
    extents on the chip) and a right-padded prefill of rows shorter and
    longer than the window followed by enough one-token steps that the
    ring wraps more than once (the ring's write, its hand-over and
    ``reach``: a ring that keeps position t - 1024 one step too long
    shows as one key too many).
(e) **The router's float32.**  A softmax computed in bfloat16 moves a
    gate by 2^-9 of itself, which no logprob shows, and exchanges
    experts whose probabilities lie within that: the program's own
    ``TopKMoE`` is run on a router whose logits are ``4 + e / 1024`` in
    a seeded order a row, exact in float32 and all equal to 4 in
    bfloat16: ``router_float32_share``, the rows whose selection is the
    float32 top-k, is 1 for a float32 softmax and next to 0 otherwise
    (limit ``ROUTER_FLOAT32_SHARE``).
"""

from __future__ import annotations

import math

import numpy as np

# Keye's block without the indexer: see the module docstring, (a)
ROUNDINGS_MELLUM = 36
# the rollout's selection is not followed, its steps round once more (b)
DECODE_SLACK = 2.5
# two programs compiled from one model may differ in the last bit
SAME_FORWARD = 1e-3
UNFOLLOWED_MAX_SHARE = 0.01
# queries a block of the reference's attention at the timed length
Q_BLOCK = 256
# (d): between rounding's reading (under 0.1) and one key's (1.0)
EDGE_KEYS = 0.5
# (e): between a float32 softmax's reading (1.0) and a bfloat16 one's (~0)
ROUTER_FLOAT32_SHARE = 0.9
SLIDING, FULL = "sliding_attention", "full_attention"
# (c): what the reference is also computed as, and is not
VARIANTS = {
    "window_ignored": {"window_on": ()},
    "window_on_full": {"window_on": (SLIDING, FULL)},
    "yarn_on_sliding": {"rope_of": ((SLIDING, FULL),)},
    "default_on_full": {"rope_of": ((FULL, SLIDING),)},
    "no_attention_factor": {"attention_factor": False},
    "gates_unnormalised": {"norm_gates": False}}


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def held_of(config: dict):
    """(offset, count) of the experts this share holds: the file's
    ``num_experts`` counts those held here (``source_values`` has the
    published count, the router's width)."""
    return int(config.get("expert_offset", 0)), int(config["num_experts"])


def layer_weights(p: dict) -> dict:
    """One block of the program's tree as the reference takes it."""
    a, m = p["attn"], p["mlp"]
    w = {"n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"]),
         "q_g": _f32(a["q_norm"]["scale"]), "k_g": _f32(a["k_norm"]["scale"]),
         "w_router": _f32(m["router"]),
         "e_gate_up": _f32(m["experts_gate_up_proj"]),
         "e_down": _f32(m["experts_down_proj"])}
    w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkvo"})
    return w


_JITTED: dict = {}


def _jitted(ref, shape: dict, held, q_block, variant: dict):
    """(one block, final norm + head + logprobs) of the reference under
    ``variant``, jitted once a configuration and variant."""
    import json

    import jax
    import jax.numpy as jnp

    key = (json.dumps(shape, sort_keys=True, default=str), held, q_block,
           tuple(sorted(variant.items())))
    if key not in _JITTED:
        step = jax.jit(
            lambda x, p, mask, sel, layer_type: ref.layer(
                x, layer_weights(p), shape, layer_type, held, mask, sel,
                q_block, info=True, **variant),
            static_argnames=("layer_type",))

        @jax.jit
        def finish(x, final_norm, lm_head, ids):
            logits = ref.head(x, {"nf_g": _f32(final_norm["scale"]),
                                  "w_head": _f32(lm_head["kernel"])}, shape)
            return (ref.next_token_logprobs(logits, ids),
                    jnp.mean(jnp.std(logits, axis=-1)))

        _JITTED[key] = (step, finish)
    return _JITTED[key]


def reference_logprobs(ctx, params: dict, ids: np.ndarray, experts=None,
                       n_real=None, spread: bool = False, **variant):
    """Teacher-forced next-token logprobs of ``ids`` [S] under the
    reference, given the program's parameter tree: [S-1] float32.
    ``experts`` [layers, S, k]: the experts to follow; ``n_real``: the
    positions from there on hold no token; ``variant``:
    ``reference_mellum2.layer``'s.  ``spread``: also ``sigma_z`` and the
    router's own top-k [layers, S, k]."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_mellum2")
    layer_tree = ctx.lib("reference_check_kimi_linear").layer_tree
    shape = ctx.config
    params = params.get("backbone", params)
    types = ref.layer_types(shape)
    S = int(ids.shape[0])
    step, finish = _jitted(ref, shape, held_of(shape),
                           Q_BLOCK if S > 2 * Q_BLOCK else None, variant)
    ids = jnp.asarray(ids, jnp.int32)
    mask = None if n_real is None else jnp.arange(S) < int(n_real)
    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    own = []
    for i, layer_type in enumerate(types):
        x, top = step(x, layer_tree(params, i, len(types)), mask,
                      None if experts is None
                      else jnp.asarray(experts[i], jnp.int32),
                      layer_type=layer_type)
        if spread:
            own.append(np.asarray(top))
    logprobs, sigma_z = finish(x, params["final_norm"], params["lm_head"],
                               ids)
    logprobs = np.asarray(logprobs)
    if not spread:
        return logprobs
    return logprobs, float(sigma_z), np.stack(own)


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_MELLUM * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def routed_forward(ctx, trainer, T: int):
    """``BaseTrainer._logprobs_fn`` with what the expert layers sow
    kept: jitted (params, sequences, prompt_lens) -> (logprobs [B, T],
    experts [layers, B, S, k])."""
    import jax
    import jax.numpy as jnp

    kimi = ctx.lib("reference_check_kimi_linear")
    n_layers = int(ctx.config["num_hidden_layers"])

    def routed(params, sequences, prompt_lens):
        from orion_tpu.ops.logprobs import (completion_window_positions,
                                            windowed_completion_logprobs)

        L = sequences.shape[1]
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                     sequences.shape)
        out, inter = trainer.model.apply(
            {"params": params}, sequences, positions,
            logits_positions=completion_window_positions(prompt_lens, T, L),
            token_mask=positions < (prompt_lens + T)[:, None],
            mutable=["intermediates"])
        return windowed_completion_logprobs(
            out[0], sequences, prompt_lens, T), kimi.selections(
                inter, n_layers, params)

    return jax.jit(routed)


def rollout_diffs(ctx, trainer, mesh, routed, params, rs, top: int):
    """Part (b): |engine - reference| over the tokens that one rollout
    of the timed shape sampled on its first two rows (a full-length
    prompt and one of five sevenths of it, ids below ``top``, in one
    right-padded batch), and |engine - the training forward| on the same
    tokens.  The reference follows the training forward's selection."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    lens = np.where(np.arange(B) % 2 == 0, P, max(5 * P // 7, 2)).astype(
        np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       rs.randint(2, top, (B, P)), 0).astype(np.int32)
    with mesh:
        rollout = trainer.generate(prompts, lens, jax.random.key(
            ctx.lib("harness").seed31(ctx.seed)))
        sampled, n_new, got = (np.asarray(x)[:2] for x in jax.device_get(
            (rollout.sequences, rollout.completion_lens,
             rollout.policy_logprobs)))
        forward, experts = routed(trainer.state.params, sampled, lens[:2])
    forward, experts = (np.asarray(x) for x in
                        jax.device_get((forward, experts)))
    d, own = [], []
    for b in range(2):
        n = int(n_new[b])
        want = reference_logprobs(ctx, params, sampled[b], experts[:, b],
                                  n_real=int(lens[b]) + n)
        first = int(lens[b]) - 1
        d.append(np.abs(got[b, :n].astype(np.float32)
                        - want[first:first + n]))
        own.append(np.abs(got[b, :n].astype(np.float32) - forward[b, :n]))
    return np.concatenate(d), np.concatenate(own)


def keys_expected(positions, first, window, D: int):
    """[n, D] float64: of the keys the query at each of ``positions``
    sees (``first`` <= s <= t: ``first`` is 0 but for a caller that
    cuts the past; under ``window`` also t - s < window), the share
    whose position is each residue mod ``D``; and how many it sees [n]."""
    t = np.asarray(positions, np.int64)[:, None]
    s = np.arange(int(np.max(positions)) + 1)[None, :]
    seen = (s <= t) & (s >= first)
    if window is not None:
        seen &= t - s < window
    counts = np.stack([np.sum(seen & (s % D == d), axis=1)
                       for d in range(D)], axis=1).astype(np.float64)
    n = np.sum(seen, axis=1)
    return counts / n[:, None], n


def window_probe(ctx, trainer, mesh) -> dict:
    """Part (d): ``{sliding,full}_{forward,decode}_edge_keys``, the
    largest distance, in keys, between the residue counts the program's
    own mixer of either kind reads and those of the configuration's
    rule, over a whole-sequence forward of four windows and over a
    right-padded prefill (rows of half a window + 3 and of two windows -
    5 real tokens) followed by one and a half windows + 8 one-token
    steps through its cache."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.transformer import cache_slots, mixer_spec

    cfg = trainer.cfg.model
    W = int(ctx.config["sliding_window"])
    held = cfg.heads_held()
    H, Hkv, D, E = held["q"], held["kv"], cfg.head_dim, cfg.hidden_size
    cdt = jnp.dtype(cfg.dtype)
    eye = np.zeros((E, D), np.float32)
    eye[:D] = np.eye(D)
    params = {"params": {
        "q_proj": {"kernel": jnp.zeros((E, H * D), jnp.float32)},
        "k_proj": {"kernel": jnp.zeros((E, Hkv * D), jnp.float32)},
        "v_proj": {"kernel": jnp.asarray(np.tile(eye, (1, Hkv)))},
        "o_proj": {"kernel": jnp.asarray(np.tile(eye.T, (H, 1)) / H)},
        "q_norm": {"scale": jnp.ones((D,), jnp.float32)},
        "k_norm": {"scale": jnp.ones((D,), jnp.float32)}}}

    def tokens(positions):
        """x [.., E]: the position's residue mod D, one-hot."""
        return jax.nn.one_hot(positions % D, E, dtype=cdt)

    L, P, N = 4 * W, 2 * W, W + W // 2 + 8
    lens = np.asarray([W // 2 + 3, 2 * W - 5], np.int32)
    out = {}
    for name, mixer in ((SLIDING, "window"), (FULL, "attention")):
        kind, kw = mixer_spec(cfg, mixer)
        module = kind(cfg, **kw)
        more = (None,) if kind.takes_token_mask else ()

        def whole():
            pos = jnp.arange(L, dtype=jnp.int32)[None]
            return module.apply(params, tokens(pos), pos, None, *more)[0]

        def decode():
            pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (2, P))
            real = pos < lens[:, None]
            cache = kind.cache_entry(cfg, 2, cache_slots(P + N), cdt)
            x = jnp.where(real[..., None], tokens(pos), 0)
            _, cache = module.apply(params, x, pos, cache,
                                    *((real,) if more else ()))

            def step(cache, i):
                at = (jnp.asarray(lens) + i)[:, None]
                y, cache = module.apply(params, tokens(at), at, cache, *more)
                return cache, y[:, 0]

            return jax.lax.scan(step, cache, jnp.arange(N))[1]

        with mesh:
            got_whole, got_steps = jax.device_get(
                (jax.jit(whole)(), jax.jit(decode)()))
        window = W if name == SLIDING else None
        want, n = keys_expected(np.arange(L), 0, window, D)
        err = np.abs(np.asarray(got_whole, np.float64)[0, :, :D] - want) \
            * n[:, None]
        short = name.split("_")[0]
        out[short + "_forward_edge_keys"] = float(np.max(err))
        worst = 0.0
        for b in range(2):
            want, n = keys_expected(lens[b] + np.arange(N), 0, window, D)
            err = np.abs(np.asarray(got_steps, np.float64)[:, b, :D]
                         - want) * n[:, None]
            worst = max(worst, float(np.max(err)))
        out[short + "_decode_edge_keys"] = worst
    return out


def router_probe(ctx, trainer, mesh, rs) -> float:
    """Part (e): the share of 64 seeded rows on which the program's own
    ``TopKMoE`` selects the float32 top-k of logits ``4 + rank / 1024``
    (row r reads column order ``rank_r``, seeded)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops.moe import TopKMoE

    cfg = trainer.cfg.model
    E, k, rows = cfg.n_routed_experts, cfg.num_experts_per_tok, 64
    rows = min(rows, cfg.hidden_size)
    rank = np.stack([rs.permutation(E) for _ in range(rows)])
    router = np.zeros((cfg.hidden_size, E), np.float32)
    router[:rows] = 4.0 + rank / 1024.0
    I = cfg.moe_intermediate_size
    mlp = {"router": jnp.asarray(router),
           "experts_gate_up_proj": jnp.zeros(
               (cfg.experts_held, cfg.hidden_size, 2 * I), jnp.float32),
           "experts_down_proj": jnp.zeros(
               (cfg.experts_held, I, cfg.hidden_size), jnp.float32)}
    x = jnp.eye(rows, cfg.hidden_size, dtype=jnp.dtype(cfg.dtype))

    @jax.jit
    def run(mlp, x):
        _, inter = TopKMoE(cfg).apply({"params": mlp}, x[None],
                                      mutable=["intermediates"])
        return inter["intermediates"]["moe_selected"][0][0]

    with mesh:
        sel = np.asarray(jax.device_get(run(mlp, x)))
    want = np.argsort(-rank, axis=1)[:, :k]
    return float(np.mean(np.all(np.sort(sel, -1) == np.sort(want, -1),
                                axis=-1)))


def check_trainer(ctx, trainer, mesh) -> dict:
    """Parts (a) to (e) of the module docstring on the trainer's own
    programs and parameters."""
    import jax

    chk = ctx.lib("reference_check")
    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    vocab = int(ctx.config["vocab_size"])
    n_layers = int(ctx.config["num_hidden_layers"])
    k = int(ctx.config["num_experts_per_tok"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(vocab, trainer.cfg.model.vocab_size)
    seqs = rs.randint(2, top, (2, P + T)).astype(np.int32)
    lens = np.full((2,), P, np.int32)
    routed = routed_forward(ctx, trainer, T)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
        lp_again, experts = routed(trainer.state.params, seqs, lens)
    lp, lp_again, experts = (np.asarray(x) for x in
                             jax.device_get((lp, lp_again, experts)))
    if experts.shape[-1] != k:
        # the reference would follow it and agree: gates over fewer
        # experts are another model, not a rounding of this one
        return dict(chk._verdict([], 0.0), ok=False,
                    why=f"the program selects {experts.shape[-1]} experts "
                        f"a token, the configuration {k}")
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    diffs, followed, spreads = [], [], []
    for b in range(2):
        want, sigma_z, _ = reference_logprobs(ctx, params, seqs[b],
                                              experts[:, b], spread=True)
        diffs.append(np.abs(lp[b, :T].astype(np.float32) - want[window]))
        followed.append(np.abs(lp[b, :T] - lp_again[b, :T]) <= SAME_FORWARD)
        spreads.append(sigma_z)
    keep = np.concatenate(followed)
    sigma_z = max(spreads)
    out = chk._verdict([np.concatenate(diffs)[keep]],
                       predicted_rms(chk, sigma_z, n_layers))
    unfollowed = float(np.mean(~keep))
    # (c), paired over the first sequence's tokens
    mine = float(np.mean(diffs[0]))
    others = {name: float(np.mean(np.abs(
        lp[0, :T].astype(np.float32) - reference_logprobs(
            ctx, params, seqs[0], experts[:, 0], **kw)[window])))
        for name, kw in VARIANTS.items()}
    # (b), (d), (e)
    d, vs_forward = rollout_diffs(ctx, trainer, mesh, routed, params, rs,
                                  top)
    edges = window_probe(ctx, trainer, mesh)
    share = router_probe(ctx, trainer, mesh, rs)
    limit = DECODE_SLACK * out["mean_tolerance"]
    parts = {
        "a_training_forward": bool(out["ok"] and np.isfinite(
            np.concatenate(diffs)).all()
            and unfollowed <= UNFOLLOWED_MAX_SHARE),
        "b_rollout": bool(d.size and np.isfinite(d).all()
                          and np.mean(d) <= limit),
        "c_this_model": all(mine < v for v in others.values()),
        "d_window_edge": all(v <= EDGE_KEYS for v in edges.values()),
        "e_router_float32": share >= ROUTER_FLOAT32_SHARE,
    }
    out.update(
        ok=all(parts.values()), parts=parts, sigma_z=sigma_z,
        tokens=int(keep.size), unfollowed_share=unfollowed,
        first_sequence_mean_abs_diff=mine,
        **{name + "_mean_abs_diff": v for name, v in others.items()},
        **edges, edge_keys_limit=EDGE_KEYS,
        router_float32_share=share,
        router_float32_share_limit=ROUTER_FLOAT32_SHARE,
        decode_tokens=int(d.size),
        decode_mean_abs_diff=float(np.mean(d)),
        decode_median_abs_diff=float(np.median(d)),
        decode_max_abs_diff=float(np.max(d)),
        decode_mean_tolerance=limit,
        decode_vs_forward_mean_abs_diff=float(np.mean(vs_forward)),
        decode_vs_forward_median_abs_diff=float(np.median(vs_forward)))
    return out
