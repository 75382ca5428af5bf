"""The comparison with ``reference_lfm2`` that decides ``correct`` for an
``lfm2_moe`` configuration (the chip's share of it: some of the routed
experts and a slice of the vocabulary).

Shaped like ``reference_check_nemotron_h.py``, whose following of the
discrete expert selection it keeps (``reference_check_dsv3``'s point 2,
its constants and arithmetic; ``reference_check_kimi_linear``'s layout
reader ``layer_tree`` and ``selections`` as they are).  This file knows
how the program lays out its parameters: a block ``layers_<i>``
(``layers_<a>to<b>`` for a stretch stacked by ``scan_layers``) holds a
mixer under ``input_norm`` + ``attn`` (a convolution layer's ``in_proj,
conv_weight, out_proj``; an attention layer's ``{q,k,v,o}_proj, q_norm,
k_norm``) and a feed-forward half under ``post_attn_norm`` + ``mlp`` (a
dense layer's ``{gate,up,down}_proj``; an expert layer's ``router,
e_score_correction_bias, experts_gate_up_proj, experts_down_proj``); the
head is ``embed/embedding`` transposed.  The reference is handed one
layer at a time, as float32.

(a) **The training forward** (``_jit_logprobs``: the convolution's chunk
    form, flash at heads of 64, the grouped expert product) on 2 seeded
    sequences of the timed length.  The reference FOLLOWS the program's
    discrete expert selection and bounds it; every compared token is
    held to the error model's mean and worst limits (below).
(b) **The rollout**: the engine's policy logprobs of one rollout of the
    timed shape, a half-length and a full-length prompt in one
    right-padded batch (128 and 256 tokens at the cell's sizes), so that
    prefill's ``token_mask``, the convolution's hand-over by real length
    and the per-head cache's real lengths are inside ``correct``, then
    ``new_tokens`` one-token steps (``short_conv.step``, the prefix
    step, the dense expert form), against the reference's teacher-forced
    logprobs of what it sampled.  Mean alone, within ``DECODE_SLACK`` of
    (a)'s mean limit (the engine sows no selection, so a step that
    selects another expert than the followed forward is off by that
    expert's output: ``reference_check_dsv3``'s point 3).
(c) **The hand-over**: the first ``conv_L_cache - 1`` generated tokens
    of each of those two rows, the only ones whose convolutions read
    what prefill handed on, bounded on their own: each within
    ``DECODE_SLACK`` times (a)'s WORST-token limit.  A dropped or
    misplaced hand-over moves these by the size of a logit and drowns
    in a mean over a thousand tokens; a rounding, or one exchanged
    expert (0.12-0.26 at the worst of 1024 tokens on Kanana's rollout),
    stays under it.  Beside it **the convolution's own mantissa**
    (``conv_float32_share`` >= 0.5): three float32 multiply-adds
    rounded to bfloat16 are three more roundings among a layer's dozens
    and no logprob shows them, so the program's own ``ShortConv`` is
    run, in both of its forms, on inputs whose taps' sum is 2^-14 in
    float32 and 0 when a product or a partial sum is rounded to
    bfloat16: the share of channels that read non-zero is 1 for a
    float32 accumulation and 0 otherwise.
(d) **Which model the program computes**, paired over the same tokens
    so that the roundings common to both cancel: the program must lie
    closer to the reference than to the reference with the taps
    reversed, without the ``c`` gate, without rotary and without the
    q/k norm.  On the first sequence.  **The bias selects and never
    gates**, looked at where it can be seen: at its seeded size (0.02
    beside scores spread over 0.2) a bias that leaked into the gates
    moves a logprob by a tenth of bfloat16's own noise, and the paired
    comparison over 1024 tokens read the two references 1-3% apart on
    the chip (0.01493 against 0.01509: no limit can sit there).  So the
    program's own ``TopKMoE`` is run on the first expert layer's
    weights with the bias TENFOLD over 256 seeded rows: its selection
    must be the reference's top-k of ``p + bias`` on at least
    ``BIAS_SELECTION_SHARE`` of the rows (without the bias it is on
    next to none), and its output, the selection followed, must lie at
    most ``BIAS_GATE_RATIO`` as far from the reference's as from the
    reference's with ``p + bias`` in the gates.  (That the cell's own
    selection follows the seeded bias is (a)'s selection bound.)

**The error model** is ``reference_check``'s: a logprob's RMS error is
``sigma_z sqrt(layers R + 3) U_BF16``, ``R`` the effective number of
full-size roundings a block adds to the residual stream.  COUNTED, a
convolution block rounds the norm's output, the in-projection's three
thirds (b, z and c are FACTORS of one product, so each one's error
reaches the output whole: 3), ``b z`` as the convolution's input, the
gated output, the out-projection and the sum onto the stream: 8; an
attention block the norm's output, q, k, v, the two per-head norms, the
two rotations, the probabilities, their product with v, the
out-projection and the sum: 12; an expert half the norm's output, gate
and up (factors: 2), their product, the down projection, the
gate-weighted combine and the sum: 7; a dense half 6; each product's
bfloat16 WEIGHTS besides (the reference takes the float32 masters): 2,
4, 3 and 3 more.  That is 15 to 25 a block were every branch the size
of the stream it is added to, and the count is NOT the number: at a
seeded initialisation the embedding's entries are 0.02 and the first
convolution's output is 16 times that (the first dense MLP's 2.5 times
what is there by then), so the stream IS the first block's two chains,
undiluted, and the later branches (0.18-0.38 of the stream a mixer,
0.07-0.9 an FFN) ride on an error they did not make.  So the constant
is CALIBRATED as ``ROUNDINGS_DSV3`` was: a bfloat16 forward of the
program's own Transformer at the published widths on the CPU, selection
followed, 2 seeds x 2 x 256 tokens, reads RMS 0.0198 and 0.0188 at
``sigma_z`` 0.905: R 46.7 and 42.2 (PERF.md section 6, PR 49).
``ROUNDINGS_LFM2 = 48``, the next multiple of 8 above both.  Mean limit
``SLACK sqrt(2 / pi)`` RMS (SLACK 1.5; the readings' means are 0.0146-
0.0157 against 0.0240: what a systematic fault must exceed is half as
much again as the program reads), worst token ``WORST_SIGMAS`` = 6 RMS
(0.120; the readings' worst 0.068).  The nearest precision below
(float8 weights: 16 times the rounding) is far outside both;
tests/bench shows that.
"""

from __future__ import annotations

import math

import numpy as np

# calibrated: see the module docstring and PERF.md section 6, PR 49
ROUNDINGS_LFM2 = 48
# the rollout's selection is not followed, its steps round once more (b)
DECODE_SLACK = 2.5
# between a float32 accumulation's reading (1.0) and a bfloat16 one's (0.0)
CONV_FLOAT32_SHARE = 0.5
# (d), the bias: rows whose selection is the reference's own; how far the
# output may lie from the published gates' against the biased gates'
BIAS_SELECTION_SHARE = 0.9
BIAS_GATE_RATIO = 0.5
# (d): what the reference is also computed as, and is not
VARIANTS = {"taps_reversed": {"taps": "reversed"},
            "no_c_gate": {"c_gate": False},
            "no_rotary": {"rotary": False},
            "no_qk_norm": {"qk_norm": False}}


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def held_of(config: dict):
    """(offset, count) of the experts this share holds: the
    configuration file's ``num_experts`` counts those held here
    (``source_values`` has the published count)."""
    return int(config.get("expert_offset", 0)), int(config["num_experts"])


def layer_weights(p: dict, kind) -> dict:
    """The block ``p`` of the program's tree as the reference takes it;
    ``kind`` its entry of ``reference_lfm2.layer_kinds``."""
    import jax.numpy as jnp

    a, m = p["attn"], p["mlp"]
    w = {"n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"])}
    if kind[0] == "conv":
        w.update(w_in=_f32(a["in_proj"]["kernel"]),
                 conv_w=_f32(a["conv_weight"]),
                 w_out=_f32(a["out_proj"]["kernel"]))
    else:
        w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkvo"})
        w.update(q_g=_f32(a["q_norm"]["scale"]),
                 k_g=_f32(a["k_norm"]["scale"]))
    if kind[1] == "dense":
        w.update(gate_up=jnp.concatenate(
            [_f32(m["gate_proj"]["kernel"]), _f32(m["up_proj"]["kernel"])],
            axis=-1), down=_f32(m["down_proj"]["kernel"]))
    else:
        w.update(w_router=_f32(m["router"]),
                 router_bias=_f32(m["e_score_correction_bias"]),
                 e_gate_up=_f32(m["experts_gate_up_proj"]),
                 e_down=_f32(m["experts_down_proj"]))
    return w


_JITTED: dict = {}


def _jitted(ref, shape: dict, held, variant: dict):
    """(one block, final norm + head + logprobs) of the reference under
    ``variant``, jitted once a configuration and variant: the check
    calls the same ones for every sequence it compares."""
    import json

    import jax
    import jax.numpy as jnp

    key = (json.dumps(shape, sort_keys=True, default=str), held,
           tuple(sorted(variant.items())))
    if key not in _JITTED:
        step = jax.jit(
            lambda x, p, mask, sel, kind: ref.layer(
                x, layer_weights(p, kind), shape, kind, held, mask, sel,
                probe=True, **variant),
            static_argnames=("kind",))

        @jax.jit
        def finish(x, final_norm, embedding, ids):
            logits = ref.head(x, {"nf_g": _f32(final_norm["scale"]),
                                  "embed": embedding}, shape)
            return (ref.next_token_logprobs(logits, ids),
                    jnp.mean(jnp.std(logits, axis=-1)))

        _JITTED[key] = (step, finish)
    return _JITTED[key]


def reference_logprobs(ctx, params: dict, ids: np.ndarray, selected=None,
                       probe: bool = False, n_real=None, **variant):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``selected`` [expert layers, L, k]: the experts to use instead of
    the reference's own top-k.  ``n_real``: the positions from there on
    hold no token.  ``variant``: ``reference_lfm2.layer``'s.  ``probe``:
    also ``{"sigma_z", "margin" [expert layers, L], "excess" [expert
    layers, L, k], "exchanged" [expert layers, L], "depth" [expert
    layers]}`` (``depth``: the blocks before each expert layer)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_lfm2")
    layer_tree = ctx.lib("reference_check_kimi_linear").layer_tree
    shape = ctx.config
    held = held_of(shape)
    params = params.get("backbone", params)
    kinds = ref.layer_kinds(shape)
    step, finish = _jitted(ref, shape, held, variant)
    ids = jnp.asarray(ids, jnp.int32)
    mask = jnp.arange(ids.shape[0]) < (ids.shape[0] if n_real is None
                                       else int(n_real))
    embedding = params["embed"]["embedding"]
    x = ref.embed(ids, {"embed": embedding})
    infos, depth = [], []
    for i, kind in enumerate(kinds):
        sel = None
        if kind[1] == "experts" and selected is not None:
            sel = jnp.asarray(selected[len(depth)], jnp.int32)
        x, info = step(x, layer_tree(params, i, len(kinds)), mask, sel,
                       kind=kind)
        if kind[1] == "experts":
            infos.append(jax.tree.map(np.asarray, info))
            depth.append(i)
    logprobs, spread = finish(x, params["final_norm"], embedding, ids)
    logprobs = np.asarray(logprobs)
    if not probe:
        return logprobs
    out = {k: np.stack([info[k] for info in infos]) for k in infos[0]}
    return logprobs, dict(out, sigma_z=float(spread),
                          depth=np.asarray(depth))


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_LFM2 * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def input_error(chk, depth):
    """Relative RMS error of an expert layer's input after ``depth``
    blocks under the model: the embedding and ``depth`` blocks on the
    residual stream, this block's mixer, and the norm's own rounded
    output."""
    return np.sqrt((np.asarray(depth, np.float64) + 0.5) * ROUNDINGS_LFM2
                   + 2.0) * chk.U_BF16


def verdict(ctx, diffs: list, probes: list, layers: int,
            followed: list) -> dict:
    """``reference_check_dsv3.verdict`` (its limits on the selection,
    its arithmetic) under this model's error model."""
    chk, dsv3 = ctx.lib("reference_check"), ctx.lib("reference_check_dsv3")
    d = np.concatenate(diffs)
    keep = np.concatenate(followed)
    sigma_z = max(p["sigma_z"] for p in probes)
    out = chk._verdict([d[keep]], predicted_rms(chk, sigma_z, layers))
    eps = input_error(chk, probes[0]["depth"])
    cat = lambda key: np.concatenate([p[key] for p in probes], axis=1)  # noqa: E731
    excess = cat("excess") / eps[:, None, None]       # [layers, n, k]
    margin = cat("margin") / eps[:, None]
    exchanged = cat("exchanged").any(axis=0)
    worst_excess = float(np.max(excess[:, keep]))
    tail = 0.5 * np.vectorize(math.erfc)(margin / math.sqrt(2.0))
    expected = float(np.sum(1.0 - np.prod(1.0 - tail, axis=0)))
    allowed = 2.0 * expected + 4.0 * math.sqrt(expected) + 2.0
    unfollowed = float(np.mean(~keep))
    ok = bool(out["ok"] and np.isfinite(d).all()
              and worst_excess <= dsv3.MARGIN_SIGMAS
              and np.sum(exchanged) <= allowed
              and unfollowed <= dsv3.UNFOLLOWED_MAX_SHARE)
    out.update(ok=ok, sigma_z=sigma_z, tokens=int(d.size),
               unfollowed_share=unfollowed,
               selection_excess_sigmas=worst_excess,
               selection_excess_limit=dsv3.MARGIN_SIGMAS,
               exchanged_share=float(np.mean(exchanged)),
               exchanged_tokens=int(np.sum(exchanged)),
               exchanges_predicted=expected, exchanges_allowed=allowed)
    return out


def rollout_diffs(ctx, trainer, mesh, routed, params, rs, top: int):
    """Per row, |engine - reference| over the tokens that one rollout of
    the timed shape sampled on its first two rows (a half-length and a
    full-length prompt of ids below ``top`` in one right-padded batch),
    and |engine - the training forward| on the same tokens.  ``routed``:
    the training forward that also returns its selection, which the
    reference follows here too (the engine sows none)."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    lens = np.where(np.arange(B) % 2 == 0, max(P // 2, 2), P).astype(
        np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       rs.randint(2, top, (B, P)), 0).astype(np.int32)
    with mesh:
        rollout = trainer.generate(prompts, lens, jax.random.key(
            ctx.lib("harness").seed31(ctx.seed)))
        sampled, n_new, got = (np.asarray(x)[:2] for x in jax.device_get(
            (rollout.sequences, rollout.completion_lens,
             rollout.policy_logprobs)))
        forward, selected = routed(trainer.state.params, sampled, lens[:2])
    forward, selected = (np.asarray(x) for x in
                         jax.device_get((forward, selected)))
    d, own = [], []
    for b in range(2):
        n = int(n_new[b])
        # what lies behind prompt + completion holds no token; before
        # it, the reference sees what the engine saw
        want = reference_logprobs(ctx, params, sampled[b], selected[:, b],
                                  n_real=int(lens[b]) + n)
        first = int(lens[b]) - 1
        d.append(np.abs(got[b, :n].astype(np.float32)
                        - want[first:first + n]))
        own.append(np.abs(got[b, :n].astype(np.float32) - forward[b, :n]))
    return d, np.concatenate(own)


def conv_float32_share(trainer, mesh) -> float:
    """The share of channels in which the program's own ``ShortConv``
    keeps what bfloat16 cannot, the lesser of its two forms' (one pass
    over three positions; a prefill of two and a one-token step).  The
    parameters are made so that every factor is exact in bfloat16 and
    the taps' sum is not: ``z = c = 1`` (they read channel 0, which holds
    1), ``b = x``, three taps ``(1 + 2^-7, 1, 1)`` over ``x = 1 + 2^-7, -(1 +
    2^-6), 0``: ``(1 + 2^-7)^2 - (1 + 2^-6) = 2^-14`` in float32, 0
    where the product or the partial sum is rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.transformer import mixer_spec

    cfg = trainer.cfg.model
    kind, kw = mixer_spec(cfg, "conv")
    module, E, taps = kind(cfg, **kw), cfg.hidden_size, cfg.conv_L_cache
    cdt = jnp.dtype(cfg.dtype)
    pos = jnp.arange(3, dtype=jnp.int32)[None]

    def run():
        eye = jnp.eye(E, dtype=jnp.float32)
        from_0 = jnp.zeros((E, E), jnp.float32).at[0].set(1.0)
        params = {"params": {
            "in_proj": {"kernel": jnp.concatenate([eye, from_0, from_0], 1)},
            "conv_weight": jnp.ones((taps, E), jnp.float32).at[0].set(
                1.0 + 2.0 ** -7),
            "out_proj": {"kernel": eye}}}
        rows = jnp.asarray([1.0 + 2.0 ** -7, -(1.0 + 2.0 ** -6), 0.0])
        x = jnp.broadcast_to(rows[None, :, None], (1, 3, E)).at[
            :, :, 0].set(1.0).astype(cdt)
        whole, _ = module.apply(params, x, pos, None, None)
        cache = kind.cache_entry(cfg, 1, 8, cdt)
        _, cache = module.apply(params, x[:, :2], pos[:, :2], cache, None)
        step, _ = module.apply(params, x[:, 2:], pos[:, 2:], cache, None)
        kept = jnp.stack([whole[0, 2, 1:], step[0, 0, 1:]]) != 0
        return jnp.min(jnp.mean(kept.astype(jnp.float32), axis=1))

    with mesh:
        return float(jax.jit(run)())


def bias_probe(ctx, trainer, mesh, params, rs) -> dict:
    """(d)'s look at the selection bias: the program's own ``TopKMoE``
    on the first expert layer's weights, the bias tenfold, over 256
    seeded unit rows.  ``bias_selection_share``: the rows whose selected
    experts are the reference's top-k of ``p + bias``;
    ``bias_gates_published_diff`` / ``bias_gates_biased_diff``: the mean
    distance of its output from the reference's with ``p`` and with ``p
    + bias`` in the gates, the program's selection followed in both."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops.moe import TopKMoE

    ref = ctx.lib("reference_lfm2")
    layer_tree = ctx.lib("reference_check_kimi_linear").layer_tree
    shape, cfg = ctx.config, trainer.cfg.model
    kinds = ref.layer_kinds(shape)
    first = next(i for i, kind in enumerate(kinds) if kind[1] == "experts")
    block = layer_tree(params.get("backbone", params), first, len(kinds))
    mlp = dict(block["mlp"], e_score_correction_bias=10.0
               * block["mlp"]["e_score_correction_bias"])
    x = jnp.asarray(rs.standard_normal((256, cfg.hidden_size)),
                    jnp.dtype(cfg.dtype))
    w = layer_weights(dict(block, mlp=mlp), kinds[first])

    @jax.jit
    def run(mlp, x, w):
        out, inter = TopKMoE(cfg).apply({"params": mlp}, x[None],
                                        mutable=["intermediates"])
        sel = inter["intermediates"]["moe_selected"][0][0]
        xf = x.astype(jnp.float32)
        with jax.default_matmul_precision(ref.HIGHEST):
            own, _ = ref.route(xf, w, shape)
            want = {g: ref.expert_ffn(xf, w, shape, held_of(shape), sel,
                                      gates=g) for g in ("published",
                                                         "biased")}
        same = jnp.all(jnp.sort(sel, -1) == jnp.sort(own, -1), axis=-1)
        got = out[0].astype(jnp.float32)
        return jnp.mean(same), {g: jnp.mean(jnp.abs(got - t))
                                for g, t in want.items()}

    with mesh:
        share, diffs = jax.device_get(run(mlp, x, w))
    return {"bias_selection_share": float(share),
            "bias_gates_published_diff": float(diffs["published"]),
            "bias_gates_biased_diff": float(diffs["biased"])}


def check_trainer(ctx, trainer, mesh) -> dict:
    """Parts (a) to (d) of the module docstring on the trainer's own
    programs and parameters."""
    import jax
    import jax.numpy as jnp

    chk = ctx.lib("reference_check")
    dsv3 = ctx.lib("reference_check_dsv3")
    kimi = ctx.lib("reference_check_kimi_linear")
    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    vocab = int(ctx.config["vocab_size"])
    n_layers = int(ctx.config["num_hidden_layers"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(vocab, trainer.cfg.model.vocab_size)
    seqs = rs.randint(2, top, (2, P + T)).astype(np.int32)
    lens = np.full((2,), P, np.int32)

    def routed(params, sequences, prompt_lens):
        """``BaseTrainer._logprobs_fn`` with the intermediates kept."""
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

    routed = jax.jit(routed)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
        lp_again, selected = routed(trainer.state.params, seqs, lens)
    lp, lp_again, selected = (np.asarray(x) for x in
                              jax.device_get((lp, lp_again, selected)))
    k = int(ctx.config["num_experts_per_tok"])
    if selected.shape[-1] != k:
        # the reference would follow it and agree: gates over fewer
        # experts are another model, not a rounding of this one
        return dict(chk._verdict([], 0.0), ok=False,
                    why=f"the program selects {selected.shape[-1]} experts "
                        f"a token, the configuration {k}")
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    diffs, probes, followed = [], [], []
    for b in range(2):
        want, probe = reference_logprobs(ctx, params, seqs[b],
                                         selected[:, b], probe=True)
        diffs.append(np.abs(lp[b, :T].astype(np.float32) - want[window]))
        followed.append(np.abs(lp[b, :T] - lp_again[b, :T])
                        <= dsv3.SAME_FORWARD)
        probes.append({k: v[:, window] if getattr(v, "ndim", 0) > 1 else v
                       for k, v in probe.items()})
    out = verdict(ctx, diffs, probes, n_layers, followed)
    # (d), paired over the first sequence's tokens
    mine = float(np.mean(diffs[0]))
    others = {name: float(np.mean(np.abs(
        lp[0, :T].astype(np.float32) - reference_logprobs(
            ctx, params, seqs[0], selected[:, 0], **kw)[window])))
        for name, kw in VARIANTS.items()}
    # (b) and (c)
    rows, own = rollout_diffs(ctx, trainer, mesh, routed, params, rs, top)
    d = np.concatenate(rows)
    kept = trainer.cfg.model.conv_L_cache - 1
    handed = np.concatenate([r[:kept] for r in rows])
    share = conv_float32_share(trainer, mesh)
    bias = bias_probe(ctx, trainer, mesh, params, rs)
    limit = DECODE_SLACK * out["mean_tolerance"]
    handed_limit = DECODE_SLACK * out["max_tolerance"]
    ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit
              and handed.size and np.max(handed) <= handed_limit
              and all(mine < v for v in others.values())
              and bias["bias_selection_share"] >= BIAS_SELECTION_SHARE
              and bias["bias_gates_published_diff"] <= BIAS_GATE_RATIO
              * bias["bias_gates_biased_diff"]
              and share >= CONV_FLOAT32_SHARE)
    out.update(ok=out["ok"] and ok, decode_tokens=int(d.size),
               first_sequence_mean_abs_diff=mine,
               **{name + "_mean_abs_diff": v for name, v in others.items()},
               **bias, bias_selection_share_limit=BIAS_SELECTION_SHARE,
               bias_gate_ratio_limit=BIAS_GATE_RATIO,
               conv_float32_share=share,
               conv_float32_share_limit=CONV_FLOAT32_SHARE,
               handover_tokens=int(handed.size),
               handover_max_abs_diff=float(np.max(handed)),
               handover_tolerance=handed_limit,
               decode_vs_forward_median_abs_diff=float(np.median(own)),
               decode_vs_forward_mean_abs_diff=float(np.mean(own)),
               decode_mean_abs_diff=float(np.mean(d)),
               decode_median_abs_diff=float(np.median(d)),
               decode_max_abs_diff=float(np.max(d)),
               decode_mean_tolerance=limit)
    return out
