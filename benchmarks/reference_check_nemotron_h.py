"""The comparison with ``reference_nemotron_h`` that decides ``correct``
for a ``nemotron_h`` configuration (the chip's share of it: a share of
every mixer's heads, of the routed experts and of the vocabulary).

Shaped like ``reference_check_olmo_hybrid.py`` (four parts) with
``reference_check_kimi_linear.py``'s following of the discrete expert
selection (whose layout reader ``layer_tree`` and state probe
``state_float32_share`` it uses as they are).  This file knows how the
program lays out its parameters: a block ``layers_<i>`` (``layers_<a>to
<b>`` or ``layers`` for a stretch stacked by ``scan_layers``) holds a
mixer under ``input_norm`` + ``attn`` (a Mamba-2 layer's ``in_proj,
conv_weight, conv_bias, A_log, D, dt_bias, norm, out_proj``; an attention
layer's ``{q,k,v,o}_proj``) and, where the pattern has an ``E`` behind
it, the expert layer under ``post_attn_norm`` + ``mlp`` (``router,
e_score_correction_bias, fc1_latent_proj, fc2_latent_proj,
experts_up_proj, experts_down_proj, shared_{up,down}_proj``): ONE block
of the program is one or two PUBLISHED layers, and the reference is
handed one published layer at a time, as float32.

(a) **The training forward** (``_jit_logprobs``: the chunked recurrence,
    flash on the attention layer, the grouped expert product in the
    latent) on 2 seeded sequences of the timed length.  The reference
    FOLLOWS the program's discrete expert selection and bounds it
    (``reference_check_dsv3``'s point 2, its constants and arithmetic);
    every compared token is held to the error model's mean and worst
    limits.
(b) **The rollout**: the engine's policy logprobs of one rollout of the
    timed shape, a full-length and a sixteenth-length prompt in one
    right-padded batch, so that prefill's ``token_mask``, the state, the
    convolution's last inputs AND the per-head cache's real lengths
    handed to decode are inside ``correct``, then ``new_tokens``
    one-token steps (``mamba2_step``, the dense expert form), against
    the reference's teacher-forced logprobs of what it sampled.  Mean
    alone, within ``DECODE_SLACK`` of (a)'s mean limit (the engine sows
    no selection: ``reference_check_dsv3``'s point 3).
(c) **The state's own mantissa** (``state_float32_share`` >= 0.5): a
    state kept in bfloat16 is one more rounding among a layer's dozens
    and no logprob shows it, so the share of state entries that bfloat16
    cannot hold is read after a prefill and four steps through the
    engine's own decode model: ~1 for a float32 accumulation, 0 for a
    state rounded anywhere on its way.
(d) **Which model the program computes**, paired over the same tokens so
    that the roundings common to both cancel: the program must lie
    closer to the reference than to the reference with the experts'
    square skipped (``relu_mean_abs_diff``), with ``silu(u) * u`` in its
    place (what a SwiGLU's code makes of one product:
    ``silu_gate_mean_abs_diff``), and with B and C taken a head at a
    time, not a group (``interleaved_mean_abs_diff``: head h reading
    group ``h % G``).  On the first sequence.

**The error model** is ``reference_check``'s: a logprob's RMS error is
``sigma_z sqrt(layers R + 3) U_BF16``, ``layers`` the PUBLISHED layers
held (11: half of a block of this repo each) and ``R`` the effective
number of full-size roundings one adds to the residual stream.
``ROUNDINGS_NEMOTRON_H`` was calibrated as ``ROUNDINGS_KIMI`` was: so
that the model reproduces a bfloat16 forward of the program's own
Transformer at the published widths on the CPU with the selection
followed (PERF.md section 6, PR 42, has the readings).  What rounds in
an M layer: the one input projection, its convolved form, ``dt x`` and
the decay-weighted ``C B^T`` on their way into the MXU (the state is
float32, rounded only as an operand), the gated and normed output and
the output projection; in an E layer: the latent projection, the
experts' two products, the projection back, the shared expert's two;
in the attention layer its projections, probabilities and output.
"""

from __future__ import annotations

import math

import numpy as np

# calibrated: see the module docstring and PERF.md section 6, PR 42
ROUNDINGS_NEMOTRON_H = 16
# the rollout's selection is not followed, its steps round once more (b)
DECODE_SLACK = 2.5
# between a float32 state's reading (1.0 but for entries that happen to
# be whole in 8 bits) and a bfloat16 state's (0.0)
STATE_FLOAT32_SHARE = 0.5
# (d): what the reference is also computed as, and is not
VARIANTS = {"relu": {"act": "relu"}, "silu_gate": {"act": "silu_gate"},
            "interleaved": {"group_map": "interleaved"}}


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def blocks_of(chars: str) -> list:
    """[(mixer character or None, whether an "E" follows it)] for the
    program's blocks: a mixer and an "E" behind it are one block."""
    out = []
    for c in chars:
        if c == "E" and out and out[-1][0] and not out[-1][1]:
            out[-1] = (out[-1][0], True)
        else:
            out.append((None, True) if c == "E" else (c, False))
    return out


def layer_weights(p: dict, char: str) -> dict:
    """The published layer ``char`` of a block ``p`` of the program's
    tree as the reference takes it."""
    if char == "E":
        m = p["mlp"]
        return {"n_g": _f32(p["post_attn_norm"]["scale"]),
                "w_router": _f32(m["router"]),
                "router_bias": _f32(m["e_score_correction_bias"]),
                "w_fc1": _f32(m["fc1_latent_proj"]["kernel"]),
                "w_fc2": _f32(m["fc2_latent_proj"]["kernel"]),
                "e_up": _f32(m["experts_up_proj"]),
                "e_down": _f32(m["experts_down_proj"]),
                "s_up": _f32(m["shared_up_proj"]["kernel"]),
                "s_down": _f32(m["shared_down_proj"]["kernel"])}
    a = p["attn"]
    w = {"n_g": _f32(p["input_norm"]["scale"])}
    if char == "M":
        w.update(w_in=_f32(a["in_proj"]["kernel"]),
                 conv_w=_f32(a["conv_weight"]), conv_b=_f32(a["conv_bias"]),
                 A_log=_f32(a["A_log"]), D=_f32(a["D"]),
                 dt_bias=_f32(a["dt_bias"]), norm_g=_f32(a["norm"]),
                 w_out=_f32(a["out_proj"]["kernel"]))
    else:
        w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkvo"})
    return w


def reference_logprobs(ctx, params: dict, ids: np.ndarray, selected=None,
                       probe: bool = False, n_real=None, **variant):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``selected`` [expert layers, L, k]: the experts to use instead of
    the reference's own top-k.  ``n_real``: the positions from there on
    hold no token.  ``variant``: ``reference_nemotron_h.layer``'s
    ``act`` / ``group_map`` / ``rotary``.  ``probe``: also ``{"sigma_z",
    "margin" [expert layers, L], "excess" [expert layers, L, k],
    "exchanged" [expert layers, L], "depth" [expert layers]}``
    (``depth``: the published layers before each expert layer)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_nemotron_h")
    layer_tree = ctx.lib("reference_check_kimi_linear").layer_tree
    shape = ctx.config
    held = ctx.lib("reference_check_dsv3").held_of(shape)
    params = params.get("backbone", params)
    blocks = blocks_of(ref.layer_chars(shape))
    step = jax.jit(
        lambda x, p, mask, sel, char: ref.layer(
            x, layer_weights(p, char), shape, char, held, mask, sel,
            probe=True, **variant),
        static_argnames=("char",))

    @jax.jit
    def finish(x, final_norm, lm_head, ids):
        logits = ref.head(x, {"nf_g": _f32(final_norm["scale"]),
                              "w_head": _f32(lm_head["kernel"])}, shape)
        return (ref.next_token_logprobs(logits, ids),
                jnp.mean(jnp.std(logits, axis=-1)))

    ids = jnp.asarray(ids, jnp.int32)
    mask = jnp.arange(ids.shape[0]) < (ids.shape[0] if n_real is None
                                       else int(n_real))
    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    infos, depth, done = [], [], 0
    for i, (mixer, experts) in enumerate(blocks):
        p = layer_tree(params, i, len(blocks))
        for char in ([mixer] if mixer else []) + (["E"] if experts else []):
            sel = None
            if char == "E" and selected is not None:
                sel = jnp.asarray(selected[len(depth)], jnp.int32)
            x, info = step(x, p, mask, sel, char=char)
            if char == "E":
                infos.append(jax.tree.map(np.asarray, info))
                depth.append(done)
            done += 1
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    if not probe:
        return logprobs
    out = {k: np.stack([info[k] for info in infos]) for k in infos[0]}
    return logprobs, dict(out, sigma_z=float(spread),
                          depth=np.asarray(depth))


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this model's roundings a
    published layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_NEMOTRON_H
                               * chk.U_BF16 ** 2 + 3.0 * chk.U_BF16 ** 2)


def input_error(chk, depth):
    """Relative RMS error of an expert layer's input after ``depth``
    published layers under the model: the embedding and ``depth`` layers
    on the residual stream, and the norm's own rounded output."""
    return np.sqrt(np.asarray(depth, np.float64) * ROUNDINGS_NEMOTRON_H
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
    """|engine - reference| over the tokens that one rollout of the
    timed shape sampled on its first two rows (a full-length and a
    sixteenth-length prompt of ids below ``top`` in one right-padded
    batch), and |engine - the training forward| on the same tokens.
    ``routed``: the training forward that also returns its selection,
    which the reference follows here too (the engine sows none)."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    lens = np.where(np.arange(B) % 2 == 0, P, max(P // 16, 2)).astype(
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
    return np.concatenate(d), np.concatenate(own)


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
    n_blocks = len(blocks_of(ctx.lib("reference_nemotron_h").layer_chars(
        ctx.config)))
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
                inter, n_blocks, params)

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
    share = kimi.state_float32_share(ctx, trainer, mesh, rs, top)
    d, own = rollout_diffs(ctx, trainer, mesh, routed, params, rs, top)
    limit = DECODE_SLACK * out["mean_tolerance"]
    ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit
              and all(mine < v for v in others.values())
              and share >= STATE_FLOAT32_SHARE)
    out.update(ok=out["ok"] and ok, decode_tokens=int(d.size),
               first_sequence_mean_abs_diff=mine,
               **{name + "_mean_abs_diff": v for name, v in others.items()},
               state_float32_share=share,
               state_float32_share_limit=STATE_FLOAT32_SHARE,
               decode_vs_forward_median_abs_diff=float(np.median(own)),
               decode_vs_forward_mean_abs_diff=float(np.mean(own)),
               decode_mean_abs_diff=float(np.mean(d)),
               decode_median_abs_diff=float(np.median(d)),
               decode_max_abs_diff=float(np.max(d)),
               decode_mean_tolerance=limit)
    return out
