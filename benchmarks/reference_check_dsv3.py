"""The comparison with ``reference_dsv3`` that decides ``correct`` for a
``deepseek_v3`` configuration (the chip's share of it).

As ``reference_check.py`` does for GPT-NeoX: this file knows how the
program lays out its parameters (``layers_<i>`` for the leading dense
layers, then ``layers`` stacked by ``scan_layers`` or further
``layers_<i>``; ``attn/{q_proj, kv_a_proj_with_mqa, kv_a_norm,
kv_b_proj, o_proj}``; a dense layer's ``mlp/{gate,up,down}_proj``; an
expert layer's ``mlp/{router, e_score_correction_bias,
experts_gate_up_proj, experts_down_proj, shared_{gate,up,down}_proj}``)
and hands them to the reference as float32, one layer at a time, so that
the float32 copy of the model never exists at once.  The trainer's own
``_jit_logprobs`` at the timed shapes is what is compared.

The tolerance is ``reference_check``'s error model (its form, its unit
rounding, its slack and its sigmas, all imported) with two additions,
each made because this block is not GPT-NeoX and each measured before
it was written (PERF.md section 6 has the readings).

1. **The roundings a layer adds.**  ``reference_check.ROUNDINGS = 6``
   was calibrated on the GPT-NeoX block.  With the selection frozen (so
   that nothing but rounding differs), a bfloat16 forward of this block
   at the published widths is off 2.4 times that model's RMS, and the
   places are visible in the program's own intermediates: the key/value
   path rounds in two more places (the latent's norm and its
   up-projection); a gated FFN is a product of two projections of the
   same input, so the error that reaches it leaves it twice as large;
   and at a seeded initialisation the first layer's FFN output is 38
   times the embedding's size, so that layer's whole chain counts
   undiluted (92 roundings' worth; the expert layers add 24 each).
   ``ROUNDINGS_DSV3 = 36`` is this block's one calibrated constant, set
   the way ROUNDINGS was: so that the model reproduces that forward on
   the CPU (RMS 0.0151 read, 0.0152 predicted, 6 layers, 256 tokens).
   The nearest precision below (fp8 weights: 16 times the rounding) is
   far outside it; tests/bench shows that.

2. **The selection is discrete.**  The program's router reads ``z``
   rounded to bfloat16 on top of the residual stream's own error, the
   reference reads it exact; where the k-th and the (k+1)-th biased
   scores lie closer than that error moves them, the program selects
   another expert, and its hidden state is then off by an expert's
   weighted output: not a rounding.  At the published widths that holds
   for most tokens in at least one of five layers (89% lie within 5
   sigmas somewhere), so holding only the others to the model would
   leave a check of a few tokens.  Instead the reference FOLLOWS the
   program's selection (the layer sows it; one more forward of the same
   model on the same sequences reads it, and its logprobs must equal
   the timed forward's), every token is held to the model's mean and
   worst limits, and the selection itself is checked where it is
   discrete: an expert the program selected and the reference would
   not may lie below the reference's k-th score by at most
   ``MARGIN_SIGMAS`` times the two scores' joint error, the error of
   ``z`` after ``l`` layers being the model's own ``sqrt(l *
   ROUNDINGS_DSV3 + 2) * U_BF16``.  The share of tokens that exchanged
   an expert is reported beside the model's prediction for it (the sum
   of the normal tails of the margins) and may not be far above it.

3. **The rollout is compared too.**  The training forward takes the
   expand path, flash and (above ``DENSE_MAX_TOKENS``) the grouped
   product; the rollout's 512 decode steps take the absorbed path over
   the ``{c, k_rope}`` cache and the dense expert form, which the
   comparison above never runs.  So the check makes one rollout of the
   timed shape with the trainer's own engine (rows of a full-length and
   of a sixteenth-length prompt drawn from the slice) and holds the raw
   policy logprobs the engine recorded for the tokens it sampled, on
   the first two rows, to the reference's teacher-forced logprobs of
   those sequences.  The engine sows no selection, so the reference
   follows that of a training forward over the same sequences, and a
   token whose decode step selected another expert is off by that
   expert's output: the mean rises (CPU, published widths, 2 x 96 and
   4 x 320 tokens: 0.0139-0.0173 against 0.0114-0.0118 for the followed
   forward) and the worst token is no rounding (0.12-0.26).  The limit
   is on the mean alone, ``DECODE_SLACK`` = 2.5 times the model's mean
   limit (0.045): the same rollout from weights rounded to fp8 reads
   0.121-0.160.  On the chip, 2 x 512 tokens: 0.0138-0.0165.

A forward in a lower precision, a dropped expert, a gate without its
scale and a bias that leaks into the gates all move the mean over every
token.  A bias left out of the selection leaves the logprobs followed
and breaks the selection bound (at the published widths on the CPU:
59.9 sigmas read against the limit of 8, and 255 of 256 tokens
exchanged where 190 were allowed; 3.1 sigmas and 101 tokens with the
bias in).  A program that selects another number of experts than the
configuration states is refused before anything is followed.
tests/bench shows all six failing.
"""

from __future__ import annotations

import math

import numpy as np

ROUNDINGS_DSV3 = 36
MARGIN_SIGMAS = 8.0
# two programs compiled from one model may differ in the last bit; a
# token on which they differ by more than this is one whose selection
# differs between them, and cannot be followed
SAME_FORWARD = 1e-3
UNFOLLOWED_MAX_SHARE = 0.01
# the rollout's selection is not followed (see 3. above)
DECODE_SLACK = 2.5


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _layer_weights(p: dict) -> dict:
    import jax.numpy as jnp

    a, m = p["attn"], p["mlp"]
    w = {"wq": _f32(a["q_proj"]["kernel"]),
         "wkva": _f32(a["kv_a_proj_with_mqa"]["kernel"]),
         "kva_g": _f32(a["kv_a_norm"]["scale"]),
         "wkvb": _f32(a["kv_b_proj"]),
         "wo": _f32(a["o_proj"]["kernel"]),
         "n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"])}
    if "router" not in m:
        w["gate_up"] = jnp.concatenate(
            [_f32(m["gate_proj"]["kernel"]), _f32(m["up_proj"]["kernel"])],
            axis=1)
        w["down"] = _f32(m["down_proj"]["kernel"])
        return w
    w.update(
        w_router=_f32(m["router"]),
        router_bias=_f32(m["e_score_correction_bias"]),
        e_gate_up=_f32(m["experts_gate_up_proj"]),
        e_down=_f32(m["experts_down_proj"]),
        s_gate_up=jnp.concatenate(
            [_f32(m["shared_gate_proj"]["kernel"]),
             _f32(m["shared_up_proj"]["kernel"])], axis=1),
        s_down=_f32(m["shared_down_proj"]["kernel"]))
    return w


def held_of(config: dict):
    """(offset, count) of the experts this share holds: the
    configuration file's ``n_routed_experts`` counts those held here
    (``source_values`` has the published count), ``expert_offset`` says
    from where."""
    return int(config.get("expert_offset", 0)), int(config["n_routed_experts"])


def reference_logprobs(ctx, params: dict, ids: np.ndarray,
                       selected=None, probe: bool = False):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``selected`` [expert layers, L, k]: the experts to use instead of
    the reference's own top-k.  ``probe``: also ``{"sigma_z", "margin"
    [expert layers, L], "excess" [expert layers, L, k], "exchanged"
    [expert layers, L], "depth" [expert layers]}`` (``depth``: the
    layers before each expert layer)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_dsv3")
    shape = ctx.config
    held = held_of(shape)
    params = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])
    lead = sum(1 for i in range(n_layers) if f"layers_{i}" in params
               and "router" not in params[f"layers_{i}"]["mlp"])

    def layer_tree(i):
        if f"layers_{i}" in params:
            return params[f"layers_{i}"]
        return jax.tree.map(lambda x: x[i - lead], params["layers"])

    @jax.jit
    def dense_step(x, p, positions):
        return ref.layer(x, _layer_weights(p), positions, shape)

    @jax.jit
    def expert_step(x, p, positions, sel):
        return ref.layer(x, _layer_weights(p), positions, shape, held, sel,
                         probe=True)

    @jax.jit
    def finish(x, final_norm, lm_head, ids):
        logits = ref.head(x, {"nf_g": _f32(final_norm["scale"]),
                              "w_head": _f32(lm_head["kernel"])}, shape)
        return (ref.next_token_logprobs(logits, ids),
                jnp.mean(jnp.std(logits, axis=-1)))

    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    infos, depth = [], []
    for i in range(n_layers):
        p = layer_tree(i)
        if "router" in p["mlp"]:
            sel = None if selected is None else jnp.asarray(
                selected[len(depth)], jnp.int32)
            x, info = expert_step(x, p, positions, sel)
            infos.append(jax.tree.map(np.asarray, info))
            depth.append(i)
        else:
            x = dense_step(x, p, positions)
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    if not probe:
        return logprobs
    out = {k: np.stack([info[k] for info in infos]) for k in infos[0]}
    return logprobs, dict(out, sigma_z=float(spread),
                          depth=np.asarray(depth))


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``chk.predicted_rms`` with this block's roundings a layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_DSV3 * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def input_error(chk, depth):
    """Relative RMS error of an expert layer's input ``z`` after
    ``depth`` layers under the model: the embedding and ``depth`` layers
    on the residual stream, and the norm's own rounded output."""
    return np.sqrt(np.asarray(depth, np.float64) * ROUNDINGS_DSV3
                   + 2.0) * chk.U_BF16


def verdict(chk, diffs: list, probes: list, layers: int,
            followed: list = None) -> dict:
    """``chk``: ``reference_check`` (its constants and its verdict);
    ``diffs``: per sequence, |program - reference| over the compared
    tokens; ``probes``: per sequence, the reference's probe cut to the
    same tokens; ``followed``: per sequence, which of those tokens the
    reference could follow (all, if None)."""
    d = np.concatenate(diffs) if diffs else np.zeros((0,), np.float32)
    if not d.size:
        return chk._verdict([], 0.0)
    keep = np.ones(d.shape, bool) if followed is None \
        else np.concatenate(followed)
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
              and worst_excess <= MARGIN_SIGMAS
              and np.sum(exchanged) <= allowed
              and unfollowed <= UNFOLLOWED_MAX_SHARE)
    out.update(ok=ok, sigma_z=sigma_z, tokens=int(d.size),
               unfollowed_share=unfollowed,
               selection_excess_sigmas=worst_excess,
               selection_excess_limit=MARGIN_SIGMAS,
               exchanged_share=float(np.mean(exchanged)),
               exchanged_tokens=int(np.sum(exchanged)),
               exchanges_predicted=expected, exchanges_allowed=allowed,
               close_share=float(np.mean((margin < 5.0).any(axis=0))))
    return out


def rollout_diffs(ctx, trainer, mesh, routed, params, rs, top: int):
    """|engine - reference| over the tokens that one rollout of the
    timed shape sampled on its first two rows (a full-length and a
    sixteenth-length prompt of ids below ``top``): prefill, then the
    absorbed path over the latent cache.  ``routed``: the training
    forward that also returns its selection, which the reference
    follows here too (the engine sows none)."""
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
        _, selected = routed(trainer.state.params, sampled, lens[:2])
    selected = np.asarray(jax.device_get(selected))
    d = []
    for b in range(2):
        # the whole row, padding and all: nothing before it attends to it
        want = reference_logprobs(ctx, params, sampled[b], selected[:, b])
        first, n = int(lens[b]) - 1, int(n_new[b])
        d.append(np.abs(got[b, :n].astype(np.float32)
                        - want[first:first + n]))
    return np.concatenate(d)


def check_trainer(ctx, trainer, mesh) -> dict:
    """The policy's per-token completion logprobs from the trainer's own
    forward (``_jit_logprobs``: the training graph, at the timed shapes)
    against the reference on the same parameters, on 2 seeded sequences
    drawn from the vocabulary slice; the reference follows the
    selection that one more forward of the same model sowed.  Then the
    policy logprobs of one rollout by the trainer's engine, on 2 of its
    rows, against the reference on what it sampled (``decode_*``)."""
    import jax
    import jax.numpy as jnp

    chk = ctx.lib("reference_check")
    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    vocab = int(ctx.config["vocab_size"])
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
            mutable=["intermediates"])
        sel = [x for path, x in
               jax.tree_util.tree_flatten_with_path(inter)[0]
               if any(getattr(k, "key", None) == "moe_selected"
                      for k in path)]
        # scanned: one [layers, B, L, k]; unrolled: one [B, L, k] a layer
        sel = jnp.concatenate([x.reshape((-1,) + x.shape[-3:]) for x in sel])
        return windowed_completion_logprobs(out[0], sequences, prompt_lens,
                                            T), sel

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
        followed.append(np.abs(lp[b, :T] - lp_again[b, :T]) <= SAME_FORWARD)
        probes.append({k: v[:, window] if getattr(v, "ndim", 0) > 1 else v
                       for k, v in probe.items()})
    out = verdict(chk, diffs, probes, int(ctx.config["num_hidden_layers"]),
                  followed)

    d = rollout_diffs(ctx, trainer, mesh, routed, params, rs, top)
    limit = DECODE_SLACK * out["mean_tolerance"]
    ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit)
    out.update(ok=out["ok"] and ok, decode_tokens=int(d.size),
               decode_mean_abs_diff=float(np.mean(d)),
               decode_median_abs_diff=float(np.median(d)),
               decode_max_abs_diff=float(np.max(d)),
               decode_mean_tolerance=limit)
    return out
