"""The comparison with ``reference_olmo_hybrid`` that decides ``correct``
for an ``olmo_hybrid`` configuration.

Shaped like ``reference_check_kimi_linear.py`` without its expert
layer (whose layout reader ``layer_tree`` and state probe
``state_float32_share`` it uses as they are): this file knows how the program lays out its parameters
(``layers_<i>`` unrolled, ``layers_<a>to<b>`` or ``layers`` for a
stretch stacked by ``scan_layers``; a GDN layer's ``attn/{q,k,v}_proj,
{q,k,v}_conv, a_proj, b_proj, A_log, dt_bias, z_proj, o_norm, o_proj``;
a full-attention layer's ``attn/{q,k,v,o}_proj, q_norm, k_norm``;
``mlp/{gate,up,down}_proj``, ``post_attn_norm``, ``post_mlp_norm``) and
hands them to the reference as float32, one layer at a time.  Four
parts, each with its limit and the limit's reason:

(a) **The training forward** (``_jit_logprobs``: the chunked delta rule
    through the kernels on a TPU, flash on the full-attention layer) on
    2 seeded sequences of the timed length, every compared token held to
    the error model's mean and worst limits (``reference_check``'s form,
    ``U_BF16`` and slack imported; this block's roundings a layer and
    its own number of sigmas for the worst token below).
(b) **The rollout**: the engine's policy logprobs of one rollout of the
    timed shape, a full-length and a sixteenth-length prompt in one
    right-padded batch, so that prefill's ``token_mask``, the state, the
    convolutions' last inputs AND the per-head cache's real lengths
    handed to decode are inside ``correct``, against the reference's
    teacher-forced logprobs of what it sampled.  Mean alone, within
    ``DECODE_SLACK`` of (a)'s mean limit: a sampled token is drawn where
    the program's own distribution puts mass, which is not where a
    seeded sequence's tokens lie, and 512 one-token steps round the
    convolutions' inputs to the compute dtype once more each; the
    slack is Kimi's and Kanana's, which read 1.2-1.3 of (a)'s mean.
(c) **The state's own mantissa** (``state_float32_share`` >= 0.5): a
    state kept in bfloat16 is one more rounding among a layer's dozens
    and no logprob shows it (PERF.md section 6, PR 32), so after a
    prefill of two chunks and four one-token steps through the engine's
    own decode model the share of state entries that bfloat16 cannot
    hold is read: ~1 for a float32 accumulation, 0 for a state rounded
    anywhere on its way.  The limit lies between the two.
(d) **No rotation**: ``rope_theta`` is null in the published config and
    the program rotates nothing on the full-attention layer.  One layer
    in four attends, so a rotation moves the mean by less than the
    limit of (a) allows; the reference is therefore also computed WITH
    rotary (``theta`` 500 000), and the program must lie closer to the
    reference without (``mean_abs_diff`` < ``rotated_mean_abs_diff``):
    paired over the same tokens, so the roundings common to both cancel.

**The error model** is ``reference_check``'s: a logprob's RMS error is
``sigma_z sqrt(layers R + 3) U_BF16`` with ``R`` the effective number of
full-size roundings a layer adds to the residual stream.
``ROUNDINGS_OLMO`` was calibrated as ``ROUNDINGS``, ``ROUNDINGS_DSV3``
and ``ROUNDINGS_KIMI`` were: so that the model reproduces a bfloat16
forward of the program's own Transformer at the published widths on the
CPU (PERF.md section 6 has the readings).  What rounds in this block:
a GDN layer's three projections, their convolved and normalised forms,
the decayed keys and queries on their way into the MXU (the state is
float32, rounded only as an operand), the output norm, the gate and the
output projection; the attention layer's projections, the two norms
over them, probabilities and output; the MLP's three products and their
SiLU; and, what a pre-norm block does not have, BOTH sublayers' outputs
pass a norm AFTER them, so every rounding of a branch reaches the
stream at the stream's own size (a pre-norm block adds a branch at
whatever size its weights give it, which at a seeded initialisation is
a fraction of the stream).  The same limits catch a computation one
precision lower: fp8 weights or activations round 16 times coarser
(PERF.md section 6 has the planted readings).
"""

from __future__ import annotations

import math

import numpy as np

# calibrated: see the module docstring and PERF.md section 6 (134.4,
# 134.9, 152.2 on three seeds at 2 x 256 tokens, 134.2, 141.4, 136.4 on
# three more at 2 x 1024: RMS 0.0325-0.0346 at sigma_z 1.24)
ROUNDINGS_OLMO = 140
# The worst token: ``reference_check`` allows 6 sigmas, which suits an
# error that is near Gaussian.  This block's is not: on the same six
# bfloat16 forwards the worst of 512 to 2048 tokens lay 3.1 to 6.0 RMS
# out (0.099-0.195), twice what a Gaussian of that many samples gives;
# the limit is set between that and what fp8 reads (PERF.md section 6)
WORST_SIGMAS_OLMO = 12.0
# the rollout's tokens are sampled, its steps round once more (b)
DECODE_SLACK = 2.5
# between a float32 state's reading (1.0 but for entries that happen to
# be whole in 8 bits) and a bfloat16 state's (0.0)
STATE_FLOAT32_SHARE = 0.5


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_weights(p: dict) -> dict:
    """One layer of the program's tree as the reference takes it."""
    a, m = p["attn"], p["mlp"]
    w = {"na_g": _f32(p["post_attn_norm"]["scale"]),
         "nf_g": _f32(p["post_mlp_norm"]["scale"]),
         "wo": _f32(a["o_proj"]["kernel"]),
         "w_gate": _f32(m["gate_proj"]["kernel"]),
         "w_up": _f32(m["up_proj"]["kernel"]),
         "w_down": _f32(m["down_proj"]["kernel"])}
    w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkv"})
    if "A_log" in a:
        w.update({"conv_" + n: _f32(a[n + "_conv"]) for n in "qkv"})
        w.update(w_a=_f32(a["a_proj"]["kernel"]),
                 w_b=_f32(a["b_proj"]["kernel"]),
                 w_z=_f32(a["z_proj"]["kernel"]),
                 A_log=_f32(a["A_log"]), dt_bias=_f32(a["dt_bias"]),
                 o_norm_g=_f32(a["o_norm"]))
    else:
        w.update(q_norm_g=_f32(a["q_norm"]["scale"]),
                 k_norm_g=_f32(a["k_norm"]["scale"]))
    return w


def reference_logprobs(ctx, params: dict, ids: np.ndarray, n_real=None,
                       rotated: bool = False, with_spread: bool = False):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``n_real``: the positions from there on hold no token.  ``rotated``:
    the full-attention layers WITH the rotation this model does not
    have.  ``with_spread``: also the logits' standard deviation over the
    vocabulary, mean over positions (the error model's SIGMA_Z)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_olmo_hybrid")
    layer_tree = ctx.lib("reference_check_kimi_linear").layer_tree
    shape = ctx.config
    params = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])

    step = jax.jit(
        lambda x, p, mask, kind, rotated: ref.layer(
            x, layer_weights(p), shape, kind, mask, rotated),
        static_argnames=("kind", "rotated"))

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
    for i in range(n_layers):
        x = step(x, layer_tree(params, i, n_layers), mask,
                 kind=ref.mixer_kind(shape, i), rotated=rotated)
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    return (logprobs, float(spread)) if with_spread else logprobs


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings a
    layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_OLMO * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def rollout_diffs(ctx, trainer, mesh, params, rs, top: int):
    """|engine - reference| over the tokens that one rollout of the
    timed shape sampled on its first two rows (a full-length and a
    sixteenth-length prompt of ids below ``top`` in one right-padded
    batch), and |engine - the training forward| on the same tokens."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    T = int(job["new_tokens"])
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
        forward, _ = trainer._jit_logprobs(trainer.state.params, sampled,
                                           lens[:2], max_new=T)
    forward = np.asarray(jax.device_get(forward), np.float32)
    d, own = [], []
    for b in range(2):
        n = int(n_new[b])
        # what lies behind prompt + completion holds no token; before
        # it, the reference sees what the engine saw
        want = reference_logprobs(ctx, params, sampled[b],
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

    chk = ctx.lib("reference_check")
    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    vocab = int(ctx.config["vocab_size"])
    n_layers = int(ctx.config["num_hidden_layers"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(vocab, trainer.cfg.model.vocab_size)
    seqs = rs.randint(2, top, (2, P + T)).astype(np.int32)
    lens = np.full((2,), P, np.int32)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
    lp = np.asarray(jax.device_get(lp), np.float32)
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    diffs, rotated, spreads = [], [], []
    for b in range(2):
        want, spread = reference_logprobs(ctx, params, seqs[b],
                                          with_spread=True)
        diffs.append(np.abs(lp[b, :T] - want[window]))
        rotated.append(np.abs(lp[b, :T] - reference_logprobs(
            ctx, params, seqs[b], rotated=True)[window]))
        spreads.append(spread)
    sigma_z = max(spreads)
    out = chk._verdict(diffs, predicted_rms(chk, sigma_z, n_layers))
    # the mean limit is ``reference_check``'s, the worst this block's
    worst_tol = WORST_SIGMAS_OLMO * out["predicted_rms"]
    out.update(max_tolerance=worst_tol, ok=bool(
        out["tokens"] and np.isfinite(out["max_abs_diff"])
        and out["max_abs_diff"] <= worst_tol
        and out["mean_abs_diff"] <= out["mean_tolerance"]))
    nope, rope = (float(np.mean(np.concatenate(x)))
                  for x in (diffs, rotated))
    share = ctx.lib("reference_check_kimi_linear").state_float32_share(
        ctx, trainer, mesh, rs, top)
    d, own = rollout_diffs(ctx, trainer, mesh, params, rs, top)
    limit = DECODE_SLACK * out["mean_tolerance"]
    ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit
              and nope < rope and share >= STATE_FLOAT32_SHARE)
    out.update(ok=out["ok"] and ok, sigma_z=sigma_z,
               decode_tokens=int(d.size),
               rotated_mean_abs_diff=rope,
               state_float32_share=share,
               state_float32_share_limit=STATE_FLOAT32_SHARE,
               decode_vs_forward_median_abs_diff=float(np.median(own)),
               decode_vs_forward_mean_abs_diff=float(np.mean(own)),
               decode_mean_abs_diff=float(np.mean(d)),
               decode_median_abs_diff=float(np.median(d)),
               decode_max_abs_diff=float(np.max(d)),
               decode_mean_tolerance=limit)
    return out
