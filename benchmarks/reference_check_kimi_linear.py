"""The comparison with ``reference_kimi_linear`` that decides ``correct``
for a ``kimi_linear`` configuration (the chip's share of it).

Shaped like ``reference_check_dsv3.py``, whose three parts it keeps:
this file knows how the program lays out its parameters (``layers_<i>``
for a layer that stands alone, ``layers_<a>to<b>`` or ``layers`` for a
stretch stacked by ``scan_layers``; a KDA layer's ``attn/{q,k,v}_proj,
{q,k,v}_conv, f_a_proj, f_b_proj, A_log, dt_bias, b_proj, g_a_proj,
g_b_proj, o_norm, o_proj``; a latent layer's and the FFNs' names as for
``deepseek_v3``) and hands them to the reference as float32, one layer
at a time.

1. **The training forward** (``_jit_logprobs``, the chunked delta rule,
   flash on the latent layer, the grouped expert product) on 2 seeded
   sequences of the timed length, every token held to the error model's
   mean and worst limits; the reference FOLLOWS the program's discrete
   expert selection and bounds it (``reference_check_dsv3``'s point 2,
   its constants and its arithmetic, imported).
2. **The rollout**: the engine's policy logprobs of one rollout of the
   timed shape (a full-length and a sixteenth-length prompt in one
   right-padded batch: prefill through ``token_mask``, the state handed
   to decode, then one-token steps through ``kda_step`` and the absorbed
   latent path) against the reference's teacher-forced logprobs of what
   it sampled, on the mean alone (the engine sows no selection: point 3
   there).

3. **Two things a logprob within bfloat16's own noise cannot show**,
   each measured before it was written (PERF.md section 6; CPU,
   published widths, 2 x 256 tokens and a rollout of 176 steps).
   *A state kept in bfloat16* moves the training forward's mean from
   0.0136 to 0.0133 and the rollout's from 0.0186 to 0.0205: one more
   rounding among a layer's fifty.  So the state is looked at itself:
   after a prefill of two chunks and four steps through the engine's
   own decode model, the share of its entries that bfloat16 cannot hold
   (``state_float32_share``: ~1 for a float32 accumulation, 0 for a
   state rounded anywhere on its way) must be over a half.  *Rotary on
   the latent layer* moves the mean from 0.0136 to 0.0206 under a limit
   of 0.0210, because one layer in five attends and at a seeded
   initialisation its output is a small part of the stream.  So the
   reference is also computed WITH the rotation, and the program must
   lie closer to the reference without (``mean_abs_diff`` <
   ``rotated_mean_abs_diff``): the comparison is paired over the same
   tokens, so the noise common to both cancels (0.0136 against 0.0206
   for the program, the reverse for the fault).

**The error model** is ``reference_check``'s (form, ``U_BF16``, slack
and sigmas imported) with this block's roundings a layer,
``ROUNDINGS_KIMI``, calibrated the way ``ROUNDINGS`` and
``ROUNDINGS_DSV3`` were: so that the model reproduces a bfloat16
forward of the program's own Transformer at the published widths on
the CPU with the selection followed: RMS 0.0170, 0.0179, 0.0174 read
on three seeds (2 x 256 and 2 x 768 tokens, sigma_z 0.96), which 48.9,
53.8 and 50.8 roundings a layer reproduce; 52 predicts 0.0176 (PERF.md
section 6).  ``reference_check_dsv3``'s block reads 36: a KDA layer
rounds in more places.  What rounds in a KDA layer: the three projections and their
convolved, normalised forms; the gates' low-rank products; inside a
chunk the decayed keys and queries on their way into the MXU (the
state itself is float32, rounded only as an operand); the output norm,
gate and projection.  The state is float32 in the program: a state
kept in bfloat16 rounds at each of the 16 chunk boundaries and at each
of the 512 decode steps, and those roundings do not average away (the
state is a sum over the whole past), which is what pushes that fault
outside the limits; tests/bench plants it and six more (decay dropped,
``beta = 1``, a convolution skipped, padding not masked in prefill,
rotary applied to the latent layer, k - 1 experts) and each fails at
least one limit.
"""

from __future__ import annotations

import math
import re

import numpy as np

# calibrated: see the module docstring and PERF.md section 6
ROUNDINGS_KIMI = 52
# the rollout's selection is not followed (reference_check_dsv3, 3.)
DECODE_SLACK = 2.5
# between a float32 state's reading (1.0 but for entries that happen to
# be whole in 8 bits) and a bfloat16 state's (0.0)
STATE_FLOAT32_SHARE = 0.5


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_weights(p: dict) -> dict:
    """One layer of the program's tree as the reference takes it."""
    import jax.numpy as jnp

    a, m = p["attn"], p["mlp"]
    w = {"n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"]),
         "wo": _f32(a["o_proj"]["kernel"])}
    if "A_log" in a:
        w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkv"})
        w.update({"conv_" + n: _f32(a[n + "_conv"]) for n in "qkv"})
        w.update(w_fa=_f32(a["f_a_proj"]["kernel"]),
                 w_fb=_f32(a["f_b_proj"]["kernel"]),
                 w_ga=_f32(a["g_a_proj"]["kernel"]),
                 w_gb=_f32(a["g_b_proj"]["kernel"]),
                 w_b=_f32(a["b_proj"]["kernel"]),
                 A_log=_f32(a["A_log"]), dt_bias=_f32(a["dt_bias"]),
                 o_norm_g=_f32(a["o_norm"]))
    else:
        w.update(wq=_f32(a["q_proj"]["kernel"]),
                 wkva=_f32(a["kv_a_proj_with_mqa"]["kernel"]),
                 kva_g=_f32(a["kv_a_norm"]["scale"]),
                 wkvb=_f32(a["kv_b_proj"]))
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
    configuration file's ``num_experts`` counts those held here
    (``source_values`` has the published count)."""
    return int(config.get("expert_offset", 0)), int(config["num_experts"])


_STACK = re.compile(r"^layers_(\d+)to(\d+)$")


def layer_tree(params: dict, i: int, n_layers: int):
    """Layer ``i`` of the program's tree, whatever its layout."""
    import jax

    if f"layers_{i}" in params:
        return params[f"layers_{i}"]
    for key, sub in params.items():
        m = _STACK.match(key)
        if m and int(m.group(1)) <= i <= int(m.group(2)):
            return jax.tree.map(lambda x: x[i - int(m.group(1))], sub)
    alone = sum(1 for j in range(n_layers) if f"layers_{j}" in params)
    return jax.tree.map(lambda x: x[i - alone], params["layers"])


def first_layer_of(name: str, alone: int) -> int:
    """The first layer a module of the program's tree holds, from its
    name (``layers_3``, ``layers_1to2``, or ``layers``: the one stack
    behind the layers that stand alone)."""
    m = _STACK.match(name) or re.match(r"^layers_(\d+)$", name)
    return int(m.group(1)) if m else alone


def reference_logprobs(ctx, params: dict, ids: np.ndarray,
                       selected=None, probe: bool = False, n_real=None,
                       rotated: bool = False):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``selected`` [expert layers, L, k]: the experts to use instead of
    the reference's own top-k.  ``n_real``: the positions from there on
    hold no token.  ``probe``: also ``reference_check_dsv3``'s probe
    (``sigma_z``, ``margin``, ``excess``, ``exchanged``, ``depth``).
    ``rotated``: the latent layers WITH the rotation this model does not
    have (see :func:`check_trainer`)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_kimi_linear")
    shape = ctx.config
    held = held_of(shape)
    params = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])

    dense_step = jax.jit(
        lambda x, p, mask, kind: ref.layer(x, layer_weights(p), shape, kind,
                                           mask=mask),
        static_argnames=("kind",))
    expert_step = jax.jit(
        lambda x, p, mask, sel, kind: ref.layer(
            x, layer_weights(p), shape, kind, held, sel, probe=True,
            mask=mask), static_argnames=("kind",))

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
    infos, depth = [], []
    for i in range(n_layers):
        p = layer_tree(params, i, n_layers)
        kind = ref.mixer_kind(shape, i)
        if rotated and kind == "latent":
            kind = "latent_rotated"
        if "router" in p["mlp"]:
            sel = None if selected is None else jnp.asarray(
                selected[len(depth)], jnp.int32)
            x, info = expert_step(x, p, mask, sel, kind=kind)
            infos.append(jax.tree.map(np.asarray, info))
            depth.append(i)
        else:
            x = dense_step(x, p, mask, kind=kind)
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    if not probe:
        return logprobs
    out = {k: np.stack([info[k] for info in infos]) for k in infos[0]}
    return logprobs, dict(out, sigma_z=float(spread),
                          depth=np.asarray(depth))


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings a
    layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_KIMI * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def input_error(chk, depth):
    """Relative RMS error of an expert layer's input ``z`` after
    ``depth`` layers under the model: the embedding and ``depth`` layers
    on the residual stream, and the norm's own rounded output."""
    return np.sqrt(np.asarray(depth, np.float64) * ROUNDINGS_KIMI
                   + 2.0) * chk.U_BF16


def verdict(ctx, diffs: list, probes: list, layers: int,
            followed: list = None) -> dict:
    """``reference_check_dsv3.verdict`` (its limits on the selection,
    its arithmetic) under this block's error model."""
    chk, dsv3 = ctx.lib("reference_check"), ctx.lib("reference_check_dsv3")
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
              and worst_excess <= dsv3.MARGIN_SIGMAS
              and np.sum(exchanged) <= allowed
              and unfollowed <= dsv3.UNFOLLOWED_MAX_SHARE)
    out.update(ok=ok, sigma_z=sigma_z, tokens=int(d.size),
               unfollowed_share=unfollowed,
               selection_excess_sigmas=worst_excess,
               selection_excess_limit=dsv3.MARGIN_SIGMAS,
               exchanged_share=float(np.mean(exchanged)),
               exchanged_tokens=int(np.sum(exchanged)),
               exchanges_predicted=expected, exchanges_allowed=allowed,
               close_share=float(np.mean((margin < 5.0).any(axis=0))))
    return out


def selections(inter, n_layers: int, params: dict):
    """[expert layers, B, L, k]: what the expert layers sowed, in layer
    order, whatever the layout (a stack sows [length, B, L, k])."""
    import jax
    import jax.numpy as jnp

    params = params.get("backbone", params)
    alone = sum(1 for j in range(n_layers) if f"layers_{j}" in params)
    found = []
    for path, x in jax.tree_util.tree_flatten_with_path(inter)[0]:
        keys = [getattr(k, "key", None) for k in path]
        if "moe_selected" not in keys:
            continue
        module = next(k for k in keys if isinstance(k, str)
                      and k.startswith("layers"))
        found.append((first_layer_of(module, alone),
                      x.reshape((-1,) + x.shape[-3:])))
    return jnp.concatenate([x for _, x in sorted(found,
                                                 key=lambda t: t[0])])


def rollout_diffs(ctx, trainer, mesh, routed, params, rs, top: int):
    """|engine - reference| over the tokens that one rollout of the
    timed shape sampled on its first two rows (a full-length and a
    sixteenth-length prompt of ids below ``top`` in one right-padded
    batch): prefill under ``token_mask``, the states handed to decode,
    then ``kda_step`` and the absorbed path.  ``routed``: the training
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


def state_float32_share(ctx, trainer, mesh, rs, top: int) -> float:
    """The share of the recurrent states' entries that bfloat16 cannot
    hold, after a prefill over two chunks and four one-token steps
    through the engine's own decode model and parameters.  A state
    accumulated in float32 has low mantissa bits set in nearly every
    entry; one rounded to bfloat16 anywhere on its way (a chunk
    boundary, a decode step) in none."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.transformer import init_cache, prep_decode_params

    eng = trainer.engine
    P, steps = 128, 4
    ids = jnp.asarray(rs.randint(2, top, (2, P + steps)), jnp.int32)
    lens = jnp.asarray([P, P // 2], jnp.int32)

    def run(params):
        params = prep_decode_params(params, eng.model_cfg,
                                    eng.cfg.quantize_weights)
        cache = init_cache(eng._decode_cfg, 2, P + steps,
                           dtype=jnp.dtype(eng._decode_cfg.dtype))
        pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (2, P))
        _, cache = eng._decode_model.apply(
            {"params": params}, ids[:, :P], pos, cache,
            logits_positions=(lens - 1)[:, None],
            token_mask=pos < lens[:, None])
        for t in range(steps):
            _, cache = eng._decode_model.apply(
                {"params": params}, ids[:, P + t:P + t + 1],
                (lens + t)[:, None], cache)
        states = jnp.concatenate([c["S"].reshape(-1) for c in cache
                                  if "S" in c])
        # bfloat16 is float32's upper half: read the lower 16 bits (a
        # round trip through bfloat16 is a pair of converts that the
        # TPU's compiler removes as excess precision: on the chip it
        # found every entry unchanged)
        low = jax.lax.bitcast_convert_type(states, jnp.uint32) & 0xFFFF
        return jnp.sum((low != 0) & (states != 0)) / jnp.sum(states != 0)

    with mesh:
        return float(jax.jit(run)(trainer.state.params))


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
    dsv3 = ctx.lib("reference_check_dsv3")
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
            out[0], sequences, prompt_lens, T), selections(
                inter, n_layers, params)

    routed = jax.jit(routed)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
        lp_again, selected = routed(trainer.state.params, seqs, lens)
    lp, lp_again, selected = (np.asarray(x) for x in
                              jax.device_get((lp, lp_again, selected)))
    k = int(ctx.config["num_experts_per_token"])
    if selected.shape[-1] != k:
        # the reference would follow it and agree: gates over fewer
        # experts are another model, not a rounding of this one
        return dict(chk._verdict([], 0.0), ok=False,
                    why=f"the program selects {selected.shape[-1]} experts "
                        f"a token, the configuration {k}")
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    diffs, probes, followed, rotated = [], [], [], []
    for b in range(2):
        want, probe = reference_logprobs(ctx, params, seqs[b],
                                         selected[:, b], probe=True)
        diffs.append(np.abs(lp[b, :T].astype(np.float32) - want[window]))
        rotated.append(np.abs(lp[b, :T].astype(np.float32)
                              - reference_logprobs(
                                  ctx, params, seqs[b], selected[:, b],
                                  rotated=True)[window]))
        followed.append(np.abs(lp[b, :T] - lp_again[b, :T])
                        <= dsv3.SAME_FORWARD)
        probes.append({k: v[:, window] if getattr(v, "ndim", 0) > 1 else v
                       for k, v in probe.items()})
    out = verdict(ctx, diffs, probes, n_layers, followed)
    # which of the two the program computes: paired over the same
    # tokens, so the roundings common to both comparisons cancel
    nope, rope = (float(np.mean(np.concatenate(x)))
                  for x in (diffs, rotated))
    share = state_float32_share(ctx, trainer, mesh, rs, top)

    d, own = rollout_diffs(ctx, trainer, mesh, routed, params, rs, top)
    limit = DECODE_SLACK * out["mean_tolerance"]
    ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit
              and nope < rope and share >= STATE_FLOAT32_SHARE)
    out.update(ok=out["ok"] and ok, decode_tokens=int(d.size),
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
