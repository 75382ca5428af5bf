"""The comparison with ``reference_sdar`` that decides ``correct`` for an
``sdar_moe`` configuration (SDAR's language model: the Qwen3-MoE block
generating by diffusion over blocks; the chip's share of it).

Shaped like ``reference_check_keye_dsa.py``: this file knows how the
program lays out its parameters (``layers`` stacked by ``scan_layers``
or ``layers_<i>``; ``attn/{q,k,v,o}_proj, q_norm, k_norm``;
``mlp/{router, experts_gate_up_proj, experts_down_proj}``;
``input_norm``, ``post_attn_norm``) and hands them to the reference as
float32, one layer at a time.  A reference row is ``[clean ; ONE noisy
stream]`` (1284 entries at the timed sizes: its [32, 1284, 1284] scores
fit whole), so the program's one forward of all streams is compared
with something that is not itself.

The program makes one discrete choice a layer, the router's 8 of 128
experts, and a discrete choice made from bf16 inputs differs from the
float32 one wherever two candidates lie within rounding: in parts (a)
and (b) the reference FOLLOWS the program's, sown as ``moe_selected``,
as every expert cell's check does.  Three parts decide ``correct``;
each limit stands beside its reason.

(a) **The trainer's own log-probability program.**  ``_jit_logprobs`` on
    2 seeded sequences of the timed shape (one prompt of full length,
    one ragged, every block's new positions revealed in a seeded order)
    against the reference's ``denoising_steps`` separate forwards, given
    the experts that one more forward of the same model sowed (that
    forward's log-probabilities must equal the timed one's).  Nothing
    but rounding differs then, and every token is held to
    ``reference_check``'s error model (its form, unit rounding, slack
    and sigmas imported) with this block's roundings a layer,
    ``ROUNDINGS_SDAR``: the pre-norm expert block's 36 of
    ``reference_check_dsv3`` (calibrated there on a bf16 forward of that
    block at the same hidden size, expert width and depth), kept as
    ``reference_check_keye_dsa`` keeps it because this is that block
    without the indexer: it rounds in the same places.  An attention or
    a head computed one precision lower (fp8: 16 times the rounding) is
    far outside it; tests/bench plants both.
(b) **The engine.**  One rollout of the timed shape by the trainer's
    engine: its policy log-probabilities of what it revealed on the
    first two rows (a full-length and a ragged prompt) against the
    reference's trace log-probabilities teacher-forced on the same
    trace (the reference following the experts of the trainer's trace
    forward over the same sequences; the engine sows none, so a step
    that chose other experts shows as a difference), and against that
    trace forward itself.  Mean alone, within ``DECODE_SLACK`` of (a)'s
    mean limit, as the other expert cells'.  And the trace is well
    formed: every completion position revealed exactly once, one a step
    a block (``block_length / denoising_steps``), nothing revealed
    behind the block of a stop token.
(c) **Controls that must fail.**  The reference (i) under a plain causal
    mask with the autoregressive shift, (ii) scoring every token from
    ``z^(0)`` (all masked) instead of its own step's state, (iii) with a
    block's commit forward skipped (the clean stream's keys from the
    last denoising step's state, one position a block still masked).
    (a)'s ``mean_abs_diff`` must lie under the program's distance to
    each, and the program must hold next to none of what a control
    differs from the reference by: ``control_share`` = <program -
    reference, control - reference> / |control - reference|^2 over the
    compared tokens is 0 for the right program but for rounding (whose
    projection falls with the square root of the tokens: 0.02 at 1024)
    and 1 for a program that computes the control, whatever the
    control's size; it must stay under ``CONTROL_SHARE_MAX``, half way.
    A distance alone does not do for (ii): with seeded random weights a
    block's revealed tokens move a neighbour's log-probability by less
    (mean 0.008 on the chip) than (a)'s limit allows rounding (0.018),
    so a program that scored every token from the all-masked state
    would pass (a); its share reads 1.
"""

from __future__ import annotations

import math

import numpy as np

ROUNDINGS_SDAR = 36
# the rollout's tokens are sampled and its experts not followed (b)
DECODE_SLACK = 2.5
# two programs compiled from one model may differ in the last bit
SAME_FORWARD = 1e-3
UNFOLLOWED_MAX_SHARE = 0.01
# (c): between the right program's 0 and a control's 1
CONTROL_SHARE_MAX = 0.5


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_weights(p: dict) -> dict:
    """One layer of the program's tree as the reference takes it."""
    a, m = p["attn"], p["mlp"]
    w = {"n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"]),
         "q_g": _f32(a["q_norm"]["scale"]), "k_g": _f32(a["k_norm"]["scale"]),
         "w_router": _f32(m["router"]),
         "e_gate_up": _f32(m["experts_gate_up_proj"]),
         "e_down": _f32(m["experts_down_proj"])}
    w.update({"w" + n: _f32(a[n + "_proj"]["kernel"]) for n in "qkvo"})
    return w


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's tree, stacked or not."""
    import jax

    if f"layers_{i}" in params:
        return params[f"layers_{i}"]
    return jax.tree.map(lambda x: x[i], params["layers"])


def held_of(config: dict):
    """(offset, count) of the experts this share holds: the file's
    ``num_experts`` counts those held here (``source_values`` has the
    published count, the router's width), ``expert_offset`` from where."""
    return int(config.get("expert_offset", 0)), int(config["num_experts"])


def rule_of(config: dict, model_cfg) -> dict:
    """The configuration's keys with the generation rule as the
    reference reads it: the file's ``assumed`` values must be the
    program's."""
    shape = dict(config)
    told = {"block_length": model_cfg.block_length,
            "denoising_steps": model_cfg.denoising_steps,
            "mask_token_id": model_cfg.mask_id}
    for key, value in told.items():
        if int(shape.setdefault(key, value)) != value:
            raise ValueError(f"the configuration says {key}="
                             f"{shape[key]}, the program runs {value}")
    return shape


def reference_trace(ctx, shape, params: dict, ids, n_prompt: int, steps_of,
                    experts=None, n_real=None, control=None):
    """The reference's trace log-probabilities of ids [L] (prompt, then
    ``len(steps_of)`` completion positions): ([T] float32, the logits'
    spread).  ``experts`` [layers, row, k] as the program's one forward
    sowed them over ``[clean ; every stream (of ``window`` entries)]``,
    given as (array, window); ``n_real``: the clean entries from there
    on hold no token.  ``control``: None, ``"all_masked"`` or
    ``"stale_commit"`` (module text, (c))."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_sdar")
    held = held_of(shape)
    params = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])
    Bd, S, MASK = ref.rule(shape)
    ids = jnp.asarray(ids, jnp.int32)
    steps_of = jnp.asarray(steps_of, jnp.int32)
    L, T = int(ids.shape[0]), int(steps_of.shape[0])
    token_mask = None if n_real is None else jnp.arange(L) < int(n_real)

    @jax.jit
    def step(x, p, positions, mask, tm, exp):
        return ref.layer(x, layer_weights(p), positions, shape, held, mask,
                         tm, exp)

    clean = ids
    if control == "stale_commit":
        # a block's keys as its last denoising step left them
        at = n_prompt + jnp.arange(T)
        clean = ids.at[at].set(jnp.where(steps_of == S - 1, MASK, ids[at]))
    rows = []
    for s in range(S):
        row, positions, wpos = ref.stream_rows(
            ids, n_prompt, steps_of, shape, s, control == "all_masked")
        row = row.at[:L].set(clean)
        W = int(wpos.shape[0])
        mask = ref.stream_mask(jnp.arange(L), wpos, Bd)
        tm = None if token_mask is None else jnp.concatenate(
            [token_mask, jnp.ones((W,), bool)])
        exp = None
        if experts is not None:
            sown, window = experts
            exp = jnp.concatenate(
                [sown[:, :L], sown[:, L + s * window:L + s * window + W]],
                axis=1).astype(jnp.int32)
        x = ref.embed(row, {"embed": params["embed"]["embedding"]})
        for i in range(n_layers):
            x = step(x, layer_tree(params, i), positions, mask, tm,
                     None if exp is None else exp[i])
        rows.append(x[L + (n_prompt - int(wpos[0])) + jnp.arange(T)])

    @jax.jit
    def finish(rows, final_norm, lm_head):
        h = jnp.take_along_axis(
            jnp.stack(rows), jnp.clip(steps_of, 0, S - 1)[None, :, None],
            axis=0)[0]
        logits = ref.bar(ref.head(h, {
            "nf_g": _f32(final_norm["scale"]),
            "w_head": _f32(lm_head["kernel"])}, shape), MASK)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            ids[n_prompt + jnp.arange(T)][:, None], axis=-1)[:, 0]
        return lp, jnp.mean(jnp.std(logits.at[:, MASK].set(0.0), axis=-1))

    lp, spread = finish(rows, params["final_norm"], params["lm_head"])
    return np.asarray(lp), float(spread)


def reference_causal(ctx, shape, params: dict, ids, n_prompt: int, T: int):
    """Control (i): one plain causal forward with the shift."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_sdar")
    held = held_of(shape)
    params = params.get("backbone", params)
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    mask = ref.causal_mask(positions)

    @jax.jit
    def step(x, p):
        return ref.layer(x, layer_weights(p), positions, shape, held, mask)

    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    for i in range(int(shape["num_hidden_layers"])):
        x = step(x, layer_tree(params, i))
    at = n_prompt + jnp.arange(T)
    logits = ref.bar(ref.head(x[at - 1], {
        "nf_g": _f32(params["final_norm"]["scale"]),
        "w_head": _f32(params["lm_head"]["kernel"])}, shape),
        ref.rule(shape)[2])
    return np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), ids[at][:, None], axis=-1)[:, 0])


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings a
    layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_SDAR * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def sown(tree, name: str, rank: int = 3):
    """The arrays of ``rank`` dimensions a model's layers sowed under
    ``name``, stacked over the layers."""
    import jax
    import jax.numpy as jnp

    found = [x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
             if any(getattr(k, "key", None) == name for k in path)]
    if not found:
        return None
    return jnp.concatenate([x.reshape((-1,) + x.shape[-rank:])
                            for x in found])


def routed_forward(trainer):
    """``BaseTrainer._trace_forward``'s forward with what the layers sow
    kept: jitted (params, sequences, prompt_lens, reveal_step) ->
    (logprobs [B, T], experts [layers, B, row, k])."""
    import jax
    import jax.numpy as jnp

    mc = trainer.cfg.model

    def routed(params, sequences, prompt_lens, reveal_step):
        from orion_tpu.models.transformer import trace_inputs
        from orion_tpu.ops.sampling import bar_token

        T = reveal_step.shape[1]
        ids, positions, kw = trace_inputs(mc, sequences, prompt_lens,
                                          reveal_step)
        out, kept = trainer.model.apply({"params": params}, ids, positions,
                                        mutable=["intermediates"], **kw)
        targets = jnp.take_along_axis(
            sequences, jnp.clip(prompt_lens[:, None] + jnp.arange(T)[None],
                                0, sequences.shape[1] - 1), axis=1)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(bar_token(out[0], mc.mask_id), axis=-1),
            targets[..., None], axis=-1)[..., 0]
        return lp, sown(kept, "moe_selected")

    return jax.jit(routed)


def seeded_trace(rs, lens, T: int, Bd: int, S: int) -> np.ndarray:
    """[B, T] reveal steps as the static rule leaves them: a block's new
    positions revealed ``Bd / S`` a step from step 0 on, in an order
    drawn from ``rs``."""
    per = Bd // S
    out = np.zeros((len(lens), T), np.int32)
    for b, n in enumerate(lens):
        pos = int(n) + np.arange(T)
        for blk in np.unique(pos // Bd):
            idx = np.nonzero(pos // Bd == blk)[0]
            out[b, rs.permutation(idx)] = np.arange(len(idx)) // per
    return out


def trace_faults(host, Bd: int, S: int, stop_ids=()) -> list:
    """What is wrong with a rollout's trace (``GenerationResult`` on the
    host): [] for a well-formed one."""
    faults = []
    per = Bd // S
    steps = np.asarray(host.reveal_step)
    lens, n_new = np.asarray(host.prompt_lens), np.asarray(
        host.completion_lens)
    seqs = np.asarray(host.sequences)
    T = steps.shape[1]
    for b in range(steps.shape[0]):
        pos = int(lens[b]) + np.arange(T)
        n = int(n_new[b])
        # the block that holds the completion's last token is whole
        through = (pos // Bd) <= (pos[n - 1] // Bd) if n else pos < 0
        if np.any(steps[b, through] >= S) or np.any(steps[b, through] < 0):
            faults.append(f"row {b}: a position of a generated block was "
                          "never revealed")
        if np.any(steps[b, ~through] != S):
            faults.append(f"row {b}: revealed behind its last block")
        for blk in np.unique(pos[through] // Bd):
            got = np.sort(steps[b, through & (pos // Bd == blk)])
            if not np.array_equal(got, np.arange(len(got)) // per):
                faults.append(f"row {b} block {blk}: steps {got.tolist()}")
                break
        done_early = n < T
        if done_early and (not stop_ids or int(
                seqs[b, int(lens[b]) + n - 1]) not in stop_ids):
            faults.append(f"row {b}: ended at {n} without a stop token")
    return faults


def rollout_diffs(ctx, shape, trainer, mesh, routed, params, rs, top: int):
    """Part (b): |engine - reference| and |engine - the trainer's trace
    forward| over what one rollout of the timed shape revealed on its
    first two rows, and the trace's faults."""
    import jax

    job = ctx.traffic
    mc = trainer.cfg.model
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    lens = np.where(np.arange(B) % 2 == 0, P, max(4 * P // 5 + 1, 2)).astype(
        np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       rs.randint(4, top, (B, P)), 0).astype(np.int32)
    with mesh:
        rollout = trainer.generate(prompts, lens, jax.random.key(
            ctx.lib("harness").seed31(ctx.seed)))
        host = rollout.to_host()
        forward, experts = routed(trainer.state.params, host.sequences[:2],
                                  lens[:2], host.reveal_step[:2])
    forward, experts = (np.asarray(x) for x in
                        jax.device_get((forward, experts)))
    stop_ids = tuple(t for t in (trainer.engine.eos_token_id,
                                 *trainer.engine.cfg.stop_token_ids)
                     if t is not None)
    faults = trace_faults(host, mc.block_length, mc.denoising_steps, stop_ids)
    window = mc.blocks_spanned(host.reveal_step.shape[1]) * mc.block_length
    d, own = [], []
    for b in range(2):
        n = int(host.completion_lens[b])
        want, _ = reference_trace(
            ctx, shape, params, host.sequences[b], int(lens[b]),
            host.reveal_step[b], (experts[:, b], window),
            n_real=int(lens[b]) + host.reveal_step.shape[1])
        got = np.asarray(host.policy_logprobs[b, :n], np.float32)
        d.append(np.abs(got - want[:n]))
        own.append(np.abs(got - forward[b, :n]))
    return np.concatenate(d), np.concatenate(own), faults


def check_trainer(ctx, trainer, mesh) -> dict:
    """Parts (a) to (c) of the module docstring on the trainer's own
    programs and parameters."""
    import jax

    chk = ctx.lib("reference_check")
    job = ctx.traffic
    mc = trainer.cfg.model
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    shape = rule_of(ctx.config, mc)
    n_layers = int(shape["num_hidden_layers"])
    k = int(shape["num_experts_per_tok"])
    Bd, S, MASK = mc.block_length, mc.denoising_steps, mc.mask_id
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(int(shape["vocab_size"]), mc.vocab_size, MASK)
    seqs = rs.randint(4, top, (2, P + T)).astype(np.int32)
    lens = np.asarray([P, max(P - 1 - int(rs.randint(0, P // 2)), 2)],
                      np.int32)
    steps = seeded_trace(rs, lens, T, Bd, S)
    routed = routed_forward(trainer)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T, reveal_step=steps)
        lp_again, experts = routed(trainer.state.params, seqs, lens, steps)
    lp, lp_again, experts = (np.asarray(x, np.float32 if i < 2 else None)
                             for i, x in enumerate(
                                 jax.device_get((lp, lp_again, experts))))
    if experts.shape[-1] != k:
        return dict(chk._verdict([], 0.0), ok=False,
                    why=f"the program selects {experts.shape[-1]} experts "
                        f"a token, the configuration {k}")
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = mc.blocks_spanned(T) * Bd
    given, followed, spreads = [], [], []
    controls = {"causal": [], "all_masked": [], "stale_commit": []}
    for b in range(2):
        n = int(lens[b])
        ids = seqs[b, :n + T]
        want, spread = reference_trace(
            ctx, shape, params, seqs[b], n, steps[b],
            (experts[:, b], window), n_real=n + T)
        given.append(lp[b] - want)
        spreads.append(spread)
        followed.append(np.abs(lp[b] - lp_again[b]) <= SAME_FORWARD)
        # what each control differs from the reference by
        controls["causal"].append(
            reference_causal(ctx, shape, params, ids, n, T) - want)
        for name in ("all_masked", "stale_commit"):
            controls[name].append(reference_trace(
                ctx, shape, params, ids, n, steps[b], control=name)[0] - want)
    keep = np.concatenate(followed)
    sigma_z = max(spreads)
    off = np.concatenate(given)[keep]
    out = chk._verdict([np.abs(off)], predicted_rms(chk, sigma_z, n_layers))
    unfollowed = float(np.mean(~keep))
    control_means, control_shares = {}, {}
    for name, d in controls.items():
        d = np.concatenate(d)[keep]
        control_means[name] = float(np.mean(np.abs(off - d)))
        control_shares[name] = float(np.dot(off, d) / max(np.dot(d, d),
                                                          1e-30))
    d, vs_forward, faults = rollout_diffs(ctx, shape, trainer, mesh, routed,
                                          params, rs, top)
    decode_limit = DECODE_SLACK * out["mean_tolerance"]
    parts = {
        "a_trace_logprobs": bool(out["ok"]
                                 and unfollowed <= UNFOLLOWED_MAX_SHARE),
        "b_engine": bool(d.size and np.isfinite(d).all()
                         and np.mean(d) <= decode_limit and not faults),
        "c_controls_fail": bool(all(
            out["mean_abs_diff"] < control_means[name]
            and control_shares[name] < CONTROL_SHARE_MAX
            for name in controls)),
    }
    out.update(
        ok=all(parts.values()), parts=parts, sigma_z=sigma_z,
        unfollowed_share=unfollowed,
        control_mean_abs_diff=control_means, control_share=control_shares,
        control_share_limit=CONTROL_SHARE_MAX,
        trace_faults=faults[:4],
        decode_tokens=int(d.size),
        decode_mean_abs_diff=float(np.mean(d)),
        decode_median_abs_diff=float(np.median(d)),
        decode_max_abs_diff=float(np.max(d)),
        decode_mean_tolerance=decode_limit,
        decode_vs_forward_mean_abs_diff=float(np.mean(vs_forward)),
        decode_vs_forward_median_abs_diff=float(np.median(vs_forward)))
    return out
