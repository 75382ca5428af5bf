"""The comparison with ``reference_ouro`` that decides ``correct`` for an
``ouro`` configuration.

This file knows how the program lays out its parameters (``backbone/``
under a shared actor-critic trunk: ``embed/embedding``, ``layers``
stacked by ``scan_layers`` or ``layers_<i>``, a block's
``attn/{q,k,v,o}_proj``, ``mlp/{gate,up,down}_proj`` and its four norms
``input_norm``, ``attn_out_norm``, ``post_attn_norm``, ``post_mlp_norm``;
``final_norm``, ``exit_gate/{kernel,bias}``, ``lm_head``; beside it
``value_head``) and hands them to the reference as float32, one layer at
a time, the same layer's in every pass.

**The probe's norm scales.**  At a seeded initialisation every norm's
scale is 1, and then some faults are not faults: a final norm applied
once more after the last pass is the identity on an already normed
vector.  So the programs are given the trainer's parameters with every
norm's scale multiplied by a seeded draw from U(0.5, 1.5) (the big
leaves are shared, not copied), and the reference the same tree.  The
programs are the timed ones at the timed shapes: parameters are
arguments.

Three parts, each with its limit and the limit's reason:

(a) **The experience forward** (``_jit_lp_values``: the scan over passes
    around the scan over layers, flash kernels on a TPU) on a batch of
    the timed shape whose first 2 rows are seeded sequences of the timed
    length: their completion-window log-probabilities against the
    reference's, every compared token held to the error model's mean
    and worst limits (``reference_check``'s form), and the values (the
    value head on ``H_last``) against ``H_last . value_head`` of the
    reference within the same model's limit for a projection of the
    hidden state.
(b) **The rollout**: the engine's policy log-probabilities of one
    rollout of the timed shape (prefill + ``new_tokens`` one-token steps
    through every (pass, layer) cache entry; row 0 a full-length prompt,
    row 1 a half-length one in the same right-padded batch, so that the
    steps cross ``prefix_lengths`` boundaries at different times) against
    the reference's teacher-forced log-probabilities of what it sampled:
    an entry read from another pass, or a pass left out at a step, moves
    these.  Mean alone, within ``DECODE_SLACK`` of (a)'s mean limit
    (sampled tokens lie where the program's own distribution puts mass;
    the other cells' rollouts read 1.2-1.3 of (a)'s mean).
(c) **The exit masses** of that experience forward (its
    ``ut_exit_mass_<t>`` counters: means over the 2 rows' window tokens)
    against the reference's means over the same tokens, pass by pass,
    and the number of passes itself.  Limit: a gate's logit is a
    projection of ``H_t``, off by the model's relative error times ``|g
    * w_g|``; a mass moves by at most a quarter of that a gate.  No
    credit is taken for the mean over a thousand tokens, so the limit
    (0.019 at the published widths) lies some 10 times over the reading
    (0.0003-0.0022 on the CPU's bfloat16 forwards) and 10 times under
    what a softmax over the passes' logits reads instead of the sigmoids'
    product.

**The error model** is ``reference_check``'s: a logprob's RMS error is
``sigma_z sqrt(visits R + 3) U_BF16`` with ``R`` the effective number of
full-size roundings a layer VISIT adds to the residual stream and
``visits = total_ut_steps x layers``: four passes compound the rounding
of one, in squares, the final norm between them keeping the relative
error as it is.  ``ROUNDINGS_OURO`` was calibrated as the other blocks'
were: so that the model reproduces a bfloat16 forward of the program's
own Transformer at the published widths on the CPU (PERF.md section 6,
PR 56, has the readings).  What rounds in this block: four norms'
outputs, the four attention products, probabilities and output, the
MLP's three products and their SiLU; BOTH sublayers' outputs pass a
norm after them and reach the stream at the norm's scale, but the
stream grows with every block of a pass (it is normed once a pass, not
once a block), so a later block's roundings count by a smaller share.
The same limits catch a computation one precision lower: fp8 weights
round 16 times coarser (PERF.md has the planted readings).
"""

from __future__ import annotations

import math

import numpy as np

# calibrated: see the module docstring and PERF.md section 6 (PR 56):
# 31.2, 42.8, 32.0 on three seeds at 2 x 256 tokens under the probe's
# norm scales (RMS 0.0348, 0.0398, 0.0352 at sigma_z 0.95-0.97; the
# hidden state's relative error read 0.031-0.041 where the model says
# 0.040)
ROUNDINGS_OURO = 40
# The worst token: on those bfloat16 forwards the worst of 510 tokens lay
# 3.7 to 4.0 RMS out; fp8 reads 16 times the RMS itself.  Between them,
# with room for the thousand tokens and the heavier tail of the chip's
# kernels (reference_check_olmo_hybrid read 6 RMS there)
WORST_SIGMAS_OURO = 10.0
# the rollout's tokens are sampled, its steps round once more (b)
DECODE_SLACK = 2.5
# a projection of the hidden state (the values): mean limit over the
# model's (a Gaussian's mean is sqrt(2/pi) of its RMS: 0.034 here).  ONE
# direction of 2048 a seed, and the error along it is shared by the
# tokens, so the reading follows the seed far more than a logprob's
# does: 0.014 to 0.070 over the chip's first eleven seeds.  What a fault
# reads: 0.43 (the final norm once more), 0.47 (three passes), 0.62 (no
# norm between passes); fp8 weights 16 times the model's.  Between them
VALUE_SLACK = 6.0
# a mass moves by at most a quarter of its gates' logits' error
MASS_SLACK = 2.0
NORM_SCALE = (0.5, 1.5)


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_weights(p: dict) -> dict:
    """One layer of the program's tree as the reference takes it."""
    a, m = p["attn"], p["mlp"]
    return {"n1": _f32(p["input_norm"]["scale"]),
            "n2": _f32(p["attn_out_norm"]["scale"]),
            "n3": _f32(p["post_attn_norm"]["scale"]),
            "n4": _f32(p["post_mlp_norm"]["scale"]),
            "wq": _f32(a["q_proj"]["kernel"]),
            "wk": _f32(a["k_proj"]["kernel"]),
            "wv": _f32(a["v_proj"]["kernel"]),
            "wo": _f32(a["o_proj"]["kernel"]),
            "w_gate": _f32(m["gate_proj"]["kernel"]),
            "w_up": _f32(m["up_proj"]["kernel"]),
            "w_down": _f32(m["down_proj"]["kernel"])}


def layer_tree(params: dict, i: int):
    import jax

    if "layers" in params:
        return jax.tree.map(lambda x: x[i], params["layers"])
    return params[f"layers_{i}"]


def probe_params(params, rs):
    """``params`` with every norm's scale times a draw from U(*NORM_SCALE):
    the big leaves shared, the scales new."""
    import jax
    import jax.numpy as jnp

    def draw(path, x):
        names = [str(getattr(k, "key", "")) for k in path]
        if any(n.endswith("norm") for n in names) and names[-1] == "scale":
            u = rs.uniform(*NORM_SCALE, size=x.shape).astype(np.float32)
            return x * jnp.asarray(u, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(draw, params)


def reference_outputs(ctx, params: dict, ids: np.ndarray) -> dict:
    """The reference over one sequence ``ids`` [L], given the program's
    parameter tree, a layer's weights converted one call at a time:
    ``logprobs`` [L-1] (teacher-forced next-token), ``hidden`` [L, E]
    (``H_last``), ``masses`` [passes, L], ``sigma_z`` (the logits'
    standard deviation over the vocabulary, mean over positions)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_ouro")
    shape = ctx.config
    trunk = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])
    eps = float(shape["rms_norm_eps"])

    step = jax.jit(lambda x, p, positions: ref.layer(
        x, layer_weights(p), shape, positions))

    @jax.jit
    def end_of_pass(x, final_norm, gate):
        x = ref.rms_norm(x, _f32(final_norm["scale"]), eps)
        return x, ref.gate(x, {"w_g": _f32(gate["kernel"])[:, 0],
                               "b_g": _f32(gate["bias"])[0]})

    @jax.jit
    def finish(x, lm_head, ids):
        logits = ref.head(x, {"w_head": _f32(lm_head["kernel"])})
        return (ref.next_token_logprobs(logits, ids),
                jnp.mean(jnp.std(logits, axis=-1)))

    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    x = _f32(trunk["embed"]["embedding"][ids])
    lams = []
    for _ in range(int(shape["total_ut_steps"])):
        for i in range(n_layers):
            x = step(x, layer_tree(trunk, i), positions)
        x, lam = end_of_pass(x, trunk["final_norm"], trunk["exit_gate"])
        lams.append(lam)
    logprobs, spread = finish(x, trunk["lm_head"], ids)
    return {"logprobs": np.asarray(logprobs), "hidden": x,
            "masses": np.asarray(ref.exit_masses(lams)),
            "sigma_z": float(spread)}


def relative_error(chk, visits: int) -> float:
    """The hidden state's RMS error over its size under the model."""
    return math.sqrt(visits * ROUNDINGS_OURO * chk.U_BF16 ** 2
                     + 3.0 * chk.U_BF16 ** 2)


def rollout_diffs(ctx, trainer, mesh, params, rs, top: int):
    """|engine - reference| over the tokens that one rollout of the
    timed shape sampled on its first two rows (a full-length and a
    half-length prompt of ids below ``top`` in one right-padded batch)."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    lens = np.where(np.arange(B) % 2 == 0, P, max(P // 2, 2)).astype(
        np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       rs.randint(2, top, (B, P)), 0).astype(np.int32)
    # through the trainer's own call, its parameters swapped for the
    # probe's, so that the inputs are placed as the timed rollout's were
    # and the timed program is the one that runs (a call with host arrays
    # is another signature: 30 s of tracing and lowering for nothing)
    state = trainer.state
    trainer.state = state.replace(params=params) if hasattr(
        state, "replace") else type(state)(params=params)
    try:
        with mesh:
            rollout = trainer.generate(prompts, lens, jax.random.key(
                ctx.lib("harness").seed31(ctx.seed)))
            sampled, n_new, got = (
                np.asarray(x)[:2] for x in jax.device_get(
                    (rollout.sequences, rollout.completion_lens,
                     rollout.policy_logprobs)))
    finally:
        trainer.state = state
    d = []
    for b in range(2):
        n = int(n_new[b])
        # the whole row, one shape for every call: what lies behind
        # prompt + completion is later than every compared token, and
        # attention is causal
        want = reference_outputs(ctx, params, sampled[b])["logprobs"]
        first = int(lens[b]) - 1
        d.append(np.abs(got[b, :n].astype(np.float32)
                        - want[first:first + n]))
    return np.concatenate(d)


def check_trainer(ctx, trainer, mesh) -> dict:
    """Parts (a) to (c) of the module docstring on the trainer's own
    programs, on its parameters under the probe's norm scales."""
    import jax
    import jax.numpy as jnp

    chk = ctx.lib("reference_check")
    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    B = int(job["samples_per_iteration"])
    shape = ctx.config
    vocab = int(shape["vocab_size"])
    passes = int(shape["total_ut_steps"])
    visits = passes * int(shape["num_hidden_layers"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(vocab, trainer.cfg.model.vocab_size)
    params = probe_params(trainer.state.params, rs)
    seqs = rs.randint(2, top, (B, P + T)).astype(np.int32)
    lens = np.full((B,), P, np.int32)
    mask = np.zeros((B, T), np.float32)
    mask[:2] = 1.0            # the counters: means over the compared rows
    # placed as the timed forward's inputs are (the trainer's own helper)
    from orion_tpu.utils.placement import replicated_put

    with mesh:
        lp, _, values, _, counters = trainer._jit_lp_values(
            params, *replicated_put((seqs, lens, mask), params), max_new=T,
            with_entropy=False)
    lp, values, counters = jax.device_get((lp, values, counters))
    lp, values = (np.asarray(x, np.float32)[:2] for x in (lp, values))
    got_masses = [float(counters[k]) for k in sorted(counters)
                  if k.startswith("ut_exit_mass_")]
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    trunk = params.get("backbone", params)
    g = _f32(trunk["final_norm"]["scale"])
    vk = _f32(params["value_head"])[:, 0]
    diffs, vdiffs, spreads, masses = [], [], [], []
    for b in range(2):
        want = reference_outputs(ctx, params, seqs[b])
        diffs.append(np.abs(lp[b, :T] - want["logprobs"][window]))
        with jax.default_matmul_precision("highest"):
            v_want = np.asarray(want["hidden"] @ vk)[window]
        vdiffs.append(np.abs(values[b, :T] - v_want))
        spreads.append(want["sigma_z"])
        masses.append(want["masses"][:, window])
    sigma_z = max(spreads)
    rel = relative_error(chk, visits)
    out = chk._verdict(diffs, sigma_z * rel)
    worst_tol = WORST_SIGMAS_OURO * out["predicted_rms"]
    a_ok = bool(out["tokens"] and np.isfinite(out["max_abs_diff"])
                and out["max_abs_diff"] <= worst_tol
                and out["mean_abs_diff"] <= out["mean_tolerance"])
    # the values: a projection of H_last (entries of the norm's scale)
    v = np.concatenate(vdiffs)
    v_tol = VALUE_SLACK * math.sqrt(2.0 / math.pi) * rel * float(
        jnp.sqrt(jnp.sum((g * vk) ** 2)))
    v_ok = bool(np.isfinite(v).all() and float(np.mean(v)) <= v_tol)
    # (c) the masses' means, pass by pass
    want_masses = np.mean(np.concatenate(masses, axis=1), axis=1)
    w_g = _f32(trunk["exit_gate"]["kernel"])[:, 0]
    m_tol = MASS_SLACK * 0.25 * rel * float(jnp.sqrt(jnp.sum((g * w_g) ** 2)))
    if len(got_masses) == passes:
        m_diff = float(np.max(np.abs(np.asarray(got_masses) - want_masses)))
    else:
        m_diff = float("inf")
    c_ok = bool(m_diff <= m_tol
                and float(counters.get("ut_passes_per_token", 0.0))
                == float(passes))
    # (b) the rollout
    d = rollout_diffs(ctx, trainer, mesh, params, rs, top)
    limit = DECODE_SLACK * out["mean_tolerance"]
    b_ok = bool(d.size and np.isfinite(d).all() and np.mean(d) <= limit)
    out.update(
        ok=a_ok and v_ok and b_ok and c_ok,
        parts={"a_experience_forward": a_ok, "a_values": v_ok,
               "b_rollout": b_ok, "c_exit_masses": c_ok},
        max_tolerance=worst_tol, sigma_z=sigma_z,
        relative_error_model=rel,
        value_mean_abs_diff=float(np.mean(v)),
        value_max_abs_diff=float(np.max(v)), value_mean_tolerance=v_tol,
        exit_masses=got_masses, exit_masses_reference=[
            float(x) for x in want_masses],
        exit_mass_max_abs_diff=m_diff, exit_mass_tolerance=m_tol,
        passes_per_token=float(counters.get("ut_passes_per_token", 0.0)),
        decode_tokens=int(d.size),
        decode_mean_abs_diff=float(np.mean(d)) if d.size else float("nan"),
        decode_median_abs_diff=float(np.median(d)) if d.size
        else float("nan"),
        decode_max_abs_diff=float(np.max(d)) if d.size else float("nan"),
        decode_mean_tolerance=limit)
    return out
