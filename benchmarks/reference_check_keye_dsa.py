"""The comparison with ``reference_keye_dsa`` that decides ``correct`` for
a ``keye_dsa`` configuration (Keye-VL-2.0's language model: grouped-query
attention under a learned selection, over softmax-routed experts; the
chip's share of it).

Shaped like ``reference_check_dsv3.py``: this file knows how the program
lays out its parameters (``layers`` stacked by ``scan_layers`` or
``layers_<i>``; ``attn/{q,k,v,o}_proj, q_norm, k_norm, index_q_proj,
index_k_proj, index_k_norm, index_w_proj``; ``mlp/{router,
experts_gate_up_proj, experts_down_proj}``; ``input_norm``,
``post_attn_norm``) and hands them to the reference as float32, one
layer at a time, the reference's attention in blocks of ``Q_BLOCK``
queries so that no [heads, S, S] array exists at the timed length.

The program makes TWO discrete choices a layer, and a discrete choice
made from bf16 inputs differs from the float32 one wherever two
candidates lie within rounding: the router's 8 of 128 experts (as
Kanana's and Kimi-Linear's: the reference FOLLOWS the program's, sown as
``moe_selected``, in every part below) and the indexer's 2048 keys a
query.  Four parts decide ``correct``; each limit stands beside its
reason.

(a) **Given the program's selections.**  The trainer's own
    ``_jit_logprobs`` on 2 seeded sequences of the timed length (8192:
    the answer's 512 tokens are compared, every one of whose queries has
    four times more valid keys than it may keep) against the reference
    given the selections that one more forward of the same model sowed
    (``selections`` / ``sa_selected``, [keys, queries] int8 a layer;
    that forward's logprobs must equal the timed forward's).  Nothing
    but rounding differs then, and every token is held to
    ``reference_check``'s error model (its form, unit rounding, slack
    and sigmas imported) with this block's roundings a layer,
    ``ROUNDINGS_KEYE``: the pre-norm expert block's 36 of
    ``reference_check_dsv3`` (calibrated there on a bf16 forward of that
    block at the same hidden size, expert width and depth, RMS 0.0151
    read against 0.0152 predicted), kept because this block rounds in
    the same places but the key/value path: two per-head norms where
    that one has the latent's norm and up-projection.  A main attention
    or a head computed one precision lower (fp8: 16 times the rounding)
    is far outside it; tests/bench plants both.
(b) **Against the reference's own selections** (experts still
    followed).  Where the 2048th and the 2049th score of a query lie
    closer than the indexer's bf16 inputs move them, the two pick
    different keys: ``selection_overlap`` = shared / kept, over the
    compared rows and all layers, must be at least
    ``SELECTION_OVERLAP_MIN``, and the mean difference within
    ``OWN_SELECTION_SLACK`` of (a)'s mean limit.  Both limits are set
    from the builder's bf16 runs on the chip, between what those read
    and what a wrong selection reads (PERF.md section 6, PR 40, has the
    readings): a key within rounding of the 2048th score changes sides,
    and swapping one of 2048 near-uniformly weighted keys moves an
    output by about 1/2048 of a value's size, so the mean moves little;
    a selection that ignores the scores overlaps by topk / valid keys,
    a quarter here.  The float32 CPU tests need overlap 1.0.
(c) **Decode through the cache.**  One rollout of the timed shape by the
    trainer's engine (a full-length and a 6144-token prompt in one
    right-padded batch): prefill through ``sparse_fwd`` over the cache,
    then one-token steps that select and gather; the engine's policy
    logprobs of what it sampled on the first two rows against the
    reference's teacher-forced ones, the reference following the
    selections of a training forward over the same sequences (the engine
    sows none: a step that selected other keys or experts than the full
    forward shows as a difference), and against that training forward
    itself.  Mean alone, within ``DECODE_SLACK`` of (a)'s mean limit, as
    the other expert cells'.
(d) **A wrong selection must fail.**  The reference given the LAST 2048
    keys of every query (a sliding window) in place of the indexer's
    must differ from the program by more than (a)'s mean limit, and
    (a)'s ``mean_abs_diff`` must lie under that control's: the check
    can tell the selection from one that ignores the indexer.
"""

from __future__ import annotations

import math

import numpy as np

ROUNDINGS_KEYE = 36
# (b): set from the chip's bf16 readings, see the module docstring
SELECTION_OVERLAP_MIN = 0.9
OWN_SELECTION_SLACK = 2.0
# the rollout's tokens are sampled and its selections not followed (c)
DECODE_SLACK = 2.5
# two programs compiled from one model may differ in the last bit
SAME_FORWARD = 1e-3
UNFOLLOWED_MAX_SHARE = 0.01
# queries a block of the reference's attention at the timed length
Q_BLOCK = 256

def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layer_weights(p: dict) -> dict:
    """One layer of the program's tree as the reference takes it."""
    a, m = p["attn"], p["mlp"]
    w = {"n1_g": _f32(p["input_norm"]["scale"]),
         "n2_g": _f32(p["post_attn_norm"]["scale"]),
         "q_g": _f32(a["q_norm"]["scale"]), "k_g": _f32(a["k_norm"]["scale"]),
         "wiq": _f32(a["index_q_proj"]["kernel"]),
         "wik": _f32(a["index_k_proj"]["kernel"]),
         "wiw": _f32(a["index_w_proj"]["kernel"]),
         "ik_g": _f32(a["index_k_norm"]["scale"]),
         "ik_b": _f32(a["index_k_norm"]["bias"]),
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


def reference_logprobs(ctx, params: dict, ids: np.ndarray, selections=None,
                       experts=None, n_real=None, last_rows: int = 0):
    """Teacher-forced next-token logprobs of ``ids`` [S] under the
    reference, given the program's parameter tree: [S-1] float32.
    ``selections``: None (the reference's own), ``"window"`` (part (d)'s
    control) or [layers, keys, queries] int8 as the program sows them;
    ``experts`` [layers, S, k]: the experts to follow; ``n_real``: the
    positions from there on hold no token.  ``last_rows`` > 0: also
    {"sigma_z", "selection": per layer [last_rows, S] bool, the keys the
    sequence's last queries attended to, "experts" [layers, S, k] the
    router's own}."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference_keye_dsa")
    shape = ctx.config
    held = held_of(shape)
    params = params.get("backbone", params)
    n_layers = int(shape["num_hidden_layers"])
    S = int(ids.shape[0])
    q_block = Q_BLOCK if S > 2 * Q_BLOCK else None
    rows = int(last_rows)

    def step(x, p, positions, mask, sel, exp):
        if sel is not None and sel.dtype == jnp.int8:
            sel = sel.T != 0                   # [keys, queries] as sown
        y, inf = ref.layer(x, layer_weights(p), positions, shape, held,
                           mask, sel, exp, q_block, info=True)
        return y, {"selection": inf["selection"][S - rows:],
                   "experts": inf["experts"]}

    step = jax.jit(step)

    @jax.jit
    def finish(x, final_norm, lm_head, ids):
        logits = ref.head(x, {"nf_g": _f32(final_norm["scale"]),
                              "w_head": _f32(lm_head["kernel"])}, shape)
        return (ref.next_token_logprobs(logits, ids),
                jnp.mean(jnp.std(logits, axis=-1)))

    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(S)
    mask = None if n_real is None else positions < int(n_real)
    window = ref.window_selection(positions, ref.sa_sizes(shape)[2]) \
        if isinstance(selections, str) else None
    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    infos = []
    for i in range(n_layers):
        sel = window if window is not None else (
            None if selections is None else selections[i])
        x, inf = step(x, layer_tree(params, i), positions, mask, sel,
                      None if experts is None
                      else jnp.asarray(experts[i], jnp.int32))
        if rows:
            infos.append(jax.tree.map(np.asarray, inf))
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    if not rows:
        return logprobs
    return logprobs, {"sigma_z": float(spread),
                      "selection": [inf["selection"] for inf in infos],
                      "experts": np.stack([inf["experts"] for inf in infos])}


def predicted_rms(chk, sigma_z: float, layers: int) -> float:
    """``reference_check.predicted_rms`` with this block's roundings a
    layer."""
    return sigma_z * math.sqrt(layers * ROUNDINGS_KEYE * chk.U_BF16 ** 2
                               + 3.0 * chk.U_BF16 ** 2)


def sown(tree, name: str, rank: int = 3):
    """The arrays of ``rank`` dimensions a model's layers sowed under
    ``name``, stacked over the layers: scanned, one [layers, ...];
    unrolled, one a layer."""
    import jax
    import jax.numpy as jnp

    found = [x for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
             if any(getattr(k, "key", None) == name for k in path)]
    if not found:
        return None
    return jnp.concatenate([x.reshape((-1,) + x.shape[-rank:])
                            for x in found])


def routed_forward(trainer, T: int):
    """``BaseTrainer._logprobs_fn`` with what the layers sow kept:
    jitted (params, sequences, prompt_lens) -> (logprobs [B, T], experts
    [layers, B, S, k], selections [layers, B, keys, queries] int8 or
    None where no call had more keys than ``topk``)."""
    import jax
    import jax.numpy as jnp

    def routed(params, sequences, prompt_lens):
        from orion_tpu.ops.logprobs import (completion_window_positions,
                                            windowed_completion_logprobs)

        L = sequences.shape[1]
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                     sequences.shape)
        out, kept = trainer.model.apply(
            {"params": params}, sequences, positions,
            logits_positions=completion_window_positions(prompt_lens, T, L),
            token_mask=positions < (prompt_lens + T)[:, None],
            mutable=["intermediates", "selections"])
        return (windowed_completion_logprobs(out[0], sequences, prompt_lens,
                                             T),
                sown(kept, "moe_selected"), sown(kept, "sa_selected"))

    return jax.jit(routed)


def overlap(theirs, own) -> float:
    """shared / kept over the rows compared: ``theirs`` [layers, keys,
    queries] int8 (the program's), ``own`` per layer [rows, keys] bool
    (the reference's, the LAST rows of the sequence)."""
    shared = kept = 0
    for sel, mine in zip(theirs, own):
        rows = mine.shape[0]
        prog = np.asarray(sel[:, sel.shape[1] - rows:]).T != 0
        shared += int(np.sum(prog & mine))
        kept += int(np.sum(mine))
    return shared / max(kept, 1)


def rollout_diffs(ctx, trainer, mesh, routed, params, rs, top: int):
    """Part (c): |engine - reference| over the tokens that one rollout of
    the timed shape sampled on its first two rows (a full-length prompt
    and one of four fifths of it, ids below ``top``), and |engine - the
    training forward| on the same tokens."""
    import jax

    job = ctx.traffic
    P, B = int(job["prompt_len"]), int(job["samples_per_iteration"])
    T = int(job["new_tokens"])
    lens = np.where(np.arange(B) % 2 == 0, P, max(4 * P // 5, 2)).astype(
        np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       rs.randint(2, top, (B, P)), 0).astype(np.int32)
    with mesh:
        rollout = trainer.generate(prompts, lens, jax.random.key(
            ctx.lib("harness").seed31(ctx.seed)))
        sampled, n_new, got = (np.asarray(x)[:2] for x in jax.device_get(
            (rollout.sequences, rollout.completion_lens,
             rollout.policy_logprobs)))
        forward, experts, sels = routed(trainer.state.params, sampled,
                                        lens[:2])
    forward, experts = (np.asarray(x) for x in
                        jax.device_get((forward, experts)))
    d, own = [], []
    for b in range(2):
        n = int(n_new[b])
        want = reference_logprobs(
            ctx, params, sampled[b],
            None if sels is None else sels[:, b], experts[:, b],
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
    k = int(ctx.config["num_experts_per_tok"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    top = min(vocab, trainer.cfg.model.vocab_size)
    seqs = rs.randint(2, top, (2, P + T)).astype(np.int32)
    lens = np.full((2,), P, np.int32)
    routed = routed_forward(trainer, T)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
        lp_again, experts, sels = routed(trainer.state.params, seqs, lens)
    lp, lp_again, experts = (np.asarray(x, np.float32 if i < 2 else None)
                             for i, x in enumerate(
                                 jax.device_get((lp, lp_again, experts))))
    if experts.shape[-1] != k:
        return dict(chk._verdict([], 0.0), ok=False,
                    why=f"the program selects {experts.shape[-1]} experts "
                        f"a token, the configuration {k}")
    if sels is None:
        return dict(chk._verdict([], 0.0), ok=False,
                    why="the program sowed no selection: no query of the "
                        "compared sequences had more keys than topk")
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    window = slice(P - 1, P - 1 + T)     # token t's logprob: hidden t - 1
    given, own, control, followed, spreads, overlaps = [], [], [], [], [], []
    for b in range(2):
        got = lp[b, :T]
        want = reference_logprobs(ctx, params, seqs[b], sels[:, b],
                                  experts[:, b])
        given.append(np.abs(got - want[window]))
        want, inf = reference_logprobs(ctx, params, seqs[b], None,
                                       experts[:, b], last_rows=T + 1)
        own.append(np.abs(got - want[window]))
        spreads.append(inf["sigma_z"])
        # the rows whose outputs the compared logprobs read: the last T + 1
        overlaps.append(overlap(jax.device_get(
            sels[:, b, :, P - 1:]), inf["selection"]))
        control.append(np.abs(got - reference_logprobs(
            ctx, params, seqs[b], "window", experts[:, b])[window]))
        followed.append(np.abs(got - lp_again[b, :T]) <= SAME_FORWARD)
    keep = np.concatenate(followed)
    sigma_z = max(spreads)
    out = chk._verdict([np.concatenate(given)[keep]],
                       predicted_rms(chk, sigma_z, n_layers))
    unfollowed = float(np.mean(~keep))
    own_mean, window_mean = (float(np.mean(np.concatenate(x)[keep]))
                             for x in (own, control))
    selection_overlap = float(np.mean(overlaps))
    own_limit = OWN_SELECTION_SLACK * out["mean_tolerance"]
    d, vs_forward = rollout_diffs(ctx, trainer, mesh, routed, params, rs, top)
    decode_limit = DECODE_SLACK * out["mean_tolerance"]
    parts = {
        "a_given_selections": bool(out["ok"]
                                   and unfollowed <= UNFOLLOWED_MAX_SHARE),
        "b_own_selections": bool(own_mean <= own_limit and selection_overlap
                                 >= SELECTION_OVERLAP_MIN),
        "c_decode": bool(d.size and np.isfinite(d).all()
                         and np.mean(d) <= decode_limit),
        "d_wrong_selection_fails": bool(
            window_mean > out["mean_tolerance"]
            and out["mean_abs_diff"] < window_mean),
    }
    out.update(
        ok=all(parts.values()), parts=parts, sigma_z=sigma_z,
        unfollowed_share=unfollowed,
        own_selection_mean_abs_diff=own_mean,
        own_selection_mean_tolerance=own_limit,
        selection_overlap=selection_overlap,
        selection_overlap_limit=SELECTION_OVERLAP_MIN,
        window_selection_mean_abs_diff=window_mean,
        decode_tokens=int(d.size),
        decode_mean_abs_diff=float(np.mean(d)),
        decode_median_abs_diff=float(np.median(d)),
        decode_max_abs_diff=float(np.max(d)),
        decode_mean_tolerance=decode_limit,
        decode_vs_forward_mean_abs_diff=float(np.mean(vs_forward)),
        decode_vs_forward_median_abs_diff=float(np.median(vs_forward)))
    return out
