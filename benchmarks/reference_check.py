"""The comparison with the plain reference that decides ``correct``.

``reference.py`` knows nothing of the program; this file is the one
place that knows how the program lays out its parameters (a flax tree:
``embed/embedding``, ``layers`` stacked by ``scan_layers`` or
``layers_<i>``, ``attn/{q,k,v,o}_proj``, ``mlp/{up,down}_proj``,
``input_norm``, ``post_attn_norm``, ``final_norm``, ``lm_head``; a
Dense is ``{kernel, bias}`` or, quantised, ``{kernel_q, scale, bias}``)
and hands them to the reference as float32, one layer at a time so
that a 6.9B-width model's float32 copy never has to exist at once.
Quantised kernels are given dequantised (``kernel_q * scale``): the
reference then computes with the engine's own int8 weights and the
tolerance covers bf16 activations and the int8 KV cache, nothing else.

Logprobs are compared, never tokens: with random weights the largest
logit changes on rounding.
"""

from __future__ import annotations

import math

import numpy as np

# How far may a token's logprob lie from the reference's?  The bound is
# worked out from what the program rounds, not fitted to what it read.
#
# The program computes in bfloat16 from the same weights the reference
# is given.  One rounding to bfloat16 (8 significant bits, to nearest)
# is off by at most 2**-9 of the value, uniformly: U_BF16 RMS.  A layer
# rounds in some 14 places (each LayerNorm's output; the weights and the
# output of each of four matrix products; the attention's probabilities
# and output; the GELU; the residual sum), sums inside a product being
# kept in float32.  Each place's error counts by its branch's share of
# the residual stream, which is under one, so the EFFECTIVE number of
# full-size roundings a layer adds is smaller: ROUNDINGS = 6 is the one
# calibrated constant here, set so that the model reproduces a bfloat16
# forward of the program's own Transformer on the CPU at the 1B widths
# (RMS 0.0104 read, 0.0100 predicted, 383 tokens), and then held for
# every configuration, cell and check.  Independent roundings add in
# squares, every layer adds a term of the stream's own size, and the
# final LayerNorm keeps the relative error, so the hidden state the head
# sees is off by sqrt(layers * ROUNDINGS + 3) * U_BF16 of its size (3:
# the embedding, the final LayerNorm's output, the logit itself), and a
# logit by that times the logits' spread SIGMA_Z (their standard
# deviation over the vocabulary, taken from the reference's own logits
# in every check).  The logsumexp averages over the vocabulary and adds
# nothing to speak of, so a logprob's RMS error is the logit's.  The
# model is for real depths: at 2 layers of 64 it reads twofold low.
#
# An int8 KV cache (one absmax scale per cached vector: the step is
# max/127, the error uniform within half a step, max/RMS about 3 for a
# head's 128-256 features) is off by Q_INT8 of the vector's size.  With
# seeded random weights attention is close to uniform over the context,
# so the error of the averaged values, and of the scores, falls with
# the square root of the keys attended: at least the prompt's length.
#
# A check passes when the MEAN absolute difference is within SLACK of
# the model's (sqrt(2/pi) of the RMS, for a Gaussian) and the WORST
# within WORST_SIGMAS of the RMS.  The mean is the sharp test: it moves
# with any systematic fault (a wrong scale, a dropped bias, fewer
# mantissa bits: fp8 would be off 16-fold).  The worst catches a fault
# in few tokens (a wrong page: an O(1) change of the hidden state there,
# tens of sigmas).  Predicted from this model BEFORE the readings were
# set beside it (PERF.md section 6): at the 1B widths (16 layers,
# SIGMA_Z about 0.9) RMS 0.0101, so mean <= 0.0121 and worst <= 0.061.
U_BF16 = 2.0 ** -9 / math.sqrt(3.0)
ROUNDINGS = 6
Q_INT8 = 3.0 / (127.0 * math.sqrt(12.0))
SLACK = 1.5
WORST_SIGMAS = 6.0


def predicted_rms(sigma_z: float, layers: int, int8_kv_context: int = 0
                  ) -> float:
    """RMS error of a per-token logprob under the model above.
    ``int8_kv_context``: the fewest keys a compared token attends to
    through an int8 cache; 0 where there is no such cache."""
    per_layer = ROUNDINGS * U_BF16 ** 2
    if int8_kv_context:
        per_layer += 2.0 * Q_INT8 ** 2 / int8_kv_context     # keys, values
    return sigma_z * math.sqrt(layers * per_layer + 3.0 * U_BF16 ** 2)


def _f32(dense: dict):
    """A Dense's kernel as float32 [in, out], dequantised if int8."""
    import jax.numpy as jnp

    if "kernel_q" in dense:
        return dense["kernel_q"].astype(jnp.float32) * \
            dense["scale"].astype(jnp.float32)[None, :]
    return dense["kernel"].astype(jnp.float32)


def _bias(dense: dict, n: int):
    import jax.numpy as jnp

    if "bias" in dense:
        return dense["bias"].astype(jnp.float32)
    return jnp.zeros((n,), jnp.float32)


def _layer_weights(p: dict) -> dict:
    import jax.numpy as jnp

    a, m = p["attn"], p["mlp"]
    wq, wk, wv, wo = (_f32(a[k]) for k in
                      ("q_proj", "k_proj", "v_proj", "o_proj"))
    w_in, w_out = _f32(m["up_proj"]), _f32(m["down_proj"])
    f = lambda x: x.astype(jnp.float32)  # noqa: E731
    return {
        "wq": wq, "bq": _bias(a["q_proj"], wq.shape[1]),
        "wk": wk, "bk": _bias(a["k_proj"], wk.shape[1]),
        "wv": wv, "bv": _bias(a["v_proj"], wv.shape[1]),
        "wo": wo, "bo": _bias(a["o_proj"], wo.shape[1]),
        "w_in": w_in, "b_in": _bias(m["up_proj"], w_in.shape[1]),
        "w_out": w_out, "b_out": _bias(m["down_proj"], w_out.shape[1]),
        "ln1_g": f(p["input_norm"]["scale"]),
        "ln1_b": f(p["input_norm"]["bias"]),
        "ln2_g": f(p["post_attn_norm"]["scale"]),
        "ln2_b": f(p["post_attn_norm"]["bias"]),
    }


def reference_logprobs(ctx, params: dict, ids: np.ndarray,
                       with_spread: bool = False):
    """Teacher-forced next-token logprobs of ``ids`` [L] under the
    reference, given the program's parameter tree: [L-1] float32.
    ``with_spread``: also the logits' standard deviation over the
    vocabulary, mean over positions (the error model's SIGMA_Z)."""
    import jax
    import jax.numpy as jnp

    ref = ctx.lib("reference")
    shape = ctx.config
    params = params.get("backbone", params)
    n_layers = int(ctx.config["num_hidden_layers"])

    def layer_tree(i):
        if "layers" in params:
            return jax.tree.map(lambda x: x[i], params["layers"])
        return params[f"layers_{i}"]

    @jax.jit
    def step(x, p, positions):
        return ref.layer(x, _layer_weights(p), positions, shape)

    @jax.jit
    def finish(x, final_norm, lm_head, ids):
        w = {"lnf_g": final_norm["scale"].astype(jnp.float32),
             "lnf_b": final_norm["bias"].astype(jnp.float32),
             "w_head": _f32(lm_head)}
        logits = ref.head(x, w, shape)
        return (ref.next_token_logprobs(logits, ids),
                jnp.mean(jnp.std(logits, axis=-1)))

    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    x = ref.embed(ids, {"embed": params["embed"]["embedding"]})
    for i in range(n_layers):
        x = step(x, layer_tree(i), positions)
    logprobs, spread = finish(x, params["final_norm"], params["lm_head"], ids)
    logprobs = np.asarray(logprobs)
    return (logprobs, float(spread)) if with_spread else logprobs


def _verdict(diffs: list, rms: float) -> dict:
    d = np.concatenate(diffs) if diffs else np.zeros((0,), np.float32)
    worst = float(np.max(d)) if d.size else float("nan")
    mean = float(np.mean(d)) if d.size else float("nan")
    mean_tol = SLACK * math.sqrt(2.0 / math.pi) * rms
    worst_tol = WORST_SIGMAS * rms
    ok = bool(d.size and np.isfinite(d).all() and worst <= worst_tol
              and mean <= mean_tol)
    return {"ok": ok, "max_abs_diff": worst, "mean_abs_diff": mean,
            "predicted_rms": rms, "mean_tolerance": mean_tol,
            "max_tolerance": worst_tol, "tokens": int(d.size)}


def check_trainer(ctx, trainer, mesh) -> dict:
    """The policy's per-token completion logprobs from the trainer's own
    forward (``_jit_logprobs``: the training graph) against the
    reference on the same parameters, on 2 seeded sequences."""
    import jax

    job = ctx.traffic
    P, T = int(job["prompt_len"]), int(job["new_tokens"])
    vocab = int(ctx.config["vocab_size"])
    rs = np.random.RandomState(ctx.lib("harness").seed31(ctx.seed))
    seqs = rs.randint(2, min(vocab, trainer.cfg.model.vocab_size),
                      (2, P + T)).astype(np.int32)
    lens = np.full((2,), P, np.int32)
    with mesh:
        lp, _ = trainer._jit_logprobs(trainer.state.params, seqs, lens,
                                      max_new=T)
    lp = np.asarray(jax.device_get(lp), np.float32)
    params = jax.device_get(trainer.state.params) \
        if ctx.cell["chips"] > 1 else trainer.state.params
    diffs, spreads = [], []
    for b in range(2):
        want, spread = reference_logprobs(ctx, params, seqs[b], True)
        diffs.append(np.abs(lp[b, :T] - want[P - 1:P - 1 + T]))
        spreads.append(spread)
    layers = int(ctx.config["num_hidden_layers"])
    return dict(_verdict(diffs, predicted_rms(max(spreads), layers)),
                sigma_z=max(spreads))


def check_served(ctx, params: dict, records: list, int8_kv: bool) -> dict:
    """The sampling logprobs the completed records carried through the
    gateway against the reference's teacher-forced logprobs over prompt
    + completion.  ``records``: [(prompt_ids, tokens, logprobs)]."""
    diffs, spreads = [], []
    for prompt, tokens, logprobs in records:
        ids = np.concatenate([prompt, tokens]).astype(np.int32)
        want, spread = reference_logprobs(ctx, params, ids, True)
        diffs.append(np.abs(np.asarray(logprobs, np.float32)
                            - want[len(prompt) - 1:]))
        spreads.append(spread)
    if not records:
        return _verdict([], 0.0)
    layers = int(ctx.config["num_hidden_layers"])
    context = min(len(p) for p, _, _ in records) if int8_kv else 0
    return dict(_verdict(diffs, predicted_rms(max(spreads), layers, context)),
                sigma_z=max(spreads))
