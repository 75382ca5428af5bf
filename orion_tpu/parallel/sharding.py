"""Logical-axis → mesh-axis sharding rules.

The reference shards its actor FSDP-style via torch FSDP + NCCL
(SURVEY.md §2 #9).  Here sharding is declarative: every parameter is
annotated with *logical* axis names at init time, and these rules map
logical names to mesh axes.  XLA then inserts the all-gathers /
reduce-scatters over ICI — the compiler is the communication backend.

Rules (MaxText/T5X-style):
  embed   — the hidden/model dimension    → fsdp (ZeRO-3 shard axis)
  mlp     — the ffn intermediate dim      → tensor
  heads   — attention heads × head_dim    → tensor
  kv_heads— kv heads (GQA)                → tensor
  vocab   — embedding/unembedding vocab   → tensor
  layers  — scanned layer stack dimension → (replicated)
  latent  — latent attention's compressed KV → (replicated)
  expert  — stacked expert weights         → expert
  conv    — a depthwise convolution's taps → (replicated; its channels
            are ``heads``, as are a KDA layer's projections and
            ``dt_bias``; its low-rank gates run embed → latent → heads;
            a GDN layer's q / k / v / z projections of unequal width
            are ``heads`` too, its per-head a / b projections ``norm``)
  index   — the sparse-attention indexer's heads x dim, its one key
            head and its per-head weights → (replicated: every shard
            scores and selects for all heads of a whole sequence)
  ssm     — a Mamba-2 layer's one input projection (z | x | B | C | dt,
            parts of unequal width), its convolution's channels, gated
            norm and output projection → (replicated: a tensor shard of
            the whole width would cut across the parts; the chip's share
            of the heads is ``ModelConfig.head_share``, not a mesh axis)
  gates   — a gated short convolution's one input projection (b | c | z,
            hidden → 3 x hidden), its taps' channels ([taps, hidden])
            and its output projection's rows → (replicated: a tensor
            shard of the 3 x hidden would cut across the thirds)
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis name -> mesh axis (or None => replicate)
LOGICAL_RULES: dict = {
    "embed": "fsdp",
    "mlp": "tensor",
    "heads": "tensor",
    "kv_heads": "tensor",
    "vocab": "tensor",
    "layers": None,
    "norm": None,
    "latent": None,     # latent attention's 512 / 576: replicated
    "conv": None,       # the 4 taps of a KDA layer's convolutions
    "index": None,      # the sparse-attention indexer's projections
    "ssm": None,        # a Mamba-2 layer's projections, conv and norm
    "gates": None,      # a gated short convolution's b | c | z and taps
    "expert": "expert",
    "batch": ("data", "fsdp"),
    "seq": "seq",
}


def spec_from_logical(logical_axes: tuple, rules: Optional[dict] = None) -> P:
    rules = rules or LOGICAL_RULES
    return P(*(rules.get(name) for name in logical_axes))


def logical_to_sharding(logical_axes: tuple, mesh: Mesh,
                        rules: Optional[dict] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_from_logical(logical_axes, rules))


def param_shardings(abstract_params: Any, logical_axes: Any, mesh: Mesh,
                    rules: Optional[dict] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    ``logical_axes`` mirrors the param tree; leaves are tuples of logical
    names (one per array dim) or None (replicate).
    """
    def one(axes, p):
        if axes is None:
            return NamedSharding(mesh, P())
        return logical_to_sharding(axes, mesh, rules)

    return jax.tree.map(
        one, logical_axes, abstract_params,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and
                                        all(isinstance(e, (str, type(None))) for e in x)))


def shard_params(params: Any, logical_axes: Any, mesh: Mesh,
                 rules: Optional[dict] = None) -> Any:
    """Device_put a host param tree onto the mesh with the given rules."""
    shardings = param_shardings(params, logical_axes, mesh, rules)
    return jax.device_put(params, shardings)


# thread_resources is a private jax API.  The probe is LAZY — resolved
# on the first constrain_seq_activation call — so a jax upgrade that
# moves it breaks only runs that actually enable Megatron-SP, not every
# import of this (near-universal) module (ADVICE r3).  It still fails
# LOUDLY for the feature that needs it: a deployed SP run must not
# silently lose its memory/comm savings with no signal (ADVICE r2).
_MESH_LIB = None


def ambient_mesh():
    """The `with mesh:` context's physical mesh (None/empty outside)."""
    return _ambient_mesh()


def _ambient_mesh():
    global _MESH_LIB
    if _MESH_LIB is None:
        try:
            from jax._src import mesh as mesh_lib

            mesh_lib.thread_resources.env.physical_mesh  # probe
        except (ImportError, AttributeError) as e:  # pragma: no cover
            raise ImportError(
                "orion_tpu.parallel.sharding: jax moved the private "
                "thread_resources API used to resolve the ambient mesh "
                "for Megatron-SP activation sharding; update "
                "constrain_seq_activation for this jax version") from e
        _MESH_LIB = mesh_lib
    return _MESH_LIB.thread_resources.env.physical_mesh


def constrain_seq_activation(x):
    """Megatron-style sequence parallelism (SURVEY.md §2 parallelism
    table, row SP): constrain a [B, L, E] residual-stream activation to
    be sharded on L over the TENSOR axis.  With tensor-sharded params,
    GSPMD then places the all-gather before qkv/up projections and the
    reduce-scatter after o/down projections — exactly the AG/RS pattern
    megatron-LM hand-codes — and the norm/residual/dropout region
    between blocks computes (and stores, under remat) only L/tp of the
    activations.

    No-ops (returns x) when there is no ambient mesh, the tensor axis
    is 1, or L is indivisible/degenerate (decode steps) — so it is safe
    to leave in the model unconditionally behind the config flag.
    """
    m = _ambient_mesh()
    if m is None or m.empty:
        return x
    tp = dict(m.shape).get("tensor", 1)
    if tp <= 1 or x.ndim != 3 or x.shape[1] <= 1 or x.shape[1] % tp:
        return x
    batch = tuple(a for a in ("data", "fsdp")
                  if dict(m.shape).get(a, 1) > 1) or None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(m, P(batch, "tensor", None)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, rules: Optional[dict] = None) -> NamedSharding:
    """Sharding for [batch, seq, ...] activations / token arrays."""
    rules = rules or LOGICAL_RULES
    return NamedSharding(mesh, P(rules["batch"]))
