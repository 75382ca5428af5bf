"""Process-level platform pinning, the persistent compile cache, and
the two collective-API forwards.

- :func:`force_cpu_platform` pins this process to the CPU backend
  (optionally with N virtual host devices) for the test harness and the
  multichip dryrun.  It must run before the first backend use — jax
  initialises backends lazily, so setting ``JAX_PLATFORMS`` /
  ``XLA_FLAGS`` and the ``jax_platforms`` config value before the first
  ``jax.devices()``/dispatch is enough.
- :func:`enable_compile_cache` is THE place the persistent XLA compile
  cache is configured (launch, serve, bench, chip_smoke, conftest).
- :func:`shard_map` / :func:`axis_size` forward to ``jax.shard_map`` /
  ``lax.axis_size``.  All orion-tpu code routes through these two
  names; ``orion_tpu.analysis`` rule ``compat-import`` enforces it.
"""

from __future__ import annotations

import os
from typing import Optional

# The one fixed in-checkout cache directory (git-ignored).  The path is
# part of the cache key's reach: a directory that moves never hits, so
# no temp name, pid or timestamp ever goes in here.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Pin this process to the CPU platform, optionally forcing
    ``n_devices`` virtual host devices.

    Must run before the first backend initialization (XLA parses
    ``XLA_FLAGS`` and jax reads ``jax_platforms`` exactly once, at the
    first backend use); safe to call multiple times before that.
    """
    if n_devices is not None:
        # Replace any pre-existing device-count flag rather than
        # silently keeping it (a stale count surfaces later as a
        # confusing "need N devices, found M" error).
        flags = os.environ.get("XLA_FLAGS", "")
        kept = [f for f in flags.split()
                if "xla_force_host_platform_device_count" not in f]
        kept.append(f"--xla_force_host_platform_device_count={n_devices}")
        os.environ["XLA_FLAGS"] = " ".join(kept)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def axis_size(axis_name):
    """``lax.axis_size(axis_name)``; call inside shard_map/pmap scope."""
    from jax import lax

    return lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None, check_rep=None):
    """``jax.shard_map`` with the keyword API this repo uses.

    ``axis_names``: the MANUALLY mapped mesh axes (None => all of
    them); the rest stay auto (GSPMD shards them from the arrays' own
    NamedShardings).  ``check_rep`` is the old name of ``check_vma``;
    either spelling is accepted and forwarded as ``check_vma``.
    """
    import jax

    rep = check_vma if check_vma is not None else check_rep
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if rep is not None:
        kw["check_vma"] = bool(rep)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def enable_compile_cache() -> str:
    """Turn on the persistent XLA compile cache and return the
    directory in force.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    NOTHING is set here.  Otherwise the cache goes to the one fixed
    directory inside the checkout (:data:`DEFAULT_COMPILE_CACHE_DIR`),
    so consecutive runs of any entry point share it.  The compile-time
    threshold for an entry stays jax's own (1 s).
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
