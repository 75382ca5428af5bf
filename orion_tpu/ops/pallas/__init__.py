"""Pallas TPU kernels — the native-code layer (SURVEY.md §2 #13).

These are the TPU-native equivalents of the reference stack's CUDA
kernels: flash attention (fwd/bwd) for training and paged/ragged decode
attention for the rollout engine.  On non-TPU backends (the CPU test
harness) every kernel runs in Pallas interpret mode, so the whole suite
is testable without hardware.
"""

from __future__ import annotations

import jax

NEG_INF = -1e30


def target_platform() -> str:
    """Platform the current trace will execute on.

    An active mesh context wins over the default backend — a CPU
    fake-device mesh must compile kernels for CPU whatever the default
    backend is, and vice versa.  Both spellings are seen: the legacy
    ``with mesh:`` (thread resources) and ``jax.set_mesh`` (the concrete
    mesh context; ``jax.sharding.get_mesh`` refuses to answer inside a
    trace, which is exactly where this runs).  Then a
    ``jax.default_device`` pin, then the default backend.

    ``jax._src.mesh`` is private: if it moves, this raises — it must
    never fall through to the default backend silently, because this one
    answer decides interpreted-vs-compiled for every kernel.
    """
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    if m.empty:
        m = mesh_lib.get_concrete_mesh()
    if not m.empty:
        return m.devices.flat[0].platform
    dev = jax.config.jax_default_device
    if dev is not None:
        # a Device, or a platform name (``jax.default_device("cpu")``)
        return dev if isinstance(dev, str) else dev.platform
    return jax.default_backend()


def interpret_mode() -> bool:
    """Run kernels interpreted off-TPU (CPU test harness)."""
    return target_platform() != "tpu"


def named_pallas_call(name: str, kernel, **kw):
    """``pl.pallas_call`` under a name that reaches the device trace.

    The profiler's device event is named after the HLO instruction, and
    this jax names a custom call's instruction after the innermost
    entry of the name stack (``%attn.42`` when the call sat directly
    under the model's ``attn`` module), not after the kernel.  So the
    call runs under ``jax.named_scope(name)`` — the instruction becomes
    ``%<name>.<n>`` — and carries ``name=`` too (the ``kernel_name``
    attribute of the ``tpu_custom_call``, which Mosaic's own dumps
    use).  Names only: numerics, grid and block shapes are untouched.
    """
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, name=name, **kw)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return run
