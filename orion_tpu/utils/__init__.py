from orion_tpu.utils.metrics import MetricsWriter  # noqa: F401


def __getattr__(name):
    # orbax takes seconds to import (10 of launch's 20 s on the CPU
    # box, PR 31): whoever checkpoints pays for it, not every process.
    if name == "CheckpointManager":
        from orion_tpu.utils.checkpoint import CheckpointManager

        return CheckpointManager
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
