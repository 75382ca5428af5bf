"""orion-tpu: a TPU-native online-RLHF training framework.

Built from scratch on JAX/XLA/Pallas/pjit with the capabilities of the
reference framework (`mnoukhov/orion`, see SURVEY.md): PPO, Online-DPO,
RLOO and GRPO training of language models with

- a JAX paged-KV rollout engine (the vLLM-equivalent) with Pallas
  attention kernels,
- reward-model / critic forward passes as XLA programs,
- FSDP-style actor updates (all-gather + reduce-scatter over ICI) driven
  purely by sharding annotations instead of NCCL calls, and
- asynchronous decoupled rollout/learner workers whose weight-sync
  channel is an ICI reshard of the policy parameters.

NOTE on citations: the reference mount at /root/reference was empty for
every session so far (see SURVEY.md §0), so docstrings cite the
behavioral contract in SURVEY.md / BASELINE.json rather than
reference file:line locations.
"""

# first: set-up is counted from here (obs/compilewatch.py stamps it)
from orion_tpu import obs  # noqa: F401

__version__ = "0.1.0"

from orion_tpu.config import (  # noqa: F401
    ModelConfig,
    MeshConfig,
    OptimizerConfig,
    RolloutConfig,
    TrainConfig,
    PPOConfig,
    GRPOConfig,
    RLOOConfig,
    OnlineDPOConfig,
)
