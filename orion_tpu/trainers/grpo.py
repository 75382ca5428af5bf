"""GRPO trainer (SPEC config 5): group-relative advantages, rule-based
rewards, no critic, no reward model (SURVEY.md §2 #4, §3d).

Pipeline per iteration: repeat each prompt ``group_size`` times →
generate → host-side verifier scores → group-normalized advantages →
clipped-ratio policy update with explicit KL(policy ‖ ref) penalty in
the loss (k3 estimator).
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from orion_tpu.algos import (grpo_advantages, kl_penalty, masked_mean,
                             ppo_policy_loss)
from orion_tpu.config import GRPOConfig
from orion_tpu.trainers.base import BaseTrainer


class GRPOTrainer(BaseTrainer):
    cfg: GRPOConfig

    def build_experience(self, result, scores, host=None):
        k = self.cfg.group_size
        T = result.completions.shape[1]
        # Sync: old logprobs recomputed under the *training* graph so the
        # clipped ratio is exactly 1 on the first epoch; async: the stale
        # behavior policy's logprobs (see BaseTrainer.behavior_logprobs).
        old_lp = self.behavior_logprobs(result)
        ref_lp, _ = self._jit_logprobs(
            self.ref_params, result.sequences, result.prompt_lens, max_new=T,
            **self._trace_kw(result))

        adv_seq = grpo_advantages(
            jnp.asarray(scores), k,
            normalize_std=(self.cfg.variant == "grpo"))
        experience = {
            "sequences": result.sequences,
            "prompt_lens": result.prompt_lens,
            "mask": result.completion_mask,
            "old_logprobs": old_lp * result.completion_mask,
            # ref_logprobs stay unmasked: the k3 estimator exponentiates
            # (ref - lp), and a zeroed ref at pad positions would
            # overflow exp() before the mask can zero the product.
            "ref_logprobs": ref_lp,
            "advantages": adv_seq[:, None] * result.completion_mask,
            **self._trace_kw(result),
        }
        lens = (host or result).completion_lens
        stats = {  # host-side: no device fetches
            "reward_mean": float(np.mean(scores)),
            "reward_std": float(np.std(scores)),
            "completion_len_mean": float(np.mean(np.asarray(lens))),
        }
        return experience, stats

    def loss_fn(self, params, mb: Dict[str, jnp.ndarray]):
        T = mb["mask"].shape[1]
        lp, (ent, aux, moe) = self._logprobs_fn(
            params, mb["sequences"], mb["prompt_lens"], max_new=T,
            **self._trace_kw(mb))
        pg_loss, stats = ppo_policy_loss(
            lp, mb["old_logprobs"], mb["advantages"], mb["mask"],
            self.cfg.clip_ratio)
        kl = kl_penalty(lp, mb["ref_logprobs"], "k3") * mb["mask"]
        kl_mean = masked_mean(kl, mb["mask"])
        loss = pg_loss + self.cfg.kl_coef * kl_mean \
            + self.cfg.model.router_aux_coef * aux
        stats = {**stats, **moe}
        stats["kl"] = kl_mean
        stats["entropy"] = masked_mean(ent, mb["mask"])
        return loss, stats
