"""RLOO trainer (SPEC config 3): k rollouts per prompt, leave-one-out
baseline, REINFORCE on sequence logprobs — no critic (SURVEY.md §2 #3).

KL lands inside the sequence-level reward by default (kl_in_reward),
the standard RLOO formulation: R_i = score_i - β·KL_seq_i.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.algos import kl_penalty, masked_mean, rloo_advantages
from orion_tpu.config import RLOOConfig
from orion_tpu.trainers.base import BaseTrainer


class RLOOTrainer(BaseTrainer):
    cfg: RLOOConfig

    def build_experience(self, result, scores, host=None):
        k = self.cfg.group_size
        T = result.completions.shape[1]
        mask = result.completion_mask
        old_lp = self.behavior_logprobs(result)
        ref_lp, _ = self._jit_logprobs(
            self.ref_params, result.sequences, result.prompt_lens, max_new=T,
            **self._trace_kw(result))

        kl_seq = jnp.sum(kl_penalty(old_lp, ref_lp, "k1") * mask, axis=1)
        adjusted = jnp.asarray(scores) - (self.cfg.kl_coef * kl_seq
                                          if self.cfg.kl_in_reward else 0.0)
        adv = rloo_advantages(adjusted, k)

        experience = {
            "sequences": result.sequences,
            "prompt_lens": result.prompt_lens,
            "mask": mask,
            "old_logprobs": old_lp * mask,
            "advantages": adv,  # [B] sequence-level
            **self._trace_kw(result),
        }
        lens = (host or result).completion_lens
        kl_mean = jnp.mean(kl_seq)
        stats = {
            "reward_mean": float(np.mean(scores)),
            # device scalar under the deferred pipeline (the sync train
            # loop fetches it with the next generation); one scalar
            # fetch otherwise (async path).
            "kl": kl_mean if self._defer_stats
            else float(jax.device_get(kl_mean)),
            "completion_len_mean": float(np.mean(np.asarray(lens))),
        }
        return experience, stats

    def loss_fn(self, params, mb: Dict[str, jnp.ndarray]):
        T = mb["mask"].shape[1]
        lp, (ent, aux, moe) = self._logprobs_fn(
            params, mb["sequences"], mb["prompt_lens"], max_new=T,
            **self._trace_kw(mb))
        seq_lp = jnp.sum(lp * mb["mask"], axis=1)
        # REINFORCE on whole-sequence logprob with a stop-grad sequence
        # importance ratio: exactly 1 on the first epoch (old_lp comes
        # from the same training graph), and the one-step off-policy
        # correction for num_epochs>1 / async staleness (SURVEY.md §3b).
        old_seq_lp = jnp.sum(mb["old_logprobs"] * mb["mask"], axis=1)
        ratio = jax.lax.stop_gradient(
            jnp.exp(jnp.clip(seq_lp - old_seq_lp, -10.0, 10.0)))
        pg_loss = -jnp.mean(mb["advantages"] * ratio * seq_lp)
        loss = pg_loss + self.cfg.model.router_aux_coef * aux
        stats = {
            "policy_loss": pg_loss,
            "entropy": masked_mean(ent, mb["mask"]),
            "seq_logprob_mean": jnp.mean(seq_lp),
            "ratio_mean": jnp.mean(ratio),
            **moe,
        }
        return loss, stats
