"""Online-DPO trainer (SPEC config 3): sample a pair per prompt, rank
with the reward source, DPO loss on (chosen, rejected) — no critic
(SURVEY.md §2 #2).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.algos import dpo_loss
from orion_tpu.config import OnlineDPOConfig
from orion_tpu.trainers.base import BaseTrainer


class OnlineDPOTrainer(BaseTrainer):
    cfg: OnlineDPOConfig

    def build_experience(self, result, scores, host=None):
        assert self.cfg.group_size == 2, "online DPO samples pairs"
        scores = np.asarray(scores)  # [2N]
        host = host or result
        T = result.completions.shape[1]
        ref_lp, _ = self._jit_logprobs(
            self.ref_params, result.sequences, result.prompt_lens, max_new=T,
            **self._trace_kw(result))
        # one scalar-array fetch (ref logprobs live on device)
        ref_seq_lp = jax.device_get(
            jnp.sum(ref_lp * result.completion_mask, axis=1))

        # rank within each consecutive pair; tied pairs get weight 0
        # (their chosen/rejected split would be arbitrary noise)
        pair_scores = scores.reshape(-1, 2)
        chosen_col = np.argmax(pair_scores, axis=1)  # [N] in {0,1}
        pair_weight = (pair_scores[:, 0] != pair_scores[:, 1]).astype(
            np.float32)
        n = len(chosen_col)
        rows = np.arange(n) * 2
        c_idx = rows + chosen_col
        r_idx = rows + (1 - chosen_col)

        # Pair gathers run on the (already fetched) host copy; the
        # experience tree crosses back host→device at the update jit.
        seqs = np.asarray(host.sequences)
        mask = np.asarray(host.completion_mask)
        lens = np.asarray(host.prompt_lens)
        experience = {
            "chosen_sequences": jnp.asarray(seqs[c_idx]),
            "rejected_sequences": jnp.asarray(seqs[r_idx]),
            "chosen_mask": jnp.asarray(mask[c_idx]),
            "rejected_mask": jnp.asarray(mask[r_idx]),
            "prompt_lens": jnp.asarray(lens[c_idx]),
            "rejected_prompt_lens": jnp.asarray(lens[r_idx]),
            "ref_chosen_lp": jnp.asarray(ref_seq_lp[c_idx]),
            "ref_rejected_lp": jnp.asarray(ref_seq_lp[r_idx]),
            "pair_weight": jnp.asarray(pair_weight),
        }
        if host.reveal_step is not None:
            # a block-diffusion pair's sampling traces
            steps = np.asarray(host.reveal_step)
            experience.update(
                chosen_reveal_step=jnp.asarray(steps[c_idx]),
                rejected_reveal_step=jnp.asarray(steps[r_idx]))
        stats = {
            "reward_mean": float(scores.mean()),
            "reward_margin": float(
                np.abs(pair_scores[:, 0] - pair_scores[:, 1]).mean()),
            "completion_len_mean": float(
                np.asarray(host.completion_lens).mean()),
        }
        return experience, stats

    def loss_fn(self, params, mb: Dict[str, jnp.ndarray]):
        T = mb["chosen_mask"].shape[1]
        c_lp, (_, c_aux, c_moe) = self._logprobs_fn(
            params, mb["chosen_sequences"], mb["prompt_lens"], max_new=T,
            **self._trace_kw(mb, "chosen_reveal_step"))
        r_lp, (_, r_aux, _) = self._logprobs_fn(
            params, mb["rejected_sequences"], mb["rejected_prompt_lens"],
            max_new=T, **self._trace_kw(mb, "rejected_reveal_step"))
        c_seq = jnp.sum(c_lp * mb["chosen_mask"], axis=1)
        r_seq = jnp.sum(r_lp * mb["rejected_mask"], axis=1)
        loss, stats = dpo_loss(
            c_seq, r_seq, mb["ref_chosen_lp"], mb["ref_rejected_lp"],
            self.cfg.beta, self.cfg.label_smoothing,
            pair_weight=mb["pair_weight"])
        loss = loss + self.cfg.model.router_aux_coef * (c_aux + r_aux)
        # the expert layer's counters: the chosen forward's
        return loss, {**stats, **c_moe}
