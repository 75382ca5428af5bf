"""PPO trainer (SPEC configs 1-2): clipped policy loss + clipped value
loss, GAE advantages, per-token KL-shaped rewards, adaptive KL
controller (SURVEY.md §2 #1, §3a).

The critic is a separate ScalarHeadModel with its own TrainState; policy
and critic update in one jitted step (two backward passes, one XLA
program — the TPU analogue of the reference's joint actor/critic step).
Old logprobs are recomputed under the *training* graph right after
generation so the importance ratio is exactly 1 on the first epoch
(eliminating sampler/trainer drift from the objective).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from orion_tpu.algos import (AdaptiveKLController, FixedKLController, gae,
                             kl_penalty, masked_mean, masked_whiten,
                             per_token_rewards, ppo_policy_loss,
                             ppo_value_loss)
from orion_tpu.config import PPOConfig
from orion_tpu.models.heads import ScalarHeadModel
from orion_tpu.trainers.base import BaseTrainer, TrainState


class PPOTrainer(BaseTrainer):
    """Two critic layouts (cfg.share_backbone):

    - separate (default): critic is a ScalarHeadModel with its own
      TrainState; joint jitted step runs two backward passes.
    - shared: ``model`` is a models.heads.ActorCriticModel; the value
      head rides the policy trunk, the loss is policy + vf_coef*value
      from ONE forward/backward, and the whole update flows through
      BaseTrainer's scanned epoch path (critic_state is None).
    """

    cfg: PPOConfig

    def __init__(self, cfg: PPOConfig, model, params,
                 critic_model: Optional[ScalarHeadModel] = None,
                 critic_params: Any = None, **kw):
        super().__init__(cfg, model, params, **kw)
        if cfg.model.block_length and (not cfg.share_backbone
                                       or cfg.async_mode):
            raise ValueError(
                "a block-diffusion policy's values are read from the state "
                "a token was revealed from, in the policy's own trace "
                "forward: PPO needs share_backbone=true and the synchronous "
                "loop (a separate critic has no trace forward)")
        if cfg.share_backbone:
            if critic_model is not None or critic_params is not None:
                raise ValueError(
                    "share_backbone=True puts the value head inside the "
                    "policy (ActorCriticModel); don't pass a critic")
            self.critic_model = None
            self.critic_state = None
            self._jit_lp_values = jax.jit(
                self._lp_values_fwd,
                static_argnames=("max_new", "with_entropy"))
        else:
            if critic_model is None or critic_params is None:
                raise ValueError(
                    "share_backbone=False needs critic_model + "
                    "critic_params (or set cfg.share_backbone=True)")
            self.critic_model = critic_model
            self.critic_state = TrainState.create(critic_params, self.tx)
            self._jit_ppo_epochs = self._jit_update(
                self._ppo_epochs_fn, (self.state, self.critic_state))
        self.kl_ctl = (AdaptiveKLController(cfg.kl_coef, cfg.kl_target,
                                            cfg.kl_horizon)
                       if cfg.adaptive_kl else FixedKLController(cfg.kl_coef))
        self._jit_values = jax.jit(self._values_fwd)

    @staticmethod
    def _gather_completion(values, prompt_lens, mask):
        """Value for completion token t reads the hidden state at the
        previous token — the same alignment as completion_logprobs
        (single source of truth for the off-by-one bug class)."""
        T = mask.shape[1]
        idx = jnp.clip(
            prompt_lens[:, None] + jnp.arange(T)[None, :] - 1,
            0, values.shape[1] - 1)
        return jnp.take_along_axis(values, idx, axis=1) * mask

    def _values_fwd(self, critic_params, sequences, prompt_lens, mask):
        positions = jnp.broadcast_to(
            jnp.arange(sequences.shape[1], dtype=jnp.int32),
            sequences.shape)
        if self.cfg.share_backbone:
            # Values-only forward on the shared trunk: skip the vocab
            # projection entirely.
            _, values, _ = self.model.apply(
                {"params": critic_params}, sequences, positions,
                with_values=True, skip_lm_head=True)
        else:
            values = self.critic_model.apply(
                {"params": critic_params}, sequences, positions)
        return self._gather_completion(values, prompt_lens, mask)

    def _lp_values_fwd(self, params, sequences, prompt_lens, mask,
                       max_new: int, with_entropy: bool = True,
                       reveal_step=None):
        """Shared-trunk forward: completion logprobs (+ entropy when the
        caller needs it — a full-vocab softmax reduce it should not pay
        for on the experience pass) AND values from one backbone pass.
        The vocab projection runs only over the completion window via
        BaseTrainer._windowed_forward (values still read the full
        hidden states)."""
        lp, ent, extra, aux, moe = self._windowed_forward(
            params, sequences, prompt_lens, max_new,
            with_entropy=with_entropy, reveal_step=reveal_step,
            mask=mask, with_values=True)
        # read where the logits were (_windowed_forward)
        return lp, ent, extra[0] * mask, aux, moe

    # ------------------------------------------------------------------
    def build_experience(self, result, scores, host=None):
        T = result.completions.shape[1]
        mask = result.completion_mask
        ut = {}
        if self.cfg.share_backbone and not self.cfg.async_mode:
            # One fused trunk pass yields old logprobs AND values.
            old_lp, _, values, _, counters = self._jit_lp_values(
                self.state.params, result.sequences, result.prompt_lens,
                mask, max_new=T, with_entropy=False,
                **self._trace_kw(result))
            # a looped stack's exit masses over this batch's real tokens
            ut = {k: v for k, v in counters.items() if k.startswith("ut_")}
        else:
            old_lp = self.behavior_logprobs(result)
            critic_params = (self.state.params if self.cfg.share_backbone
                             else self.critic_state.params)
            values = self._jit_values(
                critic_params, result.sequences, result.prompt_lens, mask)
        ref_lp, _ = self._jit_logprobs(
            self.ref_params, result.sequences, result.prompt_lens, max_new=T,
            **self._trace_kw(result))

        kl = kl_penalty(old_lp, ref_lp, "k1") * mask
        # Logged below as `kl_coef`: the PRE-update coefficient — the one
        # that actually shaped this batch's rewards.  The eager path used
        # to log the post-update value while the deferred path logged
        # pre-update (ADVICE r3): one convention now, both branches.
        kl_coef_used = self.kl_ctl.value
        rewards = per_token_rewards(jnp.asarray(scores), kl, mask,
                                    kl_coef_used, self.cfg.reward_clip)
        advantages, returns = gae(rewards, values, mask,
                                  self.cfg.gamma, self.cfg.gae_lambda)
        if self.cfg.whiten_advantages:
            advantages = masked_whiten(advantages, mask)

        dev = {
            "kl": masked_mean(kl, mask),
            "value_mean": masked_mean(values, mask),
            "return_mean": masked_mean(returns, mask), **ut,
        }
        if self._defer_stats:
            # Sync pipelined loop: leave the scalars on device; the
            # train loop fetches them with the NEXT iteration's
            # generation fetch and runs _on_host_stats (the KL
            # controller update) at the same point in the update order
            # as the eager path below.
            pass
        else:
            dev = {k: float(v) for k, v in
                   jax.device_get(dev).items()}  # one batched fetch
            self.kl_ctl.update(dev["kl"], int(mask.shape[0]))

        experience = {
            "sequences": result.sequences,
            "prompt_lens": result.prompt_lens,
            "mask": mask,
            "old_logprobs": old_lp * mask,
            "old_values": values,
            "advantages": advantages,
            "returns": returns,
            **self._trace_kw(result),
        }
        lens = (host or result).completion_lens
        stats = {
            "reward_mean": float(np.mean(scores)),
            "reward_std": float(np.std(scores)),
            "kl_coef": kl_coef_used,
            "completion_len_mean": float(np.mean(np.asarray(lens))),
            **dev,
        }
        return experience, stats

    def _on_host_stats(self, stats: dict, n_samples: int) -> None:
        """Deferred-pipeline KL-controller update (see BaseTrainer)."""
        if "kl" in stats:
            self.kl_ctl.update(float(stats["kl"]), n_samples)

    # ------------------------------------------------------------------
    def loss_fn(self, params, mb):
        """Shared-trunk joint loss: policy + vf_coef * value from ONE
        forward/backward.  Flows through BaseTrainer's scanned epoch
        program (_epochs_fn) unchanged."""
        T = mb["mask"].shape[1]
        lp, ent, values, aux, moe = self._lp_values_fwd(
            params, mb["sequences"], mb["prompt_lens"], mb["mask"],
            max_new=T, **self._trace_kw(mb))
        p_loss, p_stats = ppo_policy_loss(
            lp, mb["old_logprobs"], mb["advantages"], mb["mask"],
            self.cfg.clip_ratio)
        v_loss, v_stats = ppo_value_loss(
            values, mb["old_values"], mb["returns"], mb["mask"],
            self.cfg.value_clip)
        stats = {**p_stats, **v_stats, **moe}
        stats["entropy"] = masked_mean(ent, mb["mask"])
        return (p_loss + self.cfg.vf_coef * v_loss
                + self.cfg.model.router_aux_coef * aux), stats

    def _policy_loss(self, params, mb):
        T = mb["mask"].shape[1]
        lp, (ent, aux, moe) = self._logprobs_fn(
            params, mb["sequences"], mb["prompt_lens"], max_new=T,
            **self._trace_kw(mb))
        loss, stats = ppo_policy_loss(
            lp, mb["old_logprobs"], mb["advantages"], mb["mask"],
            self.cfg.clip_ratio)
        loss = loss + self.cfg.model.router_aux_coef * aux
        stats = {**stats, **moe}
        stats["entropy"] = masked_mean(ent, mb["mask"])
        return loss, stats

    def _value_loss(self, critic_params, mb):
        values = self._values_fwd(critic_params, mb["sequences"],
                                  mb["prompt_lens"], mb["mask"])
        loss, stats = ppo_value_loss(
            values, mb["old_values"], mb["returns"], mb["mask"],
            self.cfg.value_clip)
        return self.cfg.vf_coef * loss, stats

    def _ppo_update_fn(self, state: TrainState, critic_state: TrainState,
                       experience, idx):
        mb = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), experience)
        (p_loss, p_stats), p_grads = jax.value_and_grad(
            self._policy_loss, has_aux=True)(state.params, mb)
        (v_loss, v_stats), v_grads = jax.value_and_grad(
            self._value_loss, has_aux=True)(critic_state.params, mb)

        p_updates, p_opt = self.tx.update(p_grads, state.opt_state,
                                          state.params)
        new_state = TrainState(
            params=optax.apply_updates(state.params, p_updates),
            opt_state=p_opt, step=state.step + 1)
        v_updates, v_opt = self.tx.update(v_grads, critic_state.opt_state,
                                          critic_state.params)
        new_critic = TrainState(
            params=optax.apply_updates(critic_state.params, v_updates),
            opt_state=v_opt, step=critic_state.step + 1)

        stats = {**p_stats, **v_stats}
        stats["loss"] = p_loss + v_loss
        stats["grad_norm"] = optax.global_norm(p_grads)
        return new_state, new_critic, stats

    def _ppo_epochs_fn(self, state, critic_state, experience, idx_mat):
        """Scanned joint policy/critic epoch program (one dispatch for
        all minibatches — see BaseTrainer._epochs_fn)."""
        def step(carry, idx):
            st, cst = carry
            st, cst, stats = self._ppo_update_fn(st, cst, experience, idx)
            return (st, cst), stats

        (st, cst), stats = jax.lax.scan(
            step, (state, critic_state), idx_mat)
        return st, cst, stats

    def _update_program(self):
        if self.cfg.share_backbone:
            return super()._update_program()
        return self._jit_ppo_epochs, (self.state, self.critic_state)

    def _run_epochs(self, experience, idx_mat):
        if self.cfg.share_backbone:
            return super()._run_epochs(experience, idx_mat)
        self.state, self.critic_state, stats = self._jit_ppo_epochs(
            self.state, self.critic_state, experience, idx_mat)
        return stats
