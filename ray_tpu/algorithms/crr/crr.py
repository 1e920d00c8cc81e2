"""CRR: Critic-Regularized Regression for offline RL.

Counterpart of the reference's ``rllib/algorithms/crr/crr.py``
(CRRConfig: weight_type bin|exp, temperature, max_weight,
n_action_sample, twin_q, target_update_grad_intervals) and
``crr_torch_policy.py`` (actor = advantage-weighted behavior cloning
with weights from the critic's advantage estimate; critic = TD
regression against target nets with policy next-actions).

One jitted shard_map program per step: critic step, advantage estimate
via n sampled policy actions, weighted-BC actor step, periodic hard
target sync via a traced step-counter select (no recompiles)."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_tpu import sharding as sharding_lib

from ray_tpu.algorithms.sac.sac import SAC, SACConfig, SACJaxPolicy
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID, SampleBatch
from ray_tpu.models.distributions import SquashedGaussian
from ray_tpu.policy.jax_policy import _tree_to_device


class CRRConfig(SACConfig):
    """reference crr.py CRRConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or CRR)
        self.weight_type = "bin"  # "bin" | "exp"
        self.temperature = 1.0
        self.max_weight = 20.0
        self.n_action_sample = 4
        self.twin_q = True
        self.target_update_grad_intervals = 100
        self.num_steps_sampled_before_learning_starts = 0
        self.off_policy_estimation_methods = []

    def training(
        self,
        *,
        weight_type: Optional[str] = None,
        temperature: Optional[float] = None,
        max_weight: Optional[float] = None,
        n_action_sample: Optional[int] = None,
        target_update_grad_intervals: Optional[int] = None,
        **kwargs,
    ) -> "CRRConfig":
        super().training(**kwargs)
        if weight_type is not None:
            self.weight_type = weight_type
        if temperature is not None:
            self.temperature = temperature
        if max_weight is not None:
            self.max_weight = max_weight
        if n_action_sample is not None:
            self.n_action_sample = n_action_sample
        if target_update_grad_intervals is not None:
            self.target_update_grad_intervals = (
                target_update_grad_intervals
            )
        return self


class CRRJaxPolicy(SACJaxPolicy):
    """reference crr_torch_policy.py losses."""

    def __init__(self, observation_space, action_space, config):
        super().__init__(observation_space, action_space, config)
        # CRR targets both nets; hard-sync on a traced interval
        import jax.numpy as _jnp

        actor_params = jax.device_get(self.params["actor"])
        self.aux_state = _tree_to_device(
            {
                "target_actor": actor_params,
                "target_critic": jax.device_get(
                    self.params["critic"]
                ),
                "step": _jnp.zeros((), _jnp.int32),
            },
            self._param_sharding,
        )

    def _device_update_fn(self, batch_size=None, with_frames=False):
        """CRR's own single-update body: the generic superstep scans
        THIS (weighted-regression actor loss included), so chained CRR
        updates fuse correctly."""
        actor, critic = self.actor, self.critic
        tx_a, tx_c = self._tx_actor, self._tx_critic
        gamma = self.gamma**self.n_step
        low, high = self.low, self.high
        mesh = self.mesh
        axis = sharding_lib.data_axis(mesh)
        cfg = self.config
        weight_type = cfg.get("weight_type", "bin")
        temperature = float(cfg.get("temperature", 1.0))
        max_weight = float(cfg.get("max_weight", 20.0))
        n_sample = int(cfg.get("n_action_sample", 4))
        sync_interval = int(cfg.get("target_update_grad_intervals", 100))
        act_dim = self.action_dim

        def mean_policy_q(cp, ap, obs, rng):
            """E_{a~pi}[Q(s,a)] via n sampled actions."""
            B = obs.shape[0]
            dist = SquashedGaussian(
                actor.apply(ap, obs), low=low, high=high
            )
            rngs = jax.random.split(rng, n_sample)
            acts, _ = jax.vmap(lambda r: dist.sampled_action_logp(r))(
                rngs
            )  # (n, B, act_dim)
            acts = jnp.swapaxes(acts, 0, 1).reshape(
                B * n_sample, act_dim
            )
            obs_rep = jnp.repeat(obs, n_sample, axis=0)
            q1, q2 = critic.apply(cp, obs_rep, acts)
            q = jnp.minimum(q1, q2).reshape(B, n_sample)
            return q.mean(axis=1)

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            obs = batch[SampleBatch.OBS].astype(jnp.float32)
            next_obs = batch[SampleBatch.NEXT_OBS].astype(jnp.float32)
            rewards = batch[SampleBatch.REWARDS].astype(jnp.float32)
            not_done = 1.0 - batch[SampleBatch.TERMINATEDS].astype(
                jnp.float32
            )
            actions = batch[SampleBatch.ACTIONS].astype(jnp.float32)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            rng_t, rng_adv = jax.random.split(rng)

            # ---- critic TD step: next action from the TARGET actor ----
            next_dist = SquashedGaussian(
                actor.apply(aux["target_actor"], next_obs),
                low=low,
                high=high,
            )
            next_a, _ = next_dist.sampled_action_logp(rng_t)
            tq1, tq2 = critic.apply(
                aux["target_critic"], next_obs, next_a
            )
            td_target = jax.lax.stop_gradient(
                rewards + gamma * not_done * jnp.minimum(tq1, tq2)
            )

            def critic_loss(cp):
                q1, q2 = critic.apply(cp, obs, actions)
                return (
                    jnp.mean(jnp.square(q1 - td_target))
                    + jnp.mean(jnp.square(q2 - td_target))
                ), q1

            (c_loss, q1), c_grads = jax.value_and_grad(
                critic_loss, has_aux=True
            )(sharding_lib.varying(params["critic"], axis))
            c_grads = jax.lax.pmean(c_grads, axis)
            c_upd, c_opt = tx_c.update(
                c_grads, opt_state["critic"], params["critic"]
            )
            new_critic = optax.apply_updates(params["critic"], c_upd)

            # ---- advantage-weighted BC actor step ----
            qa1, qa2 = critic.apply(new_critic, obs, actions)
            q_data = jnp.minimum(qa1, qa2)
            v_est = mean_policy_q(
                new_critic, params["actor"], obs, rng_adv
            )
            advantage = jax.lax.stop_gradient(q_data - v_est)
            if weight_type == "exp":
                weights = jnp.clip(
                    jnp.exp(advantage / temperature), 0.0, max_weight
                )
            else:  # "bin"
                weights = (advantage > 0.0).astype(jnp.float32)

            def actor_loss(ap):
                dist = SquashedGaussian(
                    actor.apply(ap, obs), low=low, high=high
                )
                bc_logp = dist.logp(actions)
                return -jnp.mean(weights * bc_logp)

            a_loss, a_grads = jax.value_and_grad(actor_loss)(
                sharding_lib.varying(params["actor"], axis)
            )
            a_grads = jax.lax.pmean(a_grads, axis)
            a_upd, a_opt = tx_a.update(
                a_grads, opt_state["actor"], params["actor"]
            )
            new_actor = optax.apply_updates(params["actor"], a_upd)

            # ---- periodic hard target sync (traced select) ----
            step = aux["step"] + 1
            do_sync = (step % sync_interval) == 0
            new_target_actor = jax.tree_util.tree_map(
                lambda t, o: jnp.where(do_sync, o, t),
                aux["target_actor"],
                new_actor,
            )
            new_target_critic = jax.tree_util.tree_map(
                lambda t, o: jnp.where(do_sync, o, t),
                aux["target_critic"],
                new_critic,
            )

            new_params = dict(
                params, actor=new_actor, critic=new_critic
            )
            new_opt = dict(opt_state, actor=a_opt, critic=c_opt)
            new_aux = {
                "target_actor": new_target_actor,
                "target_critic": new_target_critic,
                "step": step,
            }
            stats = {
                "actor_loss": a_loss,
                "critic_loss": c_loss,
                "mean_q": jnp.mean(q1),
                "mean_advantage": jnp.mean(advantage),
                "mean_weight": jnp.mean(weights),
                "total_loss": a_loss + c_loss,
            }
            stats = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, axis), stats
            )
            return new_params, new_opt, new_aux, stats

        return device_fn


class CRR(SAC):
    """Offline training loop over JsonReader data (reference crr.py
    trains from offline input with SAC-style machinery)."""

    _default_policy_class = CRRJaxPolicy

    @classmethod
    def get_default_config(cls) -> CRRConfig:
        return CRRConfig(cls)

    def setup(self, config: Dict) -> None:
        if config.get("twin_q") is False:
            raise NotImplementedError(
                "CRR always trains twin critics (the nets are a "
                "TwinQNet); twin_q=False is not supported"
            )
        super().setup(config)
        from ray_tpu.offline.offline_ops import setup_offline_reader

        self._reader = setup_offline_reader(config)

    def training_step(self) -> Dict:
        if self._reader is None:
            return super().training_step()
        from ray_tpu.offline.offline_ops import offline_training_step

        return offline_training_step(self)
