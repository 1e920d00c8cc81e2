"""CQL: Conservative Q-Learning for offline RL.

Counterpart of the reference's ``rllib/algorithms/cql/cql.py`` (config:
bc_iters, temperature, num_actions, min_q_weight, lagrangian) and
``cql_torch_policy.py`` (the entropy-version CQL penalty: logsumexp over
{uniform-random, current-policy, next-state-policy} action Q values with
importance correction, added to the SAC critic loss; BC-warmup actor for
the first ``bc_iters`` steps).

One jitted shard_map program per step, like SAC; the BC-warmup switch is
a traced select on a step counter carried in aux_state, so warmup→SAC
transition never recompiles."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_tpu import sharding as sharding_lib

from ray_tpu.algorithms.marwil.marwil import MARWIL
from ray_tpu.algorithms.sac.sac import SAC, SACConfig, SACJaxPolicy
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID, SampleBatch
from ray_tpu.models.distributions import SquashedGaussian
from ray_tpu.policy.jax_policy import _tree_to_device


class CQLConfig(SACConfig):
    """reference cql.py CQLConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or CQL)
        self.bc_iters = 20000
        self.temperature = 1.0
        self.num_actions = 10
        self.min_q_weight = 5.0
        self.lagrangian = False
        self.num_steps_sampled_before_learning_starts = 0
        self.off_policy_estimation_methods = []

    def training(
        self,
        *,
        bc_iters: Optional[int] = None,
        temperature: Optional[float] = None,
        num_actions: Optional[int] = None,
        min_q_weight: Optional[float] = None,
        **kwargs,
    ) -> "CQLConfig":
        super().training(**kwargs)
        if bc_iters is not None:
            self.bc_iters = bc_iters
        if temperature is not None:
            self.temperature = temperature
        if num_actions is not None:
            self.num_actions = num_actions
        if min_q_weight is not None:
            self.min_q_weight = min_q_weight
        return self


class CQLJaxPolicy(SACJaxPolicy):
    """reference cql_torch_policy.py cql_loss."""

    def __init__(self, observation_space, action_space, config):
        super().__init__(observation_space, action_space, config)
        # step counter for the BC-warmup switch rides aux_state
        self.aux_state = dict(
            self.aux_state, step=jnp.zeros((), jnp.int32)
        )
        self.aux_state = _tree_to_device(
            self.aux_state, self._param_sharding
        )

    def _device_update_fn(self, batch_size=None, with_frames=False):
        """CQL's own single-update body: the generic superstep scans
        THIS (min-Q penalty included), so chained CQL updates fuse
        correctly — the legacy SAC stacked path never could."""
        actor, critic = self.actor, self.critic
        tx_a, tx_c, tx_al = (
            self._tx_actor,
            self._tx_critic,
            self._tx_alpha,
        )
        gamma, tau = self.gamma**self.n_step, self.tau
        target_entropy = self.target_entropy
        low, high = self.low, self.high
        mesh = self.mesh
        axis = sharding_lib.data_axis(mesh)
        cfg = self.config
        bc_iters = int(cfg.get("bc_iters", 20000))
        cql_temp = float(cfg.get("temperature", 1.0))
        num_actions = int(cfg.get("num_actions", 10))
        min_q_weight = float(cfg.get("min_q_weight", 5.0))
        act_dim = self.action_dim
        # log density of the uniform proposal over the action box:
        # (1/(high-low))^d (reference uses log(0.5^d) for [-1,1]).
        # Host math on static space bounds — computed once here, not
        # per trace inside the device body (RTA002).
        random_density = -float(act_dim) * np.log(high - low)

        def q_repeat(cp, obs, actions_rep):
            """Q for (B*num_actions) actions against repeated obs."""
            B = obs.shape[0]
            n_rep = actions_rep.shape[0] // B
            obs_rep = jnp.repeat(obs, n_rep, axis=0)
            q1, q2 = critic.apply(cp, obs_rep, actions_rep)
            return q1.reshape(B, n_rep), q2.reshape(B, n_rep)

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            obs = batch[SampleBatch.OBS].astype(jnp.float32)
            next_obs = batch[SampleBatch.NEXT_OBS].astype(jnp.float32)
            rewards = batch[SampleBatch.REWARDS].astype(jnp.float32)
            not_done = 1.0 - batch[SampleBatch.TERMINATEDS].astype(
                jnp.float32
            )
            actions = batch[SampleBatch.ACTIONS].astype(jnp.float32)
            B = obs.shape[0]
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            rng_t, rng_a, rng_r, rng_c, rng_n = jax.random.split(rng, 5)
            alpha = jnp.exp(params["log_alpha"])

            # ---- critic TD target (reference cql_torch_policy: policy
            # next action, NO entropy term in the target) ----
            next_dist = SquashedGaussian(
                actor.apply(params["actor"], next_obs), low=low, high=high
            )
            next_a, _ = next_dist.sampled_action_logp(rng_t)
            tq1, tq2 = critic.apply(
                aux["target_critic"], next_obs, next_a
            )
            target_q = jnp.minimum(tq1, tq2)
            td_target = jax.lax.stop_gradient(
                rewards + gamma * not_done * target_q
            )

            # sampled actions for the conservative penalty
            rand_actions = jax.random.uniform(
                rng_r, (B * num_actions, act_dim), minval=low, maxval=high
            )
            cur_dist = SquashedGaussian(
                actor.apply(params["actor"], obs), low=low, high=high
            )

            def sample_repeat(dist, rng_k):
                rngs = jax.random.split(rng_k, num_actions)
                acts, logps = jax.vmap(
                    lambda r: dist.sampled_action_logp(r)
                )(rngs)  # (num_actions, B, act_dim), (num_actions, B)
                acts = jnp.swapaxes(acts, 0, 1).reshape(
                    B * num_actions, act_dim
                )
                logps = jnp.swapaxes(logps, 0, 1)  # (B, num_actions)
                return acts, logps

            cur_acts, cur_logp = sample_repeat(cur_dist, rng_c)
            next_acts, next_logp = sample_repeat(next_dist, rng_n)

            def critic_loss(cp):
                q1, q2 = critic.apply(cp, obs, actions)
                td1 = jnp.mean(jnp.square(q1 - td_target))
                td2 = jnp.mean(jnp.square(q2 - td_target))
                q1_rand, q2_rand = q_repeat(cp, obs, rand_actions)
                q1_cur, q2_cur = q_repeat(cp, obs, cur_acts)
                q1_next, q2_next = q_repeat(cp, obs, next_acts)
                stop = jax.lax.stop_gradient
                cat1 = jnp.concatenate(
                    [
                        q1_rand - random_density,
                        q1_next - stop(next_logp),
                        q1_cur - stop(cur_logp),
                    ],
                    axis=1,
                )
                cat2 = jnp.concatenate(
                    [
                        q2_rand - random_density,
                        q2_next - stop(next_logp),
                        q2_cur - stop(cur_logp),
                    ],
                    axis=1,
                )
                min_q1 = (
                    jax.nn.logsumexp(cat1 / cql_temp, axis=1).mean()
                    * min_q_weight
                    * cql_temp
                    - q1.mean() * min_q_weight
                )
                min_q2 = (
                    jax.nn.logsumexp(cat2 / cql_temp, axis=1).mean()
                    * min_q_weight
                    * cql_temp
                    - q2.mean() * min_q_weight
                )
                loss = td1 + td2 + min_q1 + min_q2
                return loss, (q1, td1 + td2, min_q1 + min_q2)

            (c_loss, (q1, td_loss, cql_pen)), c_grads = (
                jax.value_and_grad(critic_loss, has_aux=True)(
                    sharding_lib.varying(params["critic"], axis)
                )
            )
            c_grads = jax.lax.pmean(c_grads, axis)
            c_upd, c_opt = tx_c.update(
                c_grads, opt_state["critic"], params["critic"]
            )
            new_critic = optax.apply_updates(params["critic"], c_upd)

            # ---- actor: BC warmup for bc_iters steps, then SAC ----
            in_warmup = aux["step"] < bc_iters

            def actor_loss(ap):
                dist = SquashedGaussian(
                    actor.apply(ap, obs), low=low, high=high
                )
                a_pi, logp_pi = dist.sampled_action_logp(rng_a)
                bc_logp = dist.logp(actions)
                aq1, aq2 = critic.apply(new_critic, obs, a_pi)
                sac_loss = jnp.mean(
                    alpha * logp_pi - jnp.minimum(aq1, aq2)
                )
                bc_loss = jnp.mean(alpha * logp_pi - bc_logp)
                return (
                    jnp.where(in_warmup, bc_loss, sac_loss),
                    logp_pi,
                )

            (a_loss, logp_pi), a_grads = jax.value_and_grad(
                actor_loss, has_aux=True
            )(sharding_lib.varying(params["actor"], axis))
            a_grads = jax.lax.pmean(a_grads, axis)
            a_upd, a_opt = tx_a.update(
                a_grads, opt_state["actor"], params["actor"]
            )
            new_actor = optax.apply_updates(params["actor"], a_upd)

            # ---- alpha ----
            def alpha_loss(log_alpha):
                return -jnp.mean(
                    log_alpha
                    * jax.lax.stop_gradient(logp_pi + target_entropy)
                )

            al_loss, al_grad = jax.value_and_grad(alpha_loss)(
                sharding_lib.varying(params["log_alpha"], axis)
            )
            al_grad = jax.lax.pmean(al_grad, axis)
            al_upd, al_opt = tx_al.update(
                al_grad, opt_state["log_alpha"], params["log_alpha"]
            )
            new_log_alpha = optax.apply_updates(
                params["log_alpha"], al_upd
            )

            new_target = jax.tree_util.tree_map(
                lambda t, o: (1.0 - tau) * t + tau * o,
                aux["target_critic"],
                new_critic,
            )
            new_params = {
                "actor": new_actor,
                "critic": new_critic,
                "log_alpha": new_log_alpha,
            }
            new_opt = {
                "actor": a_opt,
                "critic": c_opt,
                "log_alpha": al_opt,
            }
            new_aux = {
                "target_critic": new_target,
                "step": aux["step"] + 1,
            }
            stats = {
                "actor_loss": a_loss,
                "critic_loss": c_loss,
                "td_loss": td_loss,
                "cql_penalty": cql_pen,
                "alpha_value": alpha,
                "mean_q": jnp.mean(q1),
                "in_bc_warmup": in_warmup.astype(jnp.float32),
                "total_loss": a_loss + c_loss + al_loss,
            }
            stats = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, axis), stats
            )
            return new_params, new_opt, new_aux, stats

        return device_fn


class CQL(SAC):
    """Offline training loop: batches come from the JsonReader (or the
    replay buffer when trained online — reference cql.py keeps SAC's
    training_step and swaps the input)."""

    _default_policy_class = CQLJaxPolicy

    @classmethod
    def get_default_config(cls) -> CQLConfig:
        return CQLConfig(cls)

    def setup(self, config: Dict) -> None:
        if config.get("lagrangian"):
            raise NotImplementedError(
                "Lagrangian CQL (learned alpha_prime) is not "
                "implemented; use the fixed min_q_weight penalty"
            )
        super().setup(config)
        from ray_tpu.offline.offline_ops import setup_offline_reader

        self._reader = setup_offline_reader(config)

    def training_step(self) -> Dict:
        if self._reader is None:
            return super().training_step()
        from ray_tpu.offline.offline_ops import offline_training_step

        return offline_training_step(self)
