"""DDPG and TD3: deterministic-policy actor-critic with target networks.

Counterpart of the reference's ``rllib/algorithms/ddpg/ddpg.py`` (config;
DDPG extends SimpleQ's off-policy loop) and ``ddpg_torch_policy.py``
(actor/critic losses, target smoothing, delayed policy updates for TD3
via ``policy_delay``; ``rllib/algorithms/td3/td3.py`` is DDPG with twin
critics + smoothed targets + Gaussian exploration).

TPU-first: the whole update — critic step, (delayed) actor step, polyak
target blends for both nets — is ONE jitted shard_map program; the
delayed actor update is a ``lax.cond`` on a traced step counter carried
in aux_state, so the program never recompiles across steps."""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_tpu import sharding as sharding_lib

from ray_tpu.algorithms.algorithm_config import AlgorithmConfig  # noqa: F401
from ray_tpu.algorithms.dqn.dqn import DQN, DQNConfig
from ray_tpu.algorithms.sac.sac import _TwinQNet
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.models.base import get_activation
from ray_tpu.models.distributions import Deterministic
from ray_tpu.policy.jax_policy import JaxPolicy, _tree_to_device


class _DetActorNet(nn.Module):
    """MLP -> tanh -> affine to [low, high] (reference
    ddpg_torch_model.py policy network)."""

    action_dim: int
    low: float
    high: float
    hiddens: Sequence[int] = (400, 300)
    activation: str = "relu"

    @nn.compact
    def __call__(self, obs):
        act = get_activation(self.activation)
        x = obs.astype(jnp.float32).reshape(obs.shape[0], -1)
        for i, h in enumerate(self.hiddens):
            x = act(nn.Dense(h, name=f"fc_{i}")(x))
        raw = nn.Dense(self.action_dim, name="out")(x)
        squashed = jnp.tanh(raw)
        mid = (self.high + self.low) / 2.0
        half = (self.high - self.low) / 2.0
        return mid + half * squashed


class DDPGConfig(DQNConfig):
    """reference ddpg.py DDPGConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or DDPG)
        self.twin_q = False
        self.policy_delay = 1
        self.smooth_target_policy = False
        self.target_noise = 0.2
        self.target_noise_clip = 0.5
        self.actor_hiddens = [400, 300]
        self.actor_hidden_activation = "relu"
        self.critic_hiddens = [400, 300]
        self.critic_hidden_activation = "relu"
        self.tau = 0.002
        self.use_huber = False
        self.huber_threshold = 1.0
        self.l2_reg = 1e-6
        self.actor_lr = 1e-3
        self.critic_lr = 1e-3
        self.train_batch_size = 256
        self.rollout_fragment_length = 1
        self.num_steps_sampled_before_learning_starts = 1500
        self.target_network_update_freq = 0
        self.n_step = 1
        self.grad_clip = None
        self.exploration_config = {
            "type": "OrnsteinUhlenbeckNoise",
            "scale_timesteps": 10000,
            "initial_scale": 1.0,
            "final_scale": 0.02,
            "ou_base_scale": 0.1,
            "ou_theta": 0.15,
            "ou_sigma": 0.2,
        }
        self.replay_buffer_config = {
            "capacity": 50000,
            "prioritized_replay": False,
            "prioritized_replay_alpha": 0.6,
            "prioritized_replay_beta": 0.4,
        }

    def training(
        self,
        *,
        twin_q: Optional[bool] = None,
        policy_delay: Optional[int] = None,
        smooth_target_policy: Optional[bool] = None,
        target_noise: Optional[float] = None,
        target_noise_clip: Optional[float] = None,
        actor_hiddens: Optional[Sequence[int]] = None,
        critic_hiddens: Optional[Sequence[int]] = None,
        tau: Optional[float] = None,
        use_huber: Optional[bool] = None,
        actor_lr: Optional[float] = None,
        critic_lr: Optional[float] = None,
        l2_reg: Optional[float] = None,
        **kwargs,
    ) -> "DDPGConfig":
        super().training(**kwargs)
        if twin_q is not None:
            self.twin_q = twin_q
        if policy_delay is not None:
            self.policy_delay = policy_delay
        if smooth_target_policy is not None:
            self.smooth_target_policy = smooth_target_policy
        if target_noise is not None:
            self.target_noise = target_noise
        if target_noise_clip is not None:
            self.target_noise_clip = target_noise_clip
        if actor_hiddens is not None:
            self.actor_hiddens = list(actor_hiddens)
        if critic_hiddens is not None:
            self.critic_hiddens = list(critic_hiddens)
        if tau is not None:
            self.tau = tau
        if use_huber is not None:
            self.use_huber = use_huber
        if actor_lr is not None:
            self.actor_lr = actor_lr
        if critic_lr is not None:
            self.critic_lr = critic_lr
        if l2_reg is not None:
            self.l2_reg = l2_reg
        return self


class TD3Config(DDPGConfig):
    """reference td3.py TD3Config: twin critics, delayed + smoothed
    target policy, Gaussian exploration."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or TD3)
        self.twin_q = True
        self.policy_delay = 2
        self.smooth_target_policy = True
        self.actor_lr = 1e-3
        self.critic_lr = 1e-3
        self.exploration_config = {
            "type": "GaussianNoise",
            "stddev": 0.1,
            "initial_scale": 1.0,
            "final_scale": 1.0,
            "scale_timesteps": 1,
        }
        self.num_steps_sampled_before_learning_starts = 10000


class DDPGJaxPolicy(JaxPolicy):
    """Deterministic actor + (twin) critic with target nets (reference
    ddpg_torch_policy.py ddpg_actor_critic_loss)."""

    default_exploration = "OrnsteinUhlenbeckNoise"

    def __init__(self, observation_space, action_space, config):
        from ray_tpu.policy.policy import Policy

        Policy.__init__(self, observation_space, action_space, config)
        self.action_dim = int(np.prod(action_space.shape))
        self.low = float(np.min(action_space.low))
        self.high = float(np.max(action_space.high))

        self.mesh = sharding_lib.resolve_mesh(config)
        self.n_shards = sharding_lib.num_shards(self.mesh)
        self._param_sharding = sharding_lib.replicated(self.mesh)
        self._data_sharding = sharding_lib.batch_sharded(self.mesh)

        self.actor = _DetActorNet(
            self.action_dim,
            self.low,
            self.high,
            tuple(config.get("actor_hiddens", (400, 300))),
            config.get("actor_hidden_activation", "relu"),
        )
        self.critic = _TwinQNet(
            tuple(config.get("critic_hiddens", (400, 300))),
            config.get("critic_hidden_activation", "relu"),
        )

        seed = int(config.get("seed") or 0)
        self._rng = jax.random.PRNGKey(seed)
        self._rng, r1, r2 = jax.random.split(self._rng, 3)
        dummy_obs = jnp.zeros(
            (2,) + tuple(observation_space.shape), jnp.float32
        )
        dummy_act = jnp.zeros((2, self.action_dim), jnp.float32)
        actor_params = self.actor.init(r1, dummy_obs)
        critic_params = self.critic.init(r2, dummy_obs, dummy_act)
        self.params = _tree_to_device(
            {"actor": actor_params, "critic": critic_params},
            self._param_sharding,
        )
        self.aux_state = _tree_to_device(
            {
                "target_actor": actor_params,
                "target_critic": critic_params,
                "step": jnp.zeros((), jnp.int32),
            },
            self._param_sharding,
        )

        self._tx_actor = optax.adam(config.get("actor_lr", 1e-3))
        self._tx_critic = optax.adam(config.get("critic_lr", 1e-3))
        self.opt_state = _tree_to_device(
            {
                "actor": self._tx_actor.init(actor_params),
                "critic": self._tx_critic.init(critic_params),
            },
            self._param_sharding,
        )

        self.tau = float(config.get("tau", 0.002))
        self.gamma = float(config.get("gamma", 0.99))
        self.n_step = int(config.get("n_step", 1))
        self.twin_q = bool(config.get("twin_q", False))
        self.policy_delay = int(config.get("policy_delay", 1))

        self.coeff_values: Dict[str, float] = {}
        self._learn_fns: Dict = {}
        self._action_fn = None
        self.num_grad_updates = 0
        self._init_exploration()

    def get_initial_state(self):
        return []

    # -- inference -------------------------------------------------------

    def _build_action_fn(self):
        actor = self.actor
        exploration = self.exploration

        def fn(params, obs, rng, explore, coeffs, expl_state):
            det = actor.apply(params["actor"], obs)
            dist = Deterministic(det)
            actions, logp, expl_state = exploration.sample_fn(
                dist, rng, explore, coeffs, expl_state
            )
            return actions, expl_state

        return jax.jit(fn, static_argnames=("explore",))

    def compute_actions(
        self, obs_batch, state_batches=None, explore=True, **kwargs
    ):
        if self._action_fn is None:
            self._action_fn = self._build_action_fn()
        self.exploration.update_coeffs(
            self.coeff_values, self.global_timestep
        )
        params = self.exploration.params_for_inference(self, explore)
        self._rng, rng = jax.random.split(self._rng)
        obs = jnp.asarray(obs_batch)
        if self.exploration.needs_last_obs:
            self._last_obs = obs
        bsize = int(obs.shape[0])
        if self._expl_state_batch != bsize:
            self._expl_state = self.exploration.initial_state(bsize)
            self._expl_state_batch = bsize
        actions, self._expl_state = self._action_fn(
            params, obs, rng, bool(explore),
            self._coeff_array(), self._expl_state,
        )
        return np.asarray(actions), [], {}

    # -- learning --------------------------------------------------------

    def _td_targets(self, params, aux, batch, rng):
        """Target-Q computation shared by the loss and compute_td_error."""
        cfg = self.config
        next_obs = batch[SampleBatch.NEXT_OBS].astype(jnp.float32)
        rewards = batch[SampleBatch.REWARDS].astype(jnp.float32)
        not_done = 1.0 - batch[SampleBatch.TERMINATEDS].astype(
            jnp.float32
        )
        # per-row fold counts from adjust_nstep: fragment tails fold
        # fewer than n_step rewards, so their bootstrap discounts by
        # gamma**k, not a uniform gamma**n_step (dqn.py does the same)
        if "n_steps" in batch:
            gamma_n = self.gamma ** batch["n_steps"].astype(
                jnp.float32
            )
        else:
            gamma_n = self.gamma**self.n_step
        next_a = self.actor.apply(aux["target_actor"], next_obs)
        if cfg.get("smooth_target_policy"):
            noise = jnp.clip(
                cfg.get("target_noise", 0.2)
                * jax.random.normal(rng, next_a.shape),
                -cfg.get("target_noise_clip", 0.5),
                cfg.get("target_noise_clip", 0.5),
            )
            next_a = jnp.clip(next_a + noise, self.low, self.high)
        tq1, tq2 = self.critic.apply(
            aux["target_critic"], next_obs, next_a
        )
        target_q = jnp.minimum(tq1, tq2) if self.twin_q else tq1
        return jax.lax.stop_gradient(
            rewards + gamma_n * not_done * target_q
        )

    def _device_update_fn(self, batch_size=None, with_frames=False):
        """Single-update device body (shard_map), shared by the
        per-call learn program and the generic superstep scan
        (``JaxPolicy.learn_superstep``)."""
        actor, critic = self.actor, self.critic
        tx_a, tx_c = self._tx_actor, self._tx_critic
        tau = self.tau
        twin_q = self.twin_q
        policy_delay = self.policy_delay
        use_huber = bool(self.config.get("use_huber", False))
        huber_d = float(self.config.get("huber_threshold", 1.0))
        l2_reg = float(self.config.get("l2_reg", 0.0) or 0.0)
        mesh = self.mesh
        axis = sharding_lib.data_axis(mesh)

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            obs = batch[SampleBatch.OBS].astype(jnp.float32)
            actions = batch[SampleBatch.ACTIONS].astype(jnp.float32)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            td_target = self._td_targets(params, aux, batch, rng)

            # ---- critic step ----
            # prioritized-replay importance weights (Ape-X DDPG path);
            # absent column -> uniform
            is_weights = batch.get(
                "weights", jnp.ones_like(td_target)
            )

            def critic_loss(cp):
                q1, q2 = critic.apply(cp, obs, actions)
                err1 = q1 - td_target
                err2 = q2 - td_target

                def base_loss(err):
                    if use_huber:
                        a = jnp.abs(err)
                        return jnp.where(
                            a < huber_d,
                            0.5 * jnp.square(err),
                            huber_d * (a - 0.5 * huber_d),
                        )
                    return jnp.square(err)

                loss = jnp.mean(is_weights * base_loss(err1))
                if twin_q:
                    loss = loss + jnp.mean(
                        is_weights * base_loss(err2)
                    )
                if l2_reg:
                    loss = loss + l2_reg * optax.global_norm(cp) ** 2
                return loss, (q1, err1)

            (c_loss, (q1, td_err)), c_grads = jax.value_and_grad(
                critic_loss, has_aux=True
            )(sharding_lib.varying(params["critic"], axis))
            c_grads = jax.lax.pmean(c_grads, axis)
            c_upd, c_opt = tx_c.update(
                c_grads, opt_state["critic"], params["critic"]
            )
            new_critic = optax.apply_updates(params["critic"], c_upd)

            # ---- delayed actor step (TD3 policy_delay) ----
            def actor_loss(ap):
                a = actor.apply(ap, obs)
                aq1, _ = critic.apply(new_critic, obs, a)
                loss = -jnp.mean(aq1)
                if l2_reg:
                    loss = loss + l2_reg * optax.global_norm(ap) ** 2
                return loss

            a_loss, a_grads = jax.value_and_grad(actor_loss)(
                sharding_lib.varying(params["actor"], axis)
            )
            a_grads = jax.lax.pmean(a_grads, axis)
            a_upd, a_opt = tx_a.update(
                a_grads, opt_state["actor"], params["actor"]
            )
            updated_actor = optax.apply_updates(params["actor"], a_upd)

            step = aux["step"]
            do_update = (step % policy_delay) == 0
            new_actor = jax.tree_util.tree_map(
                lambda new, old: jnp.where(do_update, new, old),
                updated_actor,
                params["actor"],
            )
            new_a_opt = jax.tree_util.tree_map(
                lambda new, old: jnp.where(do_update, new, old),
                a_opt,
                opt_state["actor"],
            )

            # ---- polyak blends (actor target only on actor updates) ----
            new_target_critic = jax.tree_util.tree_map(
                lambda t, o: (1.0 - tau) * t + tau * o,
                aux["target_critic"],
                new_critic,
            )
            new_target_actor = jax.tree_util.tree_map(
                lambda t, o: jnp.where(
                    do_update, (1.0 - tau) * t + tau * o, t
                ),
                aux["target_actor"],
                new_actor,
            )

            new_params = {"actor": new_actor, "critic": new_critic}
            new_opt = {"actor": new_a_opt, "critic": c_opt}
            new_aux = {
                "target_actor": new_target_actor,
                "target_critic": new_target_critic,
                "step": step + 1,
            }
            stats = {
                "actor_loss": a_loss,
                "critic_loss": c_loss,
                "mean_q": jnp.mean(q1),
                "mean_td_error": jnp.mean(td_err),
                "total_loss": a_loss + c_loss,
            }
            stats = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, axis), stats
            )
            return new_params, new_opt, new_aux, stats

        return device_fn

    def _build_learn_fn(self, batch_size: int):
        return self._wrap_update_program(
            self._device_update_fn(batch_size), batch_size
        )

    # -- superstep contract (JaxPolicy.learn_superstep) ------------------

    @property
    def supports_superstep(self) -> bool:
        return (
            not self._superstep_opt_out
            and type(self)._build_learn_fn
            is DDPGJaxPolicy._build_learn_fn
        )

    def _learn_coeffs(self):
        return {}

    def _updates_per_learn_call(self, batch_size: int) -> int:
        return 1

    @property
    def _td_refresh_uses_rng(self) -> bool:
        return True  # target-policy smoothing noise

    def learn_on_device_batch(
        self, dev_batch, batch_size: int, *, defer_stats: bool = False
    ) -> Dict:
        fn = self.learn_fn(batch_size)
        self._rng, rng = jax.random.split(self._rng)
        self.params, self.opt_state, self.aux_state, stats = fn(
            self.params, self.opt_state, self.aux_state, dev_batch,
            rng, {},
        )
        self.num_grad_updates += 1
        if defer_stats:
            return stats
        if self.config.get("deferred_stats"):
            # one-call lag, same contract as the JaxPolicy base
            # (docs/data_plane.md)
            prev = self.__dict__.get("_lagged_stats")
            self.__dict__["_lagged_stats"] = stats
            if prev is None:
                return {}
            stats = jax.device_get(prev)
        else:
            stats = jax.device_get(stats)
        return {k: float(v) for k, v in stats.items()}

    def _td_error_device_fn(self):
        """Signed per-sample TD error — shared by ``compute_td_error``
        and the superstep's in-scan prioritized refresh."""

        def fn(params, aux, batch, rng):
            td_target = self._td_targets(params, aux, batch, rng)
            q1, _ = self.critic.apply(
                params["critic"],
                batch[SampleBatch.OBS].astype(jnp.float32),
                batch[SampleBatch.ACTIONS].astype(jnp.float32),
            )
            return q1 - td_target

        return fn

    def compute_td_error(self, samples) -> np.ndarray:
        """Per-sample |TD error| for prioritized replay."""
        if not hasattr(self, "_td_error_fn"):
            self._td_error_fn = jax.jit(self._td_error_device_fn())
        batch = self._td_input_tree(samples)
        self._rng, rng = jax.random.split(self._rng)
        td = self._td_error_fn(self.params, self.aux_state, batch, rng)
        return np.abs(np.asarray(td))

    def update_target(self) -> None:
        """No-op: polyak blending happens inside the learn program."""

    def _batch_to_train_tree(self, samples: SampleBatch):
        keys = [
            SampleBatch.OBS,
            SampleBatch.NEXT_OBS,
            SampleBatch.ACTIONS,
            SampleBatch.REWARDS,
            SampleBatch.TERMINATEDS,
            "weights",  # PER importance correction (Ape-X)
            "n_steps",  # per-row n-step fold counts
        ]
        return {
            k: np.asarray(samples[k]) for k in keys if k in samples
        }

    def get_state(self) -> Dict:
        return {
            "weights": jax.device_get(self.params),
            "opt_state": jax.device_get(self.opt_state),
            "aux_state": jax.device_get(self.aux_state),
            "global_timestep": self.global_timestep,
            "num_grad_updates": self.num_grad_updates,
            "exploration_state": self.exploration.get_state(),
        }

    def set_state(self, state: Dict) -> None:
        self.set_weights(state["weights"])
        if "opt_state" in state:
            self.opt_state = _tree_to_device(
                state["opt_state"], self._param_sharding
            )
        if "aux_state" in state:
            self.aux_state = _tree_to_device(
                state["aux_state"], self._param_sharding
            )
        self.global_timestep = state.get("global_timestep", 0)
        self.num_grad_updates = state.get("num_grad_updates", 0)
        self.exploration.set_state(state.get("exploration_state", {}))


class DDPG(DQN):
    _default_policy_class = DDPGJaxPolicy

    @classmethod
    def get_default_config(cls) -> DDPGConfig:
        return DDPGConfig(cls)


class TD3(DDPG):
    @classmethod
    def get_default_config(cls) -> TD3Config:
        return TD3Config(cls)
