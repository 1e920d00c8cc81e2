"""SAC: soft actor-critic with twin Q, auto-tuned entropy temperature.

Counterpart of the reference's ``rllib/algorithms/sac/sac.py:274`` (config;
SAC extends DQN's off-policy training_step) and
``sac_torch_policy.py`` (actor/critic/alpha losses with three optimizers).
TPU-first: the whole update — critic step, actor step, alpha step, polyak
target blend — is ONE jitted shard_map program; the three optimizers are
three optax states advanced inside it.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_tpu import sharding as sharding_lib
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.algorithms.dqn.dqn import DQN, DQNConfig
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.models.base import get_activation
from ray_tpu.models.distributions import SquashedGaussian
from ray_tpu.policy.jax_policy import JaxPolicy, _tree_to_device


class _ActorNet(nn.Module):
    action_dim: int
    hiddens: Sequence[int] = (256, 256)
    activation: str = "relu"

    @nn.compact
    def __call__(self, obs):
        act = get_activation(self.activation)
        x = obs.astype(jnp.float32).reshape(obs.shape[0], -1)
        for i, h in enumerate(self.hiddens):
            x = act(nn.Dense(h, name=f"fc_{i}")(x))
        return nn.Dense(2 * self.action_dim, name="out")(x)


class _TwinQNet(nn.Module):
    hiddens: Sequence[int] = (256, 256)
    activation: str = "relu"

    @nn.compact
    def __call__(self, obs, actions):
        act = get_activation(self.activation)
        x0 = jnp.concatenate(
            [
                obs.astype(jnp.float32).reshape(obs.shape[0], -1),
                actions.astype(jnp.float32).reshape(
                    actions.shape[0], -1
                ),
            ],
            axis=-1,
        )
        qs = []
        for name in ("q1", "q2"):
            x = x0
            for i, h in enumerate(self.hiddens):
                x = act(nn.Dense(h, name=f"{name}_fc_{i}")(x))
            qs.append(nn.Dense(1, name=f"{name}_out")(x).squeeze(-1))
        return qs[0], qs[1]


class SACConfig(DQNConfig):
    """reference sac.py:274."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or SAC)
        self.twin_q = True
        self.tau = 5e-3
        self.initial_alpha = 1.0
        self.target_entropy = "auto"
        self.optimization = {
            "actor_learning_rate": 3e-4,
            "critic_learning_rate": 3e-4,
            "entropy_learning_rate": 3e-4,
        }
        self.train_batch_size = 256
        self.rollout_fragment_length = 1
        self.num_steps_sampled_before_learning_starts = 1500
        self.target_network_update_freq = 0
        self.q_model_config = {"fcnet_hiddens": [256, 256]}
        self.policy_model_config = {"fcnet_hiddens": [256, 256]}
        self.n_step = 1
        self.grad_clip = None
        self.replay_buffer_config = {
            "capacity": 100000,
            "prioritized_replay": False,
            "prioritized_replay_alpha": 0.6,
            "prioritized_replay_beta": 0.4,
        }

    def training(
        self,
        *,
        twin_q: Optional[bool] = None,
        tau: Optional[float] = None,
        initial_alpha: Optional[float] = None,
        target_entropy=None,
        optimization: Optional[Dict] = None,
        q_model_config: Optional[Dict] = None,
        policy_model_config: Optional[Dict] = None,
        **kwargs,
    ) -> "SACConfig":
        super().training(**kwargs)
        if twin_q is not None:
            self.twin_q = twin_q
        if tau is not None:
            self.tau = tau
        if initial_alpha is not None:
            self.initial_alpha = initial_alpha
        if target_entropy is not None:
            self.target_entropy = target_entropy
        if optimization is not None:
            self.optimization.update(optimization)
        if q_model_config is not None:
            self.q_model_config = q_model_config
        if policy_model_config is not None:
            self.policy_model_config = policy_model_config
        return self


class SACJaxPolicy(JaxPolicy):
    """Actor/critic/alpha losses fused into one jitted update
    (reference sac_torch_policy.py actor_critic_loss + three optimizers)."""

    # rollout workers act with the actor net alone — don't pull the
    # critic/target towers off-device every weight sync
    inference_weight_keys = ("actor",)

    @property
    def supports_stacked_learn(self) -> bool:
        """Whether k replay updates may fuse into one lax.scan dispatch
        (learn_on_stacked_batch). Only safe when the subclass kept
        THIS class's update body: the fused scan is built from
        SACJaxPolicy._device_update_fn, so a subclass that replaces
        _build_learn_fn with its own loss (CQL's min-Q penalty, CRR's
        weighted regression) must not be chained through it. The
        recurrent subclass opts out explicitly (sequence state columns
        need per-chunk handling)."""
        return (
            type(self)._build_learn_fn is SACJaxPolicy._build_learn_fn
            and type(self)._device_update_fn
            is SACJaxPolicy._device_update_fn
        )

    def __init__(self, observation_space, action_space, config):
        # Bypass JaxPolicy model construction: SAC has its own nets.
        from ray_tpu.policy.policy import Policy

        Policy.__init__(self, observation_space, action_space, config)
        self.action_dim = int(np.prod(action_space.shape))
        self.low = float(np.min(action_space.low))
        self.high = float(np.max(action_space.high))

        self.mesh = sharding_lib.resolve_mesh(config)
        self.n_shards = sharding_lib.num_shards(self.mesh)
        self._param_sharding = sharding_lib.replicated(self.mesh)
        self._data_sharding = sharding_lib.batch_sharded(self.mesh)

        pm_cfg = config.get("policy_model_config") or {}
        qm_cfg = config.get("q_model_config") or {}
        self.actor, self.critic = self._make_nets(pm_cfg, qm_cfg)

        seed = int(config.get("seed") or 0)
        self._rng = jax.random.PRNGKey(seed)
        self._rng, r1, r2 = jax.random.split(self._rng, 3)
        actor_params, critic_params = self._init_net_params(r1, r2)
        log_alpha = jnp.asarray(
            np.log(config.get("initial_alpha", 1.0)), jnp.float32
        )
        self.params = _tree_to_device(
            {
                "actor": actor_params,
                "critic": critic_params,
                "log_alpha": log_alpha,
            },
            self._param_sharding,
        )
        self.aux_state = _tree_to_device(
            {"target_critic": critic_params}, self._param_sharding
        )

        opt = config.get("optimization") or {}
        self._tx_actor = optax.adam(opt.get("actor_learning_rate", 3e-4))
        self._tx_critic = optax.adam(
            opt.get("critic_learning_rate", 3e-4)
        )
        self._tx_alpha = optax.adam(
            opt.get("entropy_learning_rate", 3e-4)
        )
        self.opt_state = _tree_to_device(
            {
                "actor": self._tx_actor.init(self.params["actor"]),
                "critic": self._tx_critic.init(self.params["critic"]),
                "log_alpha": self._tx_alpha.init(
                    self.params["log_alpha"]
                ),
            },
            self._param_sharding,
        )

        te = config.get("target_entropy", "auto")
        self.target_entropy = (
            -float(self.action_dim) if te in (None, "auto") else float(te)
        )
        self.tau = float(config.get("tau", 5e-3))
        self.gamma = float(config.get("gamma", 0.99))
        self.n_step = int(config.get("n_step", 1))

        self.coeff_values = {}
        self._learn_fns = {}
        self._multi_learn_fns = {}
        self._action_fn = None
        self.num_grad_updates = 0
        # device-side flattened actor snapshots maintained by the
        # fused multi-update path for round-trip-free weight sync
        self._flat_actor_dev = None
        self._flat_actor_ready = None

        # SAC's squashed-Gaussian sampling IS its exploration (the
        # reference uses StochasticSampling for SAC too); the strategy
        # object exists for the uniform hook surface (state, weights).
        self._init_exploration()

    def get_initial_state(self):
        return []

    # -- net construction (overridden by RNNSAC) -------------------------

    def _make_nets(self, pm_cfg, qm_cfg):
        actor = _ActorNet(
            self.action_dim,
            tuple(pm_cfg.get("fcnet_hiddens", (256, 256))),
            pm_cfg.get("fcnet_activation", "relu"),
        )
        critic = _TwinQNet(
            tuple(qm_cfg.get("fcnet_hiddens", (256, 256))),
            qm_cfg.get("fcnet_activation", "relu"),
        )
        return actor, critic

    def _init_net_params(self, r1, r2):
        dummy_obs = jnp.zeros(
            (2,) + tuple(self.observation_space.shape), jnp.float32
        )
        dummy_act = jnp.zeros((2, self.action_dim), jnp.float32)
        return (
            self.actor.init(r1, dummy_obs),
            self.critic.init(r2, dummy_obs, dummy_act),
        )

    # -- inference -------------------------------------------------------

    def _build_action_fn(self):
        actor = self.actor
        low, high = self.low, self.high
        exploration = self.exploration

        def fn(params, obs, rng, explore, coeffs, expl_state):
            dist_inputs = actor.apply(params["actor"], obs)
            dist = SquashedGaussian(dist_inputs, low=low, high=high)
            actions, logp, expl_state = exploration.sample_fn(
                dist, rng, explore, coeffs, expl_state
            )
            return (
                actions,
                {SampleBatch.ACTION_LOGP: logp},
                expl_state,
            )

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
        actions, extra, self._expl_state = self._action_fn(
            params, obs, rng, bool(explore),
            self._coeff_array(), self._expl_state,
        )
        return (
            np.asarray(actions),
            [],
            {k: np.asarray(v) for k, v in extra.items()},
        )

    # -- learning --------------------------------------------------------

    # Hooks the recurrent subclass overrides so ONE fused device_fn
    # serves both flat and sequence SAC:

    def _seq_resets(self, batch):
        """→ (resets for time-t forwards, resets for next-obs
        forwards); None for feedforward nets."""
        return None, None

    def _net_forward(self, net, params, *args, resets=None):
        """Apply an actor/critic net; feedforward nets ignore resets."""
        return net.apply(params, *args)

    def _loss_mask(self, batch):
        """Per-element validity mask for the losses (None = all)."""
        return None

    def _device_update_fn(self, batch_size=None, with_frames=False):
        """The single-update body shared by the per-batch program, the
        legacy fused multi-update scan, and the generic superstep
        (``JaxPolicy.learn_superstep``) — all run inside shard_map.
        ``batch_size``/``with_frames`` are part of the uniform
        signature; SAC's bespoke nets ignore both (flat obs only)."""
        actor, critic = self.actor, self.critic
        tx_a, tx_c, tx_al = (
            self._tx_actor,
            self._tx_critic,
            self._tx_alpha,
        )
        gamma, tau = self.gamma**self.n_step, self.tau
        target_entropy = self.target_entropy
        low, high = self.low, self.high
        axis = sharding_lib.data_axis(self.mesh)

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            obs = batch[SampleBatch.OBS].astype(jnp.float32)
            next_obs = batch[SampleBatch.NEXT_OBS].astype(jnp.float32)
            rewards = batch[SampleBatch.REWARDS].astype(jnp.float32)
            not_done = 1.0 - batch[SampleBatch.TERMINATEDS].astype(
                jnp.float32
            )
            actions = batch[SampleBatch.ACTIONS].astype(jnp.float32)
            resets_t, resets_tp1 = self._seq_resets(batch)
            mask = self._loss_mask(batch)
            if mask is None:
                mean = jnp.mean
            else:
                denom = jnp.maximum(jnp.sum(mask), 1.0)

                def mean(x):
                    return jnp.sum(x * mask) / denom

            rng = jax.random.fold_in(
                rng, jax.lax.axis_index(axis)
            )
            rng_t, rng_a = jax.random.split(rng)
            alpha = jnp.exp(params["log_alpha"])

            # ---- critic update ----
            next_dist = SquashedGaussian(
                self._net_forward(
                    actor, params["actor"], next_obs,
                    resets=resets_tp1,
                ),
                low=low, high=high,
            )
            next_a, next_logp = next_dist.sampled_action_logp(rng_t)
            tq1, tq2 = self._net_forward(
                critic, aux["target_critic"], next_obs, next_a,
                resets=resets_tp1,
            )
            target_q = jnp.minimum(tq1, tq2) - alpha * next_logp
            td_target = jax.lax.stop_gradient(
                rewards + gamma * not_done * target_q
            )

            def critic_loss(cp):
                q1, q2 = self._net_forward(
                    critic, cp, obs, actions, resets=resets_t
                )
                return (
                    mean(jnp.square(q1 - td_target))
                    + mean(jnp.square(q2 - td_target))
                ), (q1, q2)

            (c_loss, (q1, q2)), c_grads = jax.value_and_grad(
                critic_loss, has_aux=True
            )(sharding_lib.varying(params["critic"], axis))
            c_grads = jax.lax.pmean(c_grads, axis)
            c_upd, c_opt = tx_c.update(
                c_grads, opt_state["critic"], params["critic"]
            )
            new_critic = optax.apply_updates(params["critic"], c_upd)

            # ---- actor update (uses the fresh critic) ----
            def actor_loss(ap):
                dist = SquashedGaussian(
                    self._net_forward(
                        actor, ap, obs, resets=resets_t
                    ),
                    low=low, high=high,
                )
                a, logp = dist.sampled_action_logp(rng_a)
                aq1, aq2 = self._net_forward(
                    critic, new_critic, obs, a, resets=resets_t
                )
                return mean(
                    alpha * logp - jnp.minimum(aq1, aq2)
                ), logp

            (a_loss, logp_pi), a_grads = jax.value_and_grad(
                actor_loss, has_aux=True
            )(sharding_lib.varying(params["actor"], axis))
            a_grads = jax.lax.pmean(a_grads, axis)
            a_upd, a_opt = tx_a.update(
                a_grads, opt_state["actor"], params["actor"]
            )
            new_actor = optax.apply_updates(params["actor"], a_upd)

            # ---- alpha update ----
            def alpha_loss(log_alpha):
                return -mean(
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

            # ---- polyak target blend (reference tau soft update) ----
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
            new_aux = {"target_critic": new_target}
            stats = {
                "actor_loss": a_loss,
                "critic_loss": c_loss,
                "alpha_loss": al_loss,
                "alpha_value": alpha,
                "mean_q": mean(jnp.minimum(q1, q2)),
                "total_loss": a_loss + c_loss + al_loss,
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
        """The generic superstep scans THIS policy's own
        ``_device_update_fn`` — so unlike the legacy stacked path
        (``supports_stacked_learn``, which fuses the SAC body
        specifically), subclasses with their own update bodies
        (CQL's min-Q penalty, CRR's weighted regression) chain safely
        too. Only wholesale learn-program replacements and explicit
        opt-outs (RNNSAC's sequence state handling) are excluded."""
        return (
            not self._superstep_opt_out
            and type(self)._build_learn_fn is SACJaxPolicy._build_learn_fn
        )

    def _learn_coeffs(self):
        return {}  # the per-update path passes no coefficients

    def _updates_per_learn_call(self, batch_size: int) -> int:
        return 1

    @property
    def _td_refresh_uses_rng(self) -> bool:
        return True  # compute_td_error splits for the target resample

    def _after_superstep(self) -> None:
        # fused chains move the actor without refreshing the flat
        # device snapshots — drop them so sync can't ship stale weights
        self._flat_actor_dev = None
        self._flat_actor_ready = None

    def _build_multi_learn_fn(self, batch_size: int, k: int):
        """K replay updates fused into ONE program: ``lax.scan`` threads
        (params, opt_state, target) through k sequential updates over a
        stacked (k, batch, ...) replay sample, so one dispatch (one
        host round trip, one H2D transfer) buys k SGD steps. This is
        the TPU-shaped counterpart of the reference's training_intensity
        update loop (``dqn.py:336`` sample-and-learn rounds), which
        pays a full dispatch per update."""
        device_fn = self._device_update_fn()
        axis = sharding_lib.data_axis(self.mesh)

        def multi_fn(params, opt_state, aux, stacked, rng, coeffs):
            def body(carry, batch_k):
                params, opt_state, aux, rng = carry
                rng, sub = jax.random.split(rng)
                p, o, a, stats = device_fn(
                    params, opt_state, aux, batch_k, sub, coeffs
                )
                return (p, o, a, rng), stats

            (params, opt_state, aux, _), stats = jax.lax.scan(
                body, (params, opt_state, aux, rng), stacked
            )
            # report the final update's stats (a mean over the chain
            # would smear k distinct optimization states together)
            stats = jax.tree_util.tree_map(lambda x: x[-1], stats)
            # flattened post-chain actor, computed on device for free:
            # weight sync reads THIS single vector instead of pulling
            # the param tree leaf by leaf (one D2H per leaf)
            flat_actor = jnp.concatenate(
                [
                    x.reshape(-1).astype(jnp.float32)
                    for x in jax.tree_util.tree_leaves(
                        params["actor"]
                    )
                ]
            )
            return params, opt_state, aux, stats, flat_actor

        sharded = jax.shard_map(
            multi_fn,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(None, axis), P(), P()),
            out_specs=(P(), P(), P(), P(), P()),
        )
        rep = self._param_sharding
        dat = sharding_lib.batch_sharded(self.mesh, ndim_prefix=2)
        return sharding_lib.sharded_jit(
            sharded,
            in_specs=(rep, rep, rep, dat, rep, rep),
            out_specs=(rep, rep, rep, rep, rep),
            donate_argnums=(1,),
            label=f"multi_learn[{type(self).__name__}:{batch_size}x{k}]",
        )

    def learn_on_stacked_batch(
        self,
        stacked: Dict[str, np.ndarray],
        k: int,
        batch_size: int,
        *,
        defer_stats: bool = False,
    ) -> Dict:
        """Run k fused updates on a host tree of (k, batch, ...) arrays
        (one vectorized replay gather, reshaped). See
        :meth:`_build_multi_learn_fn`."""
        key = (batch_size, k)
        fn = self._multi_learn_fns.get(key)
        if fn is None:
            fn = self._build_multi_learn_fn(batch_size, k)
            self._multi_learn_fns[key] = fn
        sharding = sharding_lib.batch_sharded(self.mesh, ndim_prefix=2)
        if not any(
            isinstance(v, jax.Array) for v in stacked.values()
        ):
            # host-gathered chains cross H2D here; device-resident
            # replay hands jax arrays through (already resident)
            from ray_tpu.telemetry import metrics as telemetry_metrics

            telemetry_metrics.add_h2d_bytes(
                "learn", sharding_lib.tree_nbytes(stacked)
            )
        dev = jax.device_put(stacked, sharding)
        self._rng, rng = jax.random.split(self._rng)
        (
            self.params,
            self.opt_state,
            self.aux_state,
            stats,
            flat_actor,
        ) = fn(
            self.params, self.opt_state, self.aux_state, dev, rng, {}
        )
        # rotate the sync source: the PREVIOUS chain's actor (surely
        # computed by now) serves the next weight sync without waiting
        # on this chain — one round of staleness, same as sample_async
        self._flat_actor_ready = getattr(
            self, "_flat_actor_dev", None
        )
        self._flat_actor_dev = flat_actor
        self.num_grad_updates += k
        if defer_stats:
            return stats
        stats = jax.device_get(stats)
        return {k2: float(v) for k2, v in stats.items()}

    def _actor_unflatten(self, vec: np.ndarray):
        """Host-side inverse of the device-side actor flatten."""
        leaves, treedef = jax.tree_util.tree_flatten(
            self.params["actor"]
        )
        sizes = [int(np.prod(x.shape)) for x in leaves]
        parts = np.split(np.asarray(vec), np.cumsum(sizes)[:-1])
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                p.reshape(x.shape).astype(np.float32)
                for p, x in zip(parts, leaves)
            ],
        )

    def get_inference_weights(self):
        flat = getattr(self, "_flat_actor_ready", None)
        if flat is None:
            flat = getattr(self, "_flat_actor_dev", None)
        if flat is not None:
            return {"actor": self._actor_unflatten(jax.device_get(flat))}
        return super().get_inference_weights()

    def set_weights(self, weights) -> None:
        # any externally-set params invalidate the device-side flat
        # actor snapshots the fused path maintains
        self._flat_actor_dev = None
        self._flat_actor_ready = None
        super().set_weights(weights)

    def _td_error_device_fn(self):
        """Signed per-sample TD error of the min-twin critic vs the
        soft TD target — shared by ``compute_td_error`` (plain jit)
        and the superstep's in-scan prioritized refresh."""
        actor, critic = self.actor, self.critic
        gamma = self.gamma**self.n_step
        low, high = self.low, self.high

        def fn(params, aux, batch, rng):
            obs = batch[SampleBatch.OBS].astype(jnp.float32)
            next_obs = batch[SampleBatch.NEXT_OBS].astype(
                jnp.float32
            )
            rewards = batch[SampleBatch.REWARDS].astype(jnp.float32)
            not_done = 1.0 - batch[
                SampleBatch.TERMINATEDS
            ].astype(jnp.float32)
            actions = batch[SampleBatch.ACTIONS].astype(jnp.float32)
            alpha = jnp.exp(params["log_alpha"])
            next_dist = SquashedGaussian(
                actor.apply(params["actor"], next_obs),
                low=low,
                high=high,
            )
            next_a, next_logp = next_dist.sampled_action_logp(rng)
            tq1, tq2 = critic.apply(
                aux["target_critic"], next_obs, next_a
            )
            target_q = jnp.minimum(tq1, tq2) - alpha * next_logp
            td_target = rewards + gamma * not_done * target_q
            q1, q2 = critic.apply(params["critic"], obs, actions)
            return jnp.minimum(q1, q2) - td_target

        return fn

    def compute_td_error(self, samples) -> np.ndarray:
        """Per-sample |TD error| of the min-twin critic vs the soft TD
        target, for prioritized-replay priority refresh (reference
        sac_torch_policy keeps ``policy.td_error`` from the loss)."""
        if not hasattr(self, "_td_error_fn"):
            self._td_error_fn = jax.jit(self._td_error_device_fn())
        batch = self._td_input_tree(samples)
        self._rng, rng = jax.random.split(self._rng)
        td = self._td_error_fn(self.params, self.aux_state, batch, rng)
        return np.abs(np.asarray(td))

    def learn_on_device_batch(
        self, dev_batch, batch_size: int, *, defer_stats: bool = False
    ) -> Dict:
        """SAC's compiled fn threads aux_state (target critic) through the
        update, so phase 2 is overridden; phase 1 (prepare_batch) and
        learn_on_batch's composition are inherited from JaxPolicy.
        ``defer_stats`` matches the base contract: skip the blocking
        stats fetch so chained updates (training_intensity, learner
        threads) pipeline on-device."""
        fn = self.learn_fn(batch_size)
        self._rng, rng = jax.random.split(self._rng)
        self.params, self.opt_state, self.aux_state, stats = fn(
            self.params, self.opt_state, self.aux_state, dev_batch,
            rng, {},
        )
        # single-update path moves the actor without refreshing the
        # fused path's flat snapshots — drop them so sync can't ship
        # stale weights
        self._flat_actor_dev = None
        self._flat_actor_ready = None
        self.num_grad_updates += 1
        if defer_stats:
            return stats
        if self.config.get("deferred_stats"):
            # same one-call lag as the JaxPolicy base
            # (docs/data_plane.md): return the previous update's
            # stats so this dispatch never blocks on its own program
            prev = self.__dict__.get("_lagged_stats")
            self.__dict__["_lagged_stats"] = stats
            if prev is None:
                return {}
            stats = jax.device_get(prev)
        else:
            stats = jax.device_get(stats)
        return {k: float(v) for k, v in stats.items()}

    def update_target(self) -> None:
        """No-op: polyak blending happens inside the learn program."""

    def _batch_to_train_tree(self, samples: SampleBatch):
        keys = [
            SampleBatch.OBS,
            SampleBatch.NEXT_OBS,
            SampleBatch.ACTIONS,
            SampleBatch.REWARDS,
            SampleBatch.TERMINATEDS,
        ]
        out = {}
        for k in keys:
            if k not in samples:
                continue
            v = np.asarray(samples[k])
            if v.dtype == np.float64:
                # MuJoCo obs arrive f64; the loss casts to f32 on
                # device anyway — cast host-side and halve the H2D
                # bytes
                v = v.astype(np.float32)
            out[k] = v
        return out


class SAC(DQN):
    _default_policy_class = SACJaxPolicy

    @classmethod
    def get_default_config(cls) -> SACConfig:
        return SACConfig(cls)
