"""DQN family: double/dueling DQN with (prioritized) replay.

Counterpart of the reference's ``rllib/algorithms/dqn/dqn.py`` (config,
``training_step :336`` — shared by all off-policy algos) and
``rllib/algorithms/simple_q/simple_q.py:256``. The TD-loss/optimizer runs as
one jitted program; the target network lives in the policy's replicated
``aux_state`` (the reference keeps a second torch module) and is refreshed
by a host-side copy every ``target_network_update_freq`` trained steps.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.algorithms.algorithm import (
    Algorithm,
    NUM_AGENT_STEPS_SAMPLED,
    NUM_ENV_STEPS_SAMPLED,
)
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID, SampleBatch
from ray_tpu.execution.replay_buffer import (
    DevicePrioritizedReplayBuffer,
    DeviceReplayBuffer,
    MultiAgentReplayBuffer,
    PrioritizedReplayBuffer,
    resolve_device_resident,
    resolve_device_tree,
)
from ray_tpu.execution.rollout_ops import synchronous_parallel_sample
from ray_tpu.execution.train_ops import (
    NUM_AGENT_STEPS_TRAINED,
    NUM_ENV_STEPS_TRAINED,
)
from ray_tpu.algorithms.dqn.dqn_model import (
    DQNModel,
    categorical_projection,
)
from ray_tpu.policy.jax_policy import JaxPolicy
from ray_tpu.util import tracing


class DQNConfig(AlgorithmConfig):
    """reference dqn.py DQNConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or DQN)
        self.lr = 5e-4
        self.train_batch_size = 32
        self.rollout_fragment_length = 4
        self.gamma = 0.99
        self.num_steps_sampled_before_learning_starts = 1000
        self.target_network_update_freq = 500
        self.double_q = True
        self.dueling = True
        self.n_step = 1
        # Rainbow knobs (reference dqn.py: num_atoms/v_min/v_max for
        # C51 distributional Q, noisy/sigma0 for NoisyNet exploration)
        self.num_atoms = 1
        self.v_min = -10.0
        self.v_max = 10.0
        self.noisy = False
        self.sigma0 = 0.5
        self.replay_buffer_config = {
            "capacity": 50000,
            "prioritized_replay": False,
            "prioritized_replay_alpha": 0.6,
            "prioritized_replay_beta": 0.4,
        }
        self.epsilon_timesteps = 10000
        self.final_epsilon = 0.02
        self.initial_epsilon = 1.0
        self.training_intensity = None
        self.grad_clip = 40.0

    def training(
        self,
        *,
        target_network_update_freq: Optional[int] = None,
        double_q: Optional[bool] = None,
        dueling: Optional[bool] = None,
        n_step: Optional[int] = None,
        num_atoms: Optional[int] = None,
        v_min: Optional[float] = None,
        v_max: Optional[float] = None,
        noisy: Optional[bool] = None,
        sigma0: Optional[float] = None,
        replay_buffer_config: Optional[Dict] = None,
        num_steps_sampled_before_learning_starts: Optional[int] = None,
        epsilon_timesteps: Optional[int] = None,
        final_epsilon: Optional[float] = None,
        **kwargs,
    ) -> "DQNConfig":
        super().training(**kwargs)
        if target_network_update_freq is not None:
            self.target_network_update_freq = target_network_update_freq
        if double_q is not None:
            self.double_q = double_q
        if dueling is not None:
            self.dueling = dueling
        if n_step is not None:
            self.n_step = n_step
        for name, val in (
            ("num_atoms", num_atoms),
            ("v_min", v_min),
            ("v_max", v_max),
            ("noisy", noisy),
            ("sigma0", sigma0),
        ):
            if val is not None:
                setattr(self, name, val)
        if replay_buffer_config is not None:
            self.replay_buffer_config.update(replay_buffer_config)
        if num_steps_sampled_before_learning_starts is not None:
            self.num_steps_sampled_before_learning_starts = (
                num_steps_sampled_before_learning_starts
            )
        if epsilon_timesteps is not None:
            self.epsilon_timesteps = epsilon_timesteps
        if final_epsilon is not None:
            self.final_epsilon = final_epsilon
        return self


def adjust_nstep(n_step: int, gamma: float, batch: SampleBatch) -> None:
    """In-place n-step reward folding (reference
    ``rllib/utils/replay_buffers/utils.py`` / dqn postprocessing):
    rewards[t] ← sum_{k<n} gamma^k r[t+k], new_obs[t] ← obs[t+n] with
    termination-aware truncation.

    Records the actual number of folded steps per row in an ``n_steps``
    column so the TD target can discount the bootstrap by gamma**k rather
    than a uniform gamma**n_step — fragment tails fold fewer than n_step
    rewards (the reference sidesteps this by only applying n-step to
    episode-sliced trajectories)."""
    n = batch.count
    rewards = np.asarray(batch[SampleBatch.REWARDS], np.float32)
    dones = np.asarray(batch[SampleBatch.TERMINATEDS], bool)
    next_obs = np.asarray(batch[SampleBatch.NEXT_OBS])
    new_rewards = rewards.copy()
    new_next = next_obs.copy()
    new_dones = dones.copy()
    n_steps = np.ones(n, np.float32)
    for t in range(n):
        acc = rewards[t]
        last = t
        for k in range(1, n_step):
            if t + k >= n or dones[last]:
                break
            acc += (gamma**k) * rewards[t + k]
            last = t + k
        new_rewards[t] = acc
        new_next[t] = next_obs[last]
        new_dones[t] = dones[last]
        n_steps[t] = last - t + 1
    batch[SampleBatch.REWARDS] = new_rewards
    batch[SampleBatch.NEXT_OBS] = new_next
    batch[SampleBatch.TERMINATEDS] = new_dones
    batch["n_steps"] = n_steps


_EPSILON_KEYS = ("initial_epsilon", "final_epsilon", "epsilon_timesteps")


def _epsilon_exploration_config(config: Dict, force_keys=()) -> Dict:
    """Fold DQN's flat epsilon knobs into exploration_config so the
    pluggable EpsilonGreedy strategy picks them up. A user-supplied
    exploration_config wins over the flat DQNConfig defaults (which
    always exist), EXCEPT for keys in ``force_keys`` — the explicitly
    mutated knobs of an update_config/PBT call, which must override
    stale fold-ins from init time."""
    ec = dict(config.get("exploration_config") or {})
    for key in _EPSILON_KEYS:
        if key in config and (key not in ec or key in force_keys):
            ec[key] = config[key]
    # Ape-X per-worker epsilon ladder (reference apex_dqn.py /
    # rllib per_worker_exploration): worker i (1-based) of n explores
    # with the constant eps_i = 0.4 ** (1 + 7*(i-1)/(n-1)).
    if config.get("per_worker_exploration"):
        i = int(config.get("worker_index", 0))
        n = max(1, int(config.get("num_workers", 1)))
        if i > 0:
            exponent = 1.0 + 7.0 * (i - 1) / max(1, n - 1)
            eps = 0.4**exponent
            ec.update(
                initial_epsilon=eps,
                final_epsilon=eps,
                epsilon_timesteps=1,
            )
    return ec


class DQNJaxPolicy(JaxPolicy):
    """Double/dueling TD loss (reference dqn_torch_policy.py). Action
    selection is epsilon-greedy via the pluggable exploration framework
    (reference rllib/utils/exploration/epsilon_greedy.py)."""

    default_exploration = "EpsilonGreedy"
    # recurrent Q needs sequence replay with burn-in — that's R2D2's
    # machinery (which sets this True); plain DQN's uniform/PER row
    # replay cannot train an LSTM correctly
    _supports_recurrent = False

    def __init__(self, observation_space, action_space, config):
        config = dict(config)
        config["exploration_config"] = _epsilon_exploration_config(config)
        # Non-recurrent configs get the dedicated dueling/C51/noisy
        # Q-model (reference dqn_torch_model.py DQNTorchModel); the
        # recurrent path (R2D2's use_lstm) keeps the catalog LSTM whose
        # logits head IS the Q head.
        model_cfg = dict(config.get("model") or {})
        if (
            model_cfg.get("use_lstm") or model_cfg.get("use_attention")
        ) and not self._supports_recurrent:
            raise ValueError(
                "DQN with a recurrent model (use_lstm/use_attention) "
                "requires sequence replay — use the R2D2 algorithm "
                "(reference r2d2.py) instead"
            )
        self._uses_dqn_model = not any(
            model_cfg.get(k)
            for k in (
                "use_lstm",
                "use_attention",
                "custom_model",
                "use_transformer",
            )
        )
        if not self._uses_dqn_model:
            # the fallback treats the model's logits head as Q values —
            # atom-level outputs and weight noise need the built-in model
            if int(config.get("num_atoms", 1)) > 1:
                raise ValueError(
                    "distributional Q (num_atoms > 1) requires the "
                    "built-in DQNModel; it is unavailable with "
                    "use_lstm/use_attention/use_transformer/"
                    "custom_model"
                )
            if config.get("noisy"):
                raise ValueError(
                    "noisy nets require the built-in DQNModel; "
                    "unavailable with use_lstm/use_attention/"
                    "use_transformer/custom_model"
                )
        if self._uses_dqn_model:
            from ray_tpu.models.catalog import MODEL_DEFAULTS
            from ray_tpu.models.cnn import get_filter_config

            cfg = {**MODEL_DEFAULTS, **model_cfg}
            is_image = len(observation_space.shape) == 3
            if is_image:
                # VisionNet conventions: post-conv widths/activation
                # from post_fcnet_*, empty coerces to [512]
                hiddens = tuple(cfg["post_fcnet_hiddens"] or [512])
                activation = cfg["post_fcnet_activation"]
                filters = cfg["conv_filters"] or get_filter_config(
                    observation_space.shape
                )
                conv_filters = tuple(
                    (
                        int(c),
                        tuple(k) if isinstance(k, (list, tuple)) else (k, k),
                        tuple(s) if isinstance(s, (list, tuple)) else (s, s),
                    )
                    for c, k, s in filters
                )
            else:
                hiddens = tuple(cfg["fcnet_hiddens"])
                activation = cfg["fcnet_activation"]
                conv_filters = None
            config["model"] = {
                **model_cfg,
                "custom_model": DQNModel,
                "custom_model_config": {
                    "hiddens": hiddens,
                    "activation": activation,
                    "use_conv": is_image,
                    "conv_filters": conv_filters,
                    "conv_activation": cfg["conv_activation"],
                    "num_atoms": int(config.get("num_atoms", 1)),
                    "v_min": float(config.get("v_min", -10.0)),
                    "v_max": float(config.get("v_max", 10.0)),
                    "dueling": bool(config.get("dueling", True)),
                    "noisy": bool(config.get("noisy", False)),
                    "sigma0": float(config.get("sigma0", 0.5)),
                },
            }
        super().__init__(observation_space, action_space, config)
        if self.model.is_recurrent and not self._supports_recurrent:
            raise ValueError(
                "DQN cannot train a recurrent custom model with "
                "row replay — use R2D2 (reference r2d2.py)"
            )
        self._steps_since_target_update = 0

    def _init_aux_state(self):
        return {"target_params": self.params}

    def _refold_exploration_config(self, new_config: Dict) -> None:
        self.config["exploration_config"] = _epsilon_exploration_config(
            self.config, force_keys=new_config
        )

    # knobs baked into the built model's architecture/support grid: the
    # loss would retrace but the model cannot change post-init
    _ARCH_KEYS = ("num_atoms", "noisy", "dueling", "v_min", "v_max", "sigma0")

    def update_config(self, new_config: Dict) -> None:
        for key in self._ARCH_KEYS:
            if key in new_config and new_config[key] != self.config.get(
                key
            ):
                raise ValueError(
                    f"DQN architecture knob {key!r} is baked into the "
                    "built Q-model and cannot be mutated via "
                    "update_config; rebuild the policy instead"
                )
        super().update_config(new_config)
        if hasattr(self, "_td_error_fn"):
            del self._td_error_fn

    def update_target(self) -> None:
        """Copy online → target (reference update_target in
        dqn_torch_policy)."""
        self.aux_state = {"target_params": self.params}

    def _apply_model_for_actions(self, params, obs, rng, explore):
        """NoisyNet exploration: resample weight noise per action call
        while exploring (the reference's NoisyLayer resamples every
        training-mode forward); evaluation uses the mean weights."""
        if explore and self._uses_dqn_model and self.config.get("noisy"):
            return self.model.apply(params, obs, noise_key=rng)
        return super()._apply_model_for_actions(params, obs, rng, explore)

    def extra_action_out(self, dist_inputs, value, dist, rng):
        # The per-action Q values already ride ACTION_DIST_INPUTS (the
        # model head IS the Q head); don't duplicate them as a second
        # replay-buffer column.
        return {}

    # -- loss ------------------------------------------------------------

    def _q_dist(self, params, obs, noise_key=None):
        """→ (q_values, support_logits (B, A, atoms), support_probs or
        None). The DQNModel path exposes atom-level outputs; the
        recurrent/custom fallback treats the logits head as Q values."""
        if self._uses_dqn_model:
            return self.model.apply(
                params, obs, noise_key=noise_key,
                method=DQNModel.q_dist,
            )
        q, _, _ = self.model_forward(params, obs)
        return q, q[..., None], None

    def _td_error(self, params, aux, batch, rng=None):
        """Per-sample TD error (shared by the loss and the PER priority
        refresh; reference dqn_torch_policy computes it inside QLoss and
        exposes policy.compute_td_error). For distributional Q
        (num_atoms > 1) the "TD error" is the per-sample softmax
        cross-entropy to the projected target distribution, exactly the
        quantity the reference feeds PER in the C51 case."""
        cfg = self.config
        gamma = cfg.get("gamma", 0.99)
        n_step = cfg.get("n_step", 1)
        num_atoms = int(cfg.get("num_atoms", 1))
        target_params = aux["target_params"]
        # independent weight noise for online / target / selection nets
        # (NoisyNet training regime); None → mean weights
        k1 = k2 = k3 = None
        if rng is not None and cfg.get("noisy"):
            k1, k2, k3 = jax.random.split(rng, 3)

        q_all, logits_all, _ = self._q_dist(
            params, batch[SampleBatch.OBS], k1
        )
        q_next_target, _, probs_next_target = self._q_dist(
            target_params, batch[SampleBatch.NEXT_OBS], k2
        )
        actions = batch[SampleBatch.ACTIONS].astype(jnp.int32)
        q_sel = jnp.take_along_axis(
            q_all, actions[:, None], axis=-1
        ).squeeze(-1)

        if cfg.get("double_q", True):
            q_next_online, _, _ = self._q_dist(
                params, batch[SampleBatch.NEXT_OBS], k3
            )
            next_actions = jnp.argmax(q_next_online, axis=-1)
        else:
            next_actions = jnp.argmax(q_next_target, axis=-1)

        not_done = 1.0 - batch[SampleBatch.TERMINATEDS].astype(
            jnp.float32
        )
        # Per-row bootstrap exponent: fragment tails fold fewer than
        # n_step rewards (recorded by adjust_nstep in "n_steps").
        steps = batch.get("n_steps")
        bootstrap_discount = (
            gamma ** steps if steps is not None else gamma**n_step
        )
        if isinstance(bootstrap_discount, float):
            bootstrap_discount = jnp.full_like(q_sel, bootstrap_discount)

        if num_atoms > 1:
            # C51: cross-entropy to the projected target distribution
            p_next = jnp.take_along_axis(
                probs_next_target,
                next_actions[:, None, None],
                axis=1,
            ).squeeze(1)  # (B, atoms)
            m = categorical_projection(
                p_next,
                batch[SampleBatch.REWARDS],
                bootstrap_discount,
                not_done,
                float(cfg.get("v_min", -10.0)),
                float(cfg.get("v_max", 10.0)),
            )
            m = jax.lax.stop_gradient(m)
            logits_sel = jnp.take_along_axis(
                logits_all, actions[:, None, None], axis=1
            ).squeeze(1)  # (B, atoms)
            td_error = -jnp.sum(
                m * jax.nn.log_softmax(logits_sel, axis=-1), axis=-1
            )
            return td_error, q_sel, q_all

        q_next = jnp.take_along_axis(
            q_next_target, next_actions[:, None], axis=-1
        ).squeeze(-1)
        td_target = (
            batch[SampleBatch.REWARDS]
            + bootstrap_discount
            * not_done
            * jax.lax.stop_gradient(q_next)
        )
        td_error = q_sel - jax.lax.stop_gradient(td_target)
        return td_error, q_sel, q_all

    def loss_with_aux(self, params, aux, batch, rng, coeffs):
        td_error, q_sel, q_all = self._td_error(params, aux, batch, rng)
        if int(self.config.get("num_atoms", 1)) > 1:
            # td_error is already the per-sample cross-entropy loss
            per_sample = td_error
        else:
            # Huber loss (reference huber_loss, delta=1)
            abs_err = jnp.abs(td_error)
            per_sample = jnp.where(
                abs_err < 1.0,
                0.5 * jnp.square(td_error),
                abs_err - 0.5,
            )
        weights = batch.get("weights", jnp.ones_like(per_sample))
        loss = jnp.mean(weights * per_sample)
        stats = {
            "mean_q": jnp.mean(q_sel),
            "mean_td_error": jnp.mean(td_error),
            "max_q": jnp.max(q_all),
        }
        return loss, stats

    @property
    def _td_refresh_uses_rng(self) -> bool:
        # the priority pass consumes a host rng split only under
        # NoisyNet (compute_td_error's split discipline)
        return bool(self.config.get("noisy"))

    def _td_error_device_fn(self):
        """Signed per-sample TD error — shared by ``compute_td_error``
        and the superstep's in-scan prioritized refresh. Non-noisy
        configs ignore the rng argument (the per-update path passes
        None there; the in-scan caller a dummy key)."""
        noisy = bool(self.config.get("noisy"))

        def fn(params, aux, batch, rng):
            td, _, _ = self._td_error(
                params, aux, batch, rng if noisy else None
            )
            return td

        return fn

    def compute_td_error(self, samples) -> np.ndarray:
        """Per-sample |TD error| for prioritized-replay updates, aligned
        with the rows of ``samples`` (pre-tiling/trim: uses a plain jit
        forward, not the sharded nest)."""
        if not hasattr(self, "_td_error_fn"):
            self._td_error_fn = jax.jit(self._td_error_device_fn())
        batch = self._td_input_tree(samples)
        # NoisyNet: sample weight noise for the priority pass too, so
        # priorities are computed under the same training-mode network
        # family the loss minimizes (mean weights would decorrelate PER
        # priorities from the actual training TD errors).
        rng = None
        if self.config.get("noisy"):
            self._rng, rng = jax.random.split(self._rng)
        td = self._td_error_fn(self.params, self.aux_state, batch, rng)
        return np.abs(np.asarray(td))

    def after_learn_on_batch(self, stats):
        self._steps_since_target_update += 1
        return {}


class DQN(Algorithm):
    _default_policy_class = DQNJaxPolicy

    @classmethod
    def get_default_config(cls) -> DQNConfig:
        return DQNConfig(cls)

    def setup(self, config: Dict) -> None:
        super().setup(config)
        rb_cfg = config.get("replay_buffer_config") or {}
        self.local_replay_buffer = MultiAgentReplayBuffer(
            capacity=rb_cfg.get("capacity", 50000),
            prioritized=rb_cfg.get("prioritized_replay", False),
            alpha=rb_cfg.get("prioritized_replay_alpha", 0.6),
            seed=config.get("seed"),
            device_resident=resolve_device_resident(
                config, config.get("_mesh")
            ),
            device_tree=resolve_device_tree(
                config, config.get("_mesh")
            ),
            mesh=config.get("_mesh"),
            memory_cap_bytes=config.get("replay_memory_cap_bytes"),
            # columns convert to the policy's train tree ONCE, at
            # insert — the single H2D crossing of the device plane
            replay_columns_fn=lambda pid, sb: self.get_policy(
                pid
            ).replay_columns(sb),
        )
        self._last_target_update = 0

    def on_fleet_change(self, added, removed) -> None:
        """Elastic fleet: the synchronous sampling path re-reads
        ``workers.remote_workers()`` every round and needs nothing;
        the ``sample_async`` path holds one pending ref per worker of
        LAST round's fleet — drop them so the next round re-issues
        against the current fleet instead of ray.get-ing a drained
        worker's ref."""
        super().on_fleet_change(added, removed)
        if removed and getattr(self, "_pending_sample_refs", None):
            import ray_tpu as _ray

            try:
                _ray.free(self._pending_sample_refs)
            except Exception:
                pass
            self._pending_sample_refs = None

    def _single_update(self, prioritized: bool, kwargs: Dict) -> Dict:
        """One replay sample + learn round (the classic path), with
        per-sample PER priority refresh."""
        config = self.config
        train_info: Dict = {}
        train_batch = self.local_replay_buffer.sample(
            config["train_batch_size"], **kwargs
        )
        for pid, b in train_batch.policy_batches.items():
            policy = self.get_policy(pid)
            if getattr(b, "is_device_resident", False):
                # device plane: rows are already resident on the
                # learner mesh — learn without any H2D transfer
                info = policy.learn_on_device_batch(
                    dict(b.tree), b.count
                )
            else:
                info = policy.learn_on_batch(b)
            train_info[pid] = info
            if prioritized:
                buf = self.local_replay_buffer.buffers[pid]
                if isinstance(
                    buf,
                    (
                        PrioritizedReplayBuffer,
                        DevicePrioritizedReplayBuffer,
                    ),
                ):
                    # Per-sample |TD error| refresh (reference
                    # dqn.py training_step → update_priorities):
                    # a batch-mean scalar would cancel +/- errors
                    # and collapse PER to uniform sampling.
                    # Policies without per-sample errors (e.g.
                    # continuous-action subclasses) fall back to
                    # the batch-mean scalar.
                    idx = (
                        b.indices
                        if getattr(b, "is_device_resident", False)
                        else b["batch_indexes"]
                    )
                    if hasattr(policy, "compute_td_error"):
                        td = policy.compute_td_error(b)
                    else:
                        td = np.full(
                            len(idx),
                            abs(info.get("mean_td_error", 0.0)),
                        )
                    buf.update_priorities(idx, td + 1e-6)
            self._counters[NUM_ENV_STEPS_TRAINED] += b.count
        return train_info

    def _resolve_superstep_k(self) -> int:
        """K of the fused superstep contract for this run
        (sharding.superstep.resolve_superstep, cached)."""
        k = self.__dict__.get("_superstep_k")
        if k is None:
            from ray_tpu.sharding.superstep import resolve_superstep

            k = self._superstep_k = resolve_superstep(
                self.config, self.config.get("_mesh")
            )
        return k

    def _chained_updates(
        self,
        updates: int,
        prioritized: bool = False,
        beta: float = 0.4,
    ) -> Dict:
        """``updates`` replay SGD rounds back to back.

        With the superstep contract resolved on (``config.superstep``,
        docs/data_plane.md), full windows of K updates run as ONE
        compiled program per policy: one dispatch, one stats readback,
        device-replay rows gathered in place by the scan — the uniform
        generalization of what used to be a SAC-only stacked path.
        Prioritized replay chains here too (per-update ``|td|``
        refresh ships back as one stacked D2H, applied in update
        order; draws within a window see priorities as of window
        start — the documented staleness). The remainder (and policies
        whose programs can't ride the scan) falls back to per-update
        dispatch with deferred stats, so the programs still queue
        on-device and the per-dispatch latency amortizes across the
        chain; bounded lag keeps device memory in check. Others loop
        learn_on_batch."""
        import jax

        from ray_tpu import sharding as sharding_lib
        from ray_tpu.policy.jax_policy import JaxPolicy
        from ray_tpu.telemetry import metrics as telemetry_metrics

        config = self.config
        train_info: Dict = {}

        pols = {
            pid: self.get_policy(pid)
            for pid in self.workers.local_worker().policy_map
        }
        bs = int(config["train_batch_size"])
        K = self._resolve_superstep_k()
        left = updates
        if K > 1 and all(
            getattr(p, "supports_superstep", False)
            # the superstep skips prepare_batch's trim/tile, so the
            # per-update batch must already divide the data shards
            and bs % max(1, getattr(p, "n_shards", 1)) == 0
            for p in pols.values()
        ):
            from ray_tpu.execution.train_ops import (
                superstep_train_replay,
            )

            while left >= K:
                fused = False
                for pid, policy in pols.items():
                    buf = self.local_replay_buffer.buffers.get(pid)
                    if buf is None or len(buf) < bs:
                        continue
                    info = superstep_train_replay(
                        self,
                        policy,
                        buf,
                        K,
                        K,
                        bs,
                        prioritized=prioritized,
                        beta=beta,
                    )
                    if info is None:
                        # frame-pool/ragged batches: this run can't
                        # ride the scan — per-update path from here on
                        self._superstep_k = 1
                        break
                    fused = True
                    train_info[pid] = info
                    self._counters[NUM_ENV_STEPS_TRAINED] += K * bs
                if not fused or self._superstep_k == 1:
                    if fused:
                        left -= K
                    break
                left -= K
        if left <= 0:
            return train_info
        if prioritized:
            # leftover prioritized updates keep the classic
            # sample → learn → refresh cadence
            for _ in range(left):
                info = self._single_update(True, {"beta": beta})
                train_info.update(info)
            return train_info

        for _ in range(left):
            train_batch = self.local_replay_buffer.sample(
                config["train_batch_size"]
            )
            for pid, b in train_batch.policy_batches.items():
                policy = self.get_policy(pid)
                device_res = getattr(b, "is_device_resident", False)
                deferable = device_res or (
                    isinstance(policy, JaxPolicy)
                    and (
                        type(policy).learn_on_batch
                        is JaxPolicy.learn_on_batch
                    )
                    and (
                        type(policy).after_learn_on_batch
                        is JaxPolicy.after_learn_on_batch
                    )
                )
                if deferable:
                    if device_res:
                        dev, bsize = dict(b.tree), b.count
                    else:
                        tree, bsize = policy.prepare_batch(b)
                        telemetry_metrics.add_h2d_bytes(
                            "learn", sharding_lib.tree_nbytes(tree)
                        )
                        dev = jax.device_put(
                            tree, policy.batch_shardings(tree)
                        )
                    lazy = policy.learn_on_device_batch(
                        dev, bsize, defer_stats=True
                    )
                    pend = self._pending_stats = getattr(
                        self, "_pending_stats", []
                    )
                    pend.append((pid, lazy))
                    while len(pend) > 3:  # bounded on-device queue
                        old_pid, old = pend.pop(0)
                        stats = jax.device_get(old)
                        train_info[old_pid] = {
                            k: float(v) for k, v in stats.items()
                        }
                else:
                    train_info[pid] = policy.learn_on_batch(b)
                self._counters[NUM_ENV_STEPS_TRAINED] += b.count
        pend = getattr(self, "_pending_stats", None)
        while pend:
            pid, lazy = pend.pop(0)
            stats = jax.device_get(lazy)
            train_info[pid] = {
                k: float(v) for k, v in stats.items()
            }
        return train_info

    def _materialize_compressed(self, batch):
        """Rebuild stacked observation columns from worker-compressed
        frame pools (``ops/framestack.compress_replay_obs`` format:
        the pool covers OBS and NEXT_OBS exactly, terminal stacks
        included, so ``materialize_fragment`` is byte-exact here)."""
        from ray_tpu.data.sample_batch import MultiAgentBatch
        from ray_tpu.ops.framestack import (
            FRAMES as _FRAMES,
            materialize_fragment,
        )

        def mat(pid, sb):
            if _FRAMES not in sb:
                return sb
            k = int(
                self.get_policy(pid).observation_space.shape[-1]
            )
            return SampleBatch(materialize_fragment(dict(sb), k))

        if isinstance(batch, MultiAgentBatch):
            batch.policy_batches = {
                pid: mat(pid, sb)
                for pid, sb in batch.policy_batches.items()
            }
            return batch
        return mat(DEFAULT_POLICY_ID, batch)

    def __getstate__(self) -> Dict:
        """Checkpoint the replay buffer alongside the policy state
        (device rings pull back to host numpy; restore re-uploads) —
        an off-policy restore without its buffer replays the warmup
        from scratch."""
        state = super().__getstate__()
        buf = getattr(self, "local_replay_buffer", None)
        if buf is not None:
            state["replay_buffer"] = buf.get_state()
        return state

    def __setstate__(self, state: Dict) -> None:
        super().__setstate__(state)
        buf = getattr(self, "local_replay_buffer", None)
        if buf is not None and "replay_buffer" in state:
            buf.set_state(state["replay_buffer"])

    def _jax_rollout_engine_get(self):
        """Build (once) and return the fused-rollout engine
        (config.env_backend == "jax", docs/pipeline.md)."""
        eng = self.__dict__.get("_jax_rollout_engine")
        if eng is None:
            from ray_tpu.execution.jax_rollout import (
                JaxRolloutEngine,
                supports_jax_rollout_lane,
            )

            if int(self.config.get("n_step", 1)) > 1:
                raise ValueError(
                    "env_backend='jax' supports n_step=1 only (n-step "
                    "folding is a host-side postprocess)"
                )
            if self.config.get("policies"):
                raise ValueError(
                    "env_backend='jax' is single-policy"
                )
            policy = self.get_policy()
            env = self.workers.local_worker().env
            ok, reason = supports_jax_rollout_lane(policy, env)
            if not ok:
                raise ValueError(
                    "config.env_backend='jax' but the device rollout "
                    f"lane is unavailable: {reason}"
                )
            N = int(self.config.get("num_envs_per_worker", 1)) * max(
                1, int(self.config.get("num_workers", 0))
            )
            T = int(self.config.get("rollout_fragment_length", 4))
            with tracing.phase("setup:rollout_engine", num_envs=N):
                eng = JaxRolloutEngine(
                    policy,
                    env,
                    N,
                    T,
                    seed=self.config.get("seed"),
                    postprocess="none",
                )
            self._jax_rollout_engine = eng
            self._extra_metric_sources = [eng.get_metrics]
        return eng

    def _insert_rollout_tree(self, tree) -> None:
        """Absorb one dispatched rollout's device rows: the
        device-insert path for resident buffers (same donated scatter,
        zero H2D), one pull-back for host rings."""
        buf = self.local_replay_buffer._buffer(DEFAULT_POLICY_ID)
        with tracing.start_span("replay:insert"):
            if isinstance(buf, DeviceReplayBuffer):
                buf.add_device_tree(tree)
            else:
                import jax

                self.local_replay_buffer.add(
                    SampleBatch(jax.device_get(tree))
                )

    def _jax_rollout_fill(self) -> int:
        """Device rollout lane for the off-policy family
        (config.env_backend == "jax", docs/pipeline.md): one dispatched
        rollout produces transition rows ON the learner mesh, and a
        device-resident replay buffer absorbs them via
        ``add_device_tree`` — rollout rows never touch the host (a
        host-ring buffer pulls them back once, which still deletes the
        actor lane's sampling cost). Returns env steps taken."""
        tree, count = self._jax_rollout_engine_get().rollout()
        self._insert_rollout_tree(tree)
        return count

    def _interleave_ready(self) -> bool:
        """The learn-while-rollout cadence (``learn_while_rollout``,
        docs/data_plane.md) engages once the lane is warm: engine
        built, learning started, and the buffer already holds a full
        batch of PREVIOUS rounds' rows for the updates to draw from —
        until then the serial fill→learn order runs."""
        config = self.config
        if not config.get("learn_while_rollout"):
            return False
        if self.__dict__.get("_jax_rollout_engine") is None:
            return False
        buf = self.local_replay_buffer.buffers.get(DEFAULT_POLICY_ID)
        if buf is None or len(buf) < int(config["train_batch_size"]):
            return False
        return self._counters[NUM_ENV_STEPS_SAMPLED] >= config.get(
            "num_steps_sampled_before_learning_starts", 0
        )

    def _replay_update_phase(self, sampled_steps: int) -> Dict:
        """The learn half of the shared off-policy training_step:
        training-intensity debt → chained/fused replay updates (or the
        single classic round), then the target-network sync.
        ``sampled_steps`` is this round's env-step count (the debt
        accrual basis)."""
        config = self.config
        train_info: Dict = {}
        if not (
            self._counters[NUM_ENV_STEPS_SAMPLED]
            >= config.get("num_steps_sampled_before_learning_starts", 0)
            and len(self.local_replay_buffer) > 0
        ):
            return train_info
        rb_cfg = config.get("replay_buffer_config") or {}
        prioritized = rb_cfg.get("prioritized_replay", False)
        kwargs = (
            {"beta": rb_cfg.get("prioritized_replay_beta", 0.4)}
            if prioritized
            else {}
        )
        # training_intensity (reference dqn.py calculate_rr_weights
        # role): desired trained-steps : sampled-steps ratio. The
        # natural ratio of one update per round is
        # train_batch/rollout; a higher intensity runs MULTIPLE
        # replay updates per round — fused K-per-dispatch under
        # the superstep contract, per-update with deferred stats
        # otherwise, so either way consecutive SGD programs
        # pipeline on-device and the per-dispatch host cost
        # amortizes. PER joins the
        # chain only under a superstep (its stacked priority
        # refresh keeps the update-order tree writes); without
        # one, priorities must refresh between samples, so PER
        # keeps the one-update path.
        updates = 1
        ti = config.get("training_intensity")
        if ti and (
            not prioritized or self._resolve_superstep_k() > 1
        ):
            self._training_debt = (
                getattr(self, "_training_debt", 0.0)
                + sampled_steps * float(ti)
            )
            updates = int(
                self._training_debt // config["train_batch_size"]
            )
            self._training_debt -= (
                updates * config["train_batch_size"]
            )
        if updates > 1:
            train_info = self._chained_updates(
                updates,
                prioritized=prioritized,
                beta=kwargs.get("beta", 0.4),
            )
        elif updates == 1:
            train_info = self._single_update(prioritized, kwargs)
        # updates == 0: debt still accruing — sample-only round
        # target network sync
        if (
            self._counters[NUM_ENV_STEPS_TRAINED]
            - self._last_target_update
            >= config.get("target_network_update_freq", 500)
        ):
            with tracing.start_span("learn:target_sync"):
                for pid in self.workers.local_worker().policy_map:
                    self.get_policy(pid).update_target()
            self._last_target_update = self._counters[
                NUM_ENV_STEPS_TRAINED
            ]
            self._counters["num_target_updates"] += 1
        return train_info

    def training_step(self) -> Dict:
        """reference dqn.py:336 (shared off-policy training_step).

        With ``learn_while_rollout`` on the jax lane
        (docs/data_plane.md): the round's rollout-fill program is
        DISPATCHED (async), the replay superstep runs against the
        previous rounds' buffer contents while the fill executes on
        the mesh, and the fill's rows insert afterwards — acting and
        fused updates overlap in one cadence, at a one-round insert
        staleness (the draws simply cannot see rows that are still
        being produced)."""
        config = self.config
        batch = None
        interleaved = False
        jax_sampled = 0
        train_info: Dict = {}
        if config.get("env_backend") == "jax":
            if self._interleave_ready():
                tree, count = self._jax_rollout_engine_get().rollout()
                self._counters[NUM_ENV_STEPS_SAMPLED] += count
                # jax dispatch is asynchronous: the fill program is
                # queued, not finished — the superstep below neither
                # waits on it nor depends on its rows
                train_info = self._replay_update_phase(count)
                self._insert_rollout_tree(tree)
                interleaved = True
            else:
                jax_sampled = self._jax_rollout_fill()
                self._counters[NUM_ENV_STEPS_SAMPLED] += jax_sampled
        elif config.get("sample_async") and self.workers.remote_workers():
            # Overlap rollout with learning (reference's sample_async /
            # Ape-X decoupling): collect the fragment requested LAST
            # round, then immediately kick off the next one so the
            # workers sample while the driver replays + updates below.
            # Behavior weights lag the learner by exactly one round —
            # standard off-policy staleness.
            import ray_tpu as _ray

            refs = getattr(self, "_pending_sample_refs", None)
            if refs is None:
                refs = [
                    w.sample.remote()
                    for w in self.workers.remote_workers()
                ]
            batches = _ray.get(refs)
            self._pending_sample_refs = [
                w.sample.remote()
                for w in self.workers.remote_workers()
            ]
            from ray_tpu.data.sample_batch import concat_samples

            batch = concat_samples(batches)
        else:
            batch = synchronous_parallel_sample(
                worker_set=self.workers,
                max_env_steps=config.get("rollout_fragment_length", 4)
                * max(1, config.get("num_envs_per_worker", 1)),
            )
        if batch is not None:  # actor lane (jax lane inserted above)
            # worker-compressed framestack fragments
            # (compress_replay_obs pools) rebuild OBS/NEXT_OBS
            # byte-identically here, before n-step folding reads
            # NEXT_OBS and rows enter the replay ring
            batch = self._materialize_compressed(batch)
            n_step = config.get("n_step", 1)
            if n_step > 1:
                from ray_tpu.data.sample_batch import MultiAgentBatch

                if isinstance(batch, MultiAgentBatch):
                    for b in batch.policy_batches.values():
                        adjust_nstep(n_step, config["gamma"], b)
                else:
                    adjust_nstep(n_step, config["gamma"], batch)
            self._counters[NUM_ENV_STEPS_SAMPLED] += batch.env_steps()
            self.local_replay_buffer.add(batch)

        if not interleaved:
            sampled = (
                batch.env_steps() if batch is not None else jax_sampled
            )
            train_info = self._replay_update_phase(sampled)

        self.workers.sync_weights(
            global_vars={
                "timestep": self._counters[NUM_ENV_STEPS_SAMPLED]
            },
            # workers only act: ship the acting subset (SAC: actor
            # net alone — the D2H of the full param tree otherwise
            # dominates the round)
            inference_only=True,
        )
        return train_info


class SimpleQConfig(DQNConfig):
    """reference simple_q.py:256 — DQN without double/dueling/n-step."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or SimpleQ)
        self.double_q = False
        self.dueling = False
        self.n_step = 1


class SimpleQ(DQN):
    @classmethod
    def get_default_config(cls) -> SimpleQConfig:
        return SimpleQConfig(cls)
