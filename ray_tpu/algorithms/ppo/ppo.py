"""PPO: config, JAX policy (loss), and algorithm.

Counterpart of the reference's ``rllib/algorithms/ppo/ppo.py`` (PPOConfig
``:47``, ``training_step :400``, adaptive-KL update ``:433-447``) and the
torch loss ``rllib/algorithms/ppo/ppo_torch_policy.py:69``. The learner side
— advantage standardization, the clipped surrogate/vf/entropy loss, and the
``num_sgd_iter × minibatches`` SGD nest — runs as one jitted shard_map
program on the TPU mesh (see JaxPolicy).

``config.sample_prefetch > 0`` switches ``training_step`` to the
pipelined loop (docs/pipeline.md): a SamplePrefetcher thread collects,
concatenates and ``prepare_batch``-es batch k+1 and a DeviceFeeder
transfers it while the TPU runs the SGD nest for batch k. Off by
default: the synchronous path below stays bit-identical to the classic
loop on a fixed seed.
"""

from __future__ import annotations

import queue
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

import ray_tpu as ray
from ray_tpu.algorithms.algorithm import (
    Algorithm,
    NUM_AGENT_STEPS_SAMPLED,
    NUM_ENV_STEPS_SAMPLED,
)
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID, SampleBatch
from ray_tpu.evaluation.postprocessing import compute_gae_for_sample_batch
from ray_tpu.execution.rollout_ops import synchronous_parallel_sample
from ray_tpu.execution.train_ops import train_one_step
from ray_tpu.policy.jax_policy import JaxPolicy


class PPOConfig(AlgorithmConfig):
    """reference ppo.py:47."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or PPO)
        self.lr = 5e-5
        self.train_batch_size = 4000
        self.sgd_minibatch_size = 128
        self.num_sgd_iter = 30
        self.lambda_ = 1.0
        self.use_gae = True
        self.use_critic = True
        self.kl_coeff = 0.2
        self.kl_target = 0.01
        self.vf_loss_coeff = 1.0
        self.entropy_coeff = 0.0
        self.entropy_coeff_schedule = None
        self.clip_param = 0.3
        self.vf_clip_param = 10.0
        self.shuffle_sequences = True

    def training(
        self,
        *,
        lambda_: Optional[float] = None,
        use_gae: Optional[bool] = None,
        use_critic: Optional[bool] = None,
        kl_coeff: Optional[float] = None,
        kl_target: Optional[float] = None,
        sgd_minibatch_size: Optional[int] = None,
        num_sgd_iter: Optional[int] = None,
        vf_loss_coeff: Optional[float] = None,
        entropy_coeff: Optional[float] = None,
        entropy_coeff_schedule=None,
        clip_param: Optional[float] = None,
        vf_clip_param: Optional[float] = None,
        **kwargs,
    ) -> "PPOConfig":
        super().training(**kwargs)
        if lambda_ is not None:
            self.lambda_ = lambda_
        if use_gae is not None:
            self.use_gae = use_gae
        if use_critic is not None:
            self.use_critic = use_critic
        if kl_coeff is not None:
            self.kl_coeff = kl_coeff
        if kl_target is not None:
            self.kl_target = kl_target
        if sgd_minibatch_size is not None:
            self.sgd_minibatch_size = sgd_minibatch_size
        if num_sgd_iter is not None:
            self.num_sgd_iter = num_sgd_iter
        if vf_loss_coeff is not None:
            self.vf_loss_coeff = vf_loss_coeff
        if entropy_coeff is not None:
            self.entropy_coeff = entropy_coeff
        if entropy_coeff_schedule is not None:
            self.entropy_coeff_schedule = entropy_coeff_schedule
        if clip_param is not None:
            self.clip_param = clip_param
        if vf_clip_param is not None:
            self.vf_clip_param = vf_clip_param
        return self

    def to_dict(self) -> Dict:
        d = super().to_dict()
        d["lambda"] = d.pop("lambda_", 1.0)
        return d


class PPOJaxPolicy(JaxPolicy):
    """Clipped-surrogate PPO loss (reference ppo_torch_policy.py:69),
    with KL penalty adapted on host between train calls."""

    # loss never reads NEXT_OBS; don't ship a second obs column
    _ship_next_obs = False

    def _init_coeffs(self):
        self.coeff_values["kl_coeff"] = float(
            self.config.get("kl_coeff", 0.2)
        )

    def loss(self, params, batch, rng, coeffs):
        cfg = self.config
        clip_param = cfg.get("clip_param", 0.3)
        vf_clip = cfg.get("vf_clip_param", 10.0)
        vf_coeff = cfg.get("vf_loss_coeff", 1.0)

        model_stats = {}
        dist_inputs, value, _ = self.model_forward_train(
            params, batch, stats_out=model_stats
        )
        dist = self.dist_class(dist_inputs)
        prev_dist = self.dist_class(
            batch[SampleBatch.ACTION_DIST_INPUTS]
        )

        logp = dist.logp(batch[SampleBatch.ACTIONS])
        logp_ratio = jnp.exp(logp - batch[SampleBatch.ACTION_LOGP])
        advantages = batch[SampleBatch.ADVANTAGES]

        surrogate = jnp.minimum(
            advantages * logp_ratio,
            advantages
            * jnp.clip(logp_ratio, 1.0 - clip_param, 1.0 + clip_param),
        )
        action_kl = prev_dist.kl(dist)
        entropy = dist.entropy()

        value_targets = batch[SampleBatch.VALUE_TARGETS]
        vf_loss = jnp.square(value - value_targets)
        vf_loss_clipped = jnp.clip(vf_loss, 0.0, vf_clip)

        total = jnp.mean(
            -surrogate
            + coeffs["kl_coeff"] * action_kl
            + vf_coeff * vf_loss_clipped
            - coeffs["entropy_coeff"] * entropy
        )
        stats = {
            "policy_loss": jnp.mean(-surrogate),
            "vf_loss": jnp.mean(vf_loss_clipped),
            "kl": jnp.mean(action_kl),
            "entropy": jnp.mean(entropy),
            "vf_explained_var": _explained_variance(
                value_targets, value
            ),
            **model_stats,
        }
        return total, stats

    def after_learn_on_batch(self, stats: Dict[str, float]) -> Dict:
        """Adaptive KL coefficient (reference ppo.py:433-447 /
        ppo_torch_policy KLCoeffMixin.update_kl)."""
        kl = stats.get("kl", 0.0)
        target = self.config.get("kl_target", 0.01)
        if self.coeff_values["kl_coeff"] > 0.0:
            if kl > 2.0 * target:
                self.coeff_values["kl_coeff"] *= 1.5
            elif kl < 0.5 * target:
                self.coeff_values["kl_coeff"] *= 0.5
        return {"cur_kl_coeff": self.coeff_values["kl_coeff"]}

    def postprocess_trajectory(
        self, sample_batch, other_agent_batches=None, episode=None
    ):
        return compute_gae_for_sample_batch(
            self, sample_batch, other_agent_batches, episode
        )


def _explained_variance(y, pred):
    y_var = jnp.var(y)
    diff_var = jnp.var(y - pred)
    return jnp.maximum(-1.0, 1.0 - diff_var / (y_var + 1e-8))


def _standardize_advantages(b) -> None:
    """reference ppo.py:415 standardize_fields."""
    adv = np.asarray(b[SampleBatch.ADVANTAGES], np.float32)
    b[SampleBatch.ADVANTAGES] = (
        (adv - adv.mean()) / max(1e-4, adv.std())
    ).astype(np.float32)


class PPO(Algorithm):
    _default_policy_class = PPOJaxPolicy

    @classmethod
    def get_default_config(cls) -> PPOConfig:
        return PPOConfig(cls)

    def setup(self, config: Dict) -> None:
        super().setup(config)
        self._sample_pipeline = None
        self._prefetch_feeder = None

    def training_step(self) -> Dict:
        """reference ppo.py:400."""
        if self.config.get("env_backend") == "jax":
            return self._training_step_jax_rollout()
        if self._use_sample_prefetch():
            return self._training_step_prefetch()
        train_batch = synchronous_parallel_sample(
            worker_set=self.workers,
            max_env_steps=self.config["train_batch_size"],
        )
        self._counters[NUM_ENV_STEPS_SAMPLED] += train_batch.env_steps()
        self._counters[NUM_AGENT_STEPS_SAMPLED] += (
            train_batch.env_steps()
        )

        # standardize advantages across the full train batch
        # (reference ppo.py:415 standardize_fields)
        from ray_tpu.data.sample_batch import MultiAgentBatch

        if isinstance(train_batch, MultiAgentBatch):
            for b in train_batch.policy_batches.values():
                _standardize_advantages(b)
        else:
            _standardize_advantages(train_batch)

        train_info = train_one_step(self, train_batch)

        # broadcast new weights + timestep to rollout workers
        self.workers.sync_weights(
            global_vars={
                "timestep": self._counters[NUM_ENV_STEPS_SAMPLED]
            }
        )
        if self.config.get("observation_filter") not in (
            None,
            "NoFilter",
        ):
            self.workers.sync_filters()
        return train_info

    # -- device rollout lane (config.env_backend == "jax") ---------------

    def _jax_engine(self):
        """Lazily build the device rollout engine (docs/pipeline.md):
        N = num_envs_per_worker × max(1, num_workers) env slots on the
        learner mesh, T = rollout_fragment_length — one rollout is
        exactly one train batch, so the lane's geometry contract is
        ``train_batch_size == N·T`` (fail fast otherwise)."""
        eng = self.__dict__.get("_jax_rollout_engine")
        if eng is None:
            from ray_tpu.execution.jax_rollout import (
                JaxRolloutEngine,
                supports_jax_rollout_lane,
            )
            from ray_tpu.util import tracing

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
            T = int(self.config.get("rollout_fragment_length", 200))
            if N * T != int(self.config["train_batch_size"]):
                raise ValueError(
                    "jax rollout lane needs train_batch_size == "
                    "num_envs_per_worker * max(1, num_workers) * "
                    f"rollout_fragment_length, got {N * T} != "
                    f"{self.config['train_batch_size']}"
                )
            # a step of set-up, built where the lane first needs it
            with tracing.phase("setup:rollout_engine", num_envs=N):
                eng = JaxRolloutEngine(
                    policy,
                    env,
                    N,
                    T,
                    seed=self.config.get("seed"),
                    postprocess="gae",
                    standardize_advantages=True,
                )
            self._jax_rollout_engine = eng
            # Algorithm._collect_rollout_metrics drains these — the
            # lane's episode returns come back with the stats readback
            self._extra_metric_sources.append(eng.get_metrics)
        return eng

    def _training_step_jax_rollout(self) -> Dict:
        """One training_step on the device rollout lane: K ×
        [rollout(T) + GAE + the num_sgd_iter-epoch nest] with zero
        rollout H2D — fused into ONE dispatch when
        ``jax_fused_rollout`` (default), or rollout / learn as two
        dispatches otherwise (the benchmark's middle lane)."""
        from ray_tpu.execution.train_ops import (
            NUM_AGENT_STEPS_TRAINED,
            NUM_ENV_STEPS_TRAINED,
        )

        eng = self._jax_engine()
        policy = self.get_policy()
        bsize = eng.batch_size
        K = self._resolve_superstep_k()
        fused = bool(
            self.config.get("jax_fused_rollout", True)
        ) and getattr(policy, "supports_superstep", False)

        if fused:
            feed = eng.superstep_feed()
            infos, carry, metrics, skipped = (
                policy.learn_rollout_superstep(K, bsize, feed, k_max=K)
            )
            eng.advance(carry, metrics)
            # host-side KL adaptation applies to the drained
            # per-update stats in order (the one chain of staleness —
            # docs/data_plane.md)
            for info_i in infos:
                info_i.update(policy.after_learn_on_batch(info_i))
            info = infos[-1]
            for s in skipped:
                if s:
                    self._counters["num_nan_batches_skipped"] += 1
                    self._recovery.note_skipped_batch()
            n_updates = K
        else:
            info = {}
            for _ in range(K):
                batch, bsize = eng.rollout()
                info = policy.learn_on_device_batch(
                    eng.learn_batch(batch), bsize
                )
            n_updates = K

        info["cur_lr"] = policy.coeff_values.get("lr")
        steps = n_updates * bsize
        self._counters[NUM_ENV_STEPS_SAMPLED] += steps
        self._counters[NUM_AGENT_STEPS_SAMPLED] += steps
        self._counters[NUM_ENV_STEPS_TRAINED] += steps
        self._counters[NUM_AGENT_STEPS_TRAINED] += steps
        timestep = self._counters[NUM_ENV_STEPS_SAMPLED]
        if self.workers.num_remote_workers() > 0:
            self.workers.sync_weights(
                global_vars={"timestep": timestep}
            )
        else:
            self.workers.local_worker().set_global_vars(
                {"timestep": timestep}
            )
        return {DEFAULT_POLICY_ID: info}

    # -- pipelined sampling (config.sample_prefetch) ---------------------

    def _use_sample_prefetch(self) -> bool:
        return (
            int(self.config.get("sample_prefetch") or 0) > 0
            and self.workers.num_remote_workers() > 0
            # multi-policy batches need per-policy prepare/learn
            # plumbing; they stay on the synchronous path
            and not self.config.get("policies")
        )

    def _resolve_superstep_k(self) -> int:
        """K of the fused superstep contract for the prefetch loop
        (docs/data_plane.md): one training_step consumes K prefetched
        device batches as ONE compiled K-update program. Resolved once
        (sharding.superstep.resolve_superstep) and demoted to 1 when
        the policy can't ride the scan."""
        k = self.__dict__.get("_superstep_k")
        if k is None:
            from ray_tpu.sharding.superstep import resolve_superstep

            k = resolve_superstep(
                self.config, self.config.get("_mesh")
            )
            if k > 1 and not getattr(
                self.get_policy(), "supports_superstep", False
            ):
                k = 1
            self._superstep_k = k
        return k

    def _build_sample_pipeline(self) -> None:
        from ray_tpu.execution.device_feed import DeviceFeeder
        from ray_tpu.execution.rollout_ops import SamplePrefetcher

        policy = self.get_policy()
        depth = max(
            1,
            int(self.config.get("sample_prefetch") or 1),
            self._resolve_superstep_k(),
        )
        feeder = DeviceFeeder(policy.batch_shardings, capacity=depth)
        # fixed-row contract for stacking: a superstep scans K batches
        # of identical shape, so prefetched trees trim to the largest
        # div-multiple at or under train_batch_size (prepare_batch
        # already guarantees ≥ that many rows — the prefetcher
        # collects at least train_batch_size steps)
        div = max(1, policy.n_shards) * max(
            1, getattr(policy, "_unroll_T", 1)
        )
        fixed_rows = (
            int(self.config["train_batch_size"]) // div
        ) * div

        def deliver(batch):
            # runs on the prefetch thread, overlapping the SGD nest:
            # standardize + host-tree assembly here, device transfer on
            # the feeder thread, learn on the driver thread
            _standardize_advantages(batch)
            # resilience choke point for the pipelined path, mirroring
            # train_one_step's: chaos injection counts learn batches
            # here, and the nan guard skips a poisoned batch BEFORE it
            # crosses to the device (docs/resilience.md)
            if self._fault_injector is not None:
                self._fault_injector.on_learn(batch)
            if self.config.get("nan_guard"):
                from ray_tpu.resilience.recovery import batch_is_finite

                if not batch_is_finite(batch):
                    self._counters["num_nan_batches_skipped"] += 1
                    self._recovery.note_skipped_batch()
                    return
            tree, bsize = policy.prepare_batch(batch)
            if self._superstep_k > 1 and fixed_rows > 0:
                from ray_tpu.ops.framestack import FRAMES as _FRAMES

                if _FRAMES in tree:
                    # frame-pool batches have per-batch pool sizes and
                    # can't stack — this run falls back to per-update
                    self._superstep_k = 1
                elif bsize > fixed_rows:
                    T = max(1, getattr(policy, "_unroll_T", 1))
                    tree = {
                        c: (
                            v[: fixed_rows // T]
                            if c.startswith("__chunk__")
                            else v[:fixed_rows]
                        )
                        for c, v in tree.items()
                    }
                    bsize = fixed_rows
            feeder.put(tree, (bsize, batch.env_steps(), batch.count))

        self._prefetch_feeder = feeder
        self._sample_pipeline = SamplePrefetcher(
            self.workers,
            target_steps=int(self.config["train_batch_size"]),
            deliver=deliver,
            max_in_flight=int(
                self.config.get(
                    "max_requests_in_flight_per_rollout_worker", 2
                )
            ),
        )
        # elastic fleet: the pipeline's request manager is the
        # rotation drains remove workers from, and its in-flight
        # counts are the controller's idleness signal
        if self._fleet is not None:
            self._fleet.register_manager(self._sample_pipeline.manager)

    def _next_prefetched(self):
        """Block for the next prefetched device batch, keeping the
        pipeline healthy (dead-worker recovery) while waiting."""
        import time as _time

        from ray_tpu.util import tracing

        pipe = self._sample_pipeline
        t_wait0 = _time.time()
        while True:
            if not pipe.healthy():
                raise pipe.error or RuntimeError(
                    "sample pipeline thread died"
                )
            self._recover_pipeline_workers(pipe)
            try:
                item = self._prefetch_feeder.get(timeout=1.0)
                break
            except queue.Empty:
                continue
        # how long the learner sat starved waiting on the pipeline —
        # ~0 when the prefetch overlap is doing its job
        tracing.record_span(
            "learner:queue_wait", t_wait0, _time.time()
        )
        return item

    def _training_step_prefetch(self) -> Dict:
        from ray_tpu.execution.train_ops import (
            NUM_AGENT_STEPS_TRAINED,
            NUM_ENV_STEPS_TRAINED,
        )

        if self._sample_pipeline is None:
            self._build_sample_pipeline()
        pipe = self._sample_pipeline

        dev, (bsize, env_steps, rows) = self._next_prefetched()
        policy = self.get_policy()

        K = self._resolve_superstep_k()
        if K > 1:
            # superstep over prefetched device batches: one
            # training_step = one dispatch = K updates, zero H2D here
            # (the feeder already moved each batch; the stacker is a
            # device-side reshuffle). Host-side KL adaptation applies
            # to the drained per-update stats in order — one chain of
            # staleness, documented in docs/data_plane.md.
            batches = [(dev, bsize, env_steps, rows)]
            while len(batches) < K:
                d2, (b2, e2, r2) = self._next_prefetched()
                batches.append((d2, b2, e2, r2))
            sizes = {b[1] for b in batches}
            if len(sizes) == 1:
                from ray_tpu import sharding as sharding_lib

                stack_fn = self.__dict__.get("_superstep_stack_fn")
                if stack_fn is None:
                    stack_fn = self._superstep_stack_fn = (
                        sharding_lib.build_stack_fn(
                            policy.mesh,
                            K,
                            label=f"superstep_stack[{K}]",
                        )
                    )
                stacked = stack_fn(*[b[0] for b in batches])
                infos, _, skipped = policy.learn_superstep(
                    K, bsize, stacked=dict(stacked), k_max=K
                )
                for i, info_i in enumerate(infos):
                    info_i.update(
                        policy.after_learn_on_batch(info_i)
                    )
                info = infos[-1]
                info["cur_lr"] = policy.coeff_values.get("lr")
                for s in skipped:
                    if s:
                        self._counters[
                            "num_nan_batches_skipped"
                        ] += 1
                        self._recovery.note_skipped_batch()
                for _, b2, e2, r2 in batches:
                    self._counters[NUM_ENV_STEPS_SAMPLED] += e2
                    self._counters[NUM_AGENT_STEPS_SAMPLED] += e2
                    self._counters[NUM_ENV_STEPS_TRAINED] += e2
                    self._counters[NUM_AGENT_STEPS_TRAINED] += r2
                self.workers.sync_weights(
                    global_vars={
                        "timestep": self._counters[
                            NUM_ENV_STEPS_SAMPLED
                        ]
                    }
                )
                if self.config.get("observation_filter") not in (
                    None,
                    "NoFilter",
                ):
                    self.workers.sync_filters()
                self._recover_pipeline_workers(pipe)
                return {
                    DEFAULT_POLICY_ID: info,
                    "sample_pipeline": pipe.stats(),
                }
            # ragged sizes (shouldn't happen under the fixed-row
            # contract): learn the collected batches per-update, in
            # arrival order; the last falls through to the common path
            for d2, b2, e2, r2 in batches[:-1]:
                self._counters[NUM_ENV_STEPS_SAMPLED] += e2
                self._counters[NUM_AGENT_STEPS_SAMPLED] += e2
                policy.learn_on_device_batch(d2, b2)
                self._counters[NUM_ENV_STEPS_TRAINED] += e2
                self._counters[NUM_AGENT_STEPS_TRAINED] += r2
            dev, bsize, env_steps, rows = batches[-1]

        self._counters[NUM_ENV_STEPS_SAMPLED] += env_steps
        self._counters[NUM_AGENT_STEPS_SAMPLED] += env_steps

        info = policy.learn_on_device_batch(dev, bsize)
        self._counters[NUM_ENV_STEPS_TRAINED] += env_steps
        self._counters[NUM_AGENT_STEPS_TRAINED] += rows

        self.workers.sync_weights(
            global_vars={
                "timestep": self._counters[NUM_ENV_STEPS_SAMPLED]
            }
        )
        if self.config.get("observation_filter") not in (
            None,
            "NoFilter",
        ):
            self.workers.sync_filters()
        self._recover_pipeline_workers(pipe)
        return {
            DEFAULT_POLICY_ID: info,
            "sample_pipeline": pipe.stats(),
        }

    def _recover_pipeline_workers(self, pipe) -> None:
        """Dead workers reported by the prefetcher's request manager:
        recreate (no 30 s ping probe — the manager already observed the
        death), ignore, or surface per the failure config."""
        dead = pipe.take_dead_workers()
        if not dead:
            return
        self._counters["num_dead_rollout_workers"] += len(dead)
        if self.config.get("recreate_failed_workers"):
            new = self.workers.replace_failed_workers(dead)
            pipe.add_workers(new)
        elif not self.config.get("ignore_worker_failures"):
            raise ray.core.object_store.RayActorError(
                f"{len(dead)} rollout worker(s) died in the sample "
                "pipeline"
            )

    def on_fleet_change(self, added, removed) -> None:
        """Elastic fleet: joiners enter the prefetch pipeline's
        rotation (they arrive weight+filter-synced from
        ``WorkerSet.add_workers``); drained workers were already
        retired from the registered manager by the FleetController."""
        super().on_fleet_change(added, removed)
        pipe = getattr(self, "_sample_pipeline", None)
        if pipe is not None and added:
            pipe.add_workers(added)

    def on_recovery(self, kind: str) -> None:
        """A checkpoint restore invalidates the prefetch pipeline (its
        thread may be dead — an injected crash in ``deliver`` is how
        the restore got triggered — and its queued batches belong to
        the pre-restore policy): tear it down; the next
        ``training_step`` rebuilds it lazily."""
        super().on_recovery(kind)
        if kind != "restore":
            return
        self._teardown_pipeline()

    def _teardown_pipeline(self) -> None:
        pipe = getattr(self, "_sample_pipeline", None)
        feeder = getattr(self, "_prefetch_feeder", None)
        if pipe is not None:
            pipe.request_stop()
        if feeder is not None:
            feeder.stop()
            self._prefetch_feeder = None
        if pipe is not None:
            pipe.stop()
            self._sample_pipeline = None

    def cleanup(self) -> None:
        # flag-first ordering lives in _teardown_pipeline: a deliver
        # blocked on feeder backpressure only wakes when the feeder
        # stops (its put raises), and the raise must find the stop
        # flag set
        self._teardown_pipeline()
        super().cleanup()
