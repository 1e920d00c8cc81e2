"""AlgorithmConfig: typed fluent builder → plain dict.

Counterpart of the reference's ``rllib/algorithms/algorithm_config.py:33``
(``resources :339``, ``framework :408``, ``environment :453``,
``rollouts :533``, ``training :717``, ``evaluation :800``,
``multi_agent :1027``, ``to_dict :241``). The framework is always "jax"
here; the knob kept for API parity.
"""

from __future__ import annotations

import copy
from typing import Any, Callable, Dict, Optional, Type


class AlgorithmConfig:
    def __init__(self, algo_class: Optional[type] = None):
        self.algo_class = algo_class

        # environment (reference :453)
        self.env = None
        self.env_config: Dict = {}
        self.observation_space = None
        self.action_space = None
        self.clip_actions = False
        self.normalize_actions = True
        self.horizon = None
        # rollout lane (docs/pipeline.md "two rollout lanes"):
        # "actor" (default) samples on CPU Ray-actor workers through
        # the SyncSampler; "jax" runs act → env.step → postprocess as
        # ONE jit'd program on the learner mesh (JaxVectorEnv envs
        # only — zero rollout bytes over H2D). The two lanes share
        # SampleBatch semantics and a fixed-seed parity contract
        # (tests/test_jax_env.py).
        self.env_backend = "actor"
        # "jax" lane only: fuse rollout+learn into one dispatched
        # superstep program (False keeps rollout and learn as
        # separate dispatches — the benchmark A/B's middle lane)
        self.jax_fused_rollout = True

        # framework (reference :408)
        # ray-tpu: allow[RTA012] API-parity stub: the framework is always jax here; the knob exists so reference configs round-trip
        self.framework_str = "jax"

        # rollouts (reference :533)
        self.num_workers = 0
        self.num_envs_per_worker = 1
        self.rollout_fragment_length = 200
        self.batch_mode = "truncate_episodes"
        self.observation_filter = "NoFilter"
        # ray-tpu: allow[RTA012] API-parity stub: in-process transport never serializes observations, so there is nothing to compress
        self.compress_observations = False
        self.ignore_worker_failures = False
        self.recreate_failed_workers = False
        # Pipelined sampling (docs/pipeline.md): >0 overlaps rollout
        # collection + host concat + device transfer of batch k+1 with
        # the SGD nest of batch k, at a bounded staleness of
        # `sample_prefetch` updates. 0 (default) keeps the fully
        # synchronous loop — bit-identical to the classic path.
        self.sample_prefetch = 0
        # Outstanding sample requests per rollout worker for the async
        # paths (reference max_requests_in_flight_per_rollout_worker).
        self.max_requests_in_flight_per_rollout_worker = 2

        # fault tolerance (docs/resilience.md)
        # recovery-action budget for Algorithm.train(): worker
        # recreations + checkpoint restores. < 0 = unlimited (the
        # pre-existing semantics of the two rollout flags above).
        self.max_failures = -1
        # every N iterations, save into checkpoint_root (default
        # <logdir>/resilience) and keep it as the auto-restore target;
        # 0 = off
        self.checkpoint_frequency = 0
        self.checkpoint_root = None
        # prune periodic checkpoints down to the newest N (None = keep
        # everything)
        self.keep_checkpoints_num = None
        # restartable driver-side failure (learner crash, anything
        # non-actor-death) → restore the latest checkpoint + continue
        self.restore_on_failure = False
        # skip non-finite learn batches instead of corrupting params
        self.nan_guard = False
        # single wall-clock budget for the parallel health sweep
        self.worker_health_probe_timeout_s = 10.0
        # the uniform RetryPolicy (resilience/retry.py) every
        # driver-side remote interaction draws from
        self.retry_max_attempts = 3
        self.retry_timeout_s = 60.0
        self.retry_backoff_s = 0.05
        self.retry_backoff_mult = 2.0
        self.retry_max_backoff_s = 2.0
        self.retry_jitter = 0.1
        # deterministic chaos spec (resilience/faults.py); {} = inert,
        # None additionally allows the RAY_TPU_FAULTS env fallback
        self.fault_injection: Optional[Dict] = None
        # elastic fleet (docs/resilience.md "elastic fleets &
        # preemption"): True starts a FleetController at
        # Algorithm.setup — the rollout fleet grows/shrinks at runtime
        # within [min_workers, max_workers]: preemption notices drain
        # workers gracefully (zero recovery budget), learner
        # starvation (empty sampler queues) scales up, long-idle
        # workers reap down. Batch accounting is fleet-size
        # independent, so a stable-fleet phase is bit-identical to a
        # non-elastic run on a fixed seed.
        self.elastic = False
        self.min_workers = None  # None → 1
        self.max_workers = None  # None → 2 × num_workers
        # drain budget: how long a noticed/reaped worker gets to ship
        # its final sample results + filter state before being dropped
        self.drain_grace_s = 15.0
        self.fleet_interval_s = 1.0  # monitor-thread poll period
        self.fleet_idle_timeout_s = 30.0  # reap after this long idle
        self.fleet_starvation_patience = 3  # polls before scale-up
        self.scale_up_step = 1
        # continuous checkpoint streaming (resilience/streamer.py):
        # True snapshots params/opt-state every
        # checkpoint_stream_interval supersteps on a background thread
        # (atomic write + fsync, off the critical path), bounding
        # work-lost-on-driver-crash to ~1 superstep; the recovery
        # layer restores from the stream tail when it is newer than
        # the latest periodic checkpoint.
        self.checkpoint_streaming = False
        self.checkpoint_stream_interval = 1

        # training (reference :717)
        self.gamma = 0.99
        self.lr = 0.001
        self.lr_schedule = None
        self.train_batch_size = 4000
        self.model: Dict = {}
        self.optimizer: Dict = {}
        self.grad_clip = None
        self.seed = None

        # device-resident data plane (docs/data_plane.md)
        # "auto" (default): off-policy replay rows live as device
        # arrays on the learner mesh — each transition crosses H2D
        # once at insert, never per learn step — spilling back to the
        # host ring when the projected buffer exceeds
        # replay_memory_cap_bytes (default 60% of the device's
        # reported budget). Auto engages only behind a real
        # accelerator boundary (on the CPU client "device" arrays
        # share host RAM — nothing to diet); True forces device
        # placement anywhere (still spills on the memory projection),
        # False keeps the host ring. Fixed-seed results are
        # bit-identical either way.
        self.replay_device_resident = "auto"
        self.replay_memory_cap_bytes = None
        # Device sum tree (docs/data_plane.md "device sum tree"):
        # prioritized-replay priorities live as f64 mesh arrays and a
        # sample is ONE fused draw→gather program — zero payload bytes
        # cross H2D on the sample path, and index draws reproduce the
        # host sum tree bit-exactly (the generator's raw uniform
        # stream stays host-fed). Requires device-resident rows.
        # "auto" engages behind a real accelerator; True forces it
        # (tests/benches); False keeps the host numpy tree walk.
        self.replay_device_tree = "auto"
        # Learn-while-rollout interleave for the off-policy family on
        # the fused jax rollout lane (env_backend="jax"): dispatch the
        # round's rollout-fill program asynchronously, run the replay
        # superstep against the PREVIOUS round's buffer contents while
        # the fill executes, then insert — acting and fused updates
        # overlap in one cadence (one-round insert staleness, same
        # spirit as sample_async's weight lag; docs/data_plane.md).
        self.learn_while_rollout = False
        # On-device training superstep (docs/data_plane.md): one
        # driver dispatch = K learner updates, uniformly across the
        # learner path (DQN-family chained updates incl. prioritized
        # replay, PPO's prefetch loop, IMPALA's learner thread). The
        # whole K-update chain — weights threaded through a lax.scan
        # carry, device-replay batches gathered in place, stats (and
        # PER priorities) drained as one stacked readback — runs as
        # ONE compiled program, so per-dispatch overhead amortizes
        # 1/K. "auto" (default) resolves to K=8 behind a real
        # accelerator boundary and off on the CPU client (mirroring
        # replay_device_resident); an int forces that K anywhere.
        # Fixed-seed results are bit-identical to K individual learn
        # calls (host-side stat reactions lag the chain — staleness
        # semantics in docs/data_plane.md).
        self.superstep = "auto"
        # Defer the learner's stats readback by one call: learn
        # returns right after the SGD nest is dispatched and fetches
        # the PREVIOUS call's stats (long finished) instead of
        # blocking on this one — amortizes the per-dispatch host
        # cost and readback. train() results lag
        # one learn step; host-side stat hooks (PPO kl adaptation)
        # see the lagged values.
        self.deferred_stats = False

        # learner placement (TPU-specific)
        self.learner_devices = None  # None → all visible devices
        # tensor parallelism (docs/sharding.md "2-D mesh & param
        # partitioning"): None (default) keeps the 1-D data mesh; an
        # int M (or "auto") builds the 2-D [("batch", D//M),
        # ("model", M)] mesh and places params per the model's
        # partition rules — attention/MLP kernels split across M
        # shards, so a policy too large to replicate per device still
        # trains/serves on the same mesh runtime. "auto" resolves to 1
        # on the CPU client, 2 behind an even-count accelerator.
        # model_parallel=1 is the parity geometry: per-leaf specs flow
        # but every leaf stays whole — bit-identical to replicated.
        self.model_parallel = None
        # multi-host learner fleet (docs/fleet.md): None (default)
        # keeps the single-process mesh; an int N (or "auto") builds
        # the learner mesh over the GLOBAL device view of an N-process
        # jax.distributed runtime — the batch axis spans hosts, XLA
        # routes collectives over ICI within a host and DCN across.
        # Requires dist.initialize() to have joined N processes
        # (RAY_TPU_COORDINATOR et al.; Algorithm.setup validates).
        self.hosts = None

        # exploration
        self.explore = True
        self.exploration_config: Dict = {}

        # offline data (reference :offline_data)
        self.input_ = None  # "sampler" | path/glob of JSON shards
        self.output = None  # path to write sampled batches to
        self.output_max_file_size = 64 * 1024 * 1024
        self.off_policy_estimation_methods: list = []

        # evaluation (reference :800)
        self.evaluation_interval = None
        self.evaluation_duration = 10
        # ray-tpu: allow[RTA012] API-parity stub: evaluation counts episodes only; the timesteps unit is unimplemented and documented as such
        self.evaluation_duration_unit = "episodes"
        self.evaluation_num_workers = 0
        self.evaluation_config: Dict = {}

        # multi-agent (reference :1027)
        self.policies: Dict = {}
        self.policy_mapping_fn = None
        self.policies_to_train = None

        # reporting
        self.min_time_s_per_iteration = None
        self.min_sample_timesteps_per_iteration = 0
        self.metrics_num_episodes_for_smoothing = 100

        # telemetry (docs/observability.md): empty dict = off (the
        # default hot path sees only null-spans). Keys: metrics_port
        # (int, 0 = ephemeral → Prometheus /metrics scrape target),
        # trace (bool → span tracing + per-iteration overlap rollup).
        self.telemetry_config: Dict = {}

        # debugging / resources — API-parity stubs: this runtime
        # schedules TPU meshes + CPU actors, not per-trial GPUs, and
        # logging rides the host config
        # ray-tpu: allow[RTA012] API-parity stub (see block comment)
        self.log_level = "WARN"
        # ray-tpu: allow[RTA012] API-parity stub (see block comment)
        self.num_gpus = 0
        # ray-tpu: allow[RTA012] API-parity stub (see block comment)
        self.num_cpus_per_worker = 1

        # callbacks
        self.callbacks_class = None

    # -- fluent sections -------------------------------------------------

    def environment(
        self,
        env=None,
        *,
        env_config: Optional[Dict] = None,
        observation_space=None,
        action_space=None,
        clip_actions: Optional[bool] = None,
        normalize_actions: Optional[bool] = None,
        horizon: Optional[int] = None,
        env_backend: Optional[str] = None,
        jax_fused_rollout: Optional[bool] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """``env_backend``: which rollout lane produces samples —
        ``"actor"`` (CPU Ray-actor workers, any env) or ``"jax"``
        (JaxVectorEnv rollouts jit'd onto the learner mesh, zero
        rollout H2D — docs/pipeline.md). ``jax_fused_rollout``
        additionally fuses rollout+learn into one dispatch on the jax
        lane (default True)."""
        if env is not None:
            self.env = env
        if env_config is not None:
            self.env_config = env_config
        if env_backend is not None:
            if env_backend not in ("actor", "jax"):
                raise ValueError(
                    "env_backend must be 'actor' or 'jax', got "
                    f"{env_backend!r}"
                )
            self.env_backend = env_backend
        if jax_fused_rollout is not None:
            self.jax_fused_rollout = bool(jax_fused_rollout)
        if observation_space is not None:
            self.observation_space = observation_space
        if action_space is not None:
            self.action_space = action_space
        if clip_actions is not None:
            self.clip_actions = clip_actions
        if normalize_actions is not None:
            self.normalize_actions = normalize_actions
        if horizon is not None:
            self.horizon = horizon
        return self

    def framework(self, framework: str = "jax", **kwargs) -> "AlgorithmConfig":
        self.framework_str = framework
        return self

    def rollouts(
        self,
        *,
        num_rollout_workers: Optional[int] = None,
        num_envs_per_worker: Optional[int] = None,
        rollout_fragment_length: Optional[int] = None,
        batch_mode: Optional[str] = None,
        observation_filter: Optional[str] = None,
        ignore_worker_failures: Optional[bool] = None,
        recreate_failed_workers: Optional[bool] = None,
        sample_prefetch: Optional[int] = None,
        max_requests_in_flight_per_rollout_worker: Optional[int] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        if num_rollout_workers is not None:
            self.num_workers = num_rollout_workers
        if num_envs_per_worker is not None:
            self.num_envs_per_worker = num_envs_per_worker
        if rollout_fragment_length is not None:
            self.rollout_fragment_length = rollout_fragment_length
        if batch_mode is not None:
            self.batch_mode = batch_mode
        if observation_filter is not None:
            self.observation_filter = observation_filter
        if ignore_worker_failures is not None:
            self.ignore_worker_failures = ignore_worker_failures
        if recreate_failed_workers is not None:
            self.recreate_failed_workers = recreate_failed_workers
        if sample_prefetch is not None:
            self.sample_prefetch = sample_prefetch
        if max_requests_in_flight_per_rollout_worker is not None:
            self.max_requests_in_flight_per_rollout_worker = (
                max_requests_in_flight_per_rollout_worker
            )
        return self

    def training(
        self,
        *,
        gamma: Optional[float] = None,
        lr: Optional[float] = None,
        lr_schedule=None,
        train_batch_size: Optional[int] = None,
        model: Optional[Dict] = None,
        optimizer: Optional[Dict] = None,
        grad_clip: Optional[float] = None,
        replay_device_resident=None,
        replay_memory_cap_bytes: Optional[int] = None,
        deferred_stats: Optional[bool] = None,
        superstep=None,
        replay_device_tree=None,
        learn_while_rollout: Optional[bool] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """``replay_device_resident`` / ``replay_memory_cap_bytes`` /
        ``deferred_stats`` / ``superstep`` / ``replay_device_tree`` /
        ``learn_while_rollout``: the device-resident data-plane knobs
        (docs/data_plane.md) — see the attribute comments in
        ``__init__``."""
        if gamma is not None:
            self.gamma = gamma
        if lr is not None:
            self.lr = lr
        if lr_schedule is not None:
            self.lr_schedule = lr_schedule
        if train_batch_size is not None:
            self.train_batch_size = train_batch_size
        if model is not None:
            self.model = model
        if optimizer is not None:
            self.optimizer = optimizer
        if grad_clip is not None:
            self.grad_clip = grad_clip
        if replay_device_resident is not None:
            self.replay_device_resident = replay_device_resident
        if replay_memory_cap_bytes is not None:
            self.replay_memory_cap_bytes = int(replay_memory_cap_bytes)
        if deferred_stats is not None:
            self.deferred_stats = bool(deferred_stats)
        if superstep is not None:
            self.superstep = superstep
        if replay_device_tree is not None:
            self.replay_device_tree = replay_device_tree
        if learn_while_rollout is not None:
            self.learn_while_rollout = bool(learn_while_rollout)
        for k, v in kwargs.items():
            setattr(self, k, v)
        return self

    def resources(
        self,
        *,
        num_gpus: Optional[int] = None,
        num_cpus_per_worker: Optional[int] = None,
        learner_devices: Optional[int] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        from ray_tpu import sharding as sharding_lib

        sharding_lib.refuse_removed_options(kwargs)
        if num_gpus is not None:
            self.num_gpus = num_gpus
        if num_cpus_per_worker is not None:
            self.num_cpus_per_worker = num_cpus_per_worker
        if learner_devices is not None:
            self.learner_devices = learner_devices
        return self

    def sharding(
        self,
        *,
        model_parallel=None,
        hosts=None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """Learner-plane placement (docs/sharding.md).
        ``model_parallel``: "auto" | int M — build
        the 2-D (data x model) mesh and partition params per the
        model's rules; see the attribute comment in ``__init__``.
        ``hosts``: "auto" | int N — span the learner mesh over the N
        processes of the jax.distributed runtime (the multi-host
        fleet, docs/fleet.md)."""
        from ray_tpu import sharding as sharding_lib

        sharding_lib.refuse_removed_options(kwargs)
        if hosts is not None:
            if hosts != "auto":
                h = int(hosts)
                if h < 1:
                    raise ValueError(
                        "hosts must be 'auto' or an int >= 1, got "
                        f"{hosts!r}"
                    )
                hosts = h
            self.hosts = hosts
        if model_parallel is not None:
            if model_parallel != "auto":
                m = int(model_parallel)
                if m < 1:
                    raise ValueError(
                        "model_parallel must be 'auto' or an int "
                        f">= 1, got {model_parallel!r}"
                    )
                model_parallel = m
            self.model_parallel = model_parallel
        return self

    def offline_data(
        self,
        *,
        input_=None,
        output: Optional[str] = None,
        output_max_file_size: Optional[int] = None,
        off_policy_estimation_methods=None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """reference algorithm_config.py offline_data()."""
        if input_ is not None:
            self.input_ = input_
        if output is not None:
            self.output = output
        if output_max_file_size is not None:
            self.output_max_file_size = output_max_file_size
        if off_policy_estimation_methods is not None:
            self.off_policy_estimation_methods = (
                off_policy_estimation_methods
            )
        return self

    def exploration(
        self, *, explore: Optional[bool] = None,
        exploration_config: Optional[Dict] = None, **kwargs,
    ) -> "AlgorithmConfig":
        if explore is not None:
            self.explore = explore
        if exploration_config is not None:
            self.exploration_config = exploration_config
        return self

    def evaluation(
        self,
        *,
        evaluation_interval: Optional[int] = None,
        evaluation_duration: Optional[int] = None,
        evaluation_duration_unit: Optional[str] = None,
        evaluation_num_workers: Optional[int] = None,
        evaluation_config: Optional[Dict] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        if evaluation_interval is not None:
            self.evaluation_interval = evaluation_interval
        if evaluation_duration is not None:
            self.evaluation_duration = evaluation_duration
        if evaluation_duration_unit is not None:
            self.evaluation_duration_unit = evaluation_duration_unit
        if evaluation_num_workers is not None:
            self.evaluation_num_workers = evaluation_num_workers
        if evaluation_config is not None:
            self.evaluation_config = evaluation_config
        return self

    def multi_agent(
        self,
        *,
        policies: Optional[Dict] = None,
        policy_mapping_fn: Optional[Callable] = None,
        policies_to_train=None,
        **kwargs,
    ) -> "AlgorithmConfig":
        if policies is not None:
            self.policies = policies
        if policy_mapping_fn is not None:
            self.policy_mapping_fn = policy_mapping_fn
        if policies_to_train is not None:
            self.policies_to_train = policies_to_train
        return self

    def reporting(
        self,
        *,
        min_time_s_per_iteration: Optional[float] = None,
        min_sample_timesteps_per_iteration: Optional[int] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        if min_time_s_per_iteration is not None:
            self.min_time_s_per_iteration = min_time_s_per_iteration
        if min_sample_timesteps_per_iteration is not None:
            self.min_sample_timesteps_per_iteration = (
                min_sample_timesteps_per_iteration
            )
        return self

    def debugging(
        self, *, log_level: Optional[str] = None,
        seed: Optional[int] = None, **kwargs,
    ) -> "AlgorithmConfig":
        if log_level is not None:
            self.log_level = log_level
        if seed is not None:
            self.seed = seed
        return self

    def callbacks(self, callbacks_class) -> "AlgorithmConfig":
        self.callbacks_class = callbacks_class
        return self

    def fault_tolerance(
        self,
        *,
        ignore_worker_failures: Optional[bool] = None,
        recreate_failed_workers: Optional[bool] = None,
        max_failures: Optional[int] = None,
        checkpoint_frequency: Optional[int] = None,
        checkpoint_root: Optional[str] = None,
        keep_checkpoints_num: Optional[int] = None,
        restore_on_failure: Optional[bool] = None,
        nan_guard: Optional[bool] = None,
        worker_health_probe_timeout_s: Optional[float] = None,
        retry_max_attempts: Optional[int] = None,
        retry_timeout_s: Optional[float] = None,
        retry_backoff_s: Optional[float] = None,
        retry_backoff_mult: Optional[float] = None,
        retry_max_backoff_s: Optional[float] = None,
        retry_jitter: Optional[float] = None,
        fault_injection: Optional[Dict] = None,
        elastic: Optional[bool] = None,
        min_workers: Optional[int] = None,
        max_workers: Optional[int] = None,
        drain_grace_s: Optional[float] = None,
        fleet_interval_s: Optional[float] = None,
        fleet_idle_timeout_s: Optional[float] = None,
        fleet_starvation_patience: Optional[int] = None,
        scale_up_step: Optional[int] = None,
        checkpoint_streaming: Optional[bool] = None,
        checkpoint_stream_interval: Optional[int] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """Fault-tolerance knobs (docs/resilience.md).

        ``recreate_failed_workers``: on an observed rollout-worker
        death, probe the fleet (bounded by
        ``worker_health_probe_timeout_s``), spawn weight-synced
        replacements, and continue in degraded mode meanwhile.
        ``checkpoint_frequency`` + ``restore_on_failure``: periodic
        checkpoints become the auto-restore target for restartable
        driver-side failures; prune to ``keep_checkpoints_num``.
        ``nan_guard``: skip non-finite learn batches instead of
        corrupting params. ``max_failures`` caps total recovery
        actions (< 0 = unlimited). ``retry_*``: the uniform
        RetryPolicy behind every driver-side remote interaction.
        ``fault_injection``: deterministic chaos spec for tests and
        ``bench.py --chaos`` (resilience/faults.py).
        ``elastic`` + ``min_workers``/``max_workers``: run the rollout
        fleet under a FleetController — preemption notices drain
        workers gracefully, learner starvation scales up, idle workers
        reap down (docs/resilience.md "elastic fleets & preemption").
        ``checkpoint_streaming`` + ``checkpoint_stream_interval``:
        continuous background param/opt-state snapshots bounding
        work-lost-on-driver-crash to ~1 superstep."""
        if ignore_worker_failures is not None:
            self.ignore_worker_failures = ignore_worker_failures
        if recreate_failed_workers is not None:
            self.recreate_failed_workers = recreate_failed_workers
        if max_failures is not None:
            self.max_failures = int(max_failures)
        if checkpoint_frequency is not None:
            self.checkpoint_frequency = int(checkpoint_frequency)
        if checkpoint_root is not None:
            self.checkpoint_root = checkpoint_root
        if keep_checkpoints_num is not None:
            self.keep_checkpoints_num = int(keep_checkpoints_num)
        if restore_on_failure is not None:
            self.restore_on_failure = bool(restore_on_failure)
        if nan_guard is not None:
            self.nan_guard = bool(nan_guard)
        if worker_health_probe_timeout_s is not None:
            self.worker_health_probe_timeout_s = float(
                worker_health_probe_timeout_s
            )
        if retry_max_attempts is not None:
            self.retry_max_attempts = int(retry_max_attempts)
        if retry_timeout_s is not None:
            self.retry_timeout_s = retry_timeout_s
        if retry_backoff_s is not None:
            self.retry_backoff_s = float(retry_backoff_s)
        if retry_backoff_mult is not None:
            self.retry_backoff_mult = float(retry_backoff_mult)
        if retry_max_backoff_s is not None:
            self.retry_max_backoff_s = float(retry_max_backoff_s)
        if retry_jitter is not None:
            self.retry_jitter = float(retry_jitter)
        if fault_injection is not None:
            self.fault_injection = fault_injection
        if elastic is not None:
            self.elastic = bool(elastic)
        if min_workers is not None:
            self.min_workers = int(min_workers)
        if max_workers is not None:
            self.max_workers = int(max_workers)
        if drain_grace_s is not None:
            self.drain_grace_s = float(drain_grace_s)
        if fleet_interval_s is not None:
            self.fleet_interval_s = float(fleet_interval_s)
        if fleet_idle_timeout_s is not None:
            self.fleet_idle_timeout_s = float(fleet_idle_timeout_s)
        if fleet_starvation_patience is not None:
            self.fleet_starvation_patience = int(
                fleet_starvation_patience
            )
        if scale_up_step is not None:
            self.scale_up_step = int(scale_up_step)
        if checkpoint_streaming is not None:
            self.checkpoint_streaming = bool(checkpoint_streaming)
        if checkpoint_stream_interval is not None:
            self.checkpoint_stream_interval = int(
                checkpoint_stream_interval
            )
        return self

    def telemetry(
        self,
        *,
        metrics_port: Optional[int] = None,
        trace: Optional[bool] = None,
        device_ledger=None,
        profile_iters: Optional[int] = None,
        peak_flops: Optional[float] = None,
        **kwargs,
    ) -> "AlgorithmConfig":
        """Run-telemetry activation (docs/observability.md).

        ``metrics_port``: start a Prometheus ``MetricsServer`` on this
        port at ``Algorithm.setup`` (0 = pick an ephemeral port; read
        it back from ``algo._telemetry.metrics_port``).
        ``trace``: enable span tracing end to end — remote submissions
        carry trace context, every ``train()`` result gains
        ``info/telemetry`` (stage wall-times + rollout/learn overlap
        fraction), and ``Algorithm.export_timeline(path)`` writes the
        chrome trace (with the device program lanes when the ledger
        runs).
        ``device_ledger``: the compiled-program ledger
        (docs/observability.md "device ledger") — per-program FLOPs /
        HBM bytes / MFU / recompile causes under
        ``info/device_ledger``. Defaults on whenever telemetry is
        active; ``"light"`` skips the cost/memory analysis (and its
        one extra AOT compile per traced signature), ``False``
        disables.
        ``profile_iters``: capture ``jax.profiler`` traces of the
        first N train iterations into ``<logdir>/jax_profile`` (no-op
        where the profiler is unavailable; numerics untouched —
        bit-parity-tested).
        ``peak_flops``: per-device peak FLOPs/s the MFU accounting
        divides by — overrides the built-in device-kind table (the
        CPU-container knob; ``peak_hbm_bytes_per_s`` rides along in
        kwargs)."""
        tc = dict(self.telemetry_config)
        if metrics_port is not None:
            tc["metrics_port"] = int(metrics_port)
        if trace is not None:
            tc["trace"] = bool(trace)
        if device_ledger is not None:
            tc["device_ledger"] = device_ledger
        if profile_iters is not None:
            tc["profile_iters"] = int(profile_iters)
        if peak_flops is not None:
            tc["peak_flops"] = float(peak_flops)
        tc.update(kwargs)
        self.telemetry_config = tc
        return self

    # -- conversion ------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        """reference algorithm_config.py:241."""
        out = {}
        for k, v in vars(self).items():
            if k == "algo_class":
                continue
            if k == "framework_str":
                out["framework"] = v
                continue
            if k == "input_":
                out["input"] = v
                continue
            out[k] = v
        return copy.deepcopy(
            {k: v for k, v in out.items()}
        ) if False else dict(out)

    def update_from_dict(self, d: Dict) -> "AlgorithmConfig":
        for k, v in d.items():
            if k == "framework":
                self.framework_str = v
            elif k == "num_rollout_workers":
                self.num_workers = v
            elif k == "input":
                self.input_ = v
            else:
                setattr(self, k, v)
        return self

    def copy(self) -> "AlgorithmConfig":
        new = self.__class__()
        new.__dict__.update(copy.deepcopy(self.__dict__))
        return new

    def build(self, env=None, logger_creator=None):
        if env is not None:
            self.env = env
        cls = self.algo_class
        if cls is None:
            raise ValueError("No algo_class bound to this config")
        return cls(config=self.to_dict(), env=self.env)

    def validate(self) -> None:
        pass
