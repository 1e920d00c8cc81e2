"""Algorithm: Trainable subclass owning WorkerSet(s) and the training loop.

Counterpart of the reference's ``rllib/algorithms/algorithm.py:134``
(``setup :312``, ``step :547``, ``evaluate :650``, ``training_step :841``,
``save_checkpoint :1438``, ``__getstate__ :2186``).
"""

from __future__ import annotations

import collections
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Type

import numpy as np

import ray_tpu as ray
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID
from ray_tpu.env.registry import get_env_creator
from ray_tpu.evaluation.metrics import summarize_episodes
from ray_tpu.evaluation.worker_set import WorkerSet
from ray_tpu.tune.trainable import Trainable
from ray_tpu.util import tracing

NUM_ENV_STEPS_SAMPLED = "num_env_steps_sampled"
NUM_AGENT_STEPS_SAMPLED = "num_agent_steps_sampled"


class Algorithm(Trainable):
    _default_policy_class = None

    @classmethod
    def get_default_config(cls) -> AlgorithmConfig:
        return AlgorithmConfig(cls)

    def __init__(self, config=None, env=None, logger_creator=None, **kwargs):
        if isinstance(config, AlgorithmConfig):
            config = config.to_dict()
        config = dict(config or {})
        if env is not None:
            config.setdefault("env", env)
        defaults = self.get_default_config().to_dict()
        merged = {**defaults, **config}
        with tracing.phase("setup:algorithm", algorithm=type(self).__name__):
            super().__init__(merged, logger_creator)

    def get_default_policy_class(self, config: Dict):
        return self._default_policy_class

    # -- setup -----------------------------------------------------------

    def setup(self, config: Dict) -> None:
        """reference algorithm.py:312."""
        self.callbacks = None
        cb_cls = config.get("callbacks_class")
        if cb_cls:
            self.callbacks = cb_cls()
        self._counters: Dict[str, int] = collections.defaultdict(int)
        self._timers: Dict[str, float] = collections.defaultdict(float)
        self._episode_history: List = []
        # run telemetry (docs/observability.md): activate BEFORE the
        # WorkerSet exists so the very first remote submission already
        # carries trace context; None when the config leaves it off
        from ray_tpu import telemetry as telemetry_lib

        self._telemetry = telemetry_lib.init_from_config(config)
        # iteration start stamps, for export_timeline(last_n=...)
        self._iteration_marks: collections.deque = collections.deque(
            maxlen=1024
        )
        # optional jax.profiler capture of the first N iterations
        # (telemetry(profile_iters=N); no-op fallback where the
        # profiler is unavailable — and numerics-neutral either way,
        # bit-parity-tested against telemetry off)
        tc = config.get("telemetry_config") or {}
        self._profile_iters = int(tc.get("profile_iters", 0) or 0)
        self._profiling = False
        # resilience layer (docs/resilience.md): the driver-side chaos
        # injector (None when inert) and the recovery manager step()
        # consults on failure — always present, inert until the config
        # arms it via AlgorithmConfig.fault_tolerance(...)
        from ray_tpu.resilience import faults as faults_lib
        from ray_tpu.resilience.recovery import RecoveryManager

        self._fault_injector = faults_lib.from_config(config)
        self._recovery = RecoveryManager(self)

        env_spec = config.get("env")
        env_creator = get_env_creator(env_spec) if env_spec else None
        policy_cls = self.get_default_policy_class(config)

        # Multi-controller (DCN) bring-up: when RAY_TPU_COORDINATOR is
        # set, every host running this same script joins the jax
        # distributed runtime FIRST, so the learner mesh below spans
        # all hosts' devices and gradient pmean rides ICI within a host
        # and DCN across (reference: torch.distributed init in
        # train/torch/config.py:83 / NCCL group setup).
        from ray_tpu.parallel import distributed as dist_lib
        from ray_tpu.utils.platform import ensure_compile_cache

        dist_lib.initialize()
        ensure_compile_cache()

        # learner mesh (driver-side policies): the sharding runtime's
        # ("batch",) mesh (docs/sharding.md)
        n_learner = config.get("learner_devices")
        import jax

        from ray_tpu import sharding as sharding_lib

        # sharding(hosts=N) — the multi-host learner fleet
        # (docs/fleet.md): the mesh spans the GLOBAL device view of
        # the N-process jax.distributed runtime just joined above;
        # strict resolution fails fast when the runtime geometry and
        # the config promise disagree
        hosts = sharding_lib.resolve_hosts(config, strict=True)
        devices = jax.devices()
        if n_learner:
            if hosts > 1:
                raise ValueError(
                    "learner_devices cannot trim a multi-host mesh "
                    f"(hosts={hosts}): every process's devices "
                    "participate; shrink the fleet by host instead"
                )
            devices = devices[:n_learner]
        # model_parallel (docs/sharding.md): a 2-D (data x model)
        # mesh — params of rule-declaring models split across M
        # shards instead of replicating on every device
        mp = sharding_lib.resolve_model_parallel(
            config, devices, strict=True
        )
        if mp:
            config["_mesh"] = sharding_lib.get_mesh(
                devices=devices,
                axis_shapes=[
                    ("batch", len(devices) // mp),
                    ("model", mp),
                ],
            )
        else:
            config["_mesh"] = sharding_lib.get_mesh(devices=devices)

        policy_specs = None
        policy_mapping_fn = config.get("policy_mapping_fn")
        if config.get("policies"):
            policy_specs = {}
            for pid, spec in config["policies"].items():
                if isinstance(spec, (tuple, list)):
                    cls, obs_sp, act_sp, overrides = spec
                    policy_specs[pid] = (
                        cls or policy_cls,
                        obs_sp,
                        act_sp,
                        overrides or {},
                    )
                else:
                    probe = env_creator(
                        config.get("env_config") or {}
                    )
                    policy_specs[pid] = (
                        policy_cls,
                        probe.observation_space,
                        probe.action_space,
                        {},
                    )

        num_workers = int(config.get("num_workers", 0))
        with tracing.phase("setup:workers", num_workers=num_workers):
            self.workers = WorkerSet(
                env_creator=env_creator,
                policy_cls=policy_cls,
                policy_specs=policy_specs,
                policy_mapping_fn=policy_mapping_fn,
                config=config,
                num_workers=num_workers,
            )
        # non-worker episode sources (the device rollout lane's
        # engine, drained fleet workers): callables returning
        # RolloutMetrics lists, read by _collect_rollout_metrics
        self._extra_metric_sources: List[Callable] = []
        # elastic fleet (docs/resilience.md "elastic fleets &
        # preemption"): the FleetController's monitor thread is owned
        # HERE — daemonized at setup, stop()-joined at cleanup — and
        # its fleet mutations apply only through reconcile() on the
        # driver thread between training-step rounds
        self._fleet = None
        if config.get("elastic") and int(
            config.get("num_workers", 0)
        ) > 0:
            from ray_tpu.autoscaler.fleet import FleetController

            self._fleet = FleetController(self, self.workers, config)
            self._extra_metric_sources.append(
                self._fleet.take_drained_metrics
            )
        # continuous checkpoint streaming (resilience/streamer.py):
        # background param/opt-state snapshots every few supersteps,
        # bounding work-lost-on-driver-crash to ~1 superstep
        self._ckpt_streamer = None
        if config.get("checkpoint_streaming"):
            from ray_tpu.resilience.streamer import CheckpointStreamer

            root = config.get("checkpoint_root") or os.path.join(
                self.logdir, "resilience"
            )
            self._ckpt_streamer = CheckpointStreamer(
                self,
                CheckpointStreamer.stream_root(root),
                every=int(
                    config.get("checkpoint_stream_interval", 1) or 1
                ),
            )
        self.evaluation_workers: Optional[WorkerSet] = None
        if config.get("evaluation_interval"):
            eval_config = {
                **config,
                **(config.get("evaluation_config") or {}),
                "num_workers": 0,
                # Never mirror evaluation rollouts into the offline
                # dataset — they come from a different (often
                # deterministic) distribution than training samples.
                "output": (config.get("evaluation_config") or {}).get(
                    "output"
                ),
                # Nor re-run an input factory (a PolicyServerInput
                # would try to bind the same port twice).
                "input": (config.get("evaluation_config") or {}).get(
                    "input"
                ),
            }
            self.evaluation_workers = WorkerSet(
                env_creator=env_creator,
                policy_cls=policy_cls,
                policy_specs=policy_specs,
                policy_mapping_fn=policy_mapping_fn,
                config=eval_config,
                num_workers=int(
                    config.get("evaluation_num_workers", 0)
                ),
            )
        # the compiled-program registry (sharding/registry.py): every
        # executable this config lowers, predicted up-front — warmup
        # and dispatch-diet coverage walk this one list
        # (tests/test_dispatch_diet.py asserts completeness).
        from ray_tpu.sharding import registry as registry_lib

        self.program_registry = registry_lib.for_algorithm(self)

    # -- training iteration ---------------------------------------------

    def training_step(self) -> Dict:
        """Override point (reference algorithm.py:841)."""
        raise NotImplementedError

    def _replay_tree_plane(self) -> str:
        """Which prioritized-replay tree implementation serves this
        run's draws: "device" | "host" (one plane), "mixed" (multiple
        buffers disagree — e.g. a spilled shard), or "none" (no
        prioritized buffer in play)."""
        planes = set()
        for shard in getattr(self, "replay_shards", None) or ():
            plane = getattr(shard, "tree_plane", None)
            if plane:
                planes.add(plane)
        buf = getattr(self, "local_replay_buffer", None)
        for b in (getattr(buf, "buffers", None) or {}).values():
            plane = getattr(b, "tree_plane", None)
            if plane:
                planes.add(plane)
        if not planes:
            return "none"
        if len(planes) == 1:
            return planes.pop()
        return "mixed"

    def step(self) -> Dict:
        """reference algorithm.py:547 (incl. worker-failure handling)."""
        from ray_tpu import telemetry as telemetry_lib

        config = self.config
        t0 = time.time()
        self._iteration_marks.append(t0)
        learn_before = telemetry_lib.metrics.learn_steps_total()
        superstep_before = telemetry_lib.metrics.counter_total(
            telemetry_lib.metrics.SUPERSTEP_UPDATES_TOTAL
        )
        h2d_before = telemetry_lib.metrics.h2d_bytes_by_path()
        d2h_before = telemetry_lib.metrics.d2h_bytes_by_path()
        train_info: Dict[str, Any] = {}
        min_t = config.get("min_time_s_per_iteration")
        min_ts = config.get("min_sample_timesteps_per_iteration") or 0
        ts_before = self._counters[NUM_ENV_STEPS_SAMPLED]
        self._recovery.begin_iteration()
        self._maybe_start_profile()
        # the iteration span is the driver-side root every remote
        # submission in this iteration parents under
        with tracing.start_span(
            "train:iteration", iteration=self._iteration + 1
        ):
            while True:
                try:
                    info = self.training_step()
                    if info:
                        train_info = info
                except Exception as e:
                    # resilience protocol (docs/resilience.md): worker
                    # death → bounded probe + recreate + degraded
                    # continue (per the recreate/ignore flags);
                    # restartable driver failure → restore the latest
                    # periodic checkpoint or stream tail; anything
                    # unhandled — or beyond the max_failures budget —
                    # propagates
                    if not self._recovery.handle_failure(e):
                        raise
                    continue
                # elastic fleet + checkpoint stream hooks run BETWEEN
                # training-step rounds — the only point where the
                # WorkerSet may change shape, and the superstep
                # boundary the stream snapshots ride
                if self._fleet is not None:
                    self._fleet.reconcile()
                if self._ckpt_streamer is not None:
                    self._ckpt_streamer.offer()
                done_t = (
                    min_t is None or (time.time() - t0) >= min_t
                )
                done_ts = (
                    self._counters[NUM_ENV_STEPS_SAMPLED] - ts_before
                    >= min_ts
                )
                if done_t and done_ts:
                    break
            # periodic checkpoint cadence (inside the iteration span,
            # so its recovery:checkpoint span lands in this
            # iteration's telemetry window)
            self._recovery.maybe_checkpoint()
        t_train_end = time.time()
        # what train() does outside training_step has a span of its
        # own, so a profile shows it beside the layers' spans
        with tracing.start_span("train:result"):
            results = self._iteration_result(
                train_info, t0, t_train_end, ts_before, learn_before,
                superstep_before, h2d_before, d2h_before,
            )
        self._maybe_stop_profile()
        return results

    def _iteration_result(
        self, train_info, t0, t_train_end, ts_before, learn_before,
        superstep_before, h2d_before, d2h_before,
    ) -> Dict:
        """The result dict of the iteration that ran from ``t0`` to
        ``t_train_end``: counters, timers, recovery and telemetry
        roll-ups (the ``*_before`` arguments are the counter readings
        taken at ``t0``), rollout metrics, evaluation, callbacks."""
        from ray_tpu import telemetry as telemetry_lib

        config = self.config
        results: Dict[str, Any] = {}
        results["info"] = {
            "learner": train_info,
            **{k: v for k, v in self._counters.items()},
        }
        # per-stage learner timers (device transfer / compile / step,
        # Policy.last_learn_timers) — sharding-backend A/Bs read these
        # straight from train() results instead of a profiler
        learn_timers: Dict[str, Dict[str, float]] = {}
        lw = self.workers.local_worker()
        for pid, pol in (getattr(lw, "policy_map", None) or {}).items():
            t = getattr(pol, "last_learn_timers", None)
            if t:
                learn_timers[pid] = dict(t)
        if learn_timers:
            results["info"]["timers"] = learn_timers
        # resilience roll-up: restart/recovery/skip counts + time lost
        # to recovery this iteration (span-derived recovery_s appears
        # in info/telemetry too when tracing runs); with an elastic
        # fleet / checkpoint stream running, their per-iteration state
        # rides along under info/recovery/fleet and .../stream
        recovery_info = self._recovery.stats()
        if self._fleet is not None:
            recovery_info["fleet"] = self._fleet.stats()
        if self._ckpt_streamer is not None:
            recovery_info["stream"] = self._ckpt_streamer.stats()
        results["info"]["recovery"] = recovery_info
        # per-iteration telemetry roll-up: throughput gauges always
        # (they're process-local and near-free), the span-derived
        # stage times + overlap fraction only when tracing runs
        throughput = telemetry_lib.metrics.record_iteration_throughput(
            # max(0): a mid-iteration checkpoint restore can rewind
            # the sampled-steps counter below its iteration-start value
            env_steps=float(
                max(
                    0,
                    self._counters[NUM_ENV_STEPS_SAMPLED] - ts_before,
                )
            ),
            learn_steps=(
                telemetry_lib.metrics.learn_steps_total()
                - learn_before
            ),
            wall_s=t_train_end - t0,
        )
        runtime_vals = telemetry_lib.metrics.sample_runtime_gauges()
        # compiled-program ledger (docs/observability.md "device
        # ledger"): per-program FLOPs / HBM bytes / execution counts /
        # MFU / recompile causes, in every result while the ledger runs
        if telemetry_lib.device.enabled():
            results["info"]["device_ledger"] = (
                telemetry_lib.device.snapshot()
            )
        if self._iteration == 0:
            # where the seconds before this iteration went: the
            # build's steps and every compile by family and phase,
            # kept whether or not tracing is on
            from ray_tpu.sharding.compile import compile_stats

            results["info"]["setup"] = {
                "phases": tracing.phases(),
                "compile": compile_stats()["families"],
            }
        if tracing.is_enabled():
            # roll up THIS iteration's window first: worker rollout
            # spans ride the result messages and are harvested (→
            # recorded driver-side) within the same iteration that
            # consumes their batches, so blanket-deferring the window
            # an iteration (the old behavior) threw away data it
            # already had — the synchronous path never needs the lag.
            # Only when the pipelined path's sampling for this window
            # is still in flight at the edge (no sample span landed in
            # it yet) fall back to the previous, now-settled window —
            # `window_iterations_ago` says which one this is.
            # Each span is read ONCE: the cursor hands over what
            # finished since the last roll-up (the spans that can lie
            # in this window, and the late ones below), so an
            # iteration's cost does not grow with the span buffer.
            fresh, self._span_cursor = tracing.spans_since(
                getattr(self, "_span_cursor", 0)
            )
            # late-harvest accounting (fleetview satellite): a span
            # first seen THIS iteration whose interval ended before a
            # window opened missed that window's roll-up entirely —
            # credit its full duration to the window we report now
            # instead of dropping it (late_stage_times). Spans from
            # before the first window ever rolled up (worker init,
            # compile warmup) belong to NO window — not late
            first = getattr(self, "_first_window_start", None)
            if first is None:
                self._first_window_start = first = t0

            def _late_for(window_start):
                return [
                    s for s in fresh
                    if first
                    <= (s.get("end") or s.get("start") or 0.0)
                    <= window_start
                ]

            rollup = telemetry_lib.iteration_rollup(
                fresh, t0, t_train_end, late=_late_for(t0)
            )
            lag = 0
            prev = getattr(self, "_prev_iter_window", None)
            if rollup["sample_s"] == 0.0 and prev is not None:
                settled = telemetry_lib.iteration_rollup(
                    getattr(self, "_prev_iter_spans", []) + fresh, *prev,
                    late=_late_for(prev[0]),
                )
                if settled["sample_s"] > 0.0:
                    rollup, lag = settled, 1
            self._prev_iter_spans = fresh
            rollup["window_iterations_ago"] = lag
            # per-iteration H2D bytes by path (docs/data_plane.md):
            # feeder/learn/replay_insert deltas next to the stage busy
            # times — the byte diet of device-resident replay is read
            # directly off `learn` (≈0) vs `replay_insert` here
            h2d_after = telemetry_lib.metrics.h2d_bytes_by_path()
            h2d = {
                p: h2d_after.get(p, 0.0) - h2d_before.get(p, 0.0)
                for p in set(h2d_after) | set(h2d_before)
            }
            d2h_after = telemetry_lib.metrics.d2h_bytes_by_path()
            d2h = {
                p: d2h_after.get(p, 0.0) - d2h_before.get(p, 0.0)
                for p in set(d2h_after) | set(d2h_before)
            }
            learn_delta = (
                telemetry_lib.metrics.learn_steps_total()
                - learn_before
            )
            superstep_delta = (
                telemetry_lib.metrics.counter_total(
                    telemetry_lib.metrics.SUPERSTEP_UPDATES_TOTAL
                )
                - superstep_before
            )
            env_steps_iter = float(
                max(
                    0,
                    self._counters[NUM_ENV_STEPS_SAMPLED] - ts_before,
                )
            )
            backend = config.get("env_backend", "actor")
            results["info"]["telemetry"] = {
                **rollup,
                **throughput,
                **runtime_vals,
                "h2d_bytes": {**h2d, "total": sum(h2d.values())},
                # which rollout lane produced this iteration's samples
                # and what it cost over the wire (docs/pipeline.md):
                # the jax lane's bytes are its key stacks (path
                # "rollout", ≈0); the actor lane's rollout batches
                # cross on the feeder/learn paths
                "rollout_lane": {
                    "backend": backend,
                    "env_steps": env_steps_iter,
                    "h2d_bytes": (
                        h2d.get("rollout", 0.0)
                        if backend == "jax"
                        else h2d.get("feeder", 0.0)
                        + h2d.get("learn", 0.0)
                    ),
                },
                # prioritized-replay plane (docs/data_plane.md
                # "device sum tree"): which tree implementation served
                # this iteration's draws, the sample path's H2D
                # payload (0 under the device tree — only the
                # generator's raw uniform stream crosses, reported
                # apart), and the PER refresh's remaining D2H (the
                # |td| pull that feeds the host alpha-power)
                "replay": {
                    "tree": self._replay_tree_plane(),
                    "sample_h2d_bytes": h2d.get("replay_sample", 0.0),
                    "rng_h2d_bytes": h2d.get("replay_rng", 0.0),
                    "d2h_bytes": d2h.get("replay_priorities", 0.0),
                },
                # superstep contract (docs/data_plane.md): how many of
                # this iteration's learner updates rode a fused
                # K-per-dispatch program
                "superstep": {
                    "updates": superstep_delta,
                    "learn_steps": learn_delta,
                    "fused_fraction": (
                        superstep_delta / learn_delta
                        if learn_delta
                        else 0.0
                    ),
                },
            }
        self._prev_iter_window = (t0, t_train_end)
        results.update(self._collect_rollout_metrics())
        from ray_tpu.execution.train_ops import (
            NUM_ENV_STEPS_TRAINED as _TRAINED,
        )

        results[_TRAINED] = self._counters[_TRAINED]
        results["num_env_steps_sampled"] = self._counters[
            NUM_ENV_STEPS_SAMPLED
        ]
        results["timesteps_total"] = self._counters[NUM_ENV_STEPS_SAMPLED]
        self._timesteps_total = self._counters[NUM_ENV_STEPS_SAMPLED]

        if (
            self.evaluation_workers is not None
            and self.config.get("evaluation_interval")
            and (self._iteration + 1)
            % self.config["evaluation_interval"]
            == 0
        ):
            results["evaluation"] = self.evaluate()
        # feed the dashboard-lite results ring (reference: the tune/job
        # dashboard modules read equivalent state from the GCS)
        try:
            from ray_tpu.dashboard import publish_result

            publish_result(
                {"training_iteration": self._iteration + 1, **results}
            )
        except Exception:
            pass
        if self.callbacks is not None:
            self.callbacks.on_train_result(
                algorithm=self, result=results
            )
        return results

    def _maybe_start_profile(self) -> None:
        """Begin the ``telemetry(profile_iters=N)`` capture on the
        first iteration: ``jax.profiler.start_trace`` into
        ``<logdir>/jax_profile`` when the profiler is available, a
        silent no-op otherwise (the capture must never change what the
        run computes — bit-parity-tested)."""
        if self._profile_iters <= 0 or self._profiling:
            return
        try:
            import jax.profiler

            path = os.path.join(self.logdir, "jax_profile")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            self._profiling = True
        except Exception:
            # unavailable/unsupported backend: disarm instead of
            # retrying every iteration
            self._profile_iters = 0

    def _maybe_stop_profile(self) -> None:
        if not self._profiling:
            return
        self._profile_iters -= 1
        if self._profile_iters > 0:
            return
        try:
            import jax.profiler

            jax.profiler.stop_trace()
        except Exception:
            pass
        self._profiling = False

    def on_recovery(self, kind: str) -> None:
        """Hook: the RecoveryManager just absorbed a failure of
        ``kind`` (``"workers"`` or ``"restore"``). Subclasses rebuild
        whatever driver-side machinery the failure invalidated (PPO:
        the sample pipeline; IMPALA: the learner thread)."""

    def on_fleet_change(self, added: List, removed: List) -> None:
        """Hook: the FleetController just changed the fleet —
        ``added`` workers joined (already weight+filter-synced),
        ``removed`` drained out. Subclasses wire joiners into (and
        drained workers out of) whatever persistent sampling machinery
        they run (PPO: the prefetch pipeline's request manager; IMPALA:
        the sampler rotation). The synchronous paths need nothing:
        they re-read ``workers.remote_workers()`` every round."""

    def _collect_rollout_metrics(self) -> Dict:
        episodes = []
        if self.workers.num_remote_workers() > 0:
            for eps in ray.get(
                [
                    w.get_metrics.remote()
                    for w in self.workers.remote_workers()
                ]
            ):
                episodes.extend(eps)
        lw = self.workers.local_worker()
        if lw is not None:
            episodes.extend(lw.get_metrics())
        # non-worker episode sources (the device rollout lane's
        # engine): callables returning RolloutMetrics lists
        for src in getattr(self, "_extra_metric_sources", ()):
            episodes.extend(src())
        # smooth over a sliding window (reference metrics smoothing)
        self._episode_history.extend(episodes)
        window = self.config.get(
            "metrics_num_episodes_for_smoothing", 100
        )
        self._episode_history = self._episode_history[-window:]
        summary = summarize_episodes(
            self._episode_history if self._episode_history else []
        )
        summary["episodes_this_iter"] = len(episodes)
        self._episodes_total += len(episodes)
        summary["episodes_total"] = self._episodes_total
        return summary

    # -- evaluation ------------------------------------------------------

    def evaluate(self) -> Dict:
        """reference algorithm.py:650 — fans out across the evaluation
        workers when ``evaluation_num_workers > 0``; weights AND
        observation-filter statistics sync to every eval worker first
        (stale MeanStd stats under-report the policy)."""
        assert self.evaluation_workers is not None
        weights = self.workers.local_worker().get_weights()
        filters = self.workers.local_worker().get_filters()
        lw = self.evaluation_workers.local_worker()
        lw.set_weights(weights)
        lw.sync_filters(filters)
        remote = self.evaluation_workers.remote_workers()
        if remote:
            weights_ref = ray.put(weights)
            ray.get(
                [w.set_weights.remote(weights_ref) for w in remote]
                + [w.sync_filters.remote(filters) for w in remote]
            )
        duration = self.config.get("evaluation_duration", 10)
        episodes = []
        if remote:
            # Round-robin sample rounds across the eval fleet until we
            # have the requested number of episodes.
            while len(episodes) < duration:
                ray.get([w.sample.remote() for w in remote])
                for eps in ray.get(
                    [w.get_metrics.remote() for w in remote]
                ):
                    episodes.extend(eps)
        else:
            while len(episodes) < duration:
                lw.sample()
                episodes.extend(lw.get_metrics())
        return summarize_episodes(episodes)

    def export_timeline(
        self, path: str, last_n: Optional[int] = None
    ) -> str:
        """Write the chrome://tracing JSON of the run's recorded spans
        (telemetry must be on with ``trace=True`` — or
        ``RAY_TPU_TRACE=1`` — or the file holds whatever little was
        recorded). ``last_n`` keeps only the last N train iterations,
        bounded by the span buffer (``RAY_TPU_TRACE_BUFFER``). Load at
        chrome://tracing or https://ui.perfetto.dev."""
        since = None
        marks = getattr(self, "_iteration_marks", None)
        if last_n and marks:
            since = marks[-min(int(last_n), len(marks))]
        return tracing.export_chrome_trace(path, since=since)

    def compute_single_action(
        self, observation, state=None, policy_id=DEFAULT_POLICY_ID,
        explore: Optional[bool] = None, **kwargs,
    ):
        """reference algorithm.py compute_single_action."""
        policy = self.get_policy(policy_id)
        worker = self.workers.local_worker()
        if worker.preprocessor is not None:
            observation = worker.preprocessor.transform(observation)
        filt = worker.filters.get(policy_id)
        if filt is not None:
            observation = filt(observation, update=False)
        explore = (
            self.config.get("explore", True)
            if explore is None
            else explore
        )
        action, state_out, _ = policy.compute_single_action(
            observation, state, explore=explore
        )
        if state:
            return action, state_out, {}
        return action

    def get_policy(self, policy_id: str = DEFAULT_POLICY_ID):
        return self.workers.local_worker().policy_map[policy_id]

    # -- checkpointing ---------------------------------------------------

    def __getstate__(self) -> Dict:
        """reference algorithm.py:2186."""
        state = {
            "worker": self.workers.local_worker().save(),
            "counters": dict(self._counters),
            "episodes_total": self._episodes_total,
        }
        return state

    def __setstate__(self, state: Dict) -> None:
        self.workers.local_worker().restore(state["worker"])
        self._counters = collections.defaultdict(
            int, state.get("counters", {})
        )
        self._episodes_total = state.get("episodes_total", 0)
        # push restored weights to rollout workers
        self.workers.sync_weights()

    @staticmethod
    def _atomic_write(path: str, write_fn) -> None:
        """Delegate to the shared helper (``util.atomic_io``, the one
        RTA009-sanctioned implementation). Directory sync stays with
        the caller: ``save_checkpoint`` batches several files and
        issues ONE ``_fsync_dir`` at the end."""
        from ray_tpu.util.atomic_io import atomic_write

        atomic_write(path, write_fn, sync_dir=False)

    def save_checkpoint(self, checkpoint_dir: str) -> str:
        """reference algorithm.py:1438. Alongside the state, a
        metadata file records the algorithm name and config so
        :meth:`from_checkpoint` can rebuild without the caller
        knowing either (reference checkpoint ``rllib_checkpoint.json``).
        Every file lands atomically (temp + ``os.replace``): a crash
        mid-save cannot corrupt an existing checkpoint, and the
        metadata file — written LAST — marks the checkpoint complete."""
        import json

        state = self.__getstate__()
        self._atomic_write(
            os.path.join(checkpoint_dir, "algorithm_state.pkl"),
            lambda f: pickle.dump(state, f),
        )
        from ray_tpu.core import serialization as _ser

        # cloudpickle (env creators etc.); runtime-injected keys
        # ("_mesh", ...) hold live device objects and are
        # rebuilt by setup(), so they stay out of the file
        config_blob = _ser.dumps(
            {
                k: v
                for k, v in self.config.items()
                if not k.startswith("_")
            }
        )
        self._atomic_write(
            os.path.join(checkpoint_dir, "algorithm_config.pkl"),
            lambda f: f.write(config_blob),
        )
        meta = {
            "type": "Algorithm",
            "algorithm_class": type(self).__name__,
            "algorithm_name": getattr(
                self, "_registry_name", None
            ) or type(self).__name__,
        }
        self._atomic_write(
            os.path.join(checkpoint_dir, "rllib_checkpoint.json"),
            lambda f: f.write(json.dumps(meta).encode()),
        )
        # fsync the DIRECTORY: the per-file fsync+replace above makes
        # each file's content durable, but the renames themselves live
        # in the directory inode — without this a host crash can leave
        # a directory whose entries still point at the old (or no)
        # files even though the data blocks hit disk
        self._fsync_dir(checkpoint_dir)
        self._prune_old_checkpoints(checkpoint_dir)
        return checkpoint_dir

    @staticmethod
    def _fsync_dir(path: str) -> None:
        from ray_tpu.util.atomic_io import fsync_dir

        fsync_dir(path)

    def _prune_old_checkpoints(self, checkpoint_dir: str) -> None:
        """Prune sibling ``checkpoint_*`` directories down to the
        newest ``keep_checkpoints_num`` (the reference knob). The one
        just written always survives; None/0 keeps everything."""
        keep = self.config.get("keep_checkpoints_num")
        if not keep or keep < 1:
            return
        import shutil

        current = os.path.abspath(checkpoint_dir)
        parent = os.path.dirname(current)
        try:
            siblings = sorted(
                os.path.join(parent, d)
                for d in os.listdir(parent)
                if d.startswith("checkpoint_")
                and os.path.isdir(os.path.join(parent, d))
            )
        except OSError:
            return
        # zero-padded names sort chronologically; newest last
        victims = [d for d in siblings if d != current][
            : max(0, len(siblings) - int(keep))
        ]
        for d in victims:
            shutil.rmtree(d, ignore_errors=True)
        if victims:
            self._fsync_dir(parent)

    @classmethod
    def from_checkpoint(cls, checkpoint_path: str) -> "Algorithm":
        """Rebuild a ready-to-run Algorithm from a checkpoint
        directory alone (reference ``Algorithm.from_checkpoint``,
        algorithm.py:315): the stored metadata names the algorithm,
        the stored config reconstructs it, and the state restores
        into it."""
        import json

        meta_path = os.path.join(
            checkpoint_path, "rllib_checkpoint.json"
        )
        algo_cls = cls
        if cls is Algorithm:
            if not os.path.exists(meta_path):
                raise ValueError(
                    f"{checkpoint_path!r} has no rllib_checkpoint.json;"
                    " call from_checkpoint on the concrete class or"
                    " re-save with this version"
                )
            with open(meta_path) as f:
                meta = json.load(f)
            from ray_tpu.algorithms.registry import (
                get_algorithm_class,
            )

            algo_cls = get_algorithm_class(meta["algorithm_name"])
        from ray_tpu.core import serialization as _ser

        with open(
            os.path.join(checkpoint_path, "algorithm_config.pkl"), "rb"
        ) as f:
            config = _ser.loads(f.read())
        algo = algo_cls(config=config)
        algo.load_checkpoint(checkpoint_path)
        return algo

    def load_checkpoint(self, checkpoint_path: str) -> None:
        if os.path.isdir(checkpoint_path):
            checkpoint_path = os.path.join(
                checkpoint_path, "algorithm_state.pkl"
            )
        with open(checkpoint_path, "rb") as f:
            state = pickle.load(f)
        self.__setstate__(state)

    def export_policy_model(
        self, export_dir: str, policy_id: str = DEFAULT_POLICY_ID
    ) -> None:
        self.get_policy(policy_id).export_checkpoint(export_dir)

    def cleanup(self) -> None:
        # an interrupted profile_iters capture must not leak an open
        # jax.profiler session into the next run in this process
        if getattr(self, "_profiling", False):
            self._profile_iters = 0
            self._maybe_stop_profile()
        # the fleet monitor observes the WorkerSet: stop (and join) it
        # before the workers it watches go away
        if getattr(self, "_fleet", None) is not None:
            self._fleet.stop()
        if getattr(self, "_ckpt_streamer", None) is not None:
            self._ckpt_streamer.stop()
        if hasattr(self, "workers"):
            self.workers.stop()
        if getattr(self, "evaluation_workers", None) is not None:
            self.evaluation_workers.stop()
