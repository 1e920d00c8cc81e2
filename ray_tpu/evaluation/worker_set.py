"""WorkerSet: one local (learner) worker + N remote rollout actors.

Counterpart of the reference's ``rllib/evaluation/worker_set.py:50``
(``sync_weights :192``, ``foreach_worker :367``). Weight broadcast is a
single ``ray.put`` of the host pytree into the shared-memory object plane;
every actor maps the same segment (reference's object-store broadcast,
``worker_set.py:209-224``).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import ray_tpu as ray
from ray_tpu.evaluation.rollout_worker import RolloutWorker
from ray_tpu.resilience.retry import RetryPolicy, probe_actors
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing
from ray_tpu.utils.filter import MeanStdFilter

_ACTOR_DEAD_ERRORS = (
    ray.core.object_store.RayActorError,
    ray.core.object_store.WorkerCrashedError,
)


class WorkerSet:
    def __init__(
        self,
        *,
        env_creator,
        policy_cls=None,
        policy_specs=None,
        policy_mapping_fn=None,
        config: Dict,
        num_workers: int = 0,
        local_worker: bool = True,
    ):
        self._env_creator = env_creator
        self._policy_cls = policy_cls
        self._policy_specs = policy_specs
        self._policy_mapping_fn = policy_mapping_fn
        self._config = config
        self._remote_workers: List = []
        # the uniform retry/timeout/backoff schedule every driver-side
        # remote interaction below draws from (docs/resilience.md)
        self._retry = RetryPolicy.from_config(config)

        self._local_worker = None
        if local_worker:
            self._local_worker = RolloutWorker(
                env_creator=env_creator,
                policy_cls=policy_cls,
                policy_specs=policy_specs,
                policy_mapping_fn=policy_mapping_fn,
                config=config,
                worker_index=0,
                num_workers=num_workers,
            )
        if num_workers > 0:
            # the initial population needs no elastic-join sync: every
            # worker just built its policy from the same config/seed
            # the local worker did, and nothing has trained yet
            self.add_workers(num_workers, sync=False)

    def add_workers(
        self,
        num_workers: int,
        *,
        config_overrides: Optional[Dict] = None,
        sync: bool = True,
    ) -> None:
        """reference worker_set.py:234. ``config_overrides`` lets the
        recovery path hand replacements a modified config (e.g. an
        empty ``fault_injection`` spec so a recreated worker doesn't
        re-run its predecessor's death sentence). ``sync`` (default
        True — every mid-run join) queues the elastic-join
        weight+filter sync on the new actors; the constructor's
        initial population skips it."""
        if not ray.is_initialized():
            ray.init()
        RemoteWorker = ray.remote(RolloutWorker)
        start = len(self._remote_workers)
        # cross-host fleet: round-robin rollout actors over the named
        # cluster nodes ("any" = least-loaded); without the config key
        # all actors stay on the head host (core/cluster.py)
        nodes = self._config.get("worker_nodes") or []
        worker_config = {
            **self._config,
            "_mesh": None,
            **(config_overrides or {}),
        }
        # an injected kill/preemption models a lost host: the runtime's
        # in-place actor restart must not resurrect it (a restarted
        # process re-arms the injector's death sentence — fresh call
        # counts — and the chaos run never converges); the recovery
        # layer replaces the worker with a disarmed config instead
        fi = worker_config.get("fault_injection") or {}
        kill_armed = bool(
            fi.get("kill_worker") or fi.get("preempt_worker")
        )
        restarts = (
            3
            if self._config.get("recreate_failed_workers", False)
            and not kill_armed
            else 0
        )
        for i in range(num_workers):
            opts = dict(max_restarts=restarts)
            if nodes:
                opts["placement_node"] = nodes[(start + i) % len(nodes)]
            self._remote_workers.append(
                RemoteWorker.options(**opts).remote(
                    env_creator=self._env_creator,
                    policy_cls=self._policy_cls,
                    policy_specs=self._policy_specs,
                    policy_mapping_fn=self._policy_mapping_fn,
                    config=worker_config,
                    worker_index=start + i + 1,
                    num_workers=num_workers,
                )
            )
        # Elastic-join contract (docs/resilience.md): a joining worker
        # receives the CURRENT weights and observation-filter state
        # before its first sample call — actor calls execute in
        # submission order, so queuing the sync here, before the new
        # handles are ever returned to a sampling rotation, guarantees
        # it. A stale-policy first sample on scale-up would be silent
        # off-policy corruption for PPO (importance ratios computed
        # against ACTION_LOGP from weights the learner no longer has).
        if sync:
            self._sync_new_workers(self._remote_workers[start:])
        self._update_fleet_gauge()

    def _sync_new_workers(self, new_workers: List) -> None:
        if self._local_worker is None or not new_workers:
            return
        if not getattr(self._local_worker, "policy_map", None):
            return
        weights = self._local_worker.get_weights()
        filters = self._local_worker.get_filters()
        ref = ray.put(weights)
        for w in new_workers:
            try:
                w.set_weights.remote(ref)
                w.sync_filters.remote(filters)
            except _ACTOR_DEAD_ERRORS:
                continue

    def _update_fleet_gauge(self) -> None:
        telemetry_metrics.gauge(
            telemetry_metrics.ROLLOUT_WORKERS,
            "live remote rollout workers in this WorkerSet",
        ).set(float(len(self._remote_workers)))

    def local_worker(self) -> Optional[RolloutWorker]:
        return self._local_worker

    def remote_workers(self) -> List:
        return self._remote_workers

    def num_remote_workers(self) -> int:
        return len(self._remote_workers)

    # -- sync ------------------------------------------------------------

    def sync_weights(
        self,
        policies: Optional[List[str]] = None,
        global_vars: Optional[Dict] = None,
        to_worker_indices: Optional[List[int]] = None,
        inference_only: bool = False,
    ) -> None:
        """reference worker_set.py:192. ``inference_only`` ships each
        policy's acting subset (``get_inference_weights``) — the
        device→host pull of full off-policy towers (critic + target)
        otherwise dominates the sync."""
        if self._local_worker is None:
            return
        targets = self._remote_workers
        if to_worker_indices is not None:
            targets = [
                w
                for i, w in enumerate(targets)
                if i + 1 in to_worker_indices
            ]
        # the device->host pull of the acting weights is a blocking
        # read of megabytes: made only when somebody takes them
        with tracing.start_span(
            "rollout:sync_weights", workers=len(targets)
        ):
            if targets:
                ref = ray.put(
                    self._local_worker.get_weights(
                        policies, inference_only=inference_only
                    )
                )
                for w in targets:
                    try:
                        w.set_weights.remote(ref, global_vars)
                    except _ACTOR_DEAD_ERRORS:
                        # a corpse must not abort the broadcast to the
                        # rest of the fleet (recovery replaces it later)
                        continue
            else:
                telemetry_metrics.inc_weight_pulls_skipped()
        if global_vars:
            self._local_worker.set_global_vars(global_vars)

    def sync_filters(self) -> None:
        """Aggregate rollout filter deltas into the local worker's filters
        and broadcast the merged stats back (reference
        ``rllib/utils/filter_manager.py`` FilterManager.synchronize)."""
        if self._local_worker is None or not self._remote_workers:
            return
        remote_filters = []
        for w in self._remote_workers:
            try:
                remote_filters.append(
                    self._retry.call(
                        lambda w=w: ray.get(
                            w.get_filters.remote(True),
                            timeout=self._retry.timeout_s,
                        )
                    )
                )
            except _ACTOR_DEAD_ERRORS:
                continue  # dead worker contributes no filter delta
            except ray.core.object_store.GetTimeoutError:
                continue  # wedged worker: bounded skip, not a hang
        local = self._local_worker.filters
        for rf in remote_filters:
            for pid, f in rf.items():
                if pid in local and isinstance(f, MeanStdFilter):
                    local[pid].apply_changes(f, with_buffer=False)
        merged = {
            pid: f.as_serializable() for pid, f in local.items()
        }
        ref = ray.put(merged)
        for w in self._remote_workers:
            try:
                w.sync_filters.remote(ref)
            except _ACTOR_DEAD_ERRORS:
                continue

    # -- mapping ---------------------------------------------------------

    def _get_bounded(self, refs: List):
        """``ray.get`` under the retry policy: each attempt is bounded
        by the per-attempt timeout and timeouts re-wait on the backoff
        schedule (the refs keep computing across attempts — a retry
        never resubmits work), so a wedged actor costs
        ``max_attempts × timeout_s`` instead of an indefinite hang.
        Actor-death errors propagate immediately: callers of
        ``foreach_worker`` rely on them for the recreate protocol."""
        return self._retry.call(
            lambda: ray.get(refs, timeout=self._retry.timeout_s),
            retry_on=(ray.core.object_store.GetTimeoutError,),
        )

    def foreach_worker(self, fn: Callable) -> List:
        """reference worker_set.py:367."""
        out = []
        if self._local_worker is not None:
            out.append(fn(self._local_worker))
        out.extend(
            self._get_bounded(
                [w.apply.remote(fn) for w in self._remote_workers]
            )
        )
        return out

    def foreach_worker_with_index(self, fn: Callable) -> List:
        out = []
        if self._local_worker is not None:
            out.append(fn(self._local_worker, 0))
        refs = [
            w.apply.remote(fn, i + 1)
            for i, w in enumerate(self._remote_workers)
        ]
        out.extend(self._get_bounded(refs))
        return out

    def foreach_policy(self, fn: Callable) -> List:
        out = []
        for res in self.foreach_worker(
            lambda w: w.foreach_policy(fn)
        ):
            out.extend(res)
        return out

    def probe_unhealthy_workers(
        self, timeout_s: Optional[float] = None
    ) -> List[int]:
        """→ 1-based indices of workers that fail a ping (reference
        fault tolerance in worker_set / algorithm.try_recover). All
        pings fly in parallel under ONE wall-clock budget
        (``worker_health_probe_timeout_s``, default 10 s), so a single
        wedged actor delays the sweep by at most the budget instead of
        stalling the whole health check."""
        if timeout_s is None:
            timeout_s = float(
                self._config.get("worker_health_probe_timeout_s", 10.0)
            )
        return [
            i + 1
            for i in probe_actors(
                self._remote_workers, timeout_s=timeout_s
            )
        ]

    def remove_workers(self, workers: List) -> None:
        """Drop specific worker handles from the set (no ping probe).
        Used when an AsyncRequestsManager already OBSERVED the workers
        dead — probe_unhealthy_workers would spend a 30 s get-timeout
        per corpse rediscovering the fact."""
        drop = {id(w) for w in workers}
        self._remote_workers = [
            w for w in self._remote_workers if id(w) not in drop
        ]
        self._update_fleet_gauge()

    # replacements spin up with fault injection disarmed: an empty
    # spec also disables the RAY_TPU_FAULTS env fallback, so a
    # recreated worker doesn't re-run its predecessor's death sentence
    _REPLACEMENT_OVERRIDES = {"fault_injection": {}}

    def replace_failed_workers(self, dead: List) -> List:
        """Remove observed-dead workers and spawn replacements; returns
        the new handles (already weight-synced)."""
        if not dead:
            return []
        self.remove_workers(dead)
        before = len(self._remote_workers)
        # add_workers weight+filter-syncs the replacements before they
        # are returned (the elastic-join contract)
        self.add_workers(
            len(dead), config_overrides=self._REPLACEMENT_OVERRIDES
        )
        new = self._remote_workers[before:]
        telemetry_metrics.inc_worker_restarts(len(new))
        return new

    def recreate_failed_workers(self) -> int:
        """Probe the fleet (bounded), replace the unhealthy; returns
        the number of workers recreated."""
        bad = self.probe_unhealthy_workers()
        if not bad:
            return 0
        keep = [
            w
            for i, w in enumerate(self._remote_workers)
            if i + 1 not in bad
        ]
        self._remote_workers = keep
        self.add_workers(
            len(bad), config_overrides=self._REPLACEMENT_OVERRIDES
        )
        telemetry_metrics.inc_worker_restarts(len(bad))
        return len(bad)

    # -- elastic scaling (docs/resilience.md "elastic fleets") ----------

    def scale_up(self, num_workers: int) -> List:
        """Grow the fleet by ``num_workers``; returns the new handles,
        already weight+filter-synced (``add_workers``) so they can
        enter a sampling rotation immediately. Joiners spawn with
        fault injection disarmed — a scale-up must not inherit a
        chaos spec keyed on reused worker indices."""
        if num_workers <= 0:
            return []
        before = len(self._remote_workers)
        self.add_workers(
            num_workers, config_overrides=self._REPLACEMENT_OVERRIDES
        )
        return self._remote_workers[before:]

    def scale_to(self, n: int) -> Dict[str, List]:
        """Bring the fleet to exactly ``n`` remote workers. Scale-up
        spawns synced joiners; scale-down picks the newest workers as
        victims and removes them from the set (the caller — normally
        the FleetController — owns draining them first: harvesting
        in-flight work, merging filters, reaping the process).
        Returns ``{"added": [...], "removed": [...]}``."""
        n = max(0, int(n))
        cur = len(self._remote_workers)
        if n > cur:
            return {"added": self.scale_up(n - cur), "removed": []}
        if n < cur:
            victims = self._remote_workers[n:]
            self._remote_workers = self._remote_workers[:n]
            self._update_fleet_gauge()
            return {"added": [], "removed": victims}
        return {"added": [], "removed": []}

    def absorb_filters(self, remote_filters: Dict) -> None:
        """Merge one worker's flushed filter deltas into the local
        worker's filters (the drain protocol's last transfer — the
        same math ``sync_filters`` applies fleet-wide)."""
        if self._local_worker is None or not remote_filters:
            return
        local = self._local_worker.filters
        for pid, f in remote_filters.items():
            if pid in local and isinstance(f, MeanStdFilter):
                local[pid].apply_changes(f, with_buffer=False)

    @property
    def retry_policy(self) -> RetryPolicy:
        return self._retry

    def stop(self) -> None:
        if self._local_worker is not None:
            self._local_worker.stop()
        for w in self._remote_workers:
            try:
                w.stop.remote()
            except Exception:
                pass
