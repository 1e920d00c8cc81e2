"""Async learner thread for actor-learner algorithms (IMPALA/APPO/Apex).

Counterpart of the reference's ``rllib/execution/learner_thread.py:17`` and
``multi_gpu_learner_thread.py:20`` (``step :140``). Rollout batches queue in
from async worker polls; a DeviceFeeder pipelines host→device transfer so
the copy of batch k+1 overlaps the jitted SGD step of batch k (the
reference's _MultiGPULoaderThread + tower-buffer protocol, collapsed to a
double-buffered ``jax.device_put`` thread). Policies without the two-phase
JaxPolicy learn API fall back to synchronous ``learn_on_batch``.

Two further overlaps matter wherever a dispatch's host cost plus its
stats readback can exceed the nest's compute:

- **Deferred stats.** For policies without host-side
  ``after_learn_on_batch`` hooks, ``learn_on_device_batch`` runs with
  ``defer_stats=True``: the thread never blocks on the stats fetch, so
  up to ``STATS_LAG`` SGD programs queue on-device and the dispatch
  latency amortizes across them. Stats materialize ``STATS_LAG`` steps
  later, when the program has already finished (a free fetch).
- **Learner-side weight publishing.** The thread pulls host weights
  every ``publish_weights_every`` steps right after a step completes and
  parks them in a versioned slot. The driver broadcasts the published
  blob to rollout workers without ever touching the device — the
  reference's weight lock + ``get_weights`` on the driver thread would
  serialize the driver against the learner's device queue here.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Dict, Optional, Tuple

import jax

from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing

# Transfers in flight ahead of the compute step. 2 = classic double
# buffering: one batch on device waiting, one being copied.
PIPELINE_DEPTH = 2
# SGD programs allowed in the device queue before the thread materializes
# the oldest stats (which bounds queue depth AND device memory: each
# queued program pins its input batch buffers).
STATS_LAG = 3


class LearnerThread(threading.Thread):
    def __init__(
        self,
        policy,
        *,
        inqueue_size: int = 16,
        outqueue_size: int = 64,
        publish_weights_every: int = 0,
    ):
        super().__init__(daemon=True, name="learner_thread")
        self.policy = policy
        self.inqueue: "queue.Queue" = queue.Queue(maxsize=inqueue_size)
        self.outqueue: "queue.Queue" = queue.Queue(maxsize=outqueue_size)
        self.stopped = False
        self.num_steps = 0
        self.learner_info: Dict = {}
        self.queue_timer = 0.0
        self.grad_timer = 0.0
        self.publish_timer = 0.0
        # Pipeline only policies using the JaxPolicy two-phase learn API
        # through the standard composition: a subclass that overrides
        # learn_on_batch itself has semantics the split would bypass.
        from ray_tpu.policy.jax_policy import JaxPolicy

        self._pipelined = isinstance(policy, JaxPolicy) and (
            type(policy).learn_on_batch is JaxPolicy.learn_on_batch
        )
        # Stats can be deferred (and dispatches pipelined on-device) only
        # when nothing host-side consumes them between steps.
        self._defer = self._pipelined and (
            type(policy).after_learn_on_batch
            is JaxPolicy.after_learn_on_batch
        )
        self._feeder = None
        self._in_flight = 0
        self._lazy: "collections.deque" = collections.deque()
        # superstep contract (docs/data_plane.md): fuse up to K queued
        # batches into ONE compiled K-update program — one dispatch +
        # one stats drain per superstep instead of per update. Only
        # for policies whose update body rides the generic scan, and
        # only on the two-phase deferred path (host stat hooks between
        # updates would observe nothing anyway).
        self._superstep_k = 1
        try:
            if self._defer and getattr(
                policy, "supports_superstep", False
            ):
                from ray_tpu.sharding.superstep import (
                    resolve_superstep,
                )

                self._superstep_k = resolve_superstep(
                    getattr(policy, "config", None) or {},
                    getattr(policy, "mesh", None),
                )
        except Exception:
            self._superstep_k = 1
        self._depth = max(PIPELINE_DEPTH, self._superstep_k)
        self._stack_fn = None
        # Weight publishing: (version, host_weights) swapped atomically.
        self._publish_every = int(publish_weights_every)
        self._weights_lock = threading.Lock()
        self._published: Optional[Tuple[int, Dict]] = None
        self._steps_since_publish = 0
        # resilience: a thread exception parks here (healthy() flips
        # False) instead of vanishing into a dead daemon thread; the
        # chaos harness can also crash the thread deterministically
        from ray_tpu.resilience import faults as faults_lib

        self.error: Optional[BaseException] = None
        self._fault_injector = faults_lib.from_config(
            getattr(policy, "config", None) or {}
        )

    def _get_feeder(self):
        # Lazy: build on the learner thread so jax initializes there.
        if self._feeder is None:
            from ray_tpu.execution.device_feed import DeviceFeeder

            self._feeder = DeviceFeeder(
                self.policy.batch_shardings,
                capacity=max(2, self._superstep_k),
            )
        return self._feeder

    def _trim_fixed(self, tree, bsize):
        """Fixed-row contract for superstep stacking: trim a prepared
        tree to the largest shard-divisible row count at or under the
        config's train-batch geometry, so every queued batch has the
        same shape and K of them stack into one scan feed. Frame-pool
        batches (per-batch pool sizes) demote the thread to per-update
        dispatch instead."""
        from ray_tpu.ops.framestack import FRAMES as _FRAMES

        if _FRAMES in tree:
            self._superstep_k = 1
            return tree, bsize
        policy = self.policy
        cfg = getattr(policy, "config", None) or {}
        target = int(cfg.get("train_batch_size", bsize))
        # IMPALA-family trees are (num_unrolls, T, ...): rows are
        # whole unrolls, not env steps
        frag_T = int(getattr(policy, "unroll_len", 0) or 0)
        rows_target = target // frag_T if frag_T else target
        div = max(1, getattr(policy, "n_shards", 1)) * max(
            1, getattr(policy, "_unroll_T", 1)
        )
        fixed = (rows_target // div) * div
        if fixed <= 0 or bsize < fixed:
            return tree, bsize
        if bsize == fixed:
            return tree, bsize
        T = max(1, getattr(policy, "_unroll_T", 1))
        tree = {
            c: (
                v[: fixed // T]
                if c.startswith("__chunk__")
                else v[:fixed]
            )
            for c, v in tree.items()
        }
        return tree, fixed

    # ray-tpu: thread=learner
    def run(self) -> None:
        try:
            while not self.stopped:
                try:
                    self.step()
                except queue.Empty:
                    # idle: everything queued on-device has finished by
                    # now — flush any remaining deferred stats
                    self._drain_lazy(all_of_them=True)
                    continue
            self._drain_lazy(all_of_them=True)
        except BaseException as e:  # surfaced via healthy()/error
            self.error = e
        finally:
            # The learner thread owns the feeder: stopping it here (not in
            # stop(), which runs on another thread) avoids racing an
            # in-progress _pump against the feeder's stopped flag.
            if self._feeder is not None:
                self._feeder.stop()

    # ray-tpu: thread=learner
    def _pump(self, block: bool) -> bool:
        """Move one host batch inqueue → feeder. Returns True if moved."""
        batch = self.inqueue.get(timeout=0.5) if block else (
            self.inqueue.get_nowait()
        )
        if batch is None:
            self.stopped = True
            return False
        tree, bsize = self.policy.prepare_batch(batch)
        if self._superstep_k > 1:
            tree, bsize = self._trim_fixed(tree, bsize)
        self._get_feeder().put(tree, (bsize, batch.env_steps()))
        self._in_flight += 1
        return True

    # the counted drain helper: deferred stats materialize here,
    # STATS_LAG programs behind the dispatch (a free fetch)
    # ray-tpu: thread=learner drain-ok
    def _drain_lazy(self, all_of_them: bool = False) -> None:
        """Materialize deferred stats older than STATS_LAG (their
        programs have finished; the fetch is a cheap copy-out)."""
        keep = 0 if all_of_them else STATS_LAG
        while len(self._lazy) > keep:
            env_steps, stats = self._lazy.popleft()
            stats = jax.device_get(stats)
            info = {k: float(v) for k, v in stats.items()}
            info["cur_lr"] = self.policy.coeff_values.get("lr")
            self.learner_info = info
            try:
                self.outqueue.put_nowait((env_steps, info))
            except queue.Full:
                pass

    # ray-tpu: thread=learner
    def _maybe_publish(self, steps: int = 1) -> None:
        if not self._publish_every:
            return
        self._steps_since_publish += steps
        if self._steps_since_publish < self._publish_every:
            return
        t0 = time.perf_counter()
        host_w = self.policy.get_weights()
        with self._weights_lock:
            ver = (self._published[0] if self._published else 0) + 1
            self._published = (ver, host_w)
        self._steps_since_publish = 0
        self.publish_timer += time.perf_counter() - t0

    def published_weights(self) -> Optional[Tuple[int, Dict]]:
        """Latest (version, host_weights) pulled by the learner thread,
        or None before the first publish. Never touches the device."""
        with self._weights_lock:
            return self._published

    def healthy(self) -> bool:
        """False once the thread died (injected crash or real bug);
        the parked exception is in :attr:`error`."""
        return self.error is None and self.is_alive()

    # ray-tpu: thread=learner hot-path
    def step(self) -> None:
        if self._fault_injector is not None:
            self._fault_injector.on_learner_thread_step()
        if not self._pipelined:
            return self._step_sync()
        t0 = time.perf_counter()
        t_wait0 = time.time()
        # Top up the transfer pipeline; block only when nothing is in
        # flight (otherwise learn on what we have).
        if self._in_flight == 0:
            if not self._pump(block=True):
                return
        while self._in_flight < self._depth:
            try:
                if not self._pump(block=False):
                    break
            except queue.Empty:
                break
        try:
            dev, (bsize, env_steps) = self._feeder.get()
        finally:
            # A failed transfer still consumed an in-flight slot.
            self._in_flight -= 1
        self.queue_timer += time.perf_counter() - t0
        tracing.record_span(
            "learner:queue_wait", t_wait0, time.time()
        )
        telemetry_metrics.set_queue_depth(
            "learner_in", self.inqueue.qsize()
        )
        t0 = time.perf_counter()
        if self._defer and self._superstep_k > 1:
            if self._step_superstep(dev, bsize, env_steps, t0):
                return
            # demoted mid-flight (frame pools / ragged shapes):
            # fall through to the per-update deferred path
        if self._defer:
            stats = self.policy.learn_on_device_batch(
                dev, bsize, defer_stats=True
            )
            self._lazy.append((env_steps, stats))
            self.grad_timer += time.perf_counter() - t0
            self.num_steps += 1
            self._maybe_publish()
            self._drain_lazy()
            return
        info = self.policy.learn_on_device_batch(dev, bsize)
        self.grad_timer += time.perf_counter() - t0
        self.num_steps += 1
        self.learner_info = info
        self._maybe_publish()
        try:
            self.outqueue.put_nowait((env_steps, info))
        except queue.Full:
            pass

    # ray-tpu: thread=learner hot-path
    def _step_superstep(self, dev, bsize, env_steps, t0) -> bool:
        """Fuse up to ``_superstep_k`` queued device batches into one
        compiled K-update dispatch (one stats drain for the chain).
        Returns False — without consuming anything — when the first
        batch can't ride the scan (frame pools: per-batch pool sizes),
        demoting the thread to per-update dispatch. A starved or
        ragged collection learns what it gathered per-update instead
        (deferred), so throughput degrades gracefully."""
        from ray_tpu.ops.framestack import FRAMES as _FRAMES

        if _FRAMES in dev:
            self._superstep_k = 1
            return False
        k_sup = self._superstep_k
        batches = [(dev, bsize, env_steps)]
        while len(batches) < k_sup:
            while self._in_flight < self._depth:
                try:
                    if not self._pump(block=False):
                        break
                except queue.Empty:
                    break
            if self._in_flight <= 0:
                break
            try:
                d2, (b2, e2) = self._feeder.get(timeout=10.0)
            except queue.Empty:
                break
            self._in_flight -= 1
            batches.append((d2, b2, e2))
        sizes = {b[1] for b in batches}
        if len(batches) == k_sup and len(sizes) == 1:
            if self._stack_fn is None:
                from ray_tpu import sharding as sharding_lib

                self._stack_fn = sharding_lib.build_stack_fn(
                    self.policy.mesh,
                    k_sup,
                    label=f"superstep_stack[{k_sup}]",
                )
            stacked = self._stack_fn(*[b[0] for b in batches])
            infos, _, skipped = self.policy.learn_superstep(
                k_sup, bsize, stacked=dict(stacked), k_max=k_sup
            )
            self.grad_timer += time.perf_counter() - t0
            self.num_steps += k_sup
            for (_, _, e_), info in zip(batches, infos):
                info["cur_lr"] = self.policy.coeff_values.get("lr")
                self.learner_info = info
                try:
                    self.outqueue.put_nowait((e_, info))
                except queue.Full:
                    pass
            for s in skipped:
                if s:
                    telemetry_metrics.inc_skipped_batches()
            self._maybe_publish(steps=k_sup)
            return True
        # starved/ragged collection: per-update deferred dispatch
        for d_, b_, e_ in batches:
            stats = self.policy.learn_on_device_batch(
                d_, b_, defer_stats=True
            )
            self._lazy.append((e_, stats))
            self.num_steps += 1
        self.grad_timer += time.perf_counter() - t0
        self._maybe_publish(steps=len(batches))
        self._drain_lazy()
        return True

    # ray-tpu: thread=learner
    def _step_sync(self) -> None:
        t0 = time.perf_counter()
        t_wait0 = time.time()
        batch = self.inqueue.get(timeout=0.5)
        self.queue_timer += time.perf_counter() - t0
        tracing.record_span(
            "learner:queue_wait", t_wait0, time.time()
        )
        if batch is None:
            self.stopped = True
            return
        t0 = time.perf_counter()
        info = self.policy.learn_on_batch(batch)
        self.grad_timer += time.perf_counter() - t0
        self.num_steps += 1
        self.learner_info = info
        self._maybe_publish()
        try:
            self.outqueue.put_nowait((batch.env_steps(), info))
        except queue.Full:
            pass

    def add_batch(self, batch: SampleBatch, block: bool = True) -> bool:
        """Feed a rollout batch; returns False if dropped (queue full)."""
        try:
            self.inqueue.put(batch, block=block, timeout=5.0)
            telemetry_metrics.set_queue_depth(
                "learner_in", self.inqueue.qsize()
            )
            return True
        except queue.Full:
            return False

    def stop(self, join_timeout: float = 30.0) -> None:
        self.stopped = True
        try:
            self.inqueue.put_nowait(None)
        except queue.Full:
            pass
        # Join before interpreter teardown: a daemon thread killed while
        # inside a jitted XLA call aborts the process ("FATAL: exception
        # not rethrown") instead of exiting cleanly.
        if self.is_alive() and threading.current_thread() is not self:
            self.join(timeout=join_timeout)

    def stats(self) -> Dict:
        telemetry_metrics.set_queue_depth(
            "learner_out", self.outqueue.qsize()
        )
        return {
            "learner_queue_size": self.inqueue.qsize(),
            "num_steps_trained_this_thread": self.num_steps,
            "queue_wait_time_s": self.queue_timer,
            "grad_time_s": self.grad_timer,
            "weight_publish_time_s": self.publish_timer,
        }
