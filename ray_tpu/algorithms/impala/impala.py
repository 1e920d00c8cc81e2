"""IMPALA: V-trace off-policy actor-learner.

Counterpart of the reference's ``rllib/algorithms/impala/impala.py``
(config ``:344`` make_learner_thread, ``training_step :614``, weight
broadcast ``:645``) and the V-trace torch policy
(``vtrace_torch_policy.py`` + ``vtrace_torch.py:127,251``).

TPU-first design:
  - rollout workers emit FIXED (T,)-length unrolls that may span episode
    boundaries (``_fixed_unrolls``); no zero-padding or seq-len machinery —
    dones inside the fragment drive the V-trace discount resets;
  - the learner thread consumes whole unroll batches and runs ONE jitted
    program: model forward over (B·T), V-trace associative scan, loss,
    gradient, optimizer;
  - sampling and learning overlap: the shared
    ``execution.parallel_requests.AsyncRequestsManager`` keeps every
    worker saturated with ``sample.remote`` calls and harvests them
    with ``ray.wait`` to feed the thread's queue, while weights
    broadcast back to the workers that produced each batch (reference
    impala.py:645 + parallel_requests.py).
"""

from __future__ import annotations

import queue
import time
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
from ray_tpu.execution.learner_thread import LearnerThread
from ray_tpu.execution.parallel_requests import AsyncRequestsManager
from ray_tpu.execution.train_ops import (
    NUM_AGENT_STEPS_TRAINED,
    NUM_ENV_STEPS_TRAINED,
)
from ray_tpu.ops.vtrace import vtrace_from_logits
from ray_tpu.policy.jax_policy import JaxPolicy


class IMPALAConfig(AlgorithmConfig):
    """reference impala.py ImpalaConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or IMPALA)
        self.lr = 0.0005
        self.rollout_fragment_length = 50
        self.train_batch_size = 500
        self.num_workers = 2
        self.vtrace = True
        self.vtrace_clip_rho_threshold = 1.0
        self.vtrace_clip_pg_rho_threshold = 1.0
        self.vf_loss_coeff = 0.5
        self.entropy_coeff = 0.01
        self.entropy_coeff_schedule = None
        self.grad_clip = 40.0
        self.broadcast_interval = 1
        self.learner_queue_size = 16
        self.max_sample_requests_in_flight_per_worker = 2
        self.min_time_s_per_iteration = 1
        # >0 routes sample refs through aggregation actors that concat
        # fragments to train batches off-driver (reference
        # impala.py:874 process_experiences_tree_aggregation)
        self.num_aggregation_workers = 0

    def training(
        self,
        *,
        vtrace: Optional[bool] = None,
        vtrace_clip_rho_threshold: Optional[float] = None,
        vtrace_clip_pg_rho_threshold: Optional[float] = None,
        vf_loss_coeff: Optional[float] = None,
        entropy_coeff: Optional[float] = None,
        entropy_coeff_schedule=None,
        broadcast_interval: Optional[int] = None,
        learner_queue_size: Optional[int] = None,
        max_sample_requests_in_flight_per_worker: Optional[int] = None,
        **kwargs,
    ) -> "IMPALAConfig":
        super().training(**kwargs)
        if vtrace is not None:
            self.vtrace = vtrace
        if vtrace_clip_rho_threshold is not None:
            self.vtrace_clip_rho_threshold = vtrace_clip_rho_threshold
        if vtrace_clip_pg_rho_threshold is not None:
            self.vtrace_clip_pg_rho_threshold = (
                vtrace_clip_pg_rho_threshold
            )
        if vf_loss_coeff is not None:
            self.vf_loss_coeff = vf_loss_coeff
        if entropy_coeff is not None:
            self.entropy_coeff = entropy_coeff
        if entropy_coeff_schedule is not None:
            self.entropy_coeff_schedule = entropy_coeff_schedule
        if broadcast_interval is not None:
            self.broadcast_interval = broadcast_interval
        if learner_queue_size is not None:
            self.learner_queue_size = learner_queue_size
        if max_sample_requests_in_flight_per_worker is not None:
            self.max_sample_requests_in_flight_per_worker = (
                max_sample_requests_in_flight_per_worker
            )
        return self

    def aggregation(
        self, *, num_aggregation_workers: Optional[int] = None, **kwargs
    ) -> "IMPALAConfig":
        if num_aggregation_workers is not None:
            self.num_aggregation_workers = num_aggregation_workers
        return self


class ImpalaJaxPolicy(JaxPolicy):
    """V-trace policy-gradient loss over fixed (B, T) unrolls
    (reference vtrace_torch_policy.py VTraceLoss)."""

    def __init__(self, observation_space, action_space, config):
        config = dict(config)
        # One SGD pass over the whole unroll batch per learner step
        # (reference IMPALA semantics: minibatch_buffer, num_sgd_iter=1).
        T = int(config.get("rollout_fragment_length", 50))
        config.setdefault("num_sgd_iter", 1)
        config["sgd_minibatch_size"] = max(
            1, int(config.get("train_batch_size", 500)) // T
        )
        super().__init__(observation_space, action_space, config)
        self.unroll_len = T
        # IMPALA train rows are whole (T,)-fragments shaped by
        # _batch_to_train_tree; time-major handling lives in the loss
        # (_forward_unrolls), so the base class's flat-row unroll
        # chopping and T-multiple tiling must not apply.
        self._unroll_T = 1

    def _batch_to_train_tree(self, samples: SampleBatch) -> Dict[str, np.ndarray]:
        """Reshape flat rows → (num_unrolls, T, ...) + bootstrap obs."""
        T = self.unroll_len
        n = (samples.count // T) * T
        num = n // T

        def shape_col(v):
            v = np.asarray(v)[:n]
            return v.reshape((num, T) + v.shape[1:])

        from ray_tpu.ops.framestack import FRAME_IDX, FRAMES

        if FRAMES in samples:
            # worker-compressed fragments (compress_for_shipping):
            # ship the pool through; the (B, T+1) index column carries
            # obs AND the bootstrap stack (idx[-1]+1 by construction)
            idx = np.asarray(samples[FRAME_IDX], np.int32)[
                :n
            ].reshape(num, T)
            obs_cols = {
                FRAMES: np.asarray(samples[FRAMES]),
                FRAME_IDX: np.concatenate(
                    [idx, idx[:, -1:] + 1], axis=1
                ),
            }
        else:
            obs_cols = None
        out = {
            SampleBatch.ACTIONS: shape_col(samples[SampleBatch.ACTIONS]),
            SampleBatch.REWARDS: shape_col(
                samples[SampleBatch.REWARDS]
            ).astype(np.float32),
            SampleBatch.TERMINATEDS: shape_col(
                samples[SampleBatch.TERMINATEDS]
            ).astype(np.float32),
            # episode boundary of either kind (the reference's "dones"
            # drives both the V-trace discount and, for recurrent
            # models, the hidden-state reset)
            "dones": (
                shape_col(samples[SampleBatch.TERMINATEDS]).astype(
                    np.float32
                )
                + shape_col(
                    samples.get(
                        SampleBatch.TRUNCATEDS,
                        np.zeros(samples.count, np.float32),
                    )
                ).astype(np.float32)
            ).clip(max=1.0),
            SampleBatch.ACTION_LOGP: shape_col(
                samples[SampleBatch.ACTION_LOGP]
            ).astype(np.float32),
        }
        if obs_cols is not None:
            out.update(obs_cols)
            return out
        out[SampleBatch.OBS] = shape_col(samples[SampleBatch.OBS])
        out["bootstrap_obs"] = shape_col(
            samples[SampleBatch.NEXT_OBS]
        )[:, -1]
        return self._maybe_dedup_unroll_framestack(out)

    def _maybe_dedup_unroll_framestack(self, out):
        """Unroll-shaped variant of the base policy's framestack dedup:
        each (T,)-unroll plus its bootstrap obs is a sliding window of
        T + k frames (broken only at in-fragment episode resets, which
        the ``dones`` column marks), so the device transfer drops from
        (B, T+1) full k-stacks to ~(T + k) single frames per unroll.
        The (B, T+1) index column rebuilds OBS and bootstrap_obs on
        device (``_rebuild_obs_from_frames`` override)."""
        obs = out[SampleBatch.OBS]
        if (
            not self.config.get("dedup_framestack", True)
            or obs.ndim != 5
            or not 2 <= obs.shape[-1] <= 8
            or obs.nbytes
            < self.config.get("dedup_framestack_min_bytes", 1 << 20)
        ):
            return out
        from ray_tpu.ops.framestack import (
            FRAME_IDX,
            FRAMES,
            decompose_segmented_obs,
        )

        B, T = obs.shape[:2]
        ext = np.concatenate(
            [obs, out["bootstrap_obs"][:, None]], axis=1
        ).reshape((B * (T + 1),) + obs.shape[2:])
        seg = np.zeros(B * (T + 1), bool)
        seg[:: T + 1] = True  # each unroll starts a fresh window
        # the obs AFTER a done row is a reset obs (new window); the
        # bootstrap pseudo-row always slides (terminal next_obs does)
        dones = out["dones"][:, : T - 1] > 0
        seg.reshape(B, T + 1)[:, 1:T] |= dones
        dec = decompose_segmented_obs(ext, seg)
        if dec is None:
            return out
        stream, idx = dec
        out = dict(out)
        del out[SampleBatch.OBS]
        del out["bootstrap_obs"]
        out[FRAMES] = stream
        out[FRAME_IDX] = idx.reshape(B, T + 1)
        return out

    def _rebuild_obs_from_frames(self, frames, batch, stack_k):
        from ray_tpu.ops.framestack import FRAME_IDX, build_stacks

        batch = dict(batch)
        idx = batch.pop(FRAME_IDX)
        B, T1 = idx.shape
        stacks = build_stacks(frames, idx.reshape(-1), stack_k)
        stacks = stacks.reshape((B, T1) + stacks.shape[1:])
        batch[SampleBatch.OBS] = stacks[:, :-1]
        batch["bootstrap_obs"] = stacks[:, -1]
        return batch

    def _forward_unrolls(self, params, batch):
        """Forward the (B, T) fragment batch and its bootstrap obs in
        ONE pass over T+1 steps. Recurrent models run time-major with a
        zero fragment-start state and within-fragment resets driven by
        terminateds (dones already reset the V-trace discounts; this
        makes the hidden state agree). → (dist_inputs flattened over
        the T real steps, values (B, T), bootstrap_value (B,))."""
        obs = batch[SampleBatch.OBS]
        B, T = obs.shape[0], obs.shape[1]
        obs_ext = jnp.concatenate(
            [obs, batch["bootstrap_obs"][:, None]], axis=1
        )
        if self.model.is_recurrent:
            # episodes end by termination OR truncation; the hidden
            # state must reset at both (the rollout side did)
            dones = batch["dones"].astype(jnp.float32)
            resets = jnp.concatenate(
                [jnp.ones((B, 1), jnp.float32), dones], axis=1
            )
            state0 = self._zero_initial_state(obs_ext, B)
            dist_all, val_all, _ = self.model.apply(
                params, obs_ext, state0, resets=resets
            )
        else:
            flat = obs_ext.reshape((B * (T + 1),) + obs.shape[2:])
            dist_all, val_all, _ = self.model_forward(params, flat)
        dist_all = dist_all.reshape((B, T + 1) + dist_all.shape[1:])
        val_all = val_all.reshape(B, T + 1)
        dist_inputs = dist_all[:, :T].reshape(
            (B * T,) + dist_all.shape[2:]
        )
        return dist_inputs, val_all[:, :T], val_all[:, -1]

    def loss(self, params, batch, rng, coeffs):
        cfg = self.config
        gamma = cfg.get("gamma", 0.99)
        obs = batch[SampleBatch.OBS]
        B, T = obs.shape[0], obs.shape[1]

        dist_inputs, values, bootstrap_value = self._forward_unrolls(
            params, batch
        )
        values = values.reshape(B * T)
        dist = self.dist_class(dist_inputs)

        actions = batch[SampleBatch.ACTIONS]
        flat_actions = actions.reshape((B * T,) + actions.shape[2:])
        target_logp = dist.logp(flat_actions)
        entropy = dist.entropy()

        vtr = vtrace_from_logits(
            behaviour_action_log_probs=batch[SampleBatch.ACTION_LOGP],
            target_action_log_probs=target_logp.reshape(B, T),
            discounts=gamma * (1.0 - batch["dones"]),
            rewards=batch[SampleBatch.REWARDS],
            values=values.reshape(B, T),
            bootstrap_value=bootstrap_value,
            clip_rho_threshold=cfg.get("vtrace_clip_rho_threshold", 1.0),
            clip_pg_rho_threshold=cfg.get(
                "vtrace_clip_pg_rho_threshold", 1.0
            ),
        )
        pi_loss = -jnp.mean(
            vtr.pg_advantages * target_logp.reshape(B, T)
        )
        vf_loss = 0.5 * jnp.mean(
            jnp.square(vtr.vs - values.reshape(B, T))
        )
        entropy_mean = jnp.mean(entropy)
        total = (
            pi_loss
            + cfg.get("vf_loss_coeff", 0.5) * vf_loss
            - coeffs["entropy_coeff"] * entropy_mean
        )
        stats = {
            "policy_loss": pi_loss,
            "vf_loss": vf_loss,
            "entropy": entropy_mean,
            "vtrace_mean_rho_clip": jnp.mean(
                jnp.exp(
                    jnp.clip(
                        target_logp.reshape(B, T)
                        - batch[SampleBatch.ACTION_LOGP],
                        -10,
                        10,
                    )
                )
            ),
        }
        return total, stats


@ray.remote
class AggregatorWorker:
    """Off-driver batch concatenation (reference impala.py:946
    AggregatorWorker + execution/tree_agg.py): rollout fragments are
    routed here by reference and concatenated to full train batches in
    the aggregator's process, so the concat/copy work moves off the
    driver thread (on this single-host object plane the values still
    stage through driver shm; cross-node transfer is the DCN layer's
    job)."""

    def __init__(self, target_size: int):
        self.target_size = int(target_size)
        self._buf = []
        self._steps = 0

    def aggregate(self, batch):
        from ray_tpu.data.sample_batch import concat_samples

        self._buf.append(batch)
        self._steps += batch.env_steps()
        if self._steps < self.target_size:
            return None
        out = concat_samples(self._buf)
        self._buf = []
        self._steps = 0
        return out


class IMPALA(Algorithm):
    _default_policy_class = ImpalaJaxPolicy

    @classmethod
    def get_default_config(cls) -> IMPALAConfig:
        return IMPALAConfig(cls)

    def setup(self, config: Dict) -> None:
        config["_fixed_unrolls"] = True
        super().setup(config)
        # The learner thread publishes host weights every
        # broadcast_interval of ITS steps; the driver broadcasts the
        # published blob without ever touching the device (a driver-side
        # get_weights would both pay a D2H of the param tree and
        # serialize against the learner's on-device program queue).
        self._learner_thread = LearnerThread(
            self.get_policy(),
            inqueue_size=config.get("learner_queue_size", 16),
            publish_weights_every=max(
                1, int(config.get("broadcast_interval", 1))
            ),
        )
        self._learner_thread.start()
        # fragment accumulator: feed the learner whole train batches
        # (reference impala.py:614 concatenates sample batches to
        # train_batch_size before the learner queue), halving dispatch
        # and prepare_batch counts vs per-fragment feeding
        self._frag_buf: list = []
        self._frag_steps = 0
        self._train_ready: list = []  # concat batches awaiting queue room
        # weight-broadcast bookkeeping: published version each worker has
        self._worker_weight_ver: Dict = {}
        self._weights_ref = None
        self._weights_ref_ver = -1
        n_agg = int(config.get("num_aggregation_workers", 0))
        self._aggregators = [
            AggregatorWorker.remote(config.get("train_batch_size", 500))
            for _ in range(n_agg)
        ]
        self._agg_rr = 0
        self._agg_in_flight: list = []
        # worker polling rides the shared AsyncRequestsManager
        # (reference parallel_requests.py feeding impala.py:614): refs
        # mode when aggregation actors consume the fragment refs
        # directly, values mode otherwise
        self._sample_manager = AsyncRequestsManager(
            self.workers.remote_workers(),
            max_remote_requests_in_flight_per_worker=int(
                config.get(
                    "max_sample_requests_in_flight_per_worker", 2
                )
            ),
            return_object_refs=bool(self._aggregators),
            name="impala_sampler",
        )
        # elastic fleet: drains pull workers out of this rotation and
        # the controller reads its in-flight counts for idleness
        if self._fleet is not None:
            self._fleet.register_manager(self._sample_manager)

    def on_fleet_change(self, added, removed) -> None:
        """Elastic fleet: joiners enter the sampler rotation
        immediately (training_step's heal-drift add would catch them a
        round later); drained workers were already retired from the
        manager by the FleetController — just drop their stale
        weight-version bookkeeping."""
        super().on_fleet_change(added, removed)
        mgr = getattr(self, "_sample_manager", None)
        if mgr is not None and added:
            mgr.add_workers(added)
        for w in removed:
            self._worker_weight_ver.pop(id(w), None)

    def on_recovery(self, kind: str) -> None:
        """After a checkpoint restore the old learner thread is dead
        (that is usually WHY the restore ran): rebuild it around the
        restored policy so the actor-learner loop can continue."""
        super().on_recovery(kind)
        if kind != "restore":
            return
        lt = getattr(self, "_learner_thread", None)
        if lt is not None and lt.is_alive():
            lt.stop()
        self._learner_thread = LearnerThread(
            self.get_policy(),
            inqueue_size=self.config.get("learner_queue_size", 16),
            publish_weights_every=max(
                1, int(self.config.get("broadcast_interval", 1))
            ),
        )
        self._learner_thread.start()

    def training_step(self) -> Dict:
        """reference impala.py:614."""
        workers = self.workers.remote_workers()
        lt = self._learner_thread
        if not lt.is_alive():
            # surface the thread's parked exception (an injected crash
            # or a real learner bug) — with restore_on_failure set,
            # Algorithm.step's recovery path restores the latest
            # checkpoint and on_recovery rebuilds the thread
            raise lt.error or RuntimeError("learner thread died")

        if not workers:
            # degenerate synchronous mode (num_workers=0, tests):
            # accumulate local samples to a full train batch
            from ray_tpu.data.sample_batch import concat_samples

            collected = []
            steps = 0
            target = self.config.get("train_batch_size", 500)
            while steps < target:
                b = self.workers.local_worker().sample()
                collected.append(b)
                steps += b.env_steps()
            batch = concat_samples(collected)
            self._counters[NUM_ENV_STEPS_SAMPLED] += batch.env_steps()
            lt.add_batch(batch)
        else:
            # drain buffered train batches FIRST so backpressure
            # clears as soon as the learner makes queue room
            while self._train_ready:
                if lt.add_batch(self._train_ready[0], block=False):
                    self._train_ready.pop(0)
                else:
                    break
            # keep each worker saturated with sample requests — unless
            # the learner is backed up (backpressure: stop asking for
            # fragments we'd only buffer on the driver)
            mgr = self._sample_manager
            # heal drift: workers recreated by Algorithm.step's generic
            # failure path join the rotation here (no-op for known ones)
            mgr.add_workers(workers)
            backlogged = len(self._train_ready) >= 4
            if not backlogged:
                mgr.submit_available()

            if mgr.in_flight():
                ready = mgr.get_ready(timeout=2.0)
            else:
                # fully backpressured: nothing in flight to wait on —
                # give the learner a beat instead of spinning
                time.sleep(0.05)
                ready = {}
            target = int(self.config.get("train_batch_size", 500))
            for w, items in ready.items():
                for item in items:
                    if self._aggregators:
                        # tree aggregation (refs mode): hand the
                        # fragment ref to an aggregation actor; the
                        # concat to a full train batch happens in ITS
                        # process, not the driver's. Marshalling
                        # happens synchronously at .remote(), so the
                        # fragment ref can be freed right after — and
                        # a crashed worker's errored ref re-raises
                        # here, which drops the worker like the value
                        # mode harvest does.
                        agg = self._aggregators[
                            self._agg_rr % len(self._aggregators)
                        ]
                        self._agg_rr += 1
                        try:
                            self._agg_in_flight.append(
                                agg.aggregate.remote(item)
                            )
                        except (
                            ray.core.object_store.RayActorError,
                            ray.core.object_store.WorkerCrashedError,
                            ray.core.object_store.RayTaskError,
                        ):
                            mgr.report_dead(w)
                            continue
                        finally:
                            ray.free([item])
                    else:
                        batch = item
                        self._counters[NUM_ENV_STEPS_SAMPLED] += (
                            batch.env_steps()
                        )
                        # accumulate fragments into whole train batches
                        # (reference impala.py:614 — the learner
                        # consumes train_batch_size, not fragments)
                        self._frag_buf.append(batch)
                        self._frag_steps += batch.env_steps()
                        if self._frag_steps >= target:
                            from ray_tpu.data.sample_batch import (
                                concat_samples,
                            )

                            self._train_ready.append(
                                concat_samples(self._frag_buf)
                            )
                            self._frag_buf = []
                            self._frag_steps = 0
                    # broadcast the learner-published weights back to
                    # the producer (reference
                    # update_workers_if_necessary, impala.py:645) —
                    # cheap: no device access here
                    self._maybe_broadcast(w)
                    if not backlogged:
                        mgr.submit(worker=w)
            self._handle_dead_workers(mgr)

            # feed complete train batches; keep what the queue won't take
            while self._train_ready:
                if lt.add_batch(self._train_ready[0], block=False):
                    self._train_ready.pop(0)
                else:
                    break

        # collect aggregated train batches (tree-aggregation mode)
        if self._agg_in_flight:
            ready_agg, _ = ray.wait(
                self._agg_in_flight,
                num_returns=len(self._agg_in_flight),
                timeout=0,
            )
            for r in ready_agg:
                self._agg_in_flight.remove(r)
                try:
                    agg_batch = ray.get(r)
                finally:
                    ray.free([r])
                if agg_batch is not None:
                    self._counters[NUM_ENV_STEPS_SAMPLED] += (
                        agg_batch.env_steps()
                    )
                    lt.add_batch(agg_batch, block=False)

        # drain learner results
        learner_info = {}
        while True:
            try:
                steps, info = lt.outqueue.get_nowait()
            except queue.Empty:
                break
            self._counters[NUM_ENV_STEPS_TRAINED] += steps
            self._counters[NUM_AGENT_STEPS_TRAINED] += steps
            learner_info = info
        if not learner_info:
            learner_info = lt.learner_info
        return {
            DEFAULT_POLICY_ID: learner_info,
            "learner_queue": lt.stats(),
            "sample_manager": self._sample_manager.stats(),
        }

    def _handle_dead_workers(self, mgr: AsyncRequestsManager) -> None:
        """Drop-and-report protocol for the async loop: a dead worker
        leaves the sampling rotation (the manager already stopped
        submitting to it); recreate replacements when configured, never
        abort the actor-learner loop."""
        dead = mgr.take_dead_workers()
        if not dead:
            return
        self._counters["num_dead_rollout_workers"] += len(dead)
        if self.config.get("recreate_failed_workers"):
            new = self.workers.replace_failed_workers(dead)
            mgr.add_workers(new)
        else:
            self.workers.remove_workers(dead)

    def _maybe_broadcast(self, w) -> None:
        """Ship the learner thread's latest published weights to worker
        ``w`` if it hasn't seen that version yet. One ``ray.put`` per
        version; ``set_weights.remote`` marshals synchronously, so the
        previous version's blob can be freed when superseded."""
        pub = self._learner_thread.published_weights()
        if pub is None:
            return
        ver, host_w = pub
        if self._worker_weight_ver.get(id(w), 0) >= ver:
            return
        if self._weights_ref_ver != ver:
            if self._weights_ref is not None:
                ray.free([self._weights_ref])
            self._weights_ref = ray.put(host_w)
            self._weights_ref_ver = ver
        w.set_weights.remote(
            self._weights_ref,
            {"timestep": self._counters[NUM_ENV_STEPS_SAMPLED]},
        )
        self._worker_weight_ver[id(w)] = ver

    def cleanup(self) -> None:
        if hasattr(self, "_learner_thread"):
            self._learner_thread.stop()
        if getattr(self, "_weights_ref", None) is not None:
            try:
                ray.free([self._weights_ref])
            except Exception:
                pass
            self._weights_ref = None
        for a in getattr(self, "_aggregators", []):
            try:
                ray.kill(a)
            except Exception:
                pass
        super().cleanup()
