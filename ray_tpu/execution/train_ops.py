"""Training-step primitives.

Counterpart of the reference's ``rllib/execution/train_ops.py``
(``train_one_step :42``, ``multi_gpu_train_one_step :92``). The reference's
multi-GPU path — load_batch_into_buffer per device, threaded tower grads,
CPU averaging — is replaced by the JaxPolicy learner on the
``ray_tpu.sharding`` runtime: one device_put of the batch onto the mesh
(row-sharded columns, replicated params) and one ``sharded_jit``
multi-epoch SGD call, so both entry points below collapse to the same
code. Per-stage timers (transfer / compile / step) land in the policy's
``last_learn_timers`` and in the ``ray_tpu_learner_*_seconds``
histograms (utils/metrics.py); Algorithm.step copies them into
``results["info"]["timers"]``.
"""

from __future__ import annotations

from typing import Dict

from ray_tpu.data.sample_batch import (
    DEFAULT_POLICY_ID,
    MultiAgentBatch,
    SampleBatch,
)
from ray_tpu.util import tracing
from ray_tpu.utils.metrics import timer_histogram

NUM_ENV_STEPS_TRAINED = "num_env_steps_trained"
NUM_AGENT_STEPS_TRAINED = "num_agent_steps_trained"


def train_one_step(algorithm, train_batch) -> Dict:
    """reference train_ops.py:42.

    This is the driver-side learn choke point, so the resilience layer
    hooks in here (docs/resilience.md): the FaultInjector counts learn
    calls (NaN/Inf poisoning, injected crashes), and with
    ``config["nan_guard"]`` a non-finite batch is SKIPPED — counted in
    ``ray_tpu_skipped_batches_total`` and ``info/recovery`` — instead
    of being fed to the optimizer, where a single NaN would corrupt
    the params beyond repair."""
    import time as _time

    injector = getattr(algorithm, "_fault_injector", None)
    if injector is not None:
        injector.on_learn(train_batch)
    if algorithm.config.get("nan_guard"):
        from ray_tpu.resilience.recovery import batch_is_finite

        if not batch_is_finite(train_batch):
            algorithm._counters["num_nan_batches_skipped"] += 1
            recovery = getattr(algorithm, "_recovery", None)
            if recovery is not None:
                recovery.note_skipped_batch()
            return {}

    local_worker = algorithm.workers.local_worker()
    t0 = _time.perf_counter()
    with tracing.start_span(
        "train:learn_on_batch",
        env_steps=int(train_batch.env_steps()),
    ):
        info = local_worker.learn_on_batch(train_batch)
    algorithm._timers["learn_on_batch_s"] = _time.perf_counter() - t0
    timer_histogram("ray_tpu_learner_total_seconds").observe(
        algorithm._timers["learn_on_batch_s"]
    )
    algorithm._counters[NUM_ENV_STEPS_TRAINED] += train_batch.env_steps()
    algorithm._counters[NUM_AGENT_STEPS_TRAINED] += (
        train_batch.agent_steps()
        if isinstance(train_batch, MultiAgentBatch)
        else train_batch.count
    )
    return info


# On TPU the multi-device path is identical — the mesh lives inside the
# policy (reference multi_gpu_train_one_step :92 needed a separate
# buffer-loading protocol; here sharding is a device_put detail).
multi_gpu_train_one_step = train_one_step


def superstep_train_replay(
    algorithm,
    policy,
    buf,
    k: int,
    k_max: int,
    batch_size: int,
    *,
    prioritized: bool = False,
    beta: float = 0.4,
):
    """One fused superstep of ``k`` replay updates — the uniform
    K-updates-per-dispatch learner contract (docs/data_plane.md)
    shared by the whole DQN off-policy family.

    Index draws happen here, host-side, in the exact per-update
    generator call order (``draw_index_sets`` /
    ``draw_prioritized_sets``: k sequential draws, priorities frozen
    within the chain), then:

      - device-resident buffers hand their rings to the program
        (``superstep_feed``) — the scan gathers each update's rows in
        place, so only the ``(k, B)`` index matrix (plus PER weights)
        cross host→device;
      - host rings stack the k per-draw train trees into ONE
        ``(k, B, ...)`` H2D transfer.

    Prioritized buffers get the per-update ``|td|`` refresh as one
    stacked ``(k, B)`` D2H at superstep end, applied to the host sum
    tree in update order (bit-exact vs the per-update path given the
    same draws; nan-guard-skipped updates skip their refresh too).

    Returns the final update's stats dict, or None when this batch
    shape can't ride the scan (deduplicated frame pools) — the caller
    falls back to per-update chaining."""
    import jax
    import numpy as np

    from ray_tpu.execution.replay_buffer import DeviceReplayBuffer
    from ray_tpu.ops.framestack import FRAMES as _FRAMES

    device_mode = isinstance(buf, DeviceReplayBuffer) and not buf.spilled
    # a spilled device buffer delegates storage AND priority state to
    # its host ring — draw/update through that single source of truth
    src = (
        buf._host
        if isinstance(buf, DeviceReplayBuffer) and buf.spilled
        else buf
    )
    # device sum tree: the draw runs in-program and its (k_max, B)
    # index/weight matrices never exist host-side
    device_tree = (
        device_mode
        and prioritized
        and getattr(buf, "_dtree", None) is not None
    )
    refresh = prioritized and policy._td_error_device_fn() is not None
    pad = k_max - k
    # the draw schedule (host generator calls + the draw program under
    # the device tree), on whichever plane holds the priorities
    with tracing.start_span(
        "replay:draw", k=k, batch_size=batch_size
    ):
        if prioritized and device_tree:
            idx, weights = buf.draw_prioritized_sets_device(
                k, k_max, batch_size, beta
            )
        elif prioritized:
            idx, weights = src.draw_prioritized_sets(k, batch_size, beta)
        else:
            idx = src.draw_index_sets(k, batch_size)
            weights = None
        if pad and not device_tree:
            idx = np.concatenate(
                [idx, np.zeros((pad, batch_size), idx.dtype)]
            )
            if weights is not None:
                weights = np.concatenate(
                    [weights, np.ones((pad, batch_size), np.float32)]
                )

    if device_mode:
        extra = (
            {"weights": weights.astype(np.float32)}
            if weights is not None
            else {}
        )
        feed = buf.superstep_feed(idx, extra)
        infos, pri, skipped = policy.learn_superstep(
            k,
            batch_size,
            rings=feed,
            k_max=k_max,
            refresh_priorities=refresh,
        )
    else:
        trees = []
        for i in range(k):
            b = src._make_batch(idx[i])
            if prioritized:
                # same columns the per-update PER sample carries
                b["weights"] = weights[i].astype(np.float32)
                b["batch_indexes"] = idx[i].astype(np.int64)
            tree, bsize = policy.prepare_batch(b)
            if bsize != batch_size or _FRAMES in tree:
                return None  # ragged/frame-pool batch: per-update path
            trees.append(tree)
        stacked = {
            c: np.stack([t[c] for t in trees]) for c in trees[0]
        }
        if pad:
            stacked = {
                c: np.concatenate([v, np.repeat(v[:1], pad, axis=0)])
                for c, v in stacked.items()
            }
        infos, pri, skipped = policy.learn_superstep(
            k,
            batch_size,
            stacked=stacked,
            k_max=k_max,
            refresh_priorities=refresh,
        )

    # the PER refresh: host alpha-power of the drained |td|, then the
    # tree writes (one stacked device update under the device tree)
    with tracing.start_span("replay:refresh", k=k):
        if prioritized and device_tree:
            if pri is not None:
                # ONE stacked device update, applied in update order with
                # the skipped slots masked — the host tree walk is gone;
                # what remains host-side is the alpha-power on the pulled
                # |td| (docs/data_plane.md "device sum tree")
                buf.refresh_priorities_stacked(
                    idx[:k], pri, active=[not s for s in skipped]
                )
            else:
                for i in range(k):
                    if skipped[i]:
                        continue
                    buf.update_priorities(
                        idx[i],
                        np.full(
                            batch_size,
                            abs(infos[i].get("mean_td_error", 0.0)) + 1e-6,
                        ),
                    )
        elif prioritized:
            # apply in update order: overlapping draws must resolve
            # exactly as the per-update path's interleaved writes would
            for i in range(k):
                if skipped[i]:
                    continue
                if pri is not None:
                    src.update_priorities(idx[i], pri[i] + 1e-6)
                else:
                    # policies without per-sample errors: batch-mean
                    # scalar fallback (mirrors DQN._single_update)
                    src.update_priorities(
                        idx[i],
                        np.full(
                            batch_size,
                            abs(infos[i].get("mean_td_error", 0.0)) + 1e-6,
                        ),
                    )

    n_skipped = sum(1 for s in skipped if s)
    if n_skipped and algorithm is not None:
        algorithm._counters["num_nan_batches_skipped"] += n_skipped
        recovery = getattr(algorithm, "_recovery", None)
        if recovery is not None:
            for _ in range(n_skipped):
                recovery.note_skipped_batch()
    return infos[-1] if infos else {}
