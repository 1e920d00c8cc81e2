"""Run-telemetry metric catalog: the aggregate series the training
loop exports (docs/observability.md lists them all).

Counterpart of the reference's component metric defs
(``_private/metrics_agent.py:63`` aggregates per-component OpenCensus
views; ``rllib``'s equivalents live scattered in learner/sampler
stats dicts). Here every series is a process-local
:mod:`ray_tpu.utils.metrics` instrument, scraped through the
``MetricsServer`` the telemetry runtime starts.

All accessors are get-or-create and therefore safe to call from hot
paths without holding module state; instruments live in the global
metric registry (``utils.metrics._REGISTRY``).
"""

from __future__ import annotations

from typing import Dict, Optional

from ray_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    get_metric,
    timer_histogram,
)

# -- metric names (one place, so docs/tests/dashboards can't drift) ----

ENV_STEPS_PER_S = "ray_tpu_env_steps_per_s"
LEARN_STEPS_PER_S = "ray_tpu_learn_steps_per_s"
ENV_STEPS_TOTAL = "ray_tpu_env_steps_sampled_total"
LEARN_STEPS_TOTAL = "ray_tpu_learn_steps_total"
QUEUE_DEPTH = "ray_tpu_queue_depth"
REQUESTS_IN_FLIGHT = "ray_tpu_requests_in_flight"
DEAD_WORKERS_TOTAL = "ray_tpu_dead_workers_total"
ROLLOUT_WORKERS = "ray_tpu_rollout_workers"
COMPILE_TRACES = "ray_tpu_compile_traces_total"
COMPILE_RECOMPILES = "ray_tpu_compile_recompiles_total"
COMPILE_TIME_S = "ray_tpu_compile_time_seconds_total"
JAX_LIVE_BUFFERS = "ray_tpu_jax_live_buffers"
JAX_DEVICE_MEMORY = "ray_tpu_jax_device_memory_bytes"
OVERLAP_FRACTION = "ray_tpu_iteration_overlap_fraction"
ITERATION_SECONDS = "ray_tpu_iteration_seconds"
# resilience layer (docs/resilience.md)
WORKER_RESTARTS_TOTAL = "ray_tpu_worker_restarts_total"
RECOVERIES_TOTAL = "ray_tpu_recoveries_total"
SKIPPED_BATCHES_TOTAL = "ray_tpu_skipped_batches_total"
# elastic fleets & preemption (docs/resilience.md): rollout-fleet size
# by lifecycle state, preemptions by outcome (drained = graceful exit
# inside the notice window; a lost preemption fell through to the
# ordinary kill path), and the continuous checkpoint stream's
# snapshot count + how many supersteps the written tail lags the run
FLEET_SIZE = "ray_tpu_fleet_size"
PREEMPTIONS_TOTAL = "ray_tpu_preemptions_total"
# learner fleet (docs/fleet.md): hosts in the current mesh epoch and
# the epoch generation itself (a resize shows as the host gauge
# stepping and the generation bumping together) and resizes by reason
# (drain vs heartbeat-expired)
LEARNER_FLEET_HOSTS = "ray_tpu_learner_fleet_hosts"
MESH_EPOCH = "ray_tpu_mesh_epoch"
MESH_RESIZES_TOTAL = "ray_tpu_mesh_resizes_total"
# fleet-wide observability plane (docs/observability.md "Fleet view",
# telemetry/fleetview.py): per-host barrier wall at each epoch-scoped
# barrier (seconds a host's arrival led the LAST arriver's,
# skew-corrected into the KV clock frame), straggler attribution
# (times a host WAS the last arriver), each exporter's measured clock
# offset against the coordinator's KV clock, how many hosts the
# aggregator currently holds live snapshots for, and the KV
# transport's own round-trip latency measured on the heartbeat path
FLEET_BARRIER_WAIT_SECONDS = "ray_tpu_fleet_barrier_wait_seconds"
FLEET_STRAGGLER_TOTAL = "ray_tpu_fleet_straggler_total"
FLEET_CLOCK_OFFSET_SECONDS = "ray_tpu_fleet_clock_offset_seconds"
FLEET_HOSTS_REPORTING = "ray_tpu_fleet_hosts_reporting"
KV_RTT_SECONDS = "ray_tpu_kv_rtt_seconds"
# fleet control-plane fault tolerance (docs/fleet.md "Failure model &
# leadership"): KV transport retries/reconnects per host, fenced
# (stale-term) coordinator writes the KV store rejected, the leader's
# current lease term, leadership transitions (standby promotions), and
# hosts that self-fenced after losing the KV plane past the liveness
# horizon
KV_RETRIES_TOTAL = "ray_tpu_kv_retries_total"
KV_RECONNECTS_TOTAL = "ray_tpu_kv_reconnects_total"
FLEET_FENCED_WRITES_TOTAL = "ray_tpu_fleet_fenced_writes_total"
FLEET_COORDINATOR_TERM = "ray_tpu_fleet_coordinator_term"
FLEET_FAILOVERS_TOTAL = "ray_tpu_fleet_failovers_total"
FLEET_SELF_FENCES_TOTAL = "ray_tpu_fleet_self_fences_total"
CKPT_STREAM_SNAPSHOTS_TOTAL = (
    "ray_tpu_checkpoint_stream_snapshots_total"
)
CKPT_STREAM_LAG = "ray_tpu_checkpoint_stream_lag_supersteps"
# device-resident data plane (docs/data_plane.md): host→device bytes
# by path — feeder (pipelined transfer), learn (sync learn_on_batch /
# stacked-chain transfer), replay_insert (each transition's ONE
# crossing into a device-resident replay buffer)
H2D_BYTES_TOTAL = "ray_tpu_h2d_bytes_total"
# superstep learner contract (docs/data_plane.md): updates executed
# inside fused K-updates-per-dispatch programs
SUPERSTEP_UPDATES_TOTAL = "ray_tpu_superstep_updates_total"
# routed-expert load of a model that holds a share of its experts
# (models/sequence_lm): per update, the mean and the largest count
# of tokens a held expert saw (summed over updates under "stat"), and
# the (token, slot) pairs that fell on experts held elsewhere
MOE_HELD_EXPERT_TOKENS_TOTAL = "ray_tpu_moe_held_expert_tokens_total"
MOE_ABSENT_SLOTS_TOTAL = "ray_tpu_moe_absent_slots_total"
# of the held experts of a layer, the share that at least one stream's
# token reached at the same place of its fragment (a decode step of the
# fused lane, where a fragment is one stream's rollout): summed over
# updates under stat = share, the updates counted under stat = updates
MOE_DECODE_HELD_TOUCHED_TOTAL = "ray_tpu_moe_decode_held_experts_touched_total"
# a group-limited router (ops/moe.chosen_groups): of an update's tokens,
# the share whose chosen groups hold one of this chip's experts, a mean
# over the expert layers, summed over updates under stat = share, the
# updates counted under stat = updates
MOE_HELD_GROUP_CHOSEN_TOTAL = "ray_tpu_moe_held_group_chosen_total"
# a learned index over an attention cache's rows (ops/sparse_index): an
# update's statistics of its choice, each summed over updates under its
# own name less ``index_`` (stat = rows_scored_mean | rows_selected_mean
# | selected_share_mean | dense_query_share), the updates counted under
# stat = updates
ATTENTION_INDEX_SELECTION_TOTAL = "ray_tpu_attention_index_selection_total"
# which lowering each traced one-token gated-delta step took
# (ops/deltanet.py): path = kernel (the Pallas kernel: a TPU, whole
# tiles) | xla (the jax.numpy body); decay = head (a number a head:
# Gated DeltaNet) | channel (a number a key channel: Kimi Delta
# Attention). Counted when the form is traced, once per layer of a
# traced program
DELTANET_STEP_LOWERINGS_TOTAL = "ray_tpu_deltanet_step_lowerings_total"
# which lowering each traced FRAGMENT form of the gated delta rule took
# (ops/deltanet.gated_delta_chunked): path = kernel (the Pallas kernel
# pair under one custom_vjp: a TPU, a decay a head, whole tiles) | xla
# (the chunked jax.numpy text: the CPU, odd sizes, every decay a
# channel); decay as above. Counted where the path is chosen, once per
# traced call (a run of like layers under one scan is one)
DELTANET_CHUNKED_LOWERINGS_TOTAL = "ray_tpu_deltanet_chunked_lowerings_total"
# which form each traced routed-expert layer's product took
# (models/sequence_lm, ops/moe.product_lowering): path = grouped
# (only the (token, slot) pairs on held experts, sorted by expert) |
# dense (every held expert over every token). Chosen from static shapes
# and counted when the layer is traced
MOE_PRODUCT_LOWERINGS_TOTAL = "ray_tpu_moe_product_lowerings_total"
# which form each traced latent-attention layer took
# (ops/latent_attention.latent_attention): form = absorbed
# (one token against the latent rows, every slot under a mask in XLA's
# text: the rollout's step off a TPU or in float32) | absorbed_kernel
# (the same product on ops/flash_attention.step_attention, a stream's
# held key blocks only: the rollout's step where that kernel's rule
# admits it, bfloat16 on a TPU) | absorbed_fragment (a fragment against the latent rows on the tiled
# fragment kernel: the learn form where ops/flash_attention's rule
# admits it, bfloat16 on a TPU) | expanded (a fragment, keys and values
# rebuilt through W_kvb: the learn form everywhere else).
# Counted when the form is traced: once per latent layer body of a
# program (layers whose checkpointed block is the same trace once)
MLA_DECODE_LOWERINGS_TOTAL = "ray_tpu_mla_decode_lowerings_total"
# which lowering each traced one-token state-space step took
# (ops/ssd.py): path = kernel (the Pallas kernel on the run's stacked
# leaf: a TPU backend, float32, whole-tile sizes) | xla (the jax.numpy
# body, everywhere else). Counted when the form is traced: once per
# body of a run of stacked layers (a run is one scan, so its layers
# share one trace)
SSM_STEP_LOWERINGS_TOTAL = "ray_tpu_ssm_step_lowerings_total"
# which form each traced sliding-window attention layer took over its
# ring cache (models/sequence_lm): form = step (one token written to
# slot position mod window, then the ring read under the slots' own
# positions: the rollout's step) | fragment (a fragment's queries over
# the stored ring, each row's position recovered from its slot and the
# start position, and the fragment's own keys inside the window: the
# learn form). Counted when the form is traced: once per window layer
# body of a program
WINDOW_CACHE_LOWERINGS_TOTAL = "ray_tpu_window_cache_lowerings_total"
# which form each traced EVA attention layer took over its two stores
# (ops/eva_attention.eva_attention): form = step (one token, XLA's text
# over every slot of both stores under the two masks: the CPU's path) |
# kernel (one token, the two-store step kernel: only the key blocks
# inside the masks are fetched) | fragment (a fragment from stored start
# states, the summaries it completes made inside it: the learn form).
# Counted when the form is traced: once per EVA layer body of a program
EVA_LOWERINGS_TOTAL = "ray_tpu_eva_lowerings_total"
# which form each traced selective scan took (ops/selective_scan.py, a
# Mamba-1 layer's recurrence): form = step (one token, state in and
# out) | fragment (a fragment from a stored state, the state on a
# scan's carry, a chunk of tokens under one checkpoint: XLA's text, the
# CPU's and odd sizes') | kernel (a fragment on the Pallas kernels, a
# tile of channels in VMEM over the fragment's tokens: a TPU's). Counted
# when the form is traced: once per scan body of a program
SELECTIVE_SCAN_LOWERINGS_TOTAL = "ray_tpu_selective_scan_lowerings_total"
# state or an activation that ONE layer makes and later layers read
# (models/sequence_lm/model.py, the layer loop's export / import
# channel): name = what was exported ("kv": a key/value cache, "memory":
# a scan's output), readers = the layers of the traced stack that import
# it. Counted when a stack is traced: once per export of a program
SHARED_STATE_LOWERINGS_TOTAL = "ray_tpu_shared_state_lowerings_total"
# which lowering each traced attention layer's fragment form took
# (ops/cached_attention.cached_attention, every softmax attention kind
# over a stored cache, and ops/latent_attention over its latent rows): path =
# kernel (ops/flash_attention's tiled fragment kernel, forward and
# backward: a TPU backend, bfloat16, fragments and caches of whole
# blocks) | xla (the score matrices a
# block of streams at a time, everywhere else). Counted when the form
# is traced: once per attention layer body of a program
ATTENTION_FRAGMENT_LOWERINGS_TOTAL = (
    "ray_tpu_attention_fragment_lowerings_total")
# which lowering each traced softmax-attention layer's ONE-TOKEN form
# took (ops/cached_attention.cached_attention, and the latent layer's
# in ops/latent_attention): path = kernel
# (ops/flash_attention.step_attention: a full-depth cache on a TPU
# backend, bfloat16, whole key blocks; a stream's key blocks past its
# depth are not fetched) | xla (every slot under a mask: every ring, and
# everywhere else). Counted when the form is traced
ATTENTION_STEP_LOWERINGS_TOTAL = "ray_tpu_attention_step_lowerings_total"
# the geometry of each traced softmax-attention layer body
# (models/sequence_lm AttentionLayer.apply, one body over a per-layer
# description): kind = the layer's name in ``layer_types``, heads = ITS
# query heads, rope = none | default | yarn. Counted when the body is
# traced (layers whose checkpointed block is the same trace once), in
# either form, so a trace says that every layer got its own geometry
ATTENTION_LAYER_LOWERINGS_TOTAL = "ray_tpu_attention_layer_lowerings_total"
# which form each traced SGD nest's minibatch took
# (policy/jax_policy.py _nest_device_fn): form = whole (one minibatch
# of every row of the per-shard batch: the nest takes the batch as it
# lies, no permutation, no gather, no pack of a uint8 column) |
# gathered (a strict subset of rows a step: pack uint8 columns once,
# gather by a permutation's indices, unpack a minibatch). Decided from
# shapes and counted when the nest is traced: once a traced nest body
LEARN_MINIBATCH_LOWERINGS_TOTAL = "ray_tpu_learn_minibatch_lowering_total"
# tokens through the stack of a model that generates by block diffusion
# (models/sequence_lm/generation.py), by the form of the forward: form =
# denoise | commit (the rollout's block forwards: counted by the device
# rollout lane a dispatch, env steps x the passes a token takes) | clean
# | noisy (the update's passes: the learn program's own count of what it
# traced, summed over the updates it ran); and the tokens the lane
# committed. Forwards a committed token = (denoise + commit) /
# block_length / committed
DIFFUSION_TOKEN_PASSES_TOTAL = "ray_tpu_diffusion_token_passes_total"
DIFFUSION_TOKENS_COMMITTED_TOTAL = "ray_tpu_diffusion_tokens_committed_total"
# prioritized-replay segment-tree operations by op and by which tree
# implementation performed them (docs/data_plane.md "device sum
# tree"): host = the numpy SumSegmentTree walk, device = the
# mesh-resident f64 tree programs. A healthy device-tree run shows
# its sample/update ops under tree="device" and zero under "host".
REPLAY_TREE_OPS_TOTAL = "ray_tpu_replay_tree_ops_total"
# device→host payload bytes by path — the mirror of the H2D counter
# for the readbacks the data plane still performs (today:
# "replay_priorities", the stacked |td| pull that feeds the host
# alpha-power before a device-tree priority refresh)
D2H_BYTES_TOTAL = "ray_tpu_d2h_bytes_total"
# device rollout lane (docs/pipeline.md): env steps taken INSIDE
# mesh-resident rollout programs (JaxVectorEnv lane) — compare against
# ray_tpu_env_steps_sampled_total for the on-device fraction
ENV_STEPS_ON_DEVICE_TOTAL = "ray_tpu_env_steps_on_device_total"
# the lane's host half (execution/jax_rollout.py, WorkerSet.sync_weights):
# reads of a rollout's episode metrics by kind (deferred = finished
# after a later program was dispatched, so the host waited for no
# rollout | blocking = forced before one), and acting-weight pulls
# that were not made because no remote worker takes the weights
ROLLOUT_DRAINS_TOTAL = "ray_tpu_rollout_drains_total"
WEIGHT_PULLS_SKIPPED_TOTAL = "ray_tpu_weight_pulls_skipped_total"
REPLAY_ROWS = "ray_tpu_replay_buffer_rows"
REPLAY_CAPACITY = "ray_tpu_replay_buffer_capacity"
REPLAY_BYTES = "ray_tpu_replay_buffer_bytes"
# param placement (docs/sharding.md "2-D mesh & param partitioning"):
# policy parameter bytes, global vs per-device — at M-way model
# parallelism per_shard sits near global/M; and the count of batch
# leaves whose ragged leading dim forced the replication fallback
# (specs.leaf_sharding) — a nonzero rate means a hot path ships
# full-copy columns it meant to row-shard
PARAMS_BYTES = "ray_tpu_params_bytes"
SHARDING_FALLBACK_TOTAL = (
    "ray_tpu_sharding_fallback_replicated_total"
)
# inference plane (docs/serving.md): the continuous-batching policy
# server's queue depth, coalesced forward batch sizes, request count,
# end-to-end request latency (p50/p99 read off the histogram or the
# server's exact stats()), and the params version the replica serves
# (bumps on checkpoint hot-reload)
SERVE_QUEUE_DEPTH = "ray_tpu_serve_queue_depth"
SERVE_BATCH_SIZE = "ray_tpu_serve_batch_size"
SERVE_REQUESTS_TOTAL = "ray_tpu_serve_requests_total"
SERVE_LATENCY_SECONDS = "ray_tpu_serve_latency_seconds"
SERVE_PARAMS_VERSION = "ray_tpu_serve_params_version"
# serve-plane batch observability (docs/serving.md): occupancy of the
# executed bucket (1.0 = every padded row was real work) and how long
# a request waited in the queue before its batch launched
SERVE_BATCH_FILL_FRACTION = "ray_tpu_serve_batch_fill_fraction"
SERVE_QUEUE_WAIT_SECONDS = "ray_tpu_serve_queue_wait_seconds"
# ingress front door (docs/serving.md "the front door",
# ray_tpu/ingress/): per-route request counts by HTTP status, admitted
# requests currently in flight, sheds by reason (inflight budget /
# queue-wait / expired deadline), and end-to-end ingress latency
INGRESS_REQUESTS_TOTAL = "ray_tpu_ingress_requests_total"
INGRESS_INFLIGHT = "ray_tpu_ingress_inflight"
INGRESS_SHED_TOTAL = "ray_tpu_ingress_shed_total"
INGRESS_LATENCY_SECONDS = "ray_tpu_ingress_latency_seconds"
# multi-process front door (ingress/supervisor.py): live worker
# processes in the bank, workers respawned after a crash, and admitted
# in-flight per policy (the per-tenant quota's observable)
INGRESS_WORKERS = "ray_tpu_ingress_workers"
INGRESS_WORKER_RESPAWNS_TOTAL = (
    "ray_tpu_ingress_worker_respawns_total"
)
INGRESS_POLICY_INFLIGHT = "ray_tpu_ingress_policy_inflight"
# open-loop flood harness (bench.py --flood): offered vs achieved
# rate of the CURRENT sweep step, and responses by contract outcome
# (ok / shed_429 / shed_503 / expired_504)
FLOOD_OFFERED_RPS = "ray_tpu_flood_offered_rps"
FLOOD_GOODPUT_RPS = "ray_tpu_flood_goodput_rps"
FLOOD_RESPONSES_TOTAL = "ray_tpu_flood_responses_total"
# cross-replica coalescing router (ingress/router.py): dispatched
# buckets, rows merged into them, requests dropped at their deadline
# BEFORE dispatch, and batches re-routed off a dead replica
ROUTER_BATCHES_TOTAL = "ray_tpu_router_batches_total"
ROUTER_MERGED_ROWS_TOTAL = "ray_tpu_router_merged_rows_total"
ROUTER_EXPIRED_TOTAL = "ray_tpu_router_expired_total"
ROUTER_REROUTED_TOTAL = "ray_tpu_router_rerouted_total"
# the compile account (sharding/compile.py): jax's own seconds of every
# compile by program family and phase (trace | lower | backend |
# analysis, the device ledger's second compile), and the persistent
# cache's verdicts (hit | miss); family "other" is what no
# ShardedFunction compiled
COMPILE_PHASE_SECONDS_TOTAL = "ray_tpu_compile_phase_seconds_total"
COMPILE_CACHE_EVENTS_TOTAL = "ray_tpu_compile_cache_events_total"
# device-plane program ledger (docs/observability.md "device ledger",
# telemetry/device.py): per compiled program — steady-state execution
# count, cumulative device-busy seconds closed at the drain points,
# and the program's per-execution FLOPs from cost_analysis()
PROGRAM_EXECUTIONS_TOTAL = "ray_tpu_program_executions_total"
PROGRAM_DEVICE_SECONDS_TOTAL = "ray_tpu_program_device_seconds_total"
PROGRAM_FLOPS = "ray_tpu_program_flops"


def gauge(
    name: str, description: str = "", tag_keys=()
) -> Gauge:
    """Get-or-create a Gauge (idempotent, like timer_histogram)."""
    m = get_metric(name)
    if isinstance(m, Gauge):
        return m
    return Gauge(name, description, tag_keys=tag_keys)


def counter(
    name: str, description: str = "", tag_keys=()
) -> Counter:
    m = get_metric(name)
    if isinstance(m, Counter):
        return m
    return Counter(name, description, tag_keys=tag_keys)


def histogram(name: str, description: str = "") -> Histogram:
    return timer_histogram(name, description)


# -- pipeline gauges (called from the execution layer) -----------------


def set_queue_depth(queue_name: str, depth: int) -> None:
    """Depth of one bounded pipeline queue (feeder in/out, learner
    in/out, prefetch) — the saturation signal of docs/pipeline.md."""
    gauge(
        QUEUE_DEPTH,
        "bounded pipeline queue depth",
        ("queue",),
    ).set(float(depth), {"queue": queue_name})


def set_requests_in_flight(manager: str, n: int) -> None:
    gauge(
        REQUESTS_IN_FLIGHT,
        "outstanding sample requests per AsyncRequestsManager",
        ("manager",),
    ).set(float(n), {"manager": manager})


def inc_dead_workers(manager: str, n: int = 1) -> None:
    counter(
        DEAD_WORKERS_TOTAL,
        "rollout workers observed dead",
        ("manager",),
    ).inc(float(n), {"manager": manager})


def inc_worker_restarts(n: int = 1) -> None:
    """Rollout workers recreated after observed death (fed by
    WorkerSet.replace_failed_workers / recreate_failed_workers)."""
    counter(
        WORKER_RESTARTS_TOTAL,
        "rollout workers recreated after failure",
    ).inc(float(n))


def inc_recoveries(kind: str, n: int = 1) -> None:
    """Recovery actions taken by the RecoveryManager, by kind
    (``workers`` = fleet probe+recreate, ``restore`` =
    checkpoint auto-restore)."""
    counter(
        RECOVERIES_TOTAL,
        "training-loop recovery actions",
        ("kind",),
    ).inc(float(n), {"kind": kind})


def inc_skipped_batches(n: int = 1) -> None:
    """Learn batches skipped by the non-finite guard (nan_guard)."""
    counter(
        SKIPPED_BATCHES_TOTAL,
        "learn batches skipped by the non-finite guard",
    ).inc(float(n))


def set_fleet_size(
    active: int, draining: int = 0, joining: int = 0
) -> None:
    """Rollout-fleet size by lifecycle state (set by the
    FleetController on every transition; docs/resilience.md fleet
    state machine)."""
    g = gauge(
        FLEET_SIZE,
        "rollout workers by fleet lifecycle state",
        ("state",),
    )
    g.set(float(active), {"state": "active"})
    g.set(float(draining), {"state": "draining"})
    g.set(float(joining), {"state": "joining"})


def inc_preemptions(drained: bool, n: int = 1) -> None:
    """Worker preemptions observed, split by outcome: ``drained`` =
    the eviction notice was honored (graceful exit, zero recovery
    budget); otherwise the preemption fell through to the ordinary
    kill/recovery path."""
    counter(
        PREEMPTIONS_TOTAL,
        "worker preemptions by drain outcome",
        ("drained",),
    ).inc(float(n), {"drained": "true" if drained else "false"})


def set_learner_fleet(hosts: int, gen: int) -> None:
    """Learner-fleet geometry under the current mesh epoch (set by
    the FleetCoordinator on every epoch cut; docs/fleet.md)."""
    gauge(
        LEARNER_FLEET_HOSTS,
        "learner hosts in the current mesh epoch",
    ).set(float(hosts))
    gauge(
        MESH_EPOCH,
        "current learner mesh epoch generation",
    ).set(float(gen))


def inc_mesh_resizes(reason: str, n: int = 1) -> None:
    """Learner-mesh resizes by reason (``preempted`` = notice-driven
    drain, ``heartbeat-expired`` = crashed host swept by liveness)."""
    counter(
        MESH_RESIZES_TOTAL,
        "learner mesh resizes",
        ("reason",),
    ).inc(float(n), {"reason": reason})


def set_barrier_wait(host: str, epoch: int, seconds: float) -> None:
    """How long ``host``'s arrival at the latest epoch-scoped barrier
    led the LAST arriver's (0 for the straggler itself) — the per-host
    DCN stall attribution the fleet aggregator computes from KV
    arrival records, skew-corrected into the coordinator's KV clock
    frame (docs/observability.md "Fleet view")."""
    gauge(
        FLEET_BARRIER_WAIT_SECONDS,
        "seconds a host waited on the barrier's last arriver",
        ("host", "epoch"),
    ).set(float(seconds), {"host": host, "epoch": str(epoch)})


def inc_straggler(host: str, n: int = 1) -> None:
    """One barrier where ``host`` was the LAST arriver (the fleet's
    measured straggler)."""
    counter(
        FLEET_STRAGGLER_TOTAL,
        "barriers where this host arrived last",
        ("host",),
    ).inc(float(n), {"host": host})


def set_clock_offset(host: str, seconds: float) -> None:
    """``host``'s wall clock minus the coordinator's KV clock, as
    measured by the exporter's NTP-style handshake (positive = the
    host's clock runs ahead)."""
    gauge(
        FLEET_CLOCK_OFFSET_SECONDS,
        "host wall clock minus the coordinator KV clock",
        ("host",),
    ).set(float(seconds), {"host": host})


def set_hosts_reporting(n: int) -> None:
    """Hosts the fleet aggregator currently holds a live (non-aged)
    snapshot for."""
    gauge(
        FLEET_HOSTS_REPORTING,
        "hosts with a live snapshot at the fleet aggregator",
    ).set(float(n))


def set_kv_rtt(host: str, seconds: float) -> None:
    """Round-trip latency of one KV heartbeat as measured by this
    host's HeartbeatReporter — the fleet plane's own transport
    health."""
    gauge(
        KV_RTT_SECONDS,
        "KV heartbeat round-trip seconds measured per host",
        ("host",),
    ).set(float(seconds), {"host": host})


def inc_kv_retries(host: str, op: str, n: int = 1) -> None:
    """KV ops this host re-attempted after a transient transport
    failure (the retried KV transport's backoff schedule fired)."""
    counter(
        KV_RETRIES_TOTAL,
        "KV ops retried after a transient transport failure",
        ("host", "op"),
    ).inc(float(n), {"host": host, "op": op})


def inc_kv_reconnects(host: str, n: int = 1) -> None:
    """KV control-plane threads (subscriber / heartbeat / exporter) on
    this host that re-established service after an outage window."""
    counter(
        KV_RECONNECTS_TOTAL,
        "control-plane threads that reconnected after a KV outage",
        ("host",),
    ).inc(float(n), {"host": host})


def inc_fleet_fenced_write(host: str, n: int = 1) -> None:
    """Coordinator writes rejected by the KV store for carrying a
    stale lease term — each one is a split-brain write that did NOT
    happen (``host`` is the zombie writer's lease holder identity)."""
    counter(
        FLEET_FENCED_WRITES_TOTAL,
        "stale-term coordinator writes rejected by the KV store",
        ("host",),
    ).inc(float(n), {"host": host})


def set_coordinator_term(host: str, term: int) -> None:
    """The lease term under which ``host``'s coordinator currently
    holds fleet leadership (bumps on every failover)."""
    gauge(
        FLEET_COORDINATOR_TERM,
        "lease term of this host's fleet coordinator",
        ("host",),
    ).set(float(term), {"host": host})


def inc_fleet_failover(host: str, n: int = 1) -> None:
    """Leadership transitions: a standby coordinator on ``host``
    acquired the fleet lease after the previous leader let it lapse."""
    counter(
        FLEET_FAILOVERS_TOTAL,
        "standby coordinators promoted to fleet leadership",
        ("host",),
    ).inc(float(n), {"host": host})


def inc_self_fence(host: str, n: int = 1) -> None:
    """Times this host parked at its epoch boundary because it could
    not reach KV past the liveness horizon (partition self-fencing:
    the mesh may have re-formed without it)."""
    counter(
        FLEET_SELF_FENCES_TOTAL,
        "hosts parked at an epoch boundary on a KV partition",
        ("host",),
    ).inc(float(n), {"host": host})


def inc_stream_snapshots(n: int = 1) -> None:
    """Snapshots written by the continuous CheckpointStreamer."""
    counter(
        CKPT_STREAM_SNAPSHOTS_TOTAL,
        "continuous checkpoint stream snapshots written",
    ).inc(float(n))


def set_stream_lag(supersteps: int) -> None:
    """How many supersteps the written stream tail lags the live run
    (the work-lost bound on a driver crash)."""
    gauge(
        CKPT_STREAM_LAG,
        "supersteps between the run head and the written stream tail",
    ).set(float(supersteps))


def inc_superstep_updates(n: int = 1) -> None:
    """Learner updates executed inside fused superstep programs (K
    updates per dispatch — docs/data_plane.md). Compare against
    ``ray_tpu_learn_steps_total`` for the fused fraction."""
    counter(
        SUPERSTEP_UPDATES_TOTAL,
        "learner updates run inside fused superstep dispatches",
    ).inc(float(n))


def note_expert_load(infos) -> None:
    """Feed the expert-load counters from drained per-update learner
    stats (a no-op for a policy whose model reports none)."""
    for info in infos:
        if "moe_tokens_per_held_expert" not in info:
            continue
        held = counter(
            MOE_HELD_EXPERT_TOKENS_TOTAL,
            "tokens a held expert saw per update: mean and max over the "
            "held experts, summed over updates; and the updates counted",
            ("stat",),
        )
        held.inc(float(info["moe_tokens_per_held_expert"]), {"stat": "mean"})
        held.inc(
            float(info["moe_max_tokens_per_held_expert"]), {"stat": "max"}
        )
        held.inc(1.0, {"stat": "updates"})
        counter(
            MOE_ABSENT_SLOTS_TOTAL,
            "(token, slot) pairs routed to experts this chip does not hold",
        ).inc(float(info["moe_slots_on_absent_experts"]))
        if "moe_decode_held_experts_touched_share" in info:
            touched = counter(
                MOE_DECODE_HELD_TOUCHED_TOTAL,
                "share of the held experts a decode step's tokens reached, "
                "summed over updates; and the updates counted",
                ("stat",),
            )
            touched.inc(
                float(info["moe_decode_held_experts_touched_share"]),
                {"stat": "share"})
            touched.inc(1.0, {"stat": "updates"})
        if "moe_held_group_chosen_share" in info:
            chosen = counter(
                MOE_HELD_GROUP_CHOSEN_TOTAL,
                "share of the tokens whose chosen groups of experts hold a "
                "held expert, summed over updates; and the updates counted",
                ("stat",),
            )
            chosen.inc(float(info["moe_held_group_chosen_share"]), {"stat": "share"})
            chosen.inc(1.0, {"stat": "updates"})


_INDEX_STATS = ("rows_scored_mean", "rows_selected_mean", "selected_share_mean",
                "dense_query_share")


def note_index_selection(infos) -> None:
    """Feed the learned index's counter from drained per-update learner
    stats (a no-op for a model whose attention has no index)."""
    for info in infos:
        if "index_selected_share_mean" not in info:
            continue
        chosen = counter(
            ATTENTION_INDEX_SELECTION_TOTAL,
            "a learned index's choice of cache rows, an update's means over "
            "queries and layers summed over updates; and the updates counted",
            ("stat",),
        )
        for stat in _INDEX_STATS:
            chosen.inc(float(info["index_" + stat]), {"stat": stat})
        chosen.inc(1.0, {"stat": "updates"})


def index_selection() -> Dict[str, float]:
    """``{stat: sum over updates, "updates": n}`` since the process
    began ({} for a model whose attention has no index)."""
    return _totals_by_tag(ATTENTION_INDEX_SELECTION_TOTAL, "stat")


def add_diffusion_token_passes(form: str, n: float) -> None:
    counter(
        DIFFUSION_TOKEN_PASSES_TOTAL,
        "tokens through the stack of a block-diffusion model, by forward",
        ("form",),
    ).inc(float(n), {"form": form})


def note_diffusion_rollout(passes: Dict[str, float], committed: int) -> None:
    """One dispatch of the device rollout lane with a model that commits
    a block a step: its ``denoise`` and ``commit`` token-passes and the
    tokens it committed."""
    for form in ("denoise", "commit"):
        add_diffusion_token_passes(form, passes[form])
    counter(
        DIFFUSION_TOKENS_COMMITTED_TOTAL,
        "tokens committed by block-diffusion generation on the device lane",
    ).inc(float(committed))


def note_diffusion_passes(infos) -> None:
    """Feed the update's ``clean`` and ``noisy`` token-passes from
    drained per-update learner stats (a no-op for a model that reports
    none)."""
    for info in infos:
        for form in ("clean", "noisy"):
            if f"diffusion_{form}_token_passes" in info:
                add_diffusion_token_passes(
                    form, float(info[f"diffusion_{form}_token_passes"]))


def diffusion_token_passes() -> Dict[str, float]:
    """``{form: token-passes, "committed": tokens}`` since the process
    began ({} for a model that generates a token a step)."""
    out = _totals_by_tag(DIFFUSION_TOKEN_PASSES_TOTAL, "form")
    if out:
        out["committed"] = counter_total(DIFFUSION_TOKENS_COMMITTED_TOTAL)
    return out


def expert_load_totals() -> Dict[str, float]:
    """``{"mean", "max", "updates", "absent_slots"}`` sums since the
    process began ({} for a model that reports no expert load)."""
    m = get_metric(MOE_HELD_EXPERT_TOKENS_TOTAL)
    if m is None:
        return {}
    out = {dict(tags).get("stat", ""): v for tags, v in m.series()}
    out["absent_slots"] = counter_total(MOE_ABSENT_SLOTS_TOTAL)
    return out


def decode_held_experts_touched() -> Dict[str, float]:
    """``{"share", "updates"}`` sums since the process began ({} for a
    model that reports none)."""
    return _totals_by_tag(MOE_DECODE_HELD_TOUCHED_TOTAL, "stat")


def held_group_chosen() -> Dict[str, float]:
    """``{"share", "updates"}`` sums since the process began ({} for a
    model whose router chooses no groups)."""
    return _totals_by_tag(MOE_HELD_GROUP_CHOSEN_TOTAL, "stat")


def _totals_by_tag(name: str, tag: str) -> Dict[str, float]:
    """``{value of tag: total}`` of a counter ({} before its first
    increment)."""
    m = get_metric(name)
    if m is None:
        return {}
    out: Dict[str, float] = {}
    for tags, v in m.series():  # summed over the counter's other tags
        key = dict(tags).get(tag, "")
        out[key] = out.get(key, 0.0) + v
    return out


def inc_deltanet_step_lowering(path: str, decay: str) -> None:
    """One traced one-token gated-delta step took ``path`` (``kernel``
    | ``xla``): ops/deltanet.py picks from platform and shape.
    ``decay``: a number a ``head``, or a key ``channel``."""
    counter(
        DELTANET_STEP_LOWERINGS_TOTAL,
        "one-token gated-delta steps traced, by the lowering they took",
        ("path", "decay"),
    ).inc(1.0, {"path": path, "decay": decay})


def inc_deltanet_chunked_lowering(path: str, decay: str) -> None:
    """One traced fragment form of the gated delta rule took ``path``
    (``kernel`` | ``xla``): ops/deltanet.py picks from platform, the
    decay's rank and the shapes. ``decay``: a number a ``head``, or a
    key ``channel``."""
    counter(
        DELTANET_CHUNKED_LOWERINGS_TOTAL,
        "fragment forms of the gated delta rule traced, by the lowering they took",
        ("path", "decay"),
    ).inc(1.0, {"path": path, "decay": decay})


def inc_moe_product_lowering(path: str) -> None:
    """One traced routed-expert layer took ``path`` (``grouped`` |
    ``dense``) for the held experts' product."""
    counter(
        MOE_PRODUCT_LOWERINGS_TOTAL,
        "routed-expert layers traced, by the form their product took",
        ("path",),
    ).inc(1.0, {"path": path})


def moe_product_lowerings() -> Dict[str, float]:
    """``{path: traced routed-expert layers}`` since the process began."""
    return _totals_by_tag(MOE_PRODUCT_LOWERINGS_TOTAL, "path")


def inc_mla_decode_lowering(form: str) -> None:
    """One traced latent-attention layer took ``form`` (``absorbed`` |
    ``absorbed_kernel`` | ``absorbed_fragment`` | ``expanded``)."""
    counter(
        MLA_DECODE_LOWERINGS_TOTAL,
        "latent-attention layers traced, by the form they took",
        ("form",),
    ).inc(1.0, {"form": form})


def mla_decode_lowerings() -> Dict[str, float]:
    """``{form: traced latent-attention layers}`` since the process began."""
    return _totals_by_tag(MLA_DECODE_LOWERINGS_TOTAL, "form")


def inc_ssm_step_lowering(path: str) -> None:
    """One traced one-token state-space step took ``path`` (``kernel``
    or ``xla``)."""
    counter(
        SSM_STEP_LOWERINGS_TOTAL,
        "one-token state-space steps traced, by the lowering they took",
        ("path",),
    ).inc(1.0, {"path": path})


def ssm_step_lowerings() -> Dict[str, float]:
    """``{path: traced one-token steps}`` since the process began."""
    return _totals_by_tag(SSM_STEP_LOWERINGS_TOTAL, "path")


def inc_window_cache_lowering(form: str) -> None:
    """One traced sliding-window attention layer took ``form``
    (``step`` | ``fragment``) over its ring cache."""
    counter(
        WINDOW_CACHE_LOWERINGS_TOTAL,
        "sliding-window attention layers traced, by the form they took",
        ("form",),
    ).inc(1.0, {"form": form})


def inc_eva_lowering(form: str) -> None:
    """One traced EVA attention layer took ``form`` (``step`` |
    ``kernel`` | ``fragment``) over its two stores."""
    counter(
        EVA_LOWERINGS_TOTAL,
        "EVA attention layers traced, by the form they took",
        ("form",),
    ).inc(1.0, {"form": form})


def inc_selective_scan_lowering(form: str) -> None:
    """One traced selective scan took ``form`` (``step`` | ``fragment``
    | ``kernel``)."""
    counter(
        SELECTIVE_SCAN_LOWERINGS_TOTAL,
        "selective scans traced, by the form they took",
        ("form",),
    ).inc(1.0, {"form": form})


def inc_shared_state_lowering(name: str, readers: int) -> None:
    """One traced stack exported ``name`` to ``readers`` later layers."""
    counter(
        SHARED_STATE_LOWERINGS_TOTAL,
        "exports of one layer's state or activation to later layers, traced",
        ("name", "readers"),
    ).inc(1.0, {"name": name, "readers": str(int(readers))})


def inc_attention_layer_lowering(kind: str, heads: int, rope: str) -> None:
    """One traced softmax-attention layer body of this geometry."""
    counter(
        ATTENTION_LAYER_LOWERINGS_TOTAL,
        "softmax-attention layer bodies traced, by their geometry",
        ("kind", "heads", "rope"),
    ).inc(1.0, {"kind": kind, "heads": str(int(heads)), "rope": rope})


def attention_layer_lowerings() -> Dict[str, float]:
    """``{"<kind>/<heads>/<rope>": traced layer bodies}`` since the
    process began."""
    m = get_metric(ATTENTION_LAYER_LOWERINGS_TOTAL)
    if m is None:
        return {}
    out = {}
    for tags, v in m.series():
        t = dict(tags)
        out["/".join(t.get(k, "") for k in ("kind", "heads", "rope"))] = v
    return out


def inc_learn_minibatch_lowering(form: str) -> None:
    """One traced SGD nest took ``form`` (``whole`` | ``gathered``) for
    its minibatches."""
    counter(
        LEARN_MINIBATCH_LOWERINGS_TOTAL,
        "SGD nests traced, by the form their minibatches took",
        ("form",),
    ).inc(1.0, {"form": form})


def learn_minibatch_lowerings() -> Dict[str, float]:
    """``{form: traced SGD nests}`` since the process began."""
    return _totals_by_tag(LEARN_MINIBATCH_LOWERINGS_TOTAL, "form")


def inc_attention_fragment_lowering(path: str) -> None:
    """One traced attention layer's fragment form took ``path``
    (``kernel`` | ``xla`` | ``selected_kernel``: the kernel pair with a
    learned index's choice of rows as one more operand |
    ``selected_xla``: the text under the choice)."""
    counter(
        ATTENTION_FRAGMENT_LOWERINGS_TOTAL,
        "attention layers' fragment forms traced, by the lowering they took",
        ("path",),
    ).inc(1.0, {"path": path})


def attention_fragment_lowerings() -> Dict[str, float]:
    """``{path: traced fragment forms}`` since the process began."""
    return _totals_by_tag(ATTENTION_FRAGMENT_LOWERINGS_TOTAL, "path")


def inc_attention_step_lowering(path: str) -> None:
    """One traced attention layer's one-token form took ``path``
    (``kernel`` | ``xla`` | ``selected_xla``: the text over every slot
    under a learned index's choice of rows)."""
    counter(
        ATTENTION_STEP_LOWERINGS_TOTAL,
        "attention layers' one-token forms traced, by the lowering they took",
        ("path",),
    ).inc(1.0, {"path": path})


def attention_step_lowerings() -> Dict[str, float]:
    """``{path: traced one-token forms}`` since the process began."""
    return _totals_by_tag(ATTENTION_STEP_LOWERINGS_TOTAL, "path")


def window_cache_lowerings() -> Dict[str, float]:
    """``{form: traced sliding-window layers}`` since the process began."""
    return _totals_by_tag(WINDOW_CACHE_LOWERINGS_TOTAL, "form")


def eva_lowerings() -> Dict[str, float]:
    """``{form: traced EVA attention layers}`` since the process began."""
    return _totals_by_tag(EVA_LOWERINGS_TOTAL, "form")


def selective_scan_lowerings() -> Dict[str, float]:
    """``{form: traced selective scans}`` since the process began."""
    return _totals_by_tag(SELECTIVE_SCAN_LOWERINGS_TOTAL, "form")


def shared_state_lowerings() -> Dict[str, float]:
    """``{"<name>/<readers>": traced exports}`` since the process began."""
    m = get_metric(SHARED_STATE_LOWERINGS_TOTAL)
    return {} if m is None else {
        "{name}/{readers}".format(**dict(tags)): v for tags, v in m.series()}


def deltanet_step_lowerings() -> Dict[str, float]:
    """``{path: traced one-token steps}`` since the process began."""
    return _totals_by_tag(DELTANET_STEP_LOWERINGS_TOTAL, "path")


def deltanet_chunked_lowerings() -> Dict[str, float]:
    """``{"<path>/<decay>": traced fragment forms of the gated delta
    rule}`` since the process began."""
    m = get_metric(DELTANET_CHUNKED_LOWERINGS_TOTAL)
    return {} if m is None else {
        "{path}/{decay}".format(**dict(tags)): v for tags, v in m.series()}


def inc_env_steps_on_device(n: int) -> None:
    """Env steps executed inside a device rollout program (the
    JaxVectorEnv lane — zero rollout bytes over H2D)."""
    counter(
        ENV_STEPS_ON_DEVICE_TOTAL,
        "env steps taken inside mesh-resident rollout programs",
    ).inc(float(n))


def inc_rollout_drain(deferred: bool) -> None:
    """One read of a device rollout's episode metrics, by whether a
    later dispatch stood between the rollout and the read."""
    counter(
        ROLLOUT_DRAINS_TOTAL,
        "reads of a device rollout's episode metrics by kind",
        ("kind",),
    ).inc(1.0, {"kind": "deferred" if deferred else "blocking"})


def rollout_drains() -> Dict[str, float]:
    """``{kind: reads}`` since the process began."""
    return _totals_by_tag(ROLLOUT_DRAINS_TOTAL, "kind")


def inc_weight_pulls_skipped() -> None:
    counter(
        WEIGHT_PULLS_SKIPPED_TOTAL,
        "sync_weights calls that pulled no weights: nobody takes them",
    ).inc(1.0)


def add_h2d_bytes(path: str, n: int) -> None:
    """Host→device payload bytes about to cross the wire on ``path``
    (``feeder`` | ``learn`` | ``replay_insert`` | ``rollout`` — the
    device rollout lane's key stacks, its entire payload). The byte
    diet of
    docs/data_plane.md is read off this counter: a device-resident
    replay run moves each transition once (``replay_insert``) instead
    of once per learn step (``learn``)."""
    if n <= 0:
        return
    counter(
        H2D_BYTES_TOTAL,
        "host to device payload bytes by transfer path",
        ("path",),
    ).inc(float(n), {"path": path})


def inc_tree_op(op: str, tree: str, n: int = 1) -> None:
    """One segment-tree operation on the prioritized-replay path:
    ``op`` ∈ insert | update | sample, ``tree`` ∈ host | device
    (which implementation walked the tree)."""
    counter(
        REPLAY_TREE_OPS_TOTAL,
        "prioritized-replay segment-tree ops by op and tree plane",
        ("op", "tree"),
    ).inc(float(n), {"op": op, "tree": tree})


def add_d2h_bytes(path: str, n: int) -> None:
    """Device→host payload bytes about to cross on ``path``
    (``replay_priorities``: the stacked |td| pull for the host
    alpha-power — docs/data_plane.md documents why that transform
    stays host-side)."""
    if n <= 0:
        return
    counter(
        D2H_BYTES_TOTAL,
        "device to host payload bytes by transfer path",
        ("path",),
    ).inc(float(n), {"path": path})


def d2h_bytes_by_path() -> Dict[str, float]:
    """Per-path totals of the D2H byte counter ({} before any
    readback) — same shape as :func:`h2d_bytes_by_path`."""
    m = get_metric(D2H_BYTES_TOTAL)
    if m is None:
        return {}
    out: Dict[str, float] = {}
    for tags, v in m.series():
        path = dict(tags).get("path", "")
        out[path] = out.get(path, 0.0) + v
    return out


def set_replay_occupancy(
    policy_id: str, rows: int, capacity: int, nbytes: int,
    device: bool,
) -> None:
    """Occupancy of one replay buffer (device-resident or the host
    spill fallback): stored rows, row capacity, and resident storage
    bytes (for device buffers this is HBM/accelerator memory)."""
    tags = {
        "policy": policy_id,
        "storage": "device" if device else "host",
    }
    gauge(
        REPLAY_ROWS, "replay buffer stored rows", ("policy", "storage")
    ).set(float(rows), tags)
    gauge(
        REPLAY_CAPACITY,
        "replay buffer row capacity",
        ("policy", "storage"),
    ).set(float(capacity), tags)
    gauge(
        REPLAY_BYTES,
        "replay buffer resident storage bytes",
        ("policy", "storage"),
    ).set(float(nbytes), tags)


def set_params_bytes(
    policy: str, global_bytes: int, per_shard_bytes: int
) -> None:
    """Parameter memory of one policy, next to the replay/live-buffer
    gauges: ``global`` = the full tree, ``per_shard`` = what one
    device actually holds under the active placement (equal when
    replicated; ~global/M at M-way model parallelism)."""
    g = gauge(
        PARAMS_BYTES,
        "policy parameter bytes by placement",
        ("policy", "placement"),
    )
    g.set(float(global_bytes), {"policy": policy, "placement": "global"})
    g.set(
        float(per_shard_bytes),
        {"policy": policy, "placement": "per_shard"},
    )


def inc_sharding_fallback(n: int = 1) -> None:
    """Batch leaves replicated by the ragged-leading-dim fallback in
    ``sharding.specs.leaf_sharding`` (should be 0 on a healthy hot
    path)."""
    counter(
        SHARDING_FALLBACK_TOTAL,
        "batch leaves replicated by the ragged-leading-dim fallback",
    ).inc(float(n))


def set_serve_queue_depth(deployment: str, depth: int) -> None:
    """Requests waiting in one policy server's batch queue — the
    serve-plane saturation signal the queue-wait autoscaler keys off
    (docs/serving.md)."""
    gauge(
        SERVE_QUEUE_DEPTH,
        "policy-server requests waiting to be batched",
        ("deployment",),
    ).set(float(depth), {"deployment": deployment})


def observe_serve_batch(deployment: str, rows: int) -> None:
    """Size of one coalesced forward batch (pre-padding): the
    continuous-batching efficiency signal — a p50 near 1 under load
    means the batcher is flushing too eagerly."""
    m = get_metric(SERVE_BATCH_SIZE)
    if not isinstance(m, Histogram):
        m = Histogram(
            SERVE_BATCH_SIZE,
            "coalesced policy-server forward batch rows",
            boundaries=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0),
            tag_keys=("deployment",),
        )
    m.observe(float(rows), {"deployment": deployment})


def inc_serve_requests(deployment: str, n: int = 1) -> None:
    counter(
        SERVE_REQUESTS_TOTAL,
        "policy-server requests accepted",
        ("deployment",),
    ).inc(float(n), {"deployment": deployment})


def observe_serve_latency(deployment: str, seconds: float) -> None:
    """End-to-end request latency (submit → result ready): queue wait
    + batch assembly + the sharded forward + scatter."""
    m = get_metric(SERVE_LATENCY_SECONDS)
    if not isinstance(m, Histogram):
        m = Histogram(
            SERVE_LATENCY_SECONDS,
            "policy-server request latency seconds",
            tag_keys=("deployment",),
        )
    m.observe(float(seconds), {"deployment": deployment})


def set_serve_batch_fill(deployment: str, fill: float) -> None:
    """Occupancy of the bucket the last forward executed: real rows /
    bucket rows (post-padding). A sustained low fill means the batcher
    flushes under-full buckets — wasted device work per request."""
    gauge(
        SERVE_BATCH_FILL_FRACTION,
        "real rows / executed bucket rows of the last serve batch",
        ("deployment",),
    ).set(float(fill), {"deployment": deployment})


def observe_serve_queue_wait(deployment: str, seconds: float) -> None:
    """Time one request sat in the batch queue before its forward
    launched — the queue-wait component of the end-to-end latency
    histogram (and the autoscaler's saturation signal, exact
    percentiles in the server's stats())."""
    m = get_metric(SERVE_QUEUE_WAIT_SECONDS)
    if not isinstance(m, Histogram):
        m = Histogram(
            SERVE_QUEUE_WAIT_SECONDS,
            "policy-server request queue-wait seconds",
            tag_keys=("deployment",),
        )
    m.observe(float(seconds), {"deployment": deployment})


def inc_ingress_request(route: str, status: int) -> None:
    """One HTTP request answered by the ingress front door, by route
    and final status code (2xx served, 429/503 shed, 504 expired)."""
    counter(
        INGRESS_REQUESTS_TOTAL,
        "ingress HTTP requests by route and status",
        ("route", "status"),
    ).inc(1.0, {"route": route, "status": str(status)})


def set_ingress_inflight(n: int) -> None:
    """Requests admitted past the front door and not yet answered —
    the admission controller's bounded budget."""
    gauge(
        INGRESS_INFLIGHT,
        "admitted ingress requests currently in flight",
    ).set(float(n))


def inc_ingress_shed(reason: str, n: int = 1) -> None:
    """One request shed at the ingress: ``inflight`` (budget
    exhausted → 429), ``quota`` (the POLICY's in-flight share
    exhausted → 429), ``queue_wait`` (replica waits over target →
    503), or ``deadline`` (already expired on arrival → 504)."""
    counter(
        INGRESS_SHED_TOTAL,
        "requests shed by the admission controller, by reason",
        ("reason",),
    ).inc(float(n), {"reason": reason})


def observe_ingress_latency(route: str, seconds: float) -> None:
    """End-to-end ingress latency: socket accept to response write —
    the number a client actually experiences (queue wait + coalesce +
    forward + serialization)."""
    m = get_metric(INGRESS_LATENCY_SECONDS)
    if not isinstance(m, Histogram):
        m = Histogram(
            INGRESS_LATENCY_SECONDS,
            "end-to-end ingress request latency seconds",
            tag_keys=("route",),
        )
    m.observe(float(seconds), {"route": route})


def set_ingress_workers(state: str, n: int) -> None:
    """Worker-process census of the multi-process front door bank
    (ingress/supervisor.py): ``state="live"`` is the processes
    currently accepting on the shared port; ``state="target"`` the
    configured bank size."""
    gauge(
        INGRESS_WORKERS,
        "ingress worker processes by state",
        ("state",),
    ).set(float(n), {"state": state})


def inc_ingress_worker_respawns(n: int = 1) -> None:
    """One crashed ingress worker the supervisor replaced (the bank
    keeps accepting on the shared port throughout)."""
    counter(
        INGRESS_WORKER_RESPAWNS_TOTAL,
        "ingress worker processes respawned after a crash",
    ).inc(float(n))


def set_ingress_policy_inflight(policy: str, n: int) -> None:
    """Admitted in-flight requests of ONE policy — the observable the
    per-tenant quota bounds (shed reason ``quota`` fires when a
    policy's next request would exceed its share)."""
    gauge(
        INGRESS_POLICY_INFLIGHT,
        "admitted in-flight ingress requests per policy",
        ("policy",),
    ).set(float(n), {"policy": policy})


def set_flood_offered_rps(rps: float) -> None:
    """Open-loop offered arrival rate of the flood harness's current
    sweep step (arrivals are scheduled, never gated on responses)."""
    gauge(
        FLOOD_OFFERED_RPS,
        "flood harness offered arrival rate (open loop)",
    ).set(float(rps))


def set_flood_goodput_rps(rps: float) -> None:
    """In-deadline 200 responses per second the mesh actually
    sustained at the current offered rate — goodput, not throughput."""
    gauge(
        FLOOD_GOODPUT_RPS,
        "flood harness in-deadline 200 responses per second",
    ).set(float(rps))


def inc_flood_response(kind: str, n: int = 1) -> None:
    """One flood response by contract outcome: ``ok`` (200 within
    deadline), ``shed_429`` / ``shed_503`` / ``expired_504`` (the
    overload contract), ``late_200`` (a 200 past its deadline — a
    contract VIOLATION the harness asserts never happens), or
    ``error``."""
    counter(
        FLOOD_RESPONSES_TOTAL,
        "flood harness responses by contract outcome",
        ("kind",),
    ).inc(float(n), {"kind": kind})


def observe_router_batch(deployment: str, rows: int) -> None:
    """One coalesced bucket the router dispatched to a replica, with
    the rows merged into it (cross-request, cross-connection)."""
    counter(
        ROUTER_BATCHES_TOTAL,
        "coalesced buckets dispatched by the router",
        ("deployment",),
    ).inc(1.0, {"deployment": deployment})
    counter(
        ROUTER_MERGED_ROWS_TOTAL,
        "rows merged into dispatched router buckets",
        ("deployment",),
    ).inc(float(rows), {"deployment": deployment})


def inc_router_expired(deployment: str, n: int = 1) -> None:
    """Requests the router dropped at their deadline BEFORE dispatch
    (no dead device work was computed for them)."""
    counter(
        ROUTER_EXPIRED_TOTAL,
        "requests dropped at their deadline before dispatch",
        ("deployment",),
    ).inc(float(n), {"deployment": deployment})


def inc_router_rerouted(deployment: str, n: int = 1) -> None:
    """Requests re-queued off a replica that died mid-dispatch and
    routed to a surviving one."""
    counter(
        ROUTER_REROUTED_TOTAL,
        "requests rerouted off dead replicas",
        ("deployment",),
    ).inc(float(n), {"deployment": deployment})


def add_compile_phase_seconds(
    family: str, phase: str, seconds: float
) -> None:
    """Seconds jax spent in one phase of a compile of ``family``."""
    counter(
        COMPILE_PHASE_SECONDS_TOTAL,
        "seconds compiling, by program family and phase",
        ("family", "phase"),
    ).inc(float(seconds), {"family": family, "phase": phase})


def inc_compile_cache_event(family: str, result: str) -> None:
    """The persistent compile cache answered ``result`` (``hit`` |
    ``miss``) for a program of ``family``."""
    counter(
        COMPILE_CACHE_EVENTS_TOTAL,
        "persistent compile cache hits and misses, by program family",
        ("family", "result"),
    ).inc(1.0, {"family": family, "result": result})


def inc_program_execution(program: str, n: int = 1) -> None:
    """One steady-state execution of a compiled device program
    (traced/compile calls excluded — telemetry/device.py)."""
    counter(
        PROGRAM_EXECUTIONS_TOTAL,
        "compiled-program executions by program label",
        ("program",),
    ).inc(float(n), {"program": program})


def add_program_device_seconds(program: str, seconds: float) -> None:
    """Device-busy wall seconds accrued by one program's execution
    interval (dispatch start → drain point)."""
    if seconds <= 0:
        return
    counter(
        PROGRAM_DEVICE_SECONDS_TOTAL,
        "cumulative device-busy seconds by program label",
        ("program",),
    ).inc(float(seconds), {"program": program})


def set_program_flops(program: str, flops: float) -> None:
    """Per-execution FLOPs of a compiled program (XLA
    ``cost_analysis()``, captured once per traced signature)."""
    gauge(
        PROGRAM_FLOPS,
        "per-execution FLOPs of a compiled program (cost_analysis)",
        ("program",),
    ).set(float(flops), {"program": program})


def set_serve_params_version(deployment: str, version: int) -> None:
    """Monotonic params version a policy server is serving; bumps
    exactly once per applied checkpoint hot-reload."""
    gauge(
        SERVE_PARAMS_VERSION,
        "params version served (bumps on checkpoint hot-reload)",
        ("deployment",),
    ).set(float(version), {"deployment": deployment})


def h2d_bytes_by_path() -> Dict[str, float]:
    """Current per-path totals of the H2D byte counter ({} before any
    transfer). Algorithm.step diffs this across an iteration for the
    ``info/telemetry`` byte roll-up."""
    m = get_metric(H2D_BYTES_TOTAL)
    if m is None:
        return {}
    out: Dict[str, float] = {}
    for tags, v in m.series():
        path = dict(tags).get("path", "")
        out[path] = out.get(path, 0.0) + v
    return out


def counter_total(name: str) -> float:
    """Sum of a counter's series across all tag values (0.0 when the
    counter was never touched)."""
    m = get_metric(name)
    if m is None:
        return 0.0
    return sum(v for _, v in m.series())


def learn_steps_total() -> float:
    """Cumulative SGD programs dispatched in this process (fed by
    JaxPolicy.learn_on_device_batch); Algorithm.step diffs it across
    an iteration for the learn-steps/s gauge."""
    m = get_metric(LEARN_STEPS_TOTAL)
    if m is None:
        return 0.0
    return sum(v for _, v in m.series())


# -- per-iteration runtime sampling (called by Algorithm.step) ---------


def sample_runtime_gauges() -> Dict[str, float]:
    """Refresh the process-level gauges that must be polled: the
    sharded_jit compile cache and jax's live-buffer/device-memory
    state. Returns the sampled values (reported under
    ``info/telemetry`` too). Cheap enough for once-per-iteration."""
    out: Dict[str, float] = {}
    try:
        from ray_tpu.sharding.compile import compile_stats

        cs = compile_stats()
        gauge(
            COMPILE_TRACES, "sharded_jit traces (process-wide)"
        ).set(float(cs["traces"]))
        gauge(
            COMPILE_RECOMPILES,
            "sharded_jit recompiles beyond first trace",
        ).set(float(cs["recompiles"]))
        gauge(
            COMPILE_TIME_S, "cumulative sharded_jit compile seconds"
        ).set(float(cs["compile_time_s"]))
        out["compile_traces"] = float(cs["traces"])
        out["compile_recompiles"] = float(cs["recompiles"])
        out["compile_time_s"] = float(cs["compile_time_s"])
    except Exception:
        pass
    try:
        import jax

        n_live = len(jax.live_arrays())
        gauge(
            JAX_LIVE_BUFFERS, "live jax arrays in this process"
        ).set(float(n_live))
        out["jax_live_buffers"] = float(n_live)
        mem: Optional[dict] = None
        try:
            mem = jax.local_devices()[0].memory_stats()
        except Exception:
            mem = None
        if mem and "bytes_in_use" in mem:
            # per-device resident bytes (TPU/GPU backends; the CPU
            # client reports no memory_stats — gauge simply absent)
            g = gauge(
                JAX_DEVICE_MEMORY,
                "bytes in use on the learner devices",
                ("device",),
            )
            total = 0.0
            for i, d in enumerate(jax.local_devices()):
                stats = d.memory_stats() or {}
                b = float(stats.get("bytes_in_use", 0.0))
                g.set(b, {"device": str(i)})
                total += b
            out["device_memory_bytes"] = total
    except Exception:
        pass
    return out


def record_iteration_throughput(
    env_steps: float, learn_steps: float, wall_s: float
) -> Dict[str, float]:
    """Set the per-iteration throughput gauges; returns the values for
    the ``info/telemetry`` roll-up."""
    wall_s = max(wall_s, 1e-9)
    env_rate = env_steps / wall_s
    learn_rate = learn_steps / wall_s
    gauge(
        ENV_STEPS_PER_S, "env steps sampled per second (last iter)"
    ).set(env_rate)
    gauge(
        LEARN_STEPS_PER_S, "learner SGD programs per second (last iter)"
    ).set(learn_rate)
    counter(ENV_STEPS_TOTAL, "env steps sampled").inc(
        max(0.0, float(env_steps))
    )
    histogram(
        ITERATION_SECONDS, "train-iteration wall seconds"
    ).observe(wall_s)
    return {
        "env_steps_per_s": env_rate,
        "learn_steps_per_s": learn_rate,
    }
