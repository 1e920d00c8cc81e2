"""Headline benchmark: PPO learner env-steps/sec on TPU vs torch-CPU.

Measures the north-star metric from BASELINE.md: PPO learner throughput
(env frames consumed per second of learner wall-clock) on Atari-shaped
batches with the Nature-CNN policy, at the reference's pong-ppo.yaml
geometry (train batch ~4096, minibatch 512, 10 SGD epochs). Compares:

  - ray_tpu JAX/TPU learner, through the PUBLIC two-phase policy API
    (``prepare_batch`` → DeviceFeeder → ``learn_on_device_batch``): ONE
    jitted shard_map SGD nest per train batch, host→device transfer of
    batch k+1 overlapped with the compute of batch k (the reference's
    _MultiGPULoaderThread role).
  - torch-CPU learner: a faithful implementation of the reference's
    minibatch SGD loop (``rllib/policy/torch_policy.py:498-624``), run in
    full (no extrapolation).

Also reports an MFU estimate: the pure-compute time of the SGD nest is
isolated by scaling the epoch count (the marginal cost of extra epochs
excludes the fixed per-dispatch host overhead, which can exceed the
compute itself), and divided into the analytic fwd+bwd FLOPs of the
Nature CNN.

Per-round times use the MEDIAN across rounds, so one slow round (a
host hiccup, a GC pause) does not set the number.

The default mode is a device measurement: it refuses to run unless
jax's default backend is a TPU, and a sub-entry that throws fails the
run.

Observations are structured (block-textured) frames, matching real Atari
content rather than incompressible noise. Prints ONE JSON line.

Flags:  --profile       run ONE telemetry-instrumented PPO iteration
                        (docs/observability.md): writes the chrome
                        trace to benchmarks/e2e/ppo_iteration_trace.json
                        plus a telemetry-overhead A/B entry
                        (benchmarks/e2e/telemetry_overhead.json)
        --xprof DIR     capture a jax.profiler trace of the timed rounds
        --e2e           run the five BASELINE.md end-to-end configs
                        (rollout+learner; see bench_e2e.py) instead
        --chaos         fault-injection A/B (docs/resilience.md):
                        steady-state vs worker-kill + NaN-batch run,
                        writes benchmarks/e2e/chaos_recovery.json
        --replay-ab     host-ring vs device-resident replay A/B on
                        the SAC geometry (docs/data_plane.md): writes
                        benchmarks/e2e/replay_device_ab.json with
                        steps/s, per-iteration H2D bytes by path, and
                        a bitwise parity flag
        --superstep     fused K-updates-per-dispatch A/B
                        (docs/data_plane.md): per-update dispatch
                        overhead at K=1 (deferred) vs K=8 on device-
                        resident batches at the CPU smoke geometry;
                        writes benchmarks/e2e/superstep_ab.json (the
                        full bench's bench_mfu gains a `superstep`
                        sub-entry at the headline geometry)
        --jax-env       rollout-lane A/B (docs/pipeline.md): CPU-actor
                        lane vs device (jax) lane vs fused
                        rollout+learn superstep on the same
                        JaxVectorEnv, same seed, same step count;
                        writes benchmarks/e2e/jax_env_ab.json
                        (bench_mfu gains a `fused_rollout` sub-entry
                        on the jittable pong_lite port)
        --serve         inference-plane A/B (docs/serving.md):
                        continuous batching vs naive per-request
                        inference on the same fixed-seed request
                        stream at 1/8/32/128 concurrent clients —
                        latency/throughput curve, zero-recompile and
                        bitwise-parity checks; writes
                        benchmarks/e2e/serve_ab.json (bench_mfu gains
                        a `serve_forward` sub-entry at the pixel
                        geometry for the next TPU round)
        --ingress       serving front-door A/B (docs/serving.md "the
                        front door"): batched ingress (HTTP →
                        coalescing router → fused replica forwards)
                        vs the per-request serve-core HTTP path, both
                        over REAL sockets, sweeping client counts —
                        throughput + p50/p99 per path, bitwise
                        response parity, zero recompiles in the timed
                        window; writes benchmarks/e2e/ingress_ab.json
        --flood         OPEN-loop flood harness for the horizontal
                        front door (docs/serving.md "Scaling the
                        front door"): Poisson + recorded-burst
                        arrival schedules with a deadline mix, swept
                        upward to locate each config's saturation
                        knee (goodput >= 90% of offered), for 1 vs N
                        ingress worker processes on ONE shared port;
                        at 2x the knee every response must be a
                        200-inside-deadline / 429 / 503 / 504 (never
                        a hang, never a late 200); bitwise parity
                        across configs, zero recompiles per worker;
                        add --smoke for the shrunk tier-1 variant;
                        writes benchmarks/e2e/flood.json
        --elastic       elastic-fleet chaos A/B (docs/resilience.md
                        "elastic fleets & preemption"): PPO fleet
                        forced 4→2→6 via noticed preemptions +
                        autoscaler scale-up vs the PR-4 kill-only
                        path (steps/s per fleet size, drain vs kill
                        recovery cost), plus work lost on a mid-run
                        driver crash with streamed vs periodic
                        checkpoints; writes
                        benchmarks/e2e/elastic_fleet.json
        --fleet         elastic learner-mesh lane (docs/fleet.md):
                        gloo CPU fleets of 1 and 2 hosts through the
                        full rendezvous → epoch → lockstep-learn
                        protocol — steps/s by fleet size, drain
                        (noticed) vs kill (heartbeat) recovery wall,
                        and the resize wall (reshard + the twin's
                        first learn step, compile included); writes
                        benchmarks/e2e/fleet.json
        --fleet-chaos   control-plane failover lane (docs/fleet.md
                        "failure model & leadership"): coordinator
                        kill → fenced standby takeover → failover
                        epoch cut, walls vs lease TTL (gate: median
                        < 2x TTL), clean-handover comparison, and
                        the zombie's stale-term write fenced every
                        trial; control-plane only — no learners;
                        writes benchmarks/e2e/fleet_chaos.json
        --fleetobs      fleet-observability overhead A/B
                        (docs/observability.md "Fleet view"): the
                        SAME fixed-seed 2-host lockstep learn, bare
                        vs with per-host HostExporters + the rank-0
                        FleetAggregator live — median-step-wall
                        overhead (budget < 2%), bitwise-identical
                        per-step losses (hard gate), both hosts
                        host=-labeled in the merged exposition;
                        writes
                        benchmarks/e2e/fleet_observability.json
        --obs           device-ledger overhead A/B
                        (docs/observability.md "device ledger"): the
                        SAME fixed-seed superstep PPO chain with
                        telemetry fully off vs the compiled-program
                        ledger on vs ledger+tracing — steady-state
                        per-superstep wall, the one-time AOT analysis
                        compile cost, and a bitwise parity flag;
                        writes benchmarks/e2e/observability.json
                        (acceptance: ledger overhead < 2% of
                        superstep wall)
        --lint          device-contract static-analysis pass
                        (docs/static_analysis.md): whole-ray_tpu/
                        scan wall time, per-rule finding counts,
                        baseline/suppression totals; writes
                        benchmarks/e2e/static_analysis.json (pure
                        AST — runs even where jax is broken)
"""

import json
import sys
import time

import numpy as np

B, MB, ITERS = 4096, 512, 10
H, W, C, NUM_ACTIONS = 84, 84, 4, 6
# median over enough rounds that one slow round does not move it
TIMED_ROUNDS = 12


def make_frames(rng, n, h=H, w=W, c=1):
    """Blocky 84x84 single frames approximating Atari content."""
    base = rng.integers(0, 255, (n, h // 4, w // 4, c), dtype=np.uint8)
    return np.kron(base, np.ones((1, 4, 4, 1), np.uint8))


def make_batch(rng, b=B, h=H, w=W, c=C, num_actions=NUM_ACTIONS):
    """A trajectory-shaped PPO train batch: rows are sliding
    ``c``-frame stacks over one contiguous frame stream (real Atari
    layout), shipped in the deduplicated frame-pool format
    (``ray_tpu.ops.framestack``) — the obs column moves host→device
    once per unique frame instead of ``c`` times."""
    from ray_tpu.ops.framestack import frame_stream_columns

    frames = make_frames(rng, b + c - 1, h, w, 1)
    return {
        **frame_stream_columns(frames, b, c),
        "actions": rng.integers(0, num_actions, b).astype(np.int64),
        "action_logp": np.full(b, -1.79, np.float32),
        "action_dist_inputs": rng.standard_normal(
            (b, num_actions)
        ).astype(np.float32),
        "advantages": rng.standard_normal(b).astype(np.float32),
        "value_targets": rng.standard_normal(b).astype(np.float32),
    }


def materialize_stacks(batch, c=C):
    """(N, H, W, c) stacked obs from a frame-pool batch — what the
    torch baseline (and the reference's loader thread) moves per row."""
    frames = batch["obs_frames"]
    idx = batch["obs_frame_idx"]
    return np.stack(
        [
            np.concatenate(
                [frames[i + j] for j in range(c)], axis=-1
            )
            for i in idx
        ]
    )


def nature_cnn_train_flops_per_sample(h=H, w=W, c=C, num_actions=NUM_ACTIONS):
    """Analytic fwd+bwd FLOPs/sample for the Nature CNN
    (models/cnn.py NATURE_FILTERS + 512 post-fc + heads), using the
    standard train ≈ 3 × forward convention."""
    from ray_tpu.models.cnn import NATURE_FILTERS

    macs = 0
    hh, ww, ch = h, w, c
    for out_ch, (kh, kw), (sh, sw) in NATURE_FILTERS:
        hh = (hh - kh) // sh + 1
        ww = (ww - kw) // sw + 1
        macs += hh * ww * out_ch * kh * kw * ch
        ch = out_ch
    flat = hh * ww * ch
    macs += flat * 512            # post_fc
    macs += 512 * num_actions + 512  # heads
    return 3 * 2 * macs


def chip_peak_tflops(kind=None):
    """(bf16 peak TFLOP/s, device_kind) of the attached chip, from the
    one peak table (``telemetry/device.PEAK_FLOPS_TABLE``, public
    specs). A chip the table does not hold — or no chip — is an error:
    an MFU against a guessed peak is a wrong number."""
    from ray_tpu.telemetry import device as device_ledger

    kind = kind or device_ledger.device_kind()
    chips = tuple(
        row for row in device_ledger.PEAK_FLOPS_TABLE if row[0] != "cpu"
    )
    return device_ledger.table_peak(chips, kind, "peak-FLOPs") / 1e12, kind


def require_tpu():
    """The device-measurement modes refuse to time anything but a TPU;
    returns the device triple every result carries."""
    from ray_tpu.utils.platform import device_info

    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"bench.py: this mode measures a TPU; jax found "
            f"{dev['count']} {dev['platform']!r} device(s) "
            f"({dev['kind']}). Run it on the chip (chiprun), or "
            "use a targeted protocol mode (--lint, --ingress, ...)."
        )
    return dev


def _make_policy(b, mb, iters, h=H, w=W, c=C):
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    return PPOJaxPolicy(
        gym.spaces.Box(0, 255, (h, w, c), np.uint8),
        gym.spaces.Discrete(NUM_ACTIONS),
        {
            "train_batch_size": b,
            "sgd_minibatch_size": mb,
            "num_sgd_iter": iters,
            "lr": 5e-5,
        },
    )


def bench_jax(
    b=B, mb=MB, iters=ITERS, timed_rounds=TIMED_ROUNDS, h=H, w=W, c=C,
    profile_dir=None,
):
    """End-to-end learner loop (feeder-overlapped transfer + SGD nest +
    per-batch stats fetch). Returns (env_steps/s from median round
    time, per-round times)."""
    from ray_tpu.execution.device_feed import DeviceFeeder

    policy = _make_policy(b, mb, iters, h, w, c)
    rng = np.random.default_rng(0)
    host_batches = [
        policy.prepare_batch(make_batch(rng, b, h, w, c))
        for _ in range(3)
    ]

    feeder = DeviceFeeder(policy.batch_shardings)
    feeder.put(*host_batches[0])
    dev, bsize = feeder.get()
    # compile + warm through the supported entry point (this batch is
    # in the deduplicated frame-pool format; the stacks rebuild on
    # device before the SGD nest)
    policy.learn_on_device_batch(dev, bsize)

    ctx = None
    if profile_dir:
        import jax

        ctx = jax.profiler.trace(profile_dir)
        ctx.__enter__()

    # steady state: feeder transfers batch k+1 while learner runs batch k
    feeder.put(*host_batches[1 % 3])
    times = []
    for k in range(timed_rounds):
        t0 = time.perf_counter()
        dev, bsize = feeder.get()
        feeder.put(*host_batches[(k + 2) % 3])
        stats = policy.learn_on_device_batch(dev, bsize)
        stats["total_loss"]  # host sync already done by device_get
        times.append(time.perf_counter() - t0)

    # pipelined phase: defer the stats fetch so consecutive nests queue
    # on-device and the fixed per-dispatch host latency amortizes
    # across the stream — the LearnerThread
    # runs exactly this protocol (execution/learner_thread.py). Lag is
    # bounded like there (STATS_LAG) so device memory stays bounded.
    import collections

    import jax

    lazy = collections.deque()
    K = timed_rounds
    t0 = time.perf_counter()
    for k in range(K):
        dev, bsize = feeder.get()
        feeder.put(*host_batches[k % 3])
        lazy.append(
            policy.learn_on_device_batch(dev, bsize, defer_stats=True)
        )
        while len(lazy) > 3:
            jax.device_get(lazy.popleft())
    while lazy:
        jax.device_get(lazy.popleft())
    pipelined_wall = (time.perf_counter() - t0) / K

    # device-resident phase: the SAME pipelined protocol but the 3
    # batches were put on device once up front — no H2D inside the
    # loop. This isolates dispatch amortization from H2D
    # bandwidth: if the learner-thread pipelining works, steady-state
    # wall per nest here approaches pure nest compute, and effective
    # MFU approaches the epoch-isolated mfu_pct (the reference's
    # multi_gpu_learner_thread.py:20-140 keeps its GPUs fed the same
    # way — loader threads hide transfer, so the accelerator only
    # ever waits on compute).
    from ray_tpu.policy.jax_policy import _FRAMES as _F

    dev_batches = []
    for hb, bs_ in host_batches:
        hb2 = dict(hb)
        fr = hb2.pop(_F, None)
        dev_b = jax.device_put(hb2, policy.batch_shardings(hb2))
        if fr is not None:
            dev_b = dict(
                dev_b,
                **{_F: jax.device_put(fr, policy._param_sharding)},
            )
        dev_batches.append((dev_b, bs_))
    for dev_b, bs_ in dev_batches:
        jax.block_until_ready(dev_b)
    # stats drain in BATCHES of 4: every blocking device interaction
    # waits for the device to catch up regardless of payload (the
    # stats are scalars), so fetching per-nest would re-serialize the
    # stream; one batched fetch per 4 nests amortizes it the way the
    # reference's learner thread reads stats asynchronously
    lazy = collections.deque()
    t0 = time.perf_counter()
    for k in range(K):
        dev_b, bs_ = dev_batches[k % 3]
        lazy.append(
            policy.learn_on_device_batch(
                dev_b, bs_, defer_stats=True
            )
        )
        if len(lazy) >= 8:
            drain = [lazy.popleft() for _ in range(4)]
            jax.device_get(drain)
    jax.device_get(list(lazy))
    lazy.clear()
    resident_wall = (time.perf_counter() - t0) / K

    if ctx is not None:
        ctx.__exit__(None, None, None)
    feeder.stop()
    return (
        b / float(np.median(times)),
        times,
        b / pipelined_wall,
        pipelined_wall,
        b / resident_wall,
        resident_wall,
    )


def bench_mfu(b=B, mb=MB, iters=ITERS, reps=4, h=H, w=W, c=C):
    """Isolate pure SGD-nest compute by epoch scaling: time the nest at
    ``iters`` and ``4*iters`` epochs on a device-resident batch; the
    marginal time per epoch × iters is the compute of the headline
    nest, free of fixed per-dispatch overhead (which would otherwise
    be misread as low MFU)."""
    import jax

    lo, hi = iters, 4 * iters
    rng = np.random.default_rng(0)
    t_med = {}
    setups = {}
    for it in (lo, hi):
        p = _make_policy(b, mb, it, h, w, c)
        host, bsize = p.prepare_batch(make_batch(rng, b, h, w, c))
        dev = jax.device_put(host, p.batch_shardings(host))
        p.learn_on_device_batch(dict(dev), bsize)  # compile+warm
        setups[it] = (p, dev, bsize, host)
    ts = {lo: [], hi: []}
    for _ in range(reps):  # interleave against drift
        for it, (p, dev, bsize, _host) in setups.items():
            t0 = time.perf_counter()
            p.learn_on_device_batch(dict(dev), bsize)
            ts[it].append(time.perf_counter() - t0)
    for it in (lo, hi):
        t_med[it] = float(np.median(ts[it]))
    compute_per_nest = (t_med[hi] - t_med[lo]) / (hi - lo) * iters

    # deferred-stats A/B (docs/data_plane.md): the same headline nest
    # under the one-call-lag protocol (config["deferred_stats"]):
    # each call dispatches program k and fetches the stats of k-1 —
    # already finished — so the per-call stats readback (a D2H sync
    # serialized after the program on the blocking path) overlaps
    # device compute. Steady-state wall
    # per nest minus the epoch-isolated compute is the deferred
    # dispatch overhead.
    K = 2 * reps
    p, dev, bsize, host = setups[lo]
    p.config["deferred_stats"] = True
    try:
        p.learn_on_device_batch(dict(dev), bsize)  # prime the lag
        t0 = time.perf_counter()
        for _ in range(K):
            p.learn_on_device_batch(dict(dev), bsize)
        p.flush_deferred_stats()  # final program drains on the clock
        deferred_wall = (time.perf_counter() - t0) / K
    finally:
        p.config["deferred_stats"] = False
        p.flush_deferred_stats()
    deferred = {
        "wall_s_per_nest": round(deferred_wall, 4),
        "dispatch_overhead_s": round(
            max(deferred_wall - compute_per_nest, 0.0), 4
        )
        if compute_per_nest > 0
        else None,
        "lag": 1,
    }

    # superstep sub-entry (docs/data_plane.md): K nests fused into ONE
    # dispatched program (JaxPolicy.learn_superstep), so the fixed
    # per-call overhead — the 0.123 s the r05 TPU bench measured
    # against 0.046 s of nest compute — amortizes 1/K. Same
    # device-resident batch repeated K times (dispatch isolation, like
    # the deferred entry above).
    superstep = None
    from ray_tpu.policy.jax_policy import _FRAMES as _F

    Ksup = 8
    stacked = {
        cn: np.repeat(np.asarray(v)[None], Ksup, axis=0)
        for cn, v in host.items()
    }
    from ray_tpu import sharding as sharding_lib

    shard = {
        cn: (
            sharding_lib.replicated(p.mesh)
            if cn == _F
            else sharding_lib.batch_sharded(p.mesh, ndim_prefix=2)
        )
        for cn in stacked
    }
    dev_stacked = jax.device_put(stacked, shard)
    jax.block_until_ready(dev_stacked)
    p.learn_superstep(
        Ksup, bsize, stacked=dict(dev_stacked), k_max=Ksup
    )  # compile+warm
    sup_reps = max(2, reps // 2)
    t0 = time.perf_counter()
    for _ in range(sup_reps):
        p.learn_superstep(
            Ksup, bsize, stacked=dict(dev_stacked), k_max=Ksup
        )
    sup_wall = (time.perf_counter() - t0) / (sup_reps * Ksup)
    superstep = {
        "k": Ksup,
        "wall_s_per_nest": round(sup_wall, 4),
        "dispatch_overhead_s": round(
            max(sup_wall - compute_per_nest, 0.0), 4
        )
        if compute_per_nest > 0
        else None,
    }

    # fused-rollout sub-entry (docs/pipeline.md "two rollout lanes"):
    # rollout(T)+GAE+the SGD nest as ONE dispatched program on the
    # jittable pong_lite port — the zero-H2D lane the next TPU round
    # measures at scale. Smoke geometry here; env_steps/s and the
    # per-dispatch wall are the comparable numbers.
    fused_rollout = None
    from ray_tpu.algorithms.ppo.ppo import (
        PPOConfig as _PPOCfg,
        PPOJaxPolicy as _PPOPol,
    )
    from ray_tpu.env.jax_pong import PongLiteJax
    from ray_tpu.execution.jax_rollout import JaxRolloutEngine
    from ray_tpu.sharding.compile import compile_stats

    n_env, t_ro = 8, 16
    cfgj = _PPOCfg().to_dict()
    cfgj.update(
        seed=0,
        train_batch_size=n_env * t_ro,
        sgd_minibatch_size=64,
        num_sgd_iter=2,
        lr=3e-4,
    )
    cfgj["lambda"] = 0.95
    envj = PongLiteJax({})
    pj = _PPOPol(
        envj.observation_space, envj.action_space, cfgj
    )
    eng = JaxRolloutEngine(
        pj, envj, n_env, t_ro, seed=0
    )
    feed = eng.superstep_feed()
    infos, carry, mets, _ = pj.learn_rollout_superstep(
        1, eng.batch_size, feed, k_max=1
    )  # compile+warm
    eng.advance(carry, mets)
    traces0 = compile_stats()["traces"]
    fr_reps = max(2, reps // 2)
    t0 = time.perf_counter()
    for _ in range(fr_reps):
        feed = eng.superstep_feed()
        infos, carry, mets, _ = pj.learn_rollout_superstep(
            1, eng.batch_size, feed, k_max=1
        )
        eng.advance(carry, mets)
    fr_wall = (time.perf_counter() - t0) / fr_reps
    fused_rollout = {
        "env": "PongLiteJax-v0",
        "num_envs": n_env,
        "rollout_length": t_ro,
        "wall_s_per_dispatch": round(fr_wall, 4),
        "env_steps_per_s": round(eng.batch_size / fr_wall, 1),
        "recompiles_in_timed_window": (
            compile_stats()["traces"] - traces0
        ),
    }

    # serve_forward sub-entry (docs/serving.md): the inference plane's
    # fused batched forward at the pixel geometry — one dispatch of a
    # bucket of Nature-CNN action forwards on the learner-style mesh
    # (vectorized mode: the wide-hardware throughput formulation the
    # next TPU round measures at scale; the exact/bitwise mode is the
    # contract bench.py --serve asserts on MLPs).
    serve_forward = None
    from ray_tpu.serve.policy_server import BatchedPolicyServer
    from ray_tpu.sharding.compile import compile_stats

    bucket = 16
    psrv = setups[lo][0]
    srv = BatchedPolicyServer(
        psrv,
        max_batch_size=bucket,
        buckets=(bucket,),
        explore=False,
        vectorized=True,
        start=False,
    )
    obs_rows = make_frames(rng, bucket + c - 1, h, w, 1)
    obs_rows = np.concatenate(
        [obs_rows[i : i + bucket] for i in range(c)], axis=-1
    )
    srv.forward_padded(obs_rows)  # compile+warm
    traces0 = compile_stats()["traces"]
    sf_reps = max(2, reps // 2)
    t0 = time.perf_counter()
    for _ in range(sf_reps):
        srv.forward_padded(obs_rows)
    sf_wall = (time.perf_counter() - t0) / sf_reps
    serve_forward = {
        "bucket": bucket,
        "wall_s_per_forward": round(sf_wall, 4),
        "actions_per_s": round(bucket / sf_wall, 1),
        "recompiles_in_timed_window": (
            compile_stats()["traces"] - traces0
        ),
    }

    # transformer_nest sub-entry (docs/sharding.md "2-D mesh & param
    # partitioning"): the decoder-transformer SGD nest — the
    # architecture-agnostic learner proof — timed like the headline
    # nest, so the next TPU round measures the tensor-parallel torso
    # at real widths next to the Nature-CNN number (pair with
    # bench.py --model-parallel for the replicated-vs-partitioned A/B).
    transformer_nest = None
    import gymnasium as _gym

    from ray_tpu.algorithms.ppo.ppo import (
        PPOJaxPolicy as _TPPOPol,
    )
    from ray_tpu.sharding.compile import compile_stats

    t_b, t_mb, t_obs = 256, 128, 64
    pt = _TPPOPol(
        _gym.spaces.Box(-1, 1, (t_obs,), np.float32),
        _gym.spaces.Discrete(8),
        {
            "train_batch_size": t_b,
            "sgd_minibatch_size": t_mb,
            "num_sgd_iter": iters,
            "lr": 3e-4,
            "seed": 0,
            "model": {
                "use_transformer": True,
                "transformer_dim": 128,
                "transformer_num_layers": 2,
                "transformer_num_heads": 4,
                "transformer_ff_dim": 512,
                "transformer_seq_len": 8,
            },
        },
    )
    t_rng = np.random.default_rng(0)
    t_host = {
        "obs": t_rng.standard_normal((t_b, t_obs)).astype(
            np.float32
        ),
        "actions": t_rng.integers(0, 8, t_b).astype(np.int64),
        "action_logp": np.full(t_b, -2.0, np.float32),
        "action_dist_inputs": t_rng.standard_normal(
            (t_b, 8)
        ).astype(np.float32),
        "advantages": t_rng.standard_normal(t_b).astype(
            np.float32
        ),
        "value_targets": t_rng.standard_normal(t_b).astype(
            np.float32
        ),
    }
    t_prep, t_bsize = pt.prepare_batch(dict(t_host))
    t_dev = jax.device_put(t_prep, pt.batch_shardings(t_prep))
    pt.learn_on_device_batch(dict(t_dev), t_bsize)  # compile+warm
    traces0 = compile_stats()["traces"]
    tn_reps = max(2, reps // 2)
    t0 = time.perf_counter()
    for _ in range(tn_reps):
        pt.learn_on_device_batch(dict(t_dev), t_bsize)
    tn_wall = (time.perf_counter() - t0) / tn_reps
    transformer_nest = {
        "params": int(pt.model.num_params()),
        "batch": t_b,
        "wall_s_per_nest": round(tn_wall, 4),
        "recompiles_in_timed_window": (
            compile_stats()["traces"] - traces0
        ),
    }

    # replay_sample sub-entry (docs/data_plane.md "device sum tree"):
    # one fused prioritized draw→gather dispatch — prefix-descent over
    # the f64 device tree + clip + IS weights + packed-uint8 pixel row
    # gather as ONE program, zero payload H2D (only the generator's
    # raw uniform stream crosses). The wall per dispatch at the pixel
    # geometry is what the next TPU round measures at scale.
    replay_sample = None
    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
    )
    from ray_tpu.sharding.compile import compile_stats

    rs_cap, rs_b = 1 << 14, 256
    rs_rng = np.random.default_rng(0)
    rbuf = DevicePrioritizedReplayBuffer(
        capacity=rs_cap, alpha=0.6, seed=1,
        device_tree=True, label="bench_mfu",
    )
    chunk = 2048
    rows = {
        "obs": rs_rng.integers(
            0, 255, (chunk, h, w, c), dtype=np.uint8
        ),
        "actions": rs_rng.integers(0, 4, chunk).astype(np.int32),
        "rewards": rs_rng.standard_normal(chunk).astype(
            np.float32
        ),
    }
    for _ in range(rs_cap // chunk):
        rbuf.add_tree({k: v for k, v in rows.items()})
    batch = rbuf.sample(rs_b, beta=0.4)  # compile+warm
    jax.block_until_ready(batch.tree["obs"])
    traces0 = compile_stats()["traces"]
    rs_reps = 2 * reps
    t0 = time.perf_counter()
    for _ in range(rs_reps):
        batch = rbuf.sample(rs_b, beta=0.4)
    jax.block_until_ready(batch.tree["obs"])
    rs_wall = (time.perf_counter() - t0) / rs_reps
    replay_sample = {
        "capacity": rs_cap,
        "batch": rs_b,
        "wall_s_per_draw": round(rs_wall, 5),
        "rows_per_s": round(rs_b / rs_wall, 1),
        "recompiles_in_timed_window": (
            compile_stats()["traces"] - traces0
        ),
    }

    peak, kind = chip_peak_tflops()
    if compute_per_nest <= 0:
        # timing jitter inverted the medians; a clamped value would
        # report garbage TFLOP/s — flag instead
        return {
            "achieved_tflops": None,
            "peak_tflops": peak,
            "mfu_pct": None,
            "device": kind,
            "unstable_timing": True,
            "deferred_stats": deferred,
            "superstep": superstep,
            "fused_rollout": fused_rollout,
            "serve_forward": serve_forward,
            "transformer_nest": transformer_nest,
            "replay_sample": replay_sample,
        }
    flops = b * iters * nature_cnn_train_flops_per_sample(h, w, c)
    achieved = flops / compute_per_nest / 1e12
    return {
        "achieved_tflops": round(achieved, 1),
        "peak_tflops": peak,
        "mfu_pct": round(100.0 * achieved / peak, 1),
        "device": kind,
        "nest_compute_s": round(compute_per_nest, 4),
        "dispatch_overhead_s": round(
            max(t_med[lo] - compute_per_nest, 0.0), 4
        ),
        "deferred_stats": deferred,
        "superstep": superstep,
        "fused_rollout": fused_rollout,
        "serve_forward": serve_forward,
        "transformer_nest": transformer_nest,
        "replay_sample": replay_sample,
    }


def bench_torch(b=B, mb=MB, iters=ITERS) -> float:
    """Reference-semantics torch CPU learner: same net, same SGD nest,
    run in full (``rllib/policy/torch_policy.py:498-624``)."""
    import torch
    import torch.nn as nn

    torch.manual_seed(0)

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.conv = nn.Sequential(
                nn.Conv2d(C, 32, 8, 4), nn.ReLU(),
                nn.Conv2d(32, 64, 4, 2), nn.ReLU(),
                nn.Conv2d(64, 64, 3, 1), nn.ReLU(),
            )
            self.fc = nn.Sequential(nn.Linear(64 * 7 * 7, 512), nn.ReLU())
            self.pi = nn.Linear(512, NUM_ACTIONS)
            self.vf = nn.Linear(512, 1)

        def forward(self, x):
            h = self.fc(self.conv(x).flatten(1))
            return self.pi(h), self.vf(h).squeeze(-1)

    net = Net()
    opt = torch.optim.Adam(net.parameters(), lr=5e-5)
    rng = np.random.default_rng(0)
    batch = make_batch(rng, b)
    # the reference's collector hands the loader fully-materialized
    # (N, H, W, c) stacks; same data, same compute
    obs_u8 = torch.from_numpy(
        materialize_stacks(batch).transpose(0, 3, 1, 2).copy()
    )
    actions = torch.from_numpy(batch["actions"])
    old_logp = torch.from_numpy(batch["action_logp"])
    adv = torch.from_numpy(batch["advantages"])
    vt = torch.from_numpy(batch["value_targets"])

    def one_nest():
        n_mb = b // mb
        for _ in range(iters):
            perm = torch.randperm(b)
            for i in range(n_mb):
                idx = perm[i * mb : (i + 1) * mb]
                x = obs_u8[idx].float() / 255.0
                logits, value = net(x)
                logp = torch.log_softmax(logits, -1).gather(
                    1, actions[idx, None]
                ).squeeze(1)
                ratio = torch.exp(logp - old_logp[idx])
                surr = torch.minimum(
                    adv[idx] * ratio,
                    adv[idx] * ratio.clamp(0.7, 1.3),
                )
                vf_loss = (value - vt[idx]).pow(2).clamp(0, 10.0)
                loss = (-surr + vf_loss).mean()
                opt.zero_grad()
                loss.backward()
                opt.step()

    # warmup: one epoch to settle allocators/threads
    n_mb = b // mb
    for i in range(n_mb):
        idx = torch.arange(i * mb, (i + 1) * mb)
        logits, value = net(obs_u8[idx].float() / 255.0)
        (logits.sum() + value.sum()).backward()
        opt.zero_grad()
    t0 = time.perf_counter()
    one_nest()
    dt = time.perf_counter() - t0
    return b / dt


def bench_telemetry_overhead(b=1024, mb=256, iters=2, rounds=20):
    """Disabled-vs-enabled tracing A/B on the SAME fixed-seed PPO
    learn step (small MLP geometry — isolates per-call instrumentation
    cost, not model compute). The ``tracing_off`` median is the
    regression sentinel for the default path: telemetry off must stay
    within noise of an uninstrumented build."""
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch
    from ray_tpu.util import tracing

    rng = np.random.default_rng(0)
    cols = {
        SampleBatch.OBS: rng.standard_normal((b, 16)).astype(
            np.float32
        ),
        SampleBatch.ACTIONS: rng.integers(0, 6, b).astype(np.int64),
        SampleBatch.ACTION_LOGP: np.full(b, -1.79, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
            (b, 6)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: rng.standard_normal(b).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: rng.standard_normal(b).astype(
            np.float32
        ),
    }
    policy = PPOJaxPolicy(
        gym.spaces.Box(-10.0, 10.0, (16,), np.float32),
        gym.spaces.Discrete(6),
        {
            "model": {"fcnet_hiddens": [64, 64]},
            "train_batch_size": b,
            "sgd_minibatch_size": mb,
            "num_sgd_iter": iters,
            "lr": 1e-4,
            "seed": 0,
        },
    )
    policy.learn_on_batch(SampleBatch(cols))  # compile
    out = {}
    was_enabled = tracing.is_enabled()
    for mode in ("tracing_off", "tracing_on"):
        (tracing.enable if mode == "tracing_on" else tracing.disable)()
        times = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            policy.learn_on_batch(SampleBatch(cols))
            times.append(time.perf_counter() - t0)
        out[mode] = {
            "learn_step_ms_median": round(
                1e3 * float(np.median(times)), 3
            ),
            "learn_step_ms_p90": round(
                1e3 * float(np.quantile(times, 0.9)), 3
            ),
        }
    (tracing.enable if was_enabled else tracing.disable)()
    tracing.clear()
    off = out["tracing_off"]["learn_step_ms_median"]
    on = out["tracing_on"]["learn_step_ms_median"]
    out["on_vs_off"] = round(on / off, 3) if off else None
    return out


def bench_profile(trace_path=None, overhead_path=None):
    """One telemetry-instrumented PPO run (plumbing geometry, pipelined
    sampling): writes the chrome trace of the last iterations and a
    telemetry-overhead A/B entry; prints ONE summary JSON line with the
    ``info/telemetry`` roll-up (stage wall-times + overlap fraction)."""
    import os
    import urllib.request

    import ray_tpu.env.synthetic_env  # noqa: F401 registers SyntheticFast-v0
    from ray_tpu.algorithms.ppo import PPOConfig

    os.makedirs("benchmarks/e2e", exist_ok=True)
    trace_path = trace_path or "benchmarks/e2e/ppo_iteration_trace.json"
    overhead_path = (
        overhead_path or "benchmarks/e2e/telemetry_overhead.json"
    )
    cfg = (
        PPOConfig()
        .environment("SyntheticFast-v0")
        .rollouts(
            num_rollout_workers=2,
            num_envs_per_worker=8,
            rollout_fragment_length=128,
            sample_prefetch=1,
        )
        .training(
            train_batch_size=2048,
            sgd_minibatch_size=512,
            num_sgd_iter=2,
            lr=3e-4,
            model={"fcnet_hiddens": [64, 64]},
        )
        .debugging(seed=0)
        .telemetry(metrics_port=0, trace=True)
    )
    algo = cfg.build()
    try:
        tel = {}
        for _ in range(4):  # iter 1 compiles; spans settle by 3-4
            result = algo.train()
            tel = result["info"].get("telemetry", tel)
        algo.export_timeline(trace_path, last_n=2)
        port = algo._telemetry.metrics_port
        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        series = sorted(
            {
                ln.split("{")[0].split(" ")[0]
                for ln in scrape.splitlines()
                if ln.startswith("ray_tpu_")
            }
        )
    finally:
        algo.cleanup()
    overhead = bench_telemetry_overhead()
    with open(overhead_path, "w") as f:
        json.dump(overhead, f, indent=1)
    report = {
        "metric": "ppo_iteration_profile",
        "telemetry": tel,
        "trace": trace_path,
        "metrics_series": series,
        "telemetry_overhead": overhead,
        "artifacts": [trace_path, overhead_path],
    }
    print(json.dumps(report))
    return report


def bench_replay_ab(out_path=None, iters=10):
    """Host-ring vs device-resident replay A/B on the SAC geometry
    (docs/data_plane.md): the SAME fixed-seed run — same env steps,
    same learn steps, bit-identical final params (asserted) — differing
    only in where replay rows live. Reports per-iteration H2D bytes by
    path: the host ring re-transfers every sampled train batch
    (``learn``), the device plane transfers each transition once at
    insert (``replay_insert``) — at this replay ratio (train batch 256
    over 32-step fragments) that is an 8× byte diet. Writes
    ``benchmarks/e2e/replay_device_ab.json``.

    On this 1-core CPU container the steps/s of the two sides is
    expected ~flat (device arrays live in the same RAM and compute
    shares the core); the byte columns and the parity flag are the
    result. Behind a real H2D boundary the byte diet is wall-clock:
    every byte NOT re-crossing it is learner time (not measured on
    this round's chip)."""
    import os

    import jax

    from ray_tpu.algorithms.sac import SACConfig
    from ray_tpu.telemetry import metrics as telemetry_metrics

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/replay_device_ab.json"

    def run(device_resident):
        cfg = (
            SACConfig()
            .environment("Pendulum-v1")
            .rollouts(
                num_rollout_workers=0, rollout_fragment_length=32
            )
            .training(
                train_batch_size=256,
                num_steps_sampled_before_learning_starts=256,
                replay_device_resident=device_resident,
            )
            .reporting(min_time_s_per_iteration=0)
            .debugging(seed=0)
        )
        algo = cfg.build()
        try:
            # warmup to learning-start + compile outside the clock
            while (
                algo._counters["num_env_steps_sampled"] < 256 + 32
            ):
                algo.train()
            h2d0 = telemetry_metrics.h2d_bytes_by_path()
            steps0 = algo._counters["num_env_steps_sampled"]
            t0 = time.perf_counter()
            for _ in range(iters):
                algo.train()
            wall = time.perf_counter() - t0
            env_steps = (
                algo._counters["num_env_steps_sampled"] - steps0
            )
            h2d1 = telemetry_metrics.h2d_bytes_by_path()
            params = jax.device_get(algo.get_policy().params)
            buf = algo.local_replay_buffer.buffers["default_policy"]
            resident = bool(
                getattr(buf, "is_device_resident", False)
                and not getattr(buf, "spilled", False)
            )
        finally:
            algo.cleanup()
        h2d = {
            k: h2d1.get(k, 0.0) - h2d0.get(k, 0.0)
            for k in set(h2d1) | set(h2d0)
        }
        return {
            "env_steps_per_s": round(env_steps / wall, 1),
            "env_steps": int(env_steps),
            "h2d_bytes_per_iter": {
                k: round(v / iters, 1) for k, v in h2d.items()
            },
            "buffer_device_resident": resident,
        }, params

    host_side, host_params = run(False)
    dev_side, dev_params = run(True)
    parity = all(
        np.array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(host_params),
            jax.tree_util.tree_leaves(dev_params),
        )
    )
    learn_bytes = host_side["h2d_bytes_per_iter"].get("learn", 0.0)
    insert_bytes = dev_side["h2d_bytes_per_iter"].get(
        "replay_insert", 0.0
    )
    report = {
        "metric": "replay_device_ab",
        "config": {
            "env": "Pendulum-v1",
            "train_batch_size": 256,
            "rollout_fragment_length": 32,
            "iters": iters,
            "seed": 0,
        },
        "host_ring": host_side,
        "device_resident": dev_side,
        "h2d_learn_vs_insert_ratio": round(
            learn_bytes / insert_bytes, 2
        )
        if insert_bytes
        else None,
        "parity_bitwise": parity,
        "note": (
            "steps/s is expected ~flat on this 1-core CPU container "
            "(no real H2D wire, compute shares the core); the byte "
            "diet is the result — behind a real H2D boundary every "
            "re-crossed byte is learner wall-clock"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_superstep(
    out_path=None, b=256, mb=64, iters=2, kmax=8, reps=2,
):
    """Dispatch-amortization A/B of the fused superstep
    (docs/data_plane.md): per-update wall and dispatch/readback
    overhead at K=1 (the ``deferred_stats`` per-update protocol — the
    best the un-fused path can do) vs K∈{2, kmax} updates per
    dispatch (``JaxPolicy.learn_superstep``), on device-resident
    batches so the numbers isolate the host-boundary cost from H2D.
    Nest compute is epoch-isolated exactly like ``bench_mfu``, so
    ``overhead = wall − compute`` on both sides and the "nest compute
    unchanged" check is the in-scan marginal cost. Writes
    ``benchmarks/e2e/superstep_ab.json``. Defaults are a CPU smoke
    geometry (1/16 batch of the headline bench, 2 epochs — the Nature
    CNN runs minutes per full nest on a 1-core box); the TPU driver
    run re-measures at the r05 geometry via the ``superstep``
    sub-entry of ``bench_mfu``."""
    import os

    import jax

    from ray_tpu import sharding as sharding_lib
    from ray_tpu.policy.jax_policy import _FRAMES as _F

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/superstep_ab.json"
    rng = np.random.default_rng(0)

    # epoch-isolated nest compute (bench_mfu method)
    lo, hi = iters, 4 * iters
    setups = {}
    for it in (lo, hi):
        p = _make_policy(b, mb, it)
        host, bsize = p.prepare_batch(make_batch(rng, b))
        dev = jax.device_put(host, p.batch_shardings(host))
        p.learn_on_device_batch(dict(dev), bsize)  # compile+warm
        setups[it] = (p, dev, bsize, host)
    ts = {lo: [], hi: []}
    for _ in range(reps):
        for it, (p, dev, bsize, _h) in setups.items():
            t0 = time.perf_counter()
            p.learn_on_device_batch(dict(dev), bsize)
            ts[it].append(time.perf_counter() - t0)
    compute = float(
        (np.median(ts[hi]) - np.median(ts[lo])) / (hi - lo) * iters
    )

    p, dev, bsize, host = setups[lo]

    # K=1 baseline: deferred-stats per-update dispatch
    n1 = 2 * reps
    p.config["deferred_stats"] = True
    try:
        p.learn_on_device_batch(dict(dev), bsize)  # prime the lag
        t0 = time.perf_counter()
        for _ in range(n1):
            p.learn_on_device_batch(dict(dev), bsize)
        p.flush_deferred_stats()
        wall1 = (time.perf_counter() - t0) / n1
    finally:
        p.config["deferred_stats"] = False
        p.flush_deferred_stats()

    walls = {}
    for k in (2, kmax):
        stacked = {
            cn: np.repeat(np.asarray(v)[None], k, axis=0)
            for cn, v in host.items()
        }
        shard = {
            cn: (
                sharding_lib.replicated(p.mesh)
                if cn == _F
                else sharding_lib.batch_sharded(p.mesh, ndim_prefix=2)
            )
            for cn in stacked
        }
        dev_stacked = jax.device_put(stacked, shard)
        jax.block_until_ready(dev_stacked)
        p.learn_superstep(
            k, bsize, stacked=dict(dev_stacked), k_max=k
        )  # compile+warm
        n = max(2, reps)
        t0 = time.perf_counter()
        for _ in range(n):
            p.learn_superstep(
                k, bsize, stacked=dict(dev_stacked), k_max=k
            )
        walls[k] = (time.perf_counter() - t0) / (n * k)

    # "nest compute unchanged": the overhead-free marginal cost per
    # update INSIDE the scan — (T_kmax − T_2)/(kmax − 2) per dispatch.
    # Overheads subtract the LOWER of the two compute estimates (the
    # epoch-scaling one carries its own measurement noise and can land
    # a hair above a fused wall, which would clamp real overhead to 0)
    compute_in_scan = (walls[kmax] * kmax - walls[2] * 2) / (kmax - 2)
    compute_best = min(compute, compute_in_scan)

    def overhead(wall):
        return round(max(wall - compute_best, 0.0), 4)

    per_update = {
        "k1_deferred": {
            "wall_s": round(wall1, 4),
            "dispatch_overhead_s": overhead(wall1),
        },
    }
    for k in (2, kmax):
        per_update[f"k{k}"] = {
            "wall_s": round(walls[k], 4),
            "dispatch_overhead_s": overhead(walls[k]),
        }
    o1 = max(wall1 - compute_best, 0.0)
    ok = max(walls[kmax] - compute_best, 1e-4)
    report = {
        "metric": "superstep_dispatch_ab",
        "config": {
            "train_batch": b,
            "minibatch": mb,
            "num_sgd_iter": iters,
            "obs": [H, W, C],
            "kmax": kmax,
            "reps": reps,
            "device": jax.devices()[0].device_kind,
        },
        "nest_compute_s": round(compute, 4),
        "nest_compute_in_scan_s": round(compute_in_scan, 4),
        "per_update": per_update,
        "overhead_reduction_kmax_vs_k1": round(o1 / ok, 1),
        "note": (
            "device-resident feeds on both sides: the A/B isolates "
            "the per-dispatch host-boundary cost. k1_deferred is the "
            "un-fused path's best protocol (stats lag 1); the "
            "superstep pays one dispatch + one stats drain per K "
            "updates, so its per-update overhead is ~1/K of the "
            "baseline's. nest_compute_in_scan_s ≈ nest_compute_s "
            "checks the scan added no per-update compute"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_model_parallel(out_path=None, m=4, reps=4):
    """Replicated vs 2-D-partitioned transformer A/B
    (docs/sharding.md "2-D mesh & param partitioning"): the SAME
    fixed-seed transformer-PPO learn step on [("batch", D)] with
    replicated params vs [("batch", D//M), ("model", M)] with
    megatron-rule param placement — the geometry where replication is
    the memory wall: every device holds the full tree on the left,
    ~1/M of it on the right. Asserts per-shard ``params_bytes`` ~
    total/M, fixed-seed parity (model_parallel=1 bitwise; M-way to
    float-assoc tolerance — cross-shard reduction order), and zero
    recompiles in the timed window. Writes
    ``benchmarks/e2e/model_parallel_ab.json``. Runs itself under 8
    simulated host devices when the process has fewer."""
    import os
    import subprocess

    import jax

    from ray_tpu import sharding as sharding_lib

    if (
        len(jax.devices()) < 2 * m
        and not os.environ.get("_RT_MP_CHILD")
    ):
        env = {
            **os.environ,
            **sharding_lib.simulated_device_env(2 * m),
            "_RT_MP_CHILD": "1",
        }
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--model-parallel"],
            env=env,
            check=True,
        )
        return

    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.sharding.compile import compile_stats

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/model_parallel_ab.json"
    n_dev = len(jax.devices())
    b, obs_dim = 512, 64
    model = {
        "use_transformer": True,
        "transformer_dim": 256,
        "transformer_num_layers": 4,
        "transformer_num_heads": 8,
        "transformer_ff_dim": 1024,
        "transformer_seq_len": 8,
    }

    def make(mesh):
        return PPOJaxPolicy(
            gym.spaces.Box(-1, 1, (obs_dim,), np.float32),
            gym.spaces.Discrete(8),
            {
                "train_batch_size": b,
                "sgd_minibatch_size": b // 2,
                "num_sgd_iter": 2,
                "lr": 3e-4,
                "seed": 0,
                "model": dict(model),
                "_mesh": mesh,
            },
        )

    rng = np.random.default_rng(0)
    host = {
        "obs": rng.standard_normal((b, obs_dim)).astype(np.float32),
        "actions": rng.integers(0, 8, b).astype(np.int64),
        "action_logp": np.full(b, -2.0, np.float32),
        "action_dist_inputs": rng.standard_normal((b, 8)).astype(
            np.float32
        ),
        "advantages": rng.standard_normal(b).astype(np.float32),
        "value_targets": rng.standard_normal(b).astype(np.float32),
    }

    arms = {
        "replicated": sharding_lib.get_mesh(
            devices=jax.devices()[:n_dev]
        ),
        "model_parallel_1": sharding_lib.get_mesh(
            devices=jax.devices()[:n_dev],
            axis_shapes=[("batch", n_dev), ("model", 1)],
        ),
        f"model_parallel_{m}": sharding_lib.get_mesh(
            devices=jax.devices()[:n_dev],
            axis_shapes=[("batch", n_dev // m), ("model", m)],
        ),
    }
    results = {}
    weights = {}
    for name, mesh in arms.items():
        p = make(mesh)
        prep, bsize = p.prepare_batch(dict(host))
        dev = jax.device_put(prep, p.batch_shardings(prep))
        stats = p.learn_on_device_batch(dict(dev), bsize)  # warm
        weights[name] = p.get_weights()
        total = sharding_lib.tree_nbytes(p.params)
        per_shard = (
            sharding_lib.tree_shard_nbytes(
                p.params, p.param_pspecs, p.mesh
            )
            if p.param_pspecs is not None
            else total
        )
        traces0 = compile_stats()["traces"]
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            p.learn_on_device_batch(dict(dev), bsize)
            ts.append(time.perf_counter() - t0)
        results[name] = {
            "params_bytes_total": int(total),
            "params_bytes_per_shard": int(per_shard),
            "learn_wall_s_median": round(float(np.median(ts)), 4),
            "first_step_total_loss": float(stats["total_loss"]),
            "recompiles_in_timed_window": (
                compile_stats()["traces"] - traces0
            ),
        }

    # parity reference for the M-way arm: replicated on the SAME
    # data-shard count (D//M shards), so the per-shard shuffle streams
    # match and the ONLY difference is the model-axis split
    p_ref = make(
        sharding_lib.get_mesh(devices=jax.devices()[: n_dev // m])
    )
    prep, bsize = p_ref.prepare_batch(dict(host))
    dev = jax.device_put(prep, p_ref.batch_shardings(prep))
    p_ref.learn_on_device_batch(dict(dev), bsize)
    weights["replicated_ref"] = p_ref.get_weights()

    la = jax.tree_util.tree_leaves(weights["replicated"])
    l1 = jax.tree_util.tree_leaves(weights["model_parallel_1"])
    lr_ = jax.tree_util.tree_leaves(weights["replicated_ref"])
    lm = jax.tree_util.tree_leaves(weights[f"model_parallel_{m}"])
    parity_bitwise_mp1 = all(
        np.array_equal(a, c) for a, c in zip(la, l1)
    )
    parity_allclose_mpm = all(
        np.allclose(a, c, atol=5e-3) for a, c in zip(lr_, lm)
    )
    mp = results[f"model_parallel_{m}"]
    shard_ratio = (
        mp["params_bytes_per_shard"] / mp["params_bytes_total"]
    )
    out = {
        "metric": "model_parallel_ab",
        "devices": n_dev,
        "model_parallel": m,
        "geometry": {
            "batch": b,
            **{k: v for k, v in model.items() if k != "use_transformer"},
        },
        "arms": results,
        # the memory-wall headline: one device's param bytes, and how
        # close the split tree sits to the ideal total/M
        "per_shard_over_total": round(shard_ratio, 4),
        "ideal_over_total": round(1.0 / m, 4),
        "parity_bitwise_mp1": bool(parity_bitwise_mp1),
        f"parity_allclose_mp{m}": bool(parity_allclose_mpm),
    }
    assert parity_bitwise_mp1, "model_parallel=1 must be bitwise"
    assert parity_allclose_mpm, f"{m}-way parity failed"
    assert shard_ratio < 1.0 / m + 0.1, (
        f"per-shard bytes {shard_ratio:.3f} of total; expected ~1/{m}"
    )
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    return out


def bench_chaos(out_path=None, iters=6):
    """Chaos A/B (docs/resilience.md): steady-state PPO iteration time
    vs the same run with a rollout-worker kill and one NaN learn batch
    injected mid-run. Measures what a failure actually costs — the
    recovery time (probe + recreate + resync) and its
    iterations-lost equivalent — and proves the run completes with the
    fleet restored. Writes benchmarks/e2e/chaos_recovery.json."""
    import os

    import ray_tpu.env.synthetic_env  # noqa: F401 registers SyntheticFast-v0
    from ray_tpu.algorithms.ppo import PPOConfig
    from ray_tpu.telemetry import metrics as telemetry_metrics

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/chaos_recovery.json"

    def build(fault_injection):
        return (
            PPOConfig()
            .environment("SyntheticFast-v0")
            .rollouts(
                num_rollout_workers=4,
                num_envs_per_worker=4,
                rollout_fragment_length=64,
            )
            .training(
                train_batch_size=1024,
                sgd_minibatch_size=256,
                num_sgd_iter=2,
                lr=3e-4,
                model={"fcnet_hiddens": [32, 32]},
            )
            .fault_tolerance(
                recreate_failed_workers=True,
                nan_guard=True,
                worker_health_probe_timeout_s=10.0,
                fault_injection=fault_injection,
            )
            .debugging(seed=0)
            .build()
        )

    def timed_run(algo, n):
        times, last = [], {}
        for _ in range(n):
            t0 = time.perf_counter()
            last = algo.train()
            times.append(time.perf_counter() - t0)
        return times, last

    # A: steady state (injector inert, same guard/recreate config)
    algo = build({})
    try:
        timed_run(algo, 1)  # compile + fleet spin-up
        steady_times, _ = timed_run(algo, iters)
    finally:
        algo.cleanup()
    steady_median = float(np.median(steady_times))

    # B: kill one worker on its 2nd sample call, poison one learn batch
    restarts0 = telemetry_metrics.counter_total(
        telemetry_metrics.WORKER_RESTARTS_TOTAL
    )
    skipped0 = telemetry_metrics.counter_total(
        telemetry_metrics.SKIPPED_BATCHES_TOTAL
    )
    algo = build(
        {
            "kill_worker": [{"worker_index": 2, "on_call": 2}],
            "nan_batch": {"on_learn_call": 3},
        }
    )
    try:
        timed_run(algo, 1)
        chaos_times, last = timed_run(algo, iters)
        fleet_after = algo.workers.num_remote_workers()
        recovery = last["info"]["recovery"]
    finally:
        algo.cleanup()

    lost_s = max(0.0, sum(chaos_times) - iters * steady_median)
    report = {
        "metric": "chaos_recovery",
        "steady_state_s_per_iter_median": round(steady_median, 4),
        "chaos_iter_times_s": [round(t, 4) for t in chaos_times],
        "recovery_time_s": round(recovery["time_lost_s"], 4),
        "excess_wall_clock_s": round(lost_s, 4),
        "iterations_lost_equiv": round(lost_s / steady_median, 2)
        if steady_median
        else None,
        "worker_restarts": telemetry_metrics.counter_total(
            telemetry_metrics.WORKER_RESTARTS_TOTAL
        )
        - restarts0,
        "skipped_nan_batches": telemetry_metrics.counter_total(
            telemetry_metrics.SKIPPED_BATCHES_TOTAL
        )
        - skipped0,
        "fleet_restored_to": fleet_after,
        "config": {
            "num_rollout_workers": 4,
            "train_batch_size": 1024,
            "faults": "kill worker 2 @ sample call 2; "
            "NaN batch @ learn call 3",
        },
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_elastic(out_path=None):
    """Elastic-fleet chaos A/B (docs/resilience.md "elastic fleets &
    preemption"). Three phases:

    A) **elastic**: a PPO fleet forced 4 → 2 via two noticed
       preemptions (drained gracefully, zero recovery budget), then
       → 6 via an autoscaler scale-up; per-iteration steps/s grouped
       by fleet size.
    B) **kill-only** (the PR-4 path): the same two workers die with
       NO notice; recovery = probe + recreate. Drain vs kill cost.
    C) **driver crash**: work lost restoring from the continuous
       checkpoint stream (≤ 1 superstep) vs the periodic path (up to
       ``checkpoint_frequency`` iterations), plus the streamer's
       off-critical-path overhead (iteration time with streaming on
       vs off).

    Writes benchmarks/e2e/elastic_fleet.json."""
    import os
    import shutil

    import ray_tpu.env.synthetic_env  # noqa: F401 registers SyntheticFast-v0
    from ray_tpu.algorithms.ppo import PPOConfig

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/elastic_fleet.json"

    def build(elastic, fault_injection=None, **ft):
        cfg = (
            PPOConfig()
            .environment("SyntheticFast-v0")
            .rollouts(
                num_rollout_workers=4,
                num_envs_per_worker=4,
                rollout_fragment_length=64,
            )
            .training(
                train_batch_size=1024,
                sgd_minibatch_size=256,
                num_sgd_iter=2,
                lr=3e-4,
                model={"fcnet_hiddens": [32, 32]},
            )
            .fault_tolerance(
                recreate_failed_workers=True,
                worker_health_probe_timeout_s=10.0,
                fault_injection=fault_injection or {},
                **ft,
            )
            .debugging(seed=0)
        )
        if elastic:
            cfg.fault_tolerance(
                elastic=True,
                min_workers=2,
                max_workers=6,
                drain_grace_s=120.0,
                fleet_interval_s=0.2,
            )
        return cfg.build()

    def timed_iters(algo, n):
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            r = algo.train()
            dt = time.perf_counter() - t0
            out.append(
                (
                    dt,
                    algo.workers.num_remote_workers(),
                    r["info"]["recovery"],
                )
            )
        return out

    # ---- A: elastic — 4 → 2 (noticed preemptions) → 6 (scale-up) ----
    faults = {
        "preempt_worker": [
            {"worker_index": 2, "on_call": 2, "grace_s": 120.0},
            {"worker_index": 3, "on_call": 3, "grace_s": 120.0},
        ]
    }
    algo = build(elastic=True, fault_injection=faults)
    per_fleet = {}
    try:
        timed_iters(algo, 1)  # compile + spin-up
        rows = timed_iters(algo, 4)
        # bounded patience for the async notice polls to drain both
        for _ in range(8):
            if rows[-1][2]["preemptions_drained"] >= 2:
                break
            rows += timed_iters(algo, 1)
        drain_rows = list(rows)
        algo._fleet.request_scale(+4)  # → max_workers = 6
        rows += timed_iters(algo, 3)
        steps_per_iter = 1024.0
        for dt, fleet, _ in rows:
            per_fleet.setdefault(fleet, []).append(
                steps_per_iter / dt
            )
        rec = rows[-1][2]
        elastic_report = {
            "fleet_trajectory": [fleet for _, fleet, _ in rows],
            "steps_per_s_by_fleet_size": {
                str(k): round(float(np.median(v)), 1)
                for k, v in sorted(per_fleet.items())
            },
            "preemptions_drained": rec["preemptions_drained"],
            "recovery_budget_spent": rec["failures"],
            "drain_iter_times_s": [
                round(dt, 4) for dt, _, _ in drain_rows
            ],
            "fleet": rec["fleet"],
        }
    finally:
        algo.cleanup()

    # ---- B: kill-only (unnoticed) — the PR-4 recovery path ----
    algo = build(
        elastic=False,
        fault_injection={
            "kill_worker": [
                {"worker_index": 2, "on_call": 2},
                {"worker_index": 3, "on_call": 3},
            ]
        },
        max_failures=10,
    )
    try:
        timed_iters(algo, 1)
        rows = timed_iters(algo, 6)
        rec = rows[-1][2]
        kill_report = {
            "iter_times_s": [round(dt, 4) for dt, _, _ in rows],
            "recovery_time_s": rec["time_lost_s"],
            "worker_restarts": rec["worker_restarts"],
            "recovery_budget_spent": rec["failures"],
        }
    finally:
        algo.cleanup()

    # ---- C: driver crash — streamed vs periodic work lost ----
    root = "/tmp/ray_tpu_bench_elastic_ckpt"
    shutil.rmtree(root, ignore_errors=True)

    def build_local(streaming):
        return (
            PPOConfig()
            .environment("SyntheticFast-v0")
            .rollouts(
                num_rollout_workers=0,
                num_envs_per_worker=4,
                rollout_fragment_length=64,
            )
            .training(
                train_batch_size=256,
                sgd_minibatch_size=128,
                num_sgd_iter=2,
                lr=3e-4,
                model={"fcnet_hiddens": [32, 32]},
            )
            .fault_tolerance(
                checkpoint_streaming=streaming,
                checkpoint_frequency=5,
                checkpoint_root=root,
                restore_on_failure=True,
            )
            .debugging(seed=0)
            .build()
        )

    # streaming off: baseline iteration time + the periodic loss bound
    algo = build_local(streaming=False)
    try:
        timed_iters(algo, 1)
        base_times = [dt for dt, _, _ in timed_iters(algo, 6)]
        crashed_iter = algo.iteration
        # newest periodic save at checkpoint_frequency = 5
        periodic_ckpt_iter = (crashed_iter // 5) * 5
    finally:
        algo.cleanup()
    periodic_lost_iters = crashed_iter - periodic_ckpt_iter

    shutil.rmtree(root, ignore_errors=True)
    algo = build_local(streaming=True)
    try:
        timed_iters(algo, 1)
        stream_times = [dt for dt, _, _ in timed_iters(algo, 6)]
        head = algo._ckpt_streamer._superstep
        algo._ckpt_streamer.flush()
    finally:
        algo.cleanup()  # the "crash"
    restored = build_local(streaming=True)
    try:
        path = restored._recovery.restore_latest()
        from ray_tpu.resilience.streamer import CheckpointStreamer

        tail = CheckpointStreamer.peek(path)["superstep"]
    finally:
        restored.cleanup()

    crash_report = {
        "streamed_lost_supersteps": head - tail,
        "periodic_lost_iterations": periodic_lost_iters,
        "iter_s_streaming_off_median": round(
            float(np.median(base_times)), 4
        ),
        "iter_s_streaming_on_median": round(
            float(np.median(stream_times)), 4
        ),
        "restored_from": path,
    }

    report = {
        "metric": "elastic_fleet",
        "elastic": elastic_report,
        "kill_only": kill_report,
        "driver_crash": crash_report,
        "config": {
            "num_rollout_workers": 4,
            "min_workers": 2,
            "max_workers": 6,
            "train_batch_size": 1024,
            "faults_elastic": "preempt worker 2 @ call 2, worker 3 "
            "@ call 3 (grace 120 s); scale-up +4 after drains",
            "faults_kill": "kill workers 2, 3 (no notice)",
        },
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_fleet_worker():
    """Subprocess entry for the --fleet lane (one learner host of a
    gloo CPU fleet). Mirrors tests/_multihost_worker.py's protocol but
    measures walls: steps/s over the epoch mesh, then (2-host modes)
    the drain-vs-kill recovery and the resize wall. Rank 0 prints one
    ``FLEETBENCH {json}`` line."""
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")
    import gymnasium as gym

    from ray_tpu import fleet
    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch
    from ray_tpu.parallel import distributed as dist

    rank = int(os.environ["RAY_TPU_PROCESS_ID"])
    world = int(os.environ["RAY_TPU_NUM_PROCESSES"])
    mode = os.environ.get("RAY_TPU_FLEET_BENCH_MODE", "drain")
    if world > 1:
        dist.initialize()

    kv = fleet.KVClient(os.environ["RAY_TPU_KV_ADDRESS"])
    coord = fleet.FleetCoordinator(kv) if rank == 0 else None
    agent = fleet.HostAgent(
        kv, f"host{rank}", rank_hint=rank, heartbeat_interval=0.5
    )
    agent.join()
    if rank == 0:
        coord.wait_for_members(world, timeout=60.0)
        coord.propose_epoch(reason="bootstrap")
    epoch1 = agent.wait_for_epoch(1)
    mesh = fleet.epoch_mesh(epoch1)

    B = 64
    config = {
        "_mesh": mesh,
        "model": {"fcnet_hiddens": [32, 32]},
        "train_batch_size": B,
        "sgd_minibatch_size": 32,
        "num_sgd_iter": 2,
        "lr": 3e-4,
        "seed": 0,
    }
    obs_space = gym.spaces.Box(-1.0, 1.0, (16,), np.float32)
    act_space = gym.spaces.Discrete(4)
    policy = PPOJaxPolicy(obs_space, act_space, config)
    rng = np.random.default_rng(7)
    host = {
        SampleBatch.OBS: rng.standard_normal((B, 16)).astype(
            np.float32
        ),
        SampleBatch.ACTIONS: rng.integers(0, 4, B).astype(np.int64),
        SampleBatch.ACTION_LOGP: np.full(B, -1.4, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
            (B, 4)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: rng.standard_normal(B).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: rng.standard_normal(B).astype(
            np.float32
        ),
    }
    tree, bsize = policy.prepare_batch(SampleBatch(host))
    global_batch = {
        k: sharding_lib.put_global(v, policy.data_sharding)
        for k, v in tree.items()
    }
    policy.learn_on_device_batch(global_batch, bsize)  # compile
    walls = []
    for _ in range(6):
        t0 = time.perf_counter()
        policy.learn_on_device_batch(global_batch, bsize)
        walls.append(time.perf_counter() - t0)
    steps_per_s = B / float(np.median(walls))

    if world == 1:
        print(
            "FLEETBENCH "
            + json.dumps(
                {"hosts": 1, "steps_per_s": round(steps_per_s, 1)}
            )
        )
        agent.stop()
        coord.stop()
        return

    if mode == "kill":
        # the victim dies with NO notice; the survivor's heartbeat
        # sweep must detect it (the gcs_heartbeat_manager path)
        if rank == 1:
            kv.put("bench/kill_ts", time.time())
            agent.stop()
            os._exit(0)
        kill_ts = kv.get("bench/kill_ts", timeout=60.0)
        deadline = time.monotonic() + 60.0
        while True:
            coord.reconcile()
            coord.expire_dead(horizon=2.0)
            ep = coord.current_epoch()
            if ep is not None and ep.gen >= 2:
                break
            if time.monotonic() >= deadline:
                raise TimeoutError("kill never detected")
            time.sleep(0.05)
        survivor = fleet.resize_policy(
            policy, fleet.epoch_mesh(coord.current_epoch())
        )
        survivor.learn_on_batch(SampleBatch(host))
        recovery_wall = time.time() - kill_ts
        fn = survivor.learn_fn(bsize)
        print(
            "FLEETBENCH "
            + json.dumps(
                {
                    "hosts": 2,
                    "mode": "kill",
                    "steps_per_s": round(steps_per_s, 1),
                    "recovery_wall_s": round(recovery_wall, 3),
                    "resize_traces": fn.traces,
                }
            )
        )
        # rank1 is gone: skip jax.distributed teardown
        os._exit(0)

    # drain mode: provider-noticed preemption of host1
    if rank == 1:
        kv.put("bench/notice_ts", time.time())
        agent.announce_notice(reason="preempted")
    if rank == 0:
        deadline = time.monotonic() + 60.0
        while agent.poll_drain(1) is None:
            coord.reconcile()
            if time.monotonic() >= deadline:
                raise TimeoutError("drain never posted")
            time.sleep(0.02)
    agent.await_drain(1)
    policy.learn_on_device_batch(global_batch, bsize)  # drain step
    agent.barrier("drained", epoch1)
    if rank == 1:
        agent.leave()
        kv.get("bench/solo_done", timeout=120.0)
        agent.stop()
        return
    notice_ts = kv.get("bench/notice_ts", timeout=10.0)
    epoch2 = agent.wait_for_epoch(2)
    t0 = time.perf_counter()
    survivor = fleet.resize_policy(policy, fleet.epoch_mesh(epoch2))
    survivor.learn_on_batch(SampleBatch(host))
    resize_wall = time.perf_counter() - t0
    recovery_wall = time.time() - notice_ts
    fn = survivor.learn_fn(bsize)
    print(
        "FLEETBENCH "
        + json.dumps(
            {
                "hosts": 2,
                "mode": "drain",
                "steps_per_s": round(steps_per_s, 1),
                "recovery_wall_s": round(recovery_wall, 3),
                "resize_wall_s": round(resize_wall, 3),
                "resize_traces": fn.traces,
            }
        )
    )
    kv.put("bench/solo_done", True)
    coord.stop()
    agent.stop()


def bench_fleet(out_path=None):
    """Elastic learner-fleet lane (docs/fleet.md): gloo CPU fleets of
    1 and 2 hosts (2 virtual devices each) through the full
    rendezvous → epoch → lockstep-learn protocol. Reports

      - steps/s at hosts ∈ {1, 2} and the DCN scaling efficiency;
      - drain (provider-noticed) vs kill (heartbeat-detected)
        recovery wall: notice/death → first post-resize step done;
      - the resize wall: the reshard onto the survivor mesh plus the
        twin's first learn step, its compile included
        (`resize_traces` in the JSON counts it).

    Writes benchmarks/e2e/fleet.json."""
    import os
    import socket
    import subprocess

    from ray_tpu.fleet import KVServer

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/fleet.json"

    def run(world, mode="drain"):
        kv = KVServer(host="127.0.0.1")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coord_port = s.getsockname()[1]
        env_base = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "RAY_TPU_NUM_PROCESSES": str(world),
            "RAY_TPU_KV_ADDRESS": f"127.0.0.1:{kv.port}",
            "RAY_TPU_FLEET_BENCH_MODE": mode,
        }
        if world > 1:
            env_base["RAY_TPU_COORDINATOR"] = (
                f"127.0.0.1:{coord_port}"
            )
        procs = []
        for rank in range(world):
            env = {**env_base, "RAY_TPU_PROCESS_ID": str(rank)}
            procs.append(
                subprocess.Popen(
                    [sys.executable, __file__, "--fleet-worker"],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=300)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            kv.shutdown()
        for rank, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0:
                raise RuntimeError(
                    f"fleet bench rank {rank} failed:\n{out}"
                )
        for line in outs[0].splitlines():
            if line.startswith("FLEETBENCH "):
                return json.loads(line[len("FLEETBENCH ") :])
        raise RuntimeError(f"no FLEETBENCH line:\n{outs[0]}")

    one = run(world=1)
    drain = run(world=2, mode="drain")
    kill = run(world=2, mode="kill")

    report = {
        "metric": "fleet_elastic_learner_mesh",
        "steps_per_s_by_hosts": {
            "1": one["steps_per_s"],
            "2": drain["steps_per_s"],
        },
        # 2 hosts double the devices over a CPU "DCN": efficiency is
        # steps/s parity at the SAME global batch (weak scaling of
        # the collective, not more throughput)
        "dcn_scaling_efficiency": round(
            drain["steps_per_s"] / one["steps_per_s"], 3
        ),
        "drain_recovery_wall_s": drain["recovery_wall_s"],
        "kill_recovery_wall_s": kill["recovery_wall_s"],
        "resize_wall_s": drain["resize_wall_s"],
        "resize_traces": drain["resize_traces"],
        "config": {
            "world": 2,
            "devices_per_host": 2,
            "train_batch_size": 64,
            "collectives": "gloo (CPU stand-in for DCN)",
            "kill_detection_horizon_s": 2.0,
        },
        "note": (
            "on the gloo/localhost stand-in every gradient pmean is "
            "a socket round trip, so 2-host steps/s measures the "
            "protocol's lockstep correctness, not DCN bandwidth — "
            "the scaling headline belongs to the TPU round; the "
            "portable numbers here are the recovery walls"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_fleet_chaos(out_path=None):
    """Control-plane failover lane (docs/fleet.md "failure model &
    leadership"): how long the fleet is headless after its coordinator
    dies, as a function of the lease TTL.

    Per TTL, three trials of the chaos choreography on an in-process
    KV server — no learners, because the coordinator is never on the
    data path, so the portable number is pure control-plane wall: a
    leader at term 1 registers 2 hosts and cuts epoch 1; the leader
    "crashes" (renew loop stops, lease NOT released — a SIGKILL; the
    TTL must run out); an armed standby polls the lease, wins at term
    2, rebuilds the member/epoch mirror from the KV table, and cuts
    the failover epoch. The recorded wall runs kill → failover epoch
    cut (the moment hosts can resume), and the acceptance gate is
    median wall < 2x the lease TTL. Every trial also proves the
    fence: the zombie's stale-term write must raise StaleTermError
    and land in the store's fenced-write count. A clean-handover
    trial (lease released on stop) rides along per TTL — its wall is
    TTL-independent, which is the lane's point: the price of
    crash-failover IS the TTL you chose.

    Writes benchmarks/e2e/fleet_chaos.json."""
    import statistics

    from ray_tpu import fleet
    from ray_tpu.fleet import KVClient, KVServer, StaleTermError

    out_path = out_path or "benchmarks/e2e/fleet_chaos.json"
    ttls = [0.5, 1.0, 2.0]
    trials = 3

    def one_trial(ttl, release):
        server = KVServer(host="127.0.0.1")
        kv = KVClient(f"127.0.0.1:{server.port}")
        try:
            leader = fleet.FleetCoordinator(
                kv, lease_ttl=ttl, holder="leader", subscribe=False
            )
            leader.register_host("host0", rank_hint=0)
            leader.register_host("host1", rank_hint=1)
            leader.propose_epoch(reason="bootstrap")
            standby = fleet.FleetCoordinator(
                kv,
                standby=True,
                lease_ttl=ttl,
                holder="standby",
                subscribe=False,
            )
            t0 = time.perf_counter()
            leader.stop(release_lease=release)
            term = standby.acquire_leadership(timeout=10.0 + 3 * ttl)
            assert term == 2 and standby.is_leader, term
            # warm-cache restart: mirror rebuilt from the KV table
            assert sorted(standby.members()) == ["host0", "host1"]
            assert standby.current_epoch().gen == 1
            epoch = standby.propose_epoch(reason="failover")
            wall = time.perf_counter() - t0
            assert epoch.gen == 2 and epoch.hosts == (
                "host0",
                "host1",
            ), epoch
            # split-brain counter-proof: the zombie acts at term 1
            try:
                leader._put("fleet/members", {})
                raise AssertionError("zombie write was not fenced")
            except StaleTermError:
                pass
            info = kv.lease_info(fleet.LEASE_NAME)
            assert info["fenced_writes"] >= 1, info
            standby.stop()
            return wall
        finally:
            server.shutdown()

    rows = []
    for ttl in ttls:
        kills = [one_trial(ttl, release=False) for _ in range(trials)]
        clean = one_trial(ttl, release=True)
        med = statistics.median(kills)
        # the acceptance gate: a crashed coordinator costs at most
        # two TTLs of headless fleet (in practice ~1x: lease residue
        # at kill + the standby's poll cadence of TTL/4)
        assert med < 2.0 * ttl, (med, ttl)
        rows.append(
            {
                "lease_ttl_s": ttl,
                "kill_failover_walls_s": [round(w, 3) for w in kills],
                "kill_failover_median_s": round(med, 3),
                "clean_handover_wall_s": round(clean, 3),
                "median_wall_over_ttl": round(med / ttl, 2),
            }
        )

    report = {
        "metric": "fleet_chaos_failover",
        "failover_by_ttl": rows,
        "budget": "median kill-failover wall < 2x lease TTL",
        "fenced_write_proof": (
            "every trial: the killed leader's term-1 write raised "
            "StaleTermError and incremented the store's fenced count"
        ),
        "config": {
            "hosts": 2,
            "trials_per_ttl": trials,
            "fault_family": [
                "kv_drop:op@K",
                "kv_delay:ms@K",
                "partition_host:H@K",
                "kill_coordinator:@K",
            ],
        },
        "note": (
            "clean handover (lease released) is TTL-independent — "
            "headless time after a crash is dominated by the lease "
            "residue, so the TTL knob trades steady-state renew "
            "traffic against worst-case failover wall"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_fleetobs_worker():
    """Subprocess entry for the --fleetobs lane (one learner host of a
    2-host gloo CPU fleet). Same rendezvous → epoch → fixed-seed
    lockstep-learn protocol as the --fleet lane, but the variable
    under test is the fleetview plane itself: with
    ``RAY_TPU_FLEETOBS_ON=1`` every host runs a periodic
    ``HostExporter`` and rank 0 additionally runs the subscribing
    ``FleetAggregator`` — the exact coordinator-side topology of
    docs/observability.md "Fleet view". Each rank prints one
    ``FLEETOBSBENCH {json}`` line with its step walls and the
    per-step ``total_loss`` stream (bitwise parity across the A/B is
    asserted by the driver: observation must not perturb training)."""
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")
    import gymnasium as gym

    from ray_tpu import fleet
    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.data.sample_batch import SampleBatch
    from ray_tpu.parallel import distributed as dist

    rank = int(os.environ["RAY_TPU_PROCESS_ID"])
    world = int(os.environ["RAY_TPU_NUM_PROCESSES"])
    obs_on = os.environ.get("RAY_TPU_FLEETOBS_ON") == "1"
    if world > 1:
        dist.initialize()

    kv = fleet.KVClient(os.environ["RAY_TPU_KV_ADDRESS"])
    coord = fleet.FleetCoordinator(kv) if rank == 0 else None
    agent = fleet.HostAgent(
        kv, f"host{rank}", rank_hint=rank, heartbeat_interval=0.5
    )
    agent.join()
    if rank == 0:
        coord.wait_for_members(world, timeout=60.0)
        coord.propose_epoch(reason="bootstrap")
    epoch1 = agent.wait_for_epoch(1)
    mesh = fleet.epoch_mesh(epoch1)

    exporter = aggregator = None
    if obs_on:
        from ray_tpu.telemetry.fleetview import (
            FleetAggregator,
            HostExporter,
        )

        if rank == 0:
            aggregator = FleetAggregator(
                kv=kv, publish_aggregate=False
            )
        # short interval so the periodic publish actually fires
        # several times inside the timed window (the overhead under
        # measurement is the steady-state one, not a single flush)
        exporter = HostExporter(kv, f"host{rank}", interval=0.2)

    B = 64
    config = {
        "_mesh": mesh,
        "model": {"fcnet_hiddens": [32, 32]},
        "train_batch_size": B,
        "sgd_minibatch_size": 32,
        "num_sgd_iter": 2,
        "lr": 3e-4,
        "seed": 0,
    }
    obs_space = gym.spaces.Box(-1.0, 1.0, (16,), np.float32)
    act_space = gym.spaces.Discrete(4)
    policy = PPOJaxPolicy(obs_space, act_space, config)
    rng = np.random.default_rng(7)
    host = {
        SampleBatch.OBS: rng.standard_normal((B, 16)).astype(
            np.float32
        ),
        SampleBatch.ACTIONS: rng.integers(0, 4, B).astype(np.int64),
        SampleBatch.ACTION_LOGP: np.full(B, -1.4, np.float32),
        SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal(
            (B, 4)
        ).astype(np.float32),
        SampleBatch.ADVANTAGES: rng.standard_normal(B).astype(
            np.float32
        ),
        SampleBatch.VALUE_TARGETS: rng.standard_normal(B).astype(
            np.float32
        ),
    }
    tree, bsize = policy.prepare_batch(SampleBatch(host))
    global_batch = {
        k: sharding_lib.put_global(v, policy.data_sharding)
        for k, v in tree.items()
    }
    policy.learn_on_device_batch(global_batch, bsize)  # compile
    walls, losses = [], []
    for _ in range(12):
        t0 = time.perf_counter()
        stats = policy.learn_on_device_batch(global_batch, bsize)
        walls.append(time.perf_counter() - t0)
        losses.append(float(stats["total_loss"]))
    steps_per_s = B / float(np.median(walls))

    hosts_in_exposition = []
    if obs_on:
        exporter.flush()  # final snapshot so the merge sees this run
        exporter.stop()
        if aggregator is not None:
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                hosts_in_exposition = aggregator.hosts()
                if len(hosts_in_exposition) >= world:
                    break
                time.sleep(0.1)
            text = aggregator.merged_exposition()
            for h in hosts_in_exposition:
                assert f'host="{h}"' in text, (h, text[:400])
            aggregator.stop()
    print(
        "FLEETOBSBENCH "
        + json.dumps(
            {
                "rank": rank,
                "fleetobs_on": obs_on,
                "steps_per_s": round(steps_per_s, 1),
                "median_step_wall_s": float(np.median(walls)),
                "losses": losses,
                "hosts_in_exposition": sorted(hosts_in_exposition),
            }
        )
    )
    agent.barrier("fleetobs_done", epoch1)
    agent.stop()
    if coord is not None:
        coord.stop()


def bench_fleetobs(out_path=None):
    """Fleet-observability overhead A/B (docs/observability.md "Fleet
    view"): the SAME fixed-seed 2-host gloo lockstep learn, once bare
    and once with the full fleetview plane live (per-host periodic
    ``HostExporter`` + rank-0 subscribing ``FleetAggregator``).
    Reports

      - aggregator_overhead_pct: median-step-wall delta, budget < 2%;
      - losses_bitwise_identical: the per-step ``total_loss`` stream
        must match bit for bit across the A/B on every rank —
        observation reads training state, never perturbs it;
      - hosts_in_exposition: both hosts must appear (``host=``-labeled)
        in the merged exposition produced during the run.

    Writes benchmarks/e2e/fleet_observability.json."""
    import os
    import socket
    import subprocess

    from ray_tpu.fleet import KVServer

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/fleet_observability.json"
    world = 2

    def run(obs_on):
        kv = KVServer(host="127.0.0.1")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coord_port = s.getsockname()[1]
        env_base = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "RAY_TPU_NUM_PROCESSES": str(world),
            "RAY_TPU_KV_ADDRESS": f"127.0.0.1:{kv.port}",
            "RAY_TPU_COORDINATOR": f"127.0.0.1:{coord_port}",
            "RAY_TPU_FLEETOBS_ON": "1" if obs_on else "0",
        }
        procs = []
        for rank in range(world):
            env = {**env_base, "RAY_TPU_PROCESS_ID": str(rank)}
            procs.append(
                subprocess.Popen(
                    [sys.executable, __file__, "--fleetobs-worker"],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=300)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            kv.shutdown()
        recs = {}
        for rank, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0:
                raise RuntimeError(
                    f"fleetobs bench rank {rank} failed:\n{out}"
                )
            for line in out.splitlines():
                if line.startswith("FLEETOBSBENCH "):
                    recs[rank] = json.loads(
                        line[len("FLEETOBSBENCH ") :]
                    )
        if len(recs) != world:
            raise RuntimeError(
                f"missing FLEETOBSBENCH lines: {sorted(recs)}"
            )
        return recs

    off = run(obs_on=False)
    on = run(obs_on=True)

    overhead_pct = round(
        100.0
        * (on[0]["median_step_wall_s"] - off[0]["median_step_wall_s"])
        / off[0]["median_step_wall_s"],
        2,
    )
    bitwise = all(
        on[r]["losses"] == off[r]["losses"] for r in range(world)
    )
    report = {
        "metric": "fleet_observability_overhead",
        "steps_per_s": {
            "fleetobs_off": off[0]["steps_per_s"],
            "fleetobs_on": on[0]["steps_per_s"],
        },
        "median_step_wall_s": {
            "fleetobs_off": off[0]["median_step_wall_s"],
            "fleetobs_on": on[0]["median_step_wall_s"],
        },
        "aggregator_overhead_pct": overhead_pct,
        "overhead_budget_pct": 2.0,
        "losses_bitwise_identical": bitwise,
        "hosts_in_exposition": on[0]["hosts_in_exposition"],
        "config": {
            "world": world,
            "devices_per_host": 2,
            "train_batch_size": 64,
            "timed_steps": 12,
            "exporter_interval_s": 0.2,
            "collectives": "gloo (CPU stand-in for DCN)",
        },
        "note": (
            "the exporter threads publish snapshots on their own "
            "cadence while the lockstep learn runs; overhead is the "
            "median per-step wall delta, so one-off flush costs and "
            "the aggregator's subscriber thread (rank 0 only) are "
            "both in frame — the bitwise loss check is the hard "
            "gate, the percentage is the budget headline"
        ),
    }
    if not bitwise:
        raise RuntimeError(
            "fleetview observation perturbed training: per-step "
            "losses differ between fleetobs on/off"
        )
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_jax_env(out_path=None, iters=3, n_envs=32, t_rollout=64):
    """Rollout-lane A/B (docs/pipeline.md "two rollout lanes"): the
    SAME JaxVectorEnv (CartPoleJax), same fixed seed, same total env
    steps, three lanes through the full PPO Algorithm —

      - actor:  the CPU-actor lane (local SyncSampler drives the env
        through the jitted adapter; train batch crosses H2D per iter);
      - device: JAX-native rollouts on the learner mesh, rollout and
        learn as separate dispatches (env_backend="jax",
        jax_fused_rollout=False);
      - fused:  rollout(T) + GAE + the SGD nest as ONE dispatched
        program (the superstep's rollout feed) — per-iteration H2D is
        the key stacks only.

    Writes benchmarks/e2e/jax_env_ab.json with steps/s, per-iteration
    rollout H2D bytes by lane, and the fused-vs-actor speedup (the
    acceptance criterion is ≥ 4× at this geometry)."""
    from ray_tpu.algorithms.ppo.ppo import PPOConfig
    from ray_tpu.sharding.compile import compile_stats
    from ray_tpu.telemetry import metrics as telemetry_metrics

    out_path = out_path or "benchmarks/e2e/jax_env_ab.json"
    steps_per_iter = n_envs * t_rollout

    def build(backend, fused=True):
        cfg = (
            PPOConfig()
            .environment(
                "CartPoleJax-v0",
                env_backend=backend,
                jax_fused_rollout=fused,
            )
            .rollouts(
                num_rollout_workers=0,
                num_envs_per_worker=n_envs,
                rollout_fragment_length=t_rollout,
            )
            .training(
                train_batch_size=steps_per_iter,
                sgd_minibatch_size=512,
                num_sgd_iter=4,
                lr=3e-4,
                model={"fcnet_hiddens": [64, 64]},
            )
            .debugging(seed=0)
        )
        cfg.lambda_ = 0.95
        return cfg.build()

    def run(backend, fused=True):
        algo = build(backend, fused)
        try:
            algo.train()  # warmup: compiles + first episode stream
            h2d0 = telemetry_metrics.h2d_bytes_by_path()
            traces0 = compile_stats()["traces"]
            t0 = time.perf_counter()
            for _ in range(iters):
                r = algo.train()
            wall = time.perf_counter() - t0
            h2d1 = telemetry_metrics.h2d_bytes_by_path()
            d = {
                p: h2d1.get(p, 0.0) - h2d0.get(p, 0.0)
                for p in set(h2d0) | set(h2d1)
            }
            rollout_bytes = (
                d.get("rollout", 0.0)
                if backend == "jax"
                else d.get("learn", 0.0) + d.get("feeder", 0.0)
            )
            return {
                "steps_per_s": round(iters * steps_per_iter / wall, 1),
                "wall_s_per_iteration": round(wall / iters, 4),
                "rollout_h2d_bytes_per_iteration": round(
                    rollout_bytes / iters, 1
                ),
                "recompiles_in_timed_window": (
                    compile_stats()["traces"] - traces0
                ),
                "episode_reward_mean": r.get("episode_reward_mean"),
            }
        finally:
            algo.cleanup()

    report = {
        "metric": "jax_env_rollout_lane_ab",
        "env": "CartPoleJax-v0",
        "geometry": {
            "num_envs": n_envs,
            "rollout_length": t_rollout,
            "env_steps_per_iteration": steps_per_iter,
            "timed_iterations": iters,
        },
        "actor_lane": run("actor"),
        "device_lane": run("jax", fused=False),
        "fused_lane": run("jax", fused=True),
    }
    report["speedup_fused_vs_actor"] = round(
        report["fused_lane"]["steps_per_s"]
        / report["actor_lane"]["steps_per_s"],
        1,
    )
    report["speedup_device_vs_actor"] = round(
        report["device_lane"]["steps_per_s"]
        / report["actor_lane"]["steps_per_s"],
        1,
    )
    import os

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_serve(
    out_path=None,
    n_requests=512,
    clients_list=(1, 8, 32, 128),
    max_batch_size=128,
):
    """Inference-plane A/B (docs/serving.md): continuous batching vs
    naive per-request inference, same fixed-seed request stream on
    both sides, at 1/8/32/128 concurrent clients.

      - per_request: one ``compute_actions`` dispatch per request (the
        serve core's one-call-per-actor-call shape), clients serialized
        on the policy exactly like calls arriving at one replica;
      - batched: the ``BatchedPolicyServer`` coalesces the SAME stream
        into bucket-padded fused forwards (greedy flush, donated rng
        carry, zero recompiles after warmup — asserted off
        ``compile_stats``).

    Acceptance (ISSUE 9): >= 4x throughput at >= 32 clients, batched
    p99 latency no worse than 2x the per-request p99, zero recompiles
    in the timed window, and batched results bit-identical to the
    sequential reference. Writes benchmarks/e2e/serve_ab.json."""
    import threading

    import gymnasium as gym
    import jax

    from ray_tpu import sharding as sharding_lib
    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
    from ray_tpu.serve.policy_server import (
        BatchedPolicyServer,
        default_buckets,
    )
    from ray_tpu.sharding.compile import compile_stats

    out_path = out_path or "benchmarks/e2e/serve_ab.json"
    obs_space = gym.spaces.Box(-1.0, 1.0, (8,), np.float32)
    act_space = gym.spaces.Discrete(4)

    def make_policy():
        return PPOJaxPolicy(
            obs_space,
            act_space,
            {
                "seed": 0,
                "lr": 3e-4,
                "train_batch_size": 64,
                "sgd_minibatch_size": 64,
                "num_sgd_iter": 1,
                "model": {"fcnet_hiddens": [64, 64]},
                # bitwise parity is a 1-shard-mesh contract
                "_mesh": sharding_lib.get_mesh(
                    devices=jax.devices()[:1]
                ),
            },
        )

    rng = np.random.default_rng(0)
    obs_stream = rng.uniform(-1, 1, (n_requests, 8)).astype(
        np.float32
    )

    def run_clients(n_clients, issue):
        latencies = np.zeros(n_requests)
        next_i = [0]
        ilock = threading.Lock()

        def worker():
            while True:
                with ilock:
                    i = next_i[0]
                    if i >= n_requests:
                        return
                    next_i[0] += 1
                t0 = time.perf_counter()
                issue(i)
                latencies[i] = time.perf_counter() - t0

        threads = [
            threading.Thread(target=worker)
            for _ in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {
            "throughput_rps": round(n_requests / wall, 1),
            "wall_s": round(wall, 4),
            "p50_ms": round(
                float(np.percentile(latencies, 50)) * 1e3, 3
            ),
            "p99_ms": round(
                float(np.percentile(latencies, 99)) * 1e3, 3
            ),
        }

    # -- per-request side (explore=False: rng-independent, so the
    # thread interleave can't change results)
    naive = make_policy()
    naive_lock = threading.Lock()
    naive_actions = np.zeros(n_requests, np.int64)
    naive.compute_actions(obs_stream[:1], explore=False)  # compile

    def issue_naive(i):
        with naive_lock:
            a, _, _ = naive.compute_actions(
                obs_stream[i][None], explore=False
            )
        naive_actions[i] = a[0]

    # -- batched side: ONE server reused across the whole sweep
    server = BatchedPolicyServer(
        make_policy(),
        max_batch_size=max_batch_size,
        batch_wait_timeout_s=0.001,
        explore=False,
        start=False,
    )
    server.warmup()
    server.start()
    batched_actions = np.zeros(n_requests, np.int64)
    batched_logp = np.zeros(n_requests, np.float32)

    def issue_batched(i):
        a, ex = server.submit(obs_stream[i]).result(120.0)
        batched_actions[i] = a
        batched_logp[i] = ex["action_logp"]

    curve = []
    traces0 = compile_stats()["traces"]
    for c in clients_list:
        per_request = run_clients(c, issue_naive)
        batches0 = server.batches_total
        rows0 = server.batch_rows_total
        batched = run_clients(c, issue_batched)
        nb = server.batches_total - batches0
        batched["mean_batch_rows"] = round(
            (server.batch_rows_total - rows0) / max(1, nb), 2
        )
        entry = {
            "clients": c,
            "per_request": per_request,
            "batched": batched,
            "speedup": round(
                batched["throughput_rps"]
                / per_request["throughput_rps"],
                2,
            ),
            "p99_ratio": round(
                batched["p99_ms"] / per_request["p99_ms"], 2
            ),
        }
        curve.append(entry)
    recompiles = compile_stats()["traces"] - traces0

    # -- bitwise parity of the batched stream vs a fresh sequential
    # reference (same seed, same order)
    ref = make_policy()
    parity = True
    for i in range(n_requests):
        a, _, ex = ref.compute_actions(
            obs_stream[i][None], explore=False
        )
        if a[0] != batched_actions[i] or not np.array_equal(
            ex["action_logp"][0], batched_logp[i]
        ):
            parity = False
            break
    server.stop()

    wide = [e for e in curve if e["clients"] >= 32]
    report = {
        "metric": "serve_continuous_batching_ab",
        "n_requests": n_requests,
        "obs_dim": 8,
        "model": [64, 64],
        "max_batch_size": max_batch_size,
        "buckets": list(default_buckets(max_batch_size)),
        "curve": curve,
        "recompiles_in_timed_window": recompiles,
        "parity_bitwise": parity,
        "criteria": {
            "speedup_ge_4x_at_32plus_clients": all(
                e["speedup"] >= 4.0 for e in wide
            ),
            "p99_no_worse_than_2x": all(
                e["p99_ratio"] <= 2.0 for e in wide
            ),
            "zero_recompiles": recompiles == 0,
        },
    }
    import os

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_ingress(
    out_path=None,
    n_requests=256,
    clients_list=(1, 8, 32),
    max_batch_size=32,
):
    """Serving front-door A/B (docs/serving.md "the front door"),
    everything over REAL sockets:

      - per_request: the serve-core HTTP path — one request per
        replica actor call (``serve.run(policy_deployment(...),
        http_host=...)`` with ``max_batch_size=1``), exactly the
        pre-ingress architecture;
      - ingress: ``PolicyIngress`` → ``CoalescingRouter`` → one
        in-process ``BatchedPolicyServer`` replica restored from the
        SAME checkpoint — requests coalesce across connections into
        power-of-two buckets before dispatch.

    Acceptance (ISSUE 14): ingress throughput >= 4x per-request at
    32 clients, bitwise response parity, 0 recompiles in the timed
    window.
    Writes benchmarks/e2e/ingress_ab.json."""
    import os
    import shutil
    import tempfile
    import threading
    import urllib.request

    import ray_tpu as ray
    from ray_tpu.algorithms.ppo.ppo import PPO
    from ray_tpu.ingress import (
        CoalescingRouter,
        LocalReplica,
        PolicyIngress,
    )
    from ray_tpu.serve import serve
    from ray_tpu.serve.policy_server import (
        BatchedPolicyServer,
        policy_deployment,
        restore_policy,
    )
    from ray_tpu.sharding.compile import compile_stats

    out_path = out_path or "benchmarks/e2e/ingress_ab.json"
    workdir = tempfile.mkdtemp(prefix="ingress_bench_")
    ckpt_root = os.path.join(workdir, "ckpts")

    cfg = {
        "env": "CartPole-v1",
        "seed": 0,
        "num_workers": 0,
        "train_batch_size": 64,
        "sgd_minibatch_size": 64,
        "num_sgd_iter": 1,
        "lr": 3e-4,
        "model": {"fcnet_hiddens": [64, 64]},
    }
    algo = PPO(config=cfg)
    try:
        algo.save(os.path.join(ckpt_root, "checkpoint_000001"))
    finally:
        algo.cleanup()

    rng = np.random.default_rng(0)
    obs_stream = rng.uniform(
        -1.0, 1.0, (n_requests, 4)
    ).astype(np.float32)

    def post(url, payload, timeout=120.0, retries=3):
        import http.client

        req = urllib.request.Request(
            url,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        # the stdlib ThreadingHTTPServer on the per-request side
        # occasionally resets a fresh connection under rapid
        # open/close churn; a transient-layer retry keeps the A/B
        # about the serving architecture, not loopback TCP flakes
        # (retries stay inside the request's timed latency)
        for attempt in range(retries):
            try:
                with urllib.request.urlopen(
                    req, timeout=timeout
                ) as resp:
                    return json.loads(resp.read())
            except (
                ConnectionError,
                http.client.RemoteDisconnected,
            ):
                if attempt == retries - 1:
                    raise
                time.sleep(0.01 * (attempt + 1))

    def run_clients(full_url, n_clients):
        latencies = np.zeros(n_requests)
        results = [None] * n_requests
        errors = []
        next_i = [0]
        ilock = threading.Lock()

        def worker():
            while True:
                with ilock:
                    i = next_i[0]
                    if i >= n_requests:
                        return
                    next_i[0] += 1
                t0 = time.perf_counter()
                try:
                    out = post(
                        full_url, {"obs": obs_stream[i].tolist()}
                    )
                except Exception as e:
                    with ilock:
                        errors.append((i, repr(e)))
                    continue
                latencies[i] = time.perf_counter() - t0
                results[i] = out.get("result", out)

        threads = [
            threading.Thread(target=worker)
            for _ in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(
                f"{len(errors)} request(s) failed against "
                f"{full_url}; first: {errors[0]}"
            )
        return {
            "throughput_rps": round(n_requests / wall, 1),
            "wall_s": round(wall, 4),
            "p50_ms": round(
                float(np.percentile(latencies, 50)) * 1e3, 3
            ),
            "p99_ms": round(
                float(np.percentile(latencies, 99)) * 1e3, 3
            ),
        }, results

    # -- per-request side: the serve-core HTTP path ------------------
    serve.run(
        policy_deployment(
            ckpt_root,
            name="bench_naive",
            max_batch_size=1,
            watch=False,
        ),
        http_host="127.0.0.1",
    )
    naive_url = (
        f"http://127.0.0.1:{serve.http_port()}/bench_naive"
    )
    naive_curve = {}
    naive_results = None
    try:
        for c in clients_list:
            naive_curve[c], naive_results = run_clients(
                naive_url, c
            )
    finally:
        serve.shutdown()
        ray.shutdown()

    # -- ingress side: front door + router + batched replica ---------
    policy, prep, obs_filter, _info = restore_policy(ckpt_root)
    server = BatchedPolicyServer(
        policy,
        name="bench_ingress",
        max_batch_size=max_batch_size,
        batch_wait_timeout_s=0.002,
        explore=False,
        obs_filter=obs_filter,
        preprocessor=prep,
        start=False,
    )
    server.warmup()
    server.start()
    router = CoalescingRouter(
        "bench",
        [LocalReplica(server)],
        max_batch_size=max_batch_size,
        batch_wait_timeout_s=0.002,
    )
    ingress = PolicyIngress().start()
    ingress.add_policy("bench", router)
    ingress_curve = {}
    ingress_results = None
    traces0 = compile_stats()["traces"]
    try:
        for c in clients_list:
            ingress_curve[c], ingress_results = run_clients(
                ingress.url + "/v1/policy/bench/actions", c
            )
        recompiles = compile_stats()["traces"] - traces0
        router_stats = router.stats()
    finally:
        ingress.stop()
        router.stop()
        server.stop()

    parity = all(
        int(a["action"]) == int(b["action"])
        for a, b in zip(ingress_results, naive_results)
    )

    curve = [
        {
            "clients": c,
            "per_request": naive_curve[c],
            "ingress": ingress_curve[c],
            "speedup": round(
                ingress_curve[c]["throughput_rps"]
                / naive_curve[c]["throughput_rps"],
                2,
            ),
        }
        for c in clients_list
    ]
    wide = [e for e in curve if e["clients"] >= 32]
    report = {
        "metric": "ingress_front_door_ab",
        "n_requests": n_requests,
        "model": [64, 64],
        "max_batch_size": max_batch_size,
        "transport": "real sockets (HTTP/1.1, keep-alive)",
        "curve": curve,
        "router": {
            "batches_total": router_stats["batches_total"],
            "mean_merged_rows": round(
                router_stats["mean_merged_rows"], 2
            ),
        },
        "recompiles_in_timed_window": recompiles,
        "parity_bitwise": parity,
        "criteria": {
            "speedup_ge_4x_at_32_clients": all(
                e["speedup"] >= 4.0 for e in wide
            ),
            "zero_recompiles": recompiles == 0,
            "parity_bitwise": parity,
        },
    }
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_flood(out_path=None, smoke=False):
    """OPEN-loop flood harness for the horizontal front door
    (docs/serving.md "Scaling the front door"): find the saturation
    knee of 1 vs N ingress worker processes and prove the overload
    contract past it.

    Closed-loop clients (bench_ingress) can never overload a server —
    they wait for answers before sending more. This harness fires
    requests on a fixed ARRIVAL SCHEDULE regardless of completions
    (Poisson inter-arrivals per offered rate, plus a recorded bursty
    on/off stream), with a deadline mix riding along, and sweeps
    offered rates upward until goodput stops tracking offered load:

      - knee = highest offered rate whose goodput (200s inside their
        deadline) stays >= 90% of offered;
      - at 2x the knee EVERY response must be a 200-inside-deadline,
        429 (inflight/quota), 503 (queue-wait shed) or 504 (deadline)
        — never a hang, never a 200 past its deadline;
      - both configs serve the SAME checkpoint (fixed-seed obs
        stream, bitwise parity across configs, zero recompiles per
        worker after its warmup, heartbeat-asserted).

    Each config is a real ``IngressSupervisor`` bank on one shared
    port (SO_REUSEPORT where available). Writes
    benchmarks/e2e/flood.json. NOTE the honesty caveat in the report:
    on a single-core host N worker processes time-slice one CPU, so
    the knee ratio measures isolation overhead, not the >= 2.5x
    scale-out a multi-core front door shows.

    ``--smoke`` shrinks rates/durations for the tier-1 test."""
    import os
    import shutil
    import socket as socket_mod
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from ray_tpu.ingress import IngressSupervisor
    from ray_tpu.telemetry import metrics as telemetry_metrics

    out_path = out_path or "benchmarks/e2e/flood.json"
    workdir = tempfile.mkdtemp(prefix="flood_bench_")
    ckpt_root = os.path.join(workdir, "ckpts")
    repo = os.path.dirname(os.path.abspath(__file__))
    max_batch_size = 16

    if smoke:
        workers_list = (1, 2)
        rates = [10.0, 25.0]
        duration_s = 1.2
        n_obs = 32
        n_senders = 8
        overload_factor = 1.5
        run_recorded = False
        parity_n = 16
    else:
        workers_list = (1, 3)
        rates = [
            60.0, 120.0, 240.0, 480.0, 960.0, 1920.0, 3840.0,
        ]
        duration_s = 3.0
        n_obs = 128
        n_senders = 64
        overload_factor = 2.0
        run_recorded = True
        parity_n = 64

    # the checkpoint is built in a SUBPROCESS so this process never
    # initializes the XLA client before forking worker banks
    # (fork-after-jax-init is the classic deadlock)
    seed_code = (
        "import sys\n"
        "from ray_tpu.algorithms.ppo.ppo import PPO\n"
        "ckpt = sys.argv[1]\n"
        "cfg = {'env': 'CartPole-v1', 'seed': 0, 'num_workers': 0,\n"
        "       'train_batch_size': 64, 'sgd_minibatch_size': 64,\n"
        "       'num_sgd_iter': 1, 'lr': 3e-4,\n"
        "       'model': {'fcnet_hiddens': [64, 64]}}\n"
        "algo = PPO(config=cfg)\n"
        "algo.save(ckpt)\n"
        "algo.cleanup()\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + ":" + env.get("PYTHONPATH", "")
    subprocess.run(
        [
            sys.executable, "-c", seed_code,
            os.path.join(ckpt_root, "checkpoint_000001"),
        ],
        check=True, env=env, cwd=repo,
    )

    rng = np.random.default_rng(0)
    obs_stream = rng.uniform(-1.0, 1.0, (n_obs, 4)).astype(
        np.float32
    )
    # the deadline mix every run carries: most requests unbounded, a
    # slice with a meetable budget, a slice tight enough to expire
    # under congestion (ms, weight)
    deadline_mix = [(None, 0.6), (400.0, 0.25), (120.0, 0.15)]

    def worker_init(ctx):
        # runs INSIDE each forked ingress worker: full replica stack
        # per process, restored from the shared checkpoint
        from ray_tpu.ingress import CoalescingRouter, LocalReplica
        from ray_tpu.serve.policy_server import (
            BatchedPolicyServer,
            restore_policy,
        )
        from ray_tpu.sharding.compile import compile_stats

        policy, prep, obs_filter, _ = restore_policy(ckpt_root)
        server = BatchedPolicyServer(
            policy,
            name="flood",
            max_batch_size=max_batch_size,
            batch_wait_timeout_s=0.002,
            explore=False,
            obs_filter=obs_filter,
            preprocessor=prep,
            start=False,
        )
        server.warmup()
        server.start()
        router = CoalescingRouter(
            "flood",
            [LocalReplica(server)],
            max_batch_size=max_batch_size,
            batch_wait_timeout_s=0.002,
        )
        ctx.ingress.add_policy("flood", router)
        traces0 = compile_stats()["traces"]

        def extra_stats():
            return {
                "recompiles": compile_stats()["traces"] - traces0,
            }

        ctx.ingress.extra_stats = extra_stats

    def poisson_schedule(rate, dur, seed):
        r = np.random.default_rng(seed)
        gaps = r.exponential(1.0 / rate, int(rate * dur * 2) + 16)
        t = np.cumsum(gaps)
        return t[t < dur].tolist()

    def recorded_schedule(rate, dur, seed):
        # the "recorded stream": a fixed-seed bursty on/off arrival
        # trace (0.5 s periods, 3x the mean rate while on, 0.2x
        # while off) — the shape production front doors actually see
        r = np.random.default_rng(seed)
        out, t, period = [], 0.0, 0.5
        while t < dur:
            on = int(t / period) % 2 == 0
            cur = rate * (3.0 if on else 0.2)
            t += float(r.exponential(1.0 / cur))
            if t < dur:
                out.append(t)
        return out

    def run_flood(url, schedule, label, nominal_rps=None):
        """Fire the schedule OPEN-loop; classify every response."""
        n = len(schedule)
        dl_r = np.random.default_rng(1)
        choices = [d for d, _ in deadline_mix]
        weights = [w for _, w in deadline_mix]
        deadlines = [
            choices[dl_r.choice(len(choices), p=weights)]
            for _ in range(n)
        ]
        counts = {
            k: 0
            for k in (
                "ok", "late_200", "shed_429", "shed_503",
                "expired_504", "hang", "error",
            )
        }
        ok_lat = []
        lock = threading.Lock()
        idx = [0]
        t_start = time.perf_counter() + 0.1

        import http.client
        from urllib.parse import urlsplit

        parts = urlsplit(url)
        host, port = parts.hostname, parts.port
        path = parts.path

        def sender():
            # each sender owns a persistent keep-alive connection:
            # timing starts at the request WRITE (the deadline budget
            # the payload declares), not at a per-request TCP connect
            # whose accept-queue wait the server cannot observe
            conn = [None]

            def send_one(body):
                for attempt in (0, 1):
                    try:
                        if conn[0] is None:
                            conn[0] = http.client.HTTPConnection(
                                host, port, timeout=10.0
                            )
                        conn[0].request(
                            "POST",
                            path,
                            body,
                            {"Content-Type": "application/json"},
                        )
                        resp = conn[0].getresponse()
                        resp.read()
                        if (
                            resp.headers.get("Connection", "")
                            .lower()
                            == "close"
                        ):
                            conn[0].close()
                            conn[0] = None
                        return resp.status
                    except socket_mod.timeout:
                        if conn[0] is not None:
                            conn[0].close()
                            conn[0] = None
                        return "hang"
                    except Exception:
                        # a dropped keep-alive connection: retry
                        # once on a fresh one before calling it an
                        # error
                        if conn[0] is not None:
                            conn[0].close()
                            conn[0] = None
                        if attempt == 1:
                            return "error"

            while True:
                with lock:
                    i = idx[0]
                    if i >= n:
                        if conn[0] is not None:
                            conn[0].close()
                        return
                    idx[0] += 1
                delay = (
                    t_start + schedule[i] - time.perf_counter()
                )
                if delay > 0:
                    time.sleep(delay)
                dl = deadlines[i]
                payload = {
                    "obs": obs_stream[i % n_obs].tolist()
                }
                if dl is not None:
                    payload["deadline_ms"] = dl
                body = json.dumps(payload).encode()
                t0 = time.perf_counter()
                status = send_one(body)
                lat = time.perf_counter() - t0
                if status == 200:
                    # a 200 must land INSIDE its deadline (100 ms
                    # slack for time the request sat in transport
                    # buffers before the server's own deadline
                    # clock could start — everything the server CAN
                    # observe as late it already 504s)
                    if (
                        dl is not None
                        and lat * 1e3 > dl + 100.0
                    ):
                        kind = "late_200"
                    else:
                        kind = "ok"
                elif status in ("hang", "error"):
                    kind = status
                    lat = None
                else:
                    kind = {
                        429: "shed_429",
                        503: "shed_503",
                        504: "expired_504",
                    }.get(status, "error")
                    lat = None
                with lock:
                    counts[kind] += 1
                    if kind == "ok" and lat is not None:
                        ok_lat.append(lat)
                telemetry_metrics.inc_flood_response(kind)

        threads = [
            threading.Thread(target=sender, name=f"flood_{j}")
            for j in range(n_senders)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = max(
            time.perf_counter() - t_start, schedule[-1] if n else 0.0
        )
        offered = n / wall if wall else 0.0
        goodput = counts["ok"] / wall if wall else 0.0
        telemetry_metrics.set_flood_offered_rps(offered)
        telemetry_metrics.set_flood_goodput_rps(goodput)
        shed = (
            counts["shed_429"]
            + counts["shed_503"]
            + counts["expired_504"]
        )
        arr = np.asarray(ok_lat) if ok_lat else None
        return {
            "label": label,
            "n_requests": n,
            "wall_s": round(wall, 3),
            "nominal_rps": nominal_rps,
            # the sender pool has its own ceiling: offered falling
            # well short of nominal means the GENERATOR saturated,
            # not the server — the knee is a lower bound there
            "generator_capped": (
                nominal_rps is not None
                and offered < 0.8 * nominal_rps
            ),
            "offered_rps": round(offered, 1),
            "goodput_rps": round(goodput, 1),
            "p50_ms": (
                round(float(np.percentile(arr, 50)) * 1e3, 3)
                if arr is not None
                else None
            ),
            "p99_ms": (
                round(float(np.percentile(arr, 99)) * 1e3, 3)
                if arr is not None
                else None
            ),
            "shed_fraction": round(shed / n, 4) if n else 0.0,
            "counts": dict(counts),
        }

    def collect_worker_extras(sup):
        extras = []
        for _, stats in sorted(sup.worker_stats().items()):
            if stats and stats.get("extra"):
                extras.append(stats["extra"])
        return extras

    def parity_pass(url):
        """Closed-loop, sequential: the fixed-seed obs stream's
        actions, for cross-config bitwise comparison."""
        actions = []
        for i in range(parity_n):
            req = urllib.request.Request(
                url,
                data=json.dumps(
                    {"obs": obs_stream[i % n_obs].tolist()}
                ).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30.0) as r:
                actions.append(
                    int(json.loads(r.read())["action"])
                )
        return actions

    configs = {}
    parity_actions = {}
    for n_workers in workers_list:
        sup = IngressSupervisor(
            num_workers=n_workers,
            worker_init=worker_init,
            heartbeat_s=0.25,
            metrics_interval_s=1.0,
            # per-PROCESS budgets: the bank's effective in-flight
            # budget scales with the worker count, which is the
            # point — and small enough that the sender pool can
            # actually overrun it at 2x knee (429s are reachable)
            ingress_kwargs={
                "max_inflight": 32,
                "shed_queue_wait_s": 0.2,
            },
        )
        sup.start(timeout_s=600.0)
        url = sup.url + "/v1/policy/flood/actions"
        try:
            parity_actions[n_workers] = parity_pass(url)
            sweep = []
            knee = None
            saturated_streak = 0
            for rate in rates:
                entry = run_flood(
                    url,
                    poisson_schedule(rate, duration_s, seed=3),
                    f"poisson@{rate:g}",
                    nominal_rps=rate,
                )
                sweep.append(entry)
                if (
                    entry["goodput_rps"]
                    >= 0.9 * entry["offered_rps"]
                ):
                    knee = entry["offered_rps"]
                    if entry["generator_capped"]:
                        break
                    saturated_streak = 0
                else:
                    saturated_streak += 1
                    # past the knee twice: the curve is told, stop
                    if saturated_streak >= 2:
                        break
            if knee is None:  # saturated from the first rate
                knee = max(e["goodput_rps"] for e in sweep)
            overload = run_flood(
                url,
                poisson_schedule(
                    overload_factor * knee, duration_s, seed=5
                ),
                f"overload@{overload_factor:g}x_knee",
                nominal_rps=overload_factor * knee,
            )
            c = overload["counts"]
            contract_ok = (
                c["hang"] == 0
                and c["late_200"] == 0
                and c["error"] <= max(2, overload["n_requests"] // 100)
            )
            recorded = None
            if run_recorded:
                recorded = run_flood(
                    url,
                    recorded_schedule(knee, duration_s, seed=11),
                    "recorded_burst@knee",
                    nominal_rps=knee,
                )
            # wait one heartbeat so extra stats reflect the flood
            time.sleep(0.6)
            extras = collect_worker_extras(sup)
            configs[str(n_workers)] = {
                "num_workers": n_workers,
                "reuseport": sup.stats()["reuseport"],
                "sweep": sweep,
                "knee_rps": round(knee, 1),
                "overload": overload,
                "overload_contract_ok": contract_ok,
                "recorded": recorded,
                "workers": extras,
            }
        finally:
            sup.stop()

    lo, hi = str(workers_list[0]), str(workers_list[-1])
    knee_lo = configs[lo]["knee_rps"]
    knee_hi = configs[hi]["knee_rps"]
    scale_ratio = round(knee_hi / max(knee_lo, 1e-9), 2)
    parity = (
        parity_actions[workers_list[0]]
        == parity_actions[workers_list[-1]]
    )
    all_extras = [
        e for c in configs.values() for e in c["workers"]
    ]
    zero_recompiles = bool(all_extras) and all(
        e["recompiles"] == 0 for e in all_extras
    )
    report = {
        "metric": "ingress_flood",
        "smoke": smoke,
        "model": [64, 64],
        "max_batch_size": max_batch_size,
        "deadline_mix_ms": deadline_mix,
        "transport": "real sockets (HTTP/1.1), open-loop senders",
        "cpu_count": os.cpu_count(),
        "configs": configs,
        "scaleout": {
            "workers": [workers_list[0], workers_list[-1]],
            "knee_rps": [knee_lo, knee_hi],
            "ratio": scale_ratio,
            # True when the N-worker knee is a LOWER bound because
            # the load generator saturated before the bank did
            "hi_knee_generator_capped": any(
                e.get("generator_capped")
                for e in configs[hi]["sweep"]
            ),
        },
        "parity_bitwise": parity,
        "criteria": {
            "knee_found_per_config": all(
                c["knee_rps"] > 0 for c in configs.values()
            ),
            "overload_contract_429_503_504": all(
                c["overload_contract_ok"]
                for c in configs.values()
            ),
            "parity_bitwise": parity,
            "zero_recompiles": zero_recompiles,
            "scaleout_knee_ge_2p5x": scale_ratio >= 2.5,
        },
        "caveats": [
            (
                f"host has {os.cpu_count()} CPU core(s): worker "
                "processes time-slice the same core, so the knee "
                "ratio here measures process-isolation overhead, "
                "not the multi-core scale-out the >=2.5x target "
                "describes; rerun on a multi-core front-door host "
                "for the headline number"
            )
        ]
        if (os.cpu_count() or 1) <= max(workers_list)
        else [],
    }
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_apex(out_path=None, iters=4):
    """Host sum tree vs device sum tree A/B at a training_intensity-
    heavy DQN geometry, plus the learn-while-rollout interleave A/B
    (docs/data_plane.md "device sum tree & sharded Ape-X"). Writes
    ``benchmarks/e2e/apex_device_ab.json``.

    Three sections:

    - ``tree_micro``: the sample+update wall of one fused K-window at
      the heavy geometry (capacity 2^17, B=512, K=8) — the superstep's
      draw schedule + PER refresh per window, excluding the in-scan
      row gather common to both planes. Host: K sequential numpy tree
      walks + K incremental tree writes. Device: ONE draw program +
      ONE stacked update program. Asserts ≥2× and 0 steady-state
      recompiles, and that the device sample path ships zero payload
      bytes H2D (telemetry-counted; the generator's raw uniform
      stream reports separately).
    - ``dqn_e2e``: fixed-seed DQN+PER on the fused jax rollout lane,
      training_intensity-heavy, host tree vs device tree — bitwise
      param parity plus per-iteration replay byte accounting.
    - ``interleave``: serial fill→learn vs learn-while-rollout on the
      same geometry, with the measured overlap fraction
      ((serial − interleaved) / min(rollout, learn) walls; ≈0 on this
      1-core container — the cadence exists for the mesh round)."""
    import os

    import jax

    from ray_tpu.execution.replay_buffer import (
        DevicePrioritizedReplayBuffer,
    )
    from ray_tpu.sharding.compile import compile_stats
    from ray_tpu.telemetry import metrics as telemetry_metrics

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/apex_device_ab.json"

    # ---- 1. tree micro A/B: sample+update wall per fused K-window ----
    CAP, BS, K = 1 << 17, 512, 8
    rng = np.random.default_rng(0)

    def build_buf(device_tree):
        buf = DevicePrioritizedReplayBuffer(
            capacity=CAP, alpha=0.6, seed=1,
            device_tree=device_tree,
            label=f"bench_apex_{'dev' if device_tree else 'host'}",
        )
        chunk = {
            "obs": rng.standard_normal((4096, 16)).astype(np.float32),
            "actions": rng.integers(0, 4, 4096).astype(np.int32),
            "rewards": rng.standard_normal(4096).astype(np.float32),
        }
        for _ in range(CAP // 4096):
            buf.add_tree({k: v for k, v in chunk.items()})
        return buf

    td_mat = (rng.standard_normal((K, BS)).astype(np.float32)) ** 2 + 0.01
    active = [True] * K

    def window(buf):
        if buf._dtree is not None:
            idx, _w = buf.draw_prioritized_sets_device(K, K, BS, 0.4)
            buf.refresh_priorities_stacked(idx, td_mat, active)
            jax.block_until_ready(buf._dtree.sum_value)
        else:
            idx, _w = buf.draw_prioritized_sets(K, BS, 0.4)
            for i in range(K):
                buf.update_priorities(idx[i], td_mat[i] + 1e-6)

    def timed(buf, reps=30):
        for _ in range(3):
            window(buf)  # warmup/compile
        sample_b = telemetry_metrics.h2d_bytes_by_path().get(
            "replay_sample", 0.0
        )
        traces0 = compile_stats()["traces"]
        t0 = time.perf_counter()
        for _ in range(reps):
            window(buf)
        wall = (time.perf_counter() - t0) / reps
        return {
            "wall_s_per_window": round(wall, 5),
            "recompiles_in_timed_window": (
                compile_stats()["traces"] - traces0
            ),
            "sample_payload_h2d_bytes": (
                telemetry_metrics.h2d_bytes_by_path().get(
                    "replay_sample", 0.0
                )
                - sample_b
            ),
        }

    host_buf, dev_buf = build_buf(False), build_buf(True)
    micro_host, micro_dev = timed(host_buf), timed(dev_buf)
    speedup = (
        micro_host["wall_s_per_window"]
        / micro_dev["wall_s_per_window"]
    )
    tree_micro = {
        "capacity": CAP,
        "batch": BS,
        "k": K,
        "host_tree": micro_host,
        "device_tree": micro_dev,
        "sample_update_speedup": round(speedup, 2),
        "criteria": {
            "speedup_ge_2x": speedup >= 2.0,
            "zero_recompiles": (
                micro_dev["recompiles_in_timed_window"] == 0
            ),
            "zero_sample_payload_h2d": (
                micro_dev["sample_payload_h2d_bytes"] == 0.0
            ),
        },
    }

    # ---- 2. fixed-seed DQN e2e: host tree vs device tree ----
    from ray_tpu.algorithms.dqn.dqn import DQNConfig

    def build_algo(device_tree, interleave=False):
        return (
            DQNConfig()
            .environment("CartPoleJax-v0", env_backend="jax")
            .rollouts(
                num_rollout_workers=0,
                rollout_fragment_length=8,
                num_envs_per_worker=8,
            )
            .training(
                train_batch_size=256,
                num_steps_sampled_before_learning_starts=256,
                replay_buffer_config={
                    "prioritized_replay": True,
                    "capacity": 1 << 14,
                },
                training_intensity=32.0,  # 8 fused updates / round
                superstep=8,
                replay_device_resident=True,
                replay_device_tree=device_tree,
                learn_while_rollout=interleave,
                target_network_update_freq=2048,
                model={"fcnet_hiddens": [64, 64]},
            )
            .reporting(min_time_s_per_iteration=0)
            .debugging(seed=0)
            .build()
        )

    def run(device_tree, interleave=False):
        algo = build_algo(device_tree, interleave)
        try:
            algo.train()  # warmup to learning start + compile
            h2d0 = telemetry_metrics.h2d_bytes_by_path()
            d2h0 = telemetry_metrics.d2h_bytes_by_path()
            traces0 = compile_stats()["traces"]
            walls = []
            t0 = time.perf_counter()
            for _ in range(iters):
                t1 = time.perf_counter()
                algo.train()
                walls.append(time.perf_counter() - t1)
            wall = time.perf_counter() - t0
            h2d1 = telemetry_metrics.h2d_bytes_by_path()
            d2h1 = telemetry_metrics.d2h_bytes_by_path()
            params = jax.device_get(algo.get_policy().params)
            return {
                "wall_s_per_iter": round(wall / iters, 4),
                "wall_s_per_iter_median": round(
                    float(np.median(walls)), 4
                ),
                "trained_steps": int(
                    algo._counters["num_env_steps_trained"]
                ),
                "sample_h2d_bytes_per_iter": round(
                    (
                        h2d1.get("replay_sample", 0.0)
                        - h2d0.get("replay_sample", 0.0)
                    )
                    / iters,
                    1,
                ),
                "rng_h2d_bytes_per_iter": round(
                    (
                        h2d1.get("replay_rng", 0.0)
                        - h2d0.get("replay_rng", 0.0)
                    )
                    / iters,
                    1,
                ),
                "priority_d2h_bytes_per_iter": round(
                    (
                        d2h1.get("replay_priorities", 0.0)
                        - d2h0.get("replay_priorities", 0.0)
                    )
                    / iters,
                    1,
                ),
                "recompiles_in_timed_window": (
                    compile_stats()["traces"] - traces0
                ),
            }, params
        finally:
            algo.cleanup()

    e2e_host, p_host = run(False)
    e2e_dev, p_dev = run(True)
    parity = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(p_host),
            jax.tree_util.tree_leaves(p_dev),
        )
    )
    dqn_e2e = {
        "host_tree": e2e_host,
        "device_tree": e2e_dev,
        "parity_bitwise": parity,
    }

    # ---- 3. interleave A/B: learn-while-rollout overlap ----
    # serial component walls (explicit syncs): one rollout fill, one
    # fused replay window
    algo = build_algo(True)
    try:
        # warm past learning start so the replay phase actually runs
        while (
            algo._counters["num_env_steps_sampled"] < 256 + 64
        ):
            algo.train()
        eng = algo._jax_rollout_engine_get()
        t0 = time.perf_counter()
        for _ in range(8):
            tree, _ = eng.rollout()
            jax.block_until_ready(tree)
            algo._insert_rollout_tree(tree)
        rollout_wall = (time.perf_counter() - t0) / 8
        t0 = time.perf_counter()
        for _ in range(8):
            algo._replay_update_phase(64)
        learn_wall = (time.perf_counter() - t0) / 8
    finally:
        algo.cleanup()
    serial_iter = e2e_dev["wall_s_per_iter_median"]
    e2e_int, _ = run(True, interleave=True)
    # per-round wall medians (iteration == one fill+learn round at
    # min_time 0); the max possible win per round is the smaller of
    # the two component walls — saved/min(...) is the fraction of
    # that ceiling the interleave actually recovered
    saved = max(
        0.0, serial_iter - e2e_int["wall_s_per_iter_median"]
    )
    overlap_fraction = max(
        0.0, min(1.0, saved / max(min(rollout_wall, learn_wall), 1e-9))
    )
    interleave = {
        "rollout_wall_s": round(rollout_wall, 4),
        "learn_wall_s": round(learn_wall, 4),
        "serial_wall_s_per_iter": serial_iter,
        "interleaved_wall_s_per_iter": e2e_int[
            "wall_s_per_iter_median"
        ],
        "overlap_fraction": round(overlap_fraction, 3),
        "note": (
            "≈0 expected on this 1-core CPU container (one execution "
            "stream, no real H2D wire); the cadence removes the "
            "host-side fill→learn serialization the mesh round "
            "measures"
        ),
    }

    report = {
        "metric": "apex_device_ab",
        "config": {
            "tree_micro": {"capacity": CAP, "batch": BS, "k": K},
            "dqn_e2e": {
                "env": "CartPoleJax-v0",
                "train_batch_size": 256,
                "training_intensity": 32.0,
                "superstep": 8,
                "iters": iters,
                "seed": 0,
            },
        },
        "tree_micro": tree_micro,
        "dqn_e2e": dqn_e2e,
        "interleave": interleave,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_observability(
    out_path=None, b=64, mb=32, iters=1, kmax=2, reps=4,
):
    """Device-ledger overhead A/B (docs/observability.md "device
    ledger"): the same fixed-seed superstep PPO chain three ways —
    telemetry fully off, the compiled-program ledger on (full
    cost/memory analysis), and ledger + span tracing. Reports the
    steady-state per-superstep wall of each, the ledger's overhead as
    a fraction of the baseline superstep wall (< 2% is the acceptance
    bar — the steady-state hooks are timestamps and dict bumps; the
    cost-analysis AOT compile is one-time and reported separately),
    and a bitwise parity flag between the off and on chains. Writes
    ``benchmarks/e2e/observability.json``."""
    import os

    import jax

    from ray_tpu import sharding as sharding_lib
    from ray_tpu.policy.jax_policy import _FRAMES as _F
    from ray_tpu.telemetry import device as device_ledger
    from ray_tpu.util import tracing

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/observability.json"

    def run_phase(ledger: bool, trace: bool):
        device_ledger.disable()
        device_ledger.clear()
        tracing.disable()
        tracing.clear()
        if ledger:
            device_ledger.enable(analyze=True)
        if trace:
            tracing.enable()
        rng = np.random.default_rng(0)
        p = _make_policy(b, mb, iters)
        host, bsize = p.prepare_batch(make_batch(rng, b))
        stacked = {
            cn: np.repeat(np.asarray(v)[None], kmax, axis=0)
            for cn, v in host.items()
        }
        shard = {
            cn: (
                sharding_lib.replicated(p.mesh)
                if cn == _F
                else sharding_lib.batch_sharded(
                    p.mesh, ndim_prefix=2
                )
            )
            for cn in stacked
        }
        dev = jax.device_put(stacked, shard)
        jax.block_until_ready(dev)
        t0 = time.perf_counter()
        p.learn_superstep(
            kmax, bsize, stacked=dict(dev), k_max=kmax
        )  # compile + (with the ledger) the AOT analysis compile
        warm_s = time.perf_counter() - t0
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            p.learn_superstep(
                kmax, bsize, stacked=dict(dev), k_max=kmax
            )
            walls.append(time.perf_counter() - t0)
        wall = float(np.median(walls))
        snap = device_ledger.snapshot() if ledger else None
        params = jax.device_get(p.params)
        device_ledger.disable()
        tracing.disable()
        tracing.clear()
        return wall, warm_s, snap, params

    wall_off, warm_off, _, params_off = run_phase(False, False)
    wall_led, warm_led, snap, params_led = run_phase(True, False)
    wall_all, warm_all, _, _ = run_phase(True, True)

    la = jax.tree_util.tree_leaves(params_off)
    lb = jax.tree_util.tree_leaves(params_led)
    bitwise = len(la) == len(lb) and all(
        np.array_equal(x, y) for x, y in zip(la, lb)
    )
    sup = next(
        (
            p
            for p in (snap or {}).get("programs", ())
            if p["label"].startswith("superstep[PPOJaxPolicy:")
        ),
        None,
    )
    overhead_ledger = (wall_led - wall_off) / wall_off
    overhead_all = (wall_all - wall_off) / wall_off
    report = {
        "metric": "device_ledger_overhead",
        "config": {
            "train_batch": b,
            "minibatch": mb,
            "num_sgd_iter": iters,
            "kmax": kmax,
            "reps": reps,
            "device": jax.devices()[0].device_kind,
        },
        "superstep_wall_s": {
            "telemetry_off": round(wall_off, 4),
            "ledger": round(wall_led, 4),
            "ledger_and_trace": round(wall_all, 4),
        },
        "ledger_overhead_fraction": round(overhead_ledger, 4),
        "ledger_and_trace_overhead_fraction": round(
            overhead_all, 4
        ),
        "analysis_compile_s": {
            # one-time: the warmup call pays trace+compile, plus
            # (ledger phases) the disjoint AOT analysis compile
            "telemetry_off": round(warm_off, 3),
            "ledger": round(warm_led, 3),
            "ledger_and_trace": round(warm_all, 3),
        },
        "superstep_program": sup
        and {
            "flops": sup["flops"],
            "bytes_accessed": sup["bytes_accessed"],
            "memory": sup["memory"],
            "executions": sup["executions"],
            "mfu": sup["mfu"],
        },
        "bitwise_parity": bool(bitwise),
        "ok": overhead_ledger < 0.02 and bool(bitwise),
        "note": (
            "steady-state ledger hooks are timestamps + dict "
            "bumps per dispatch/drain; the cost/memory analysis "
            "pays ONE extra AOT compile per traced signature "
            "(jit's execution cache does not serve lower().compile()), "
            "visible in analysis_compile_s, never per step"
        ),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def bench_lint(out_path=None, reps=2):
    """Device-contract static-analysis pass over all of ``ray_tpu/``
    (docs/static_analysis.md): reports scan wall time (the cost the
    tier-1 gate pays every run — the gate test budgets against the
    recorded number), file count, per-rule finding counts,
    baseline/suppression totals, and the ``--since`` incremental
    wall (empty change set: the parse+program-build floor every
    pre-commit run pays). Pure AST — no jax import, so it benches
    identically on broken-accelerator images. Writes
    ``benchmarks/e2e/static_analysis.json``."""
    import os

    from ray_tpu.analysis import (
        SCHEMA_VERSION,
        default_baseline_path,
        load_baseline,
        scan_paths,
    )
    from ray_tpu.analysis.rules import all_rules

    os.makedirs("benchmarks/e2e", exist_ok=True)
    out_path = out_path or "benchmarks/e2e/static_analysis.json"
    baseline_path = default_baseline_path()
    baseline = (
        load_baseline(baseline_path)
        if os.path.exists(baseline_path)
        else []
    )
    # a couple of timed repetitions: the first pass pays cold file
    # reads, the second is the steady-state CI cost
    walls = []
    for _ in range(max(1, int(reps))):
        res = scan_paths(["ray_tpu"], baseline=baseline)
        walls.append(round(res.duration_s, 3))
    # the incremental floor: parse + whole-program build with zero
    # rule work (what `--since <rev>` costs on an unchanged tree)
    since = scan_paths(["ray_tpu"], baseline=baseline, changed=[])
    report = {
        "metric": "static_analysis",
        "schema_version": SCHEMA_VERSION,
        "rules": len(all_rules()),
        "scan_wall_s": walls[-1],
        "scan_wall_s_cold": walls[0],
        "since_wall_s": round(since.duration_s, 3),
        "files": res.files,
        "findings_unbaselined": len(res.findings),
        "findings_by_rule": res.counts(),
        "baselined": len(res.baselined),
        "baseline_entries": len(baseline),
        "stale_baseline": len(res.stale_baseline),
        "parse_errors": len(res.parse_errors),
        "ok": res.ok,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return report


def main():
    if "--lint" in sys.argv:
        bench_lint()
        return
    if "--e2e" in sys.argv:
        from bench_e2e import main as e2e_main

        e2e_main()
        return
    if "--replay-ab" in sys.argv:
        bench_replay_ab()
        return
    if "--apex" in sys.argv:
        bench_apex()
        return
    if "--superstep" in sys.argv:
        bench_superstep()
        return
    if "--jax-env" in sys.argv:
        bench_jax_env()
        return
    if "--serve" in sys.argv:
        bench_serve()
        return
    if "--ingress" in sys.argv:
        bench_ingress()
        return
    if "--flood" in sys.argv:
        bench_flood(smoke="--smoke" in sys.argv)
        return
    if "--model-parallel" in sys.argv:
        bench_model_parallel()
        return
    if "--obs" in sys.argv:
        bench_observability()
        return
    if "--profile" in sys.argv:
        bench_profile()
        return
    if "--chaos" in sys.argv:
        bench_chaos()
        return
    if "--fleet-worker" in sys.argv:
        bench_fleet_worker()
        return
    if "--fleetobs-worker" in sys.argv:
        bench_fleetobs_worker()
        return
    if "--fleetobs" in sys.argv:
        bench_fleetobs()
        return
    if "--fleet-chaos" in sys.argv:
        bench_fleet_chaos()
        return
    if "--fleet" in sys.argv:
        bench_fleet()
        return
    if "--elastic" in sys.argv:
        bench_elastic()
        return
    from ray_tpu.utils.platform import ensure_compile_cache

    device = require_tpu()
    ensure_compile_cache()
    profile_dir = None
    if "--xprof" in sys.argv:
        i = sys.argv.index("--xprof")
        profile_dir = (
            sys.argv[i + 1] if len(sys.argv) > i + 1 else "/tmp/ray_tpu_trace"
        )
    (
        jax_sps,
        times,
        pipe_sps,
        pipe_wall,
        res_sps,
        res_wall,
    ) = bench_jax(profile_dir=profile_dir)
    mfu = bench_mfu()
    torch_sps = bench_torch()
    # Effective (wall-clock) MFU of the pipelined stream — the number
    # that includes transfer and amortized dispatch, not just the
    # epoch-isolated nest compute. Its physical ceiling is the H2D
    # bandwidth: a fresh train batch must cross to the device every
    # nest, so report the transfer bound alongside (bytes
    # per batch over the nest-compute time = the bandwidth that would
    # make compute the bottleneck).
    flops_per_nest = B * ITERS * nature_cnn_train_flops_per_sample()
    peak = mfu.get("peak_tflops") or chip_peak_tflops()[0]
    effective_mfu_pct = round(
        100.0 * flops_per_nest / pipe_wall / 1e12 / peak, 1
    )
    rng = np.random.default_rng(0)
    # bytes that actually cross the wire per nest: the PREPARED tree
    # (frame-pool format), not the materialized stacks
    _p = _make_policy(B, MB, ITERS)
    _tree, _ = _p.prepare_batch(make_batch(rng))
    batch_bytes = sum(v.nbytes for v in _tree.values())
    nest_s = mfu.get("nest_compute_s")
    breakeven_mb_s = (
        round(batch_bytes / nest_s / 1e6, 1) if nest_s else None
    )
    # measured H2D bandwidth: a fresh batch must cross the wire every
    # nest, so min(measured/breakeven, 1) bounds achievable wall-clock
    # MFU on this backend no matter how deep the pipeline
    import jax

    t0 = time.perf_counter()
    devd = jax.device_put(
        {"x": np.zeros(batch_bytes, np.uint8)}
    )
    jax.block_until_ready(devd["x"])
    h2d_mb_s = round(batch_bytes / (time.perf_counter() - t0) / 1e6, 1)
    print(
        json.dumps(
            {
                # the HEADLINE is the fused-lane number (ROADMAP 5a):
                # device-resident batches + pipelined dispatch — what
                # the subsystems built since r05 actually deliver. The
                # per-nest host-feed walk (sync stats fetch, a fresh
                # batch crossing H2D every nest) rides below as
                # `sync_feed`.
                "metric": "ppo_learner_env_steps_per_sec",
                "device": device,
                "value": round(res_sps, 1),
                "unit": "env_steps/s",
                "lane": "pipelined_device_resident",
                "vs_baseline": round(res_sps / torch_sps, 2),
                "baseline_torch_cpu": round(torch_sps, 1),
                "sync_feed": {
                    "env_steps_per_sec": round(jax_sps, 1),
                    "vs_baseline": round(jax_sps / torch_sps, 2),
                    "round_times_s": [round(t, 3) for t in times],
                },
                "pipelined": {
                    "env_steps_per_sec": round(pipe_sps, 1),
                    "wall_s_per_nest": round(pipe_wall, 4),
                    "effective_mfu_pct": effective_mfu_pct,
                    "batch_bytes": int(batch_bytes),
                    "h2d_mb_s_measured": h2d_mb_s,
                    "h2d_mb_s_for_compute_bound": breakeven_mb_s,
                    "note": (
                        "a fresh (already 4x frame-deduplicated) "
                        "batch crosses H2D each nest, so wall-clock "
                        "MFU is bounded by mfu_pct x measured/"
                        "compute-bound bandwidth"
                    ),
                },
                "pipelined_device_resident": {
                    "env_steps_per_sec": round(res_sps, 1),
                    "wall_s_per_nest": round(res_wall, 4),
                    "effective_mfu_pct": round(
                        100.0
                        * flops_per_nest
                        / res_wall
                        / 1e12
                        / peak,
                        1,
                    ),
                    "note": (
                        "same pipelined protocol, batches pre-"
                        "resident on device: isolates dispatch "
                        "amortization from H2D bandwidth"
                    ),
                },
                "mfu": mfu,
                "config": {
                    "train_batch": B,
                    "minibatch": MB,
                    "num_sgd_iter": ITERS,
                    "obs": [H, W, C],
                },
            }
        )
    )


if __name__ == "__main__":
    main()
