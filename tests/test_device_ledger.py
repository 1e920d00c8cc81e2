"""Device-plane observability: the compiled-program ledger
(``ray_tpu/telemetry/device.py``, docs/observability.md "device
ledger").

Covers the ISSUE-13 tentpole seams:
- ledger rows: cost_analysis FLOPs / bytes, memory_analysis HBM
  footprint, steady-state execution counts, device-busy time closed at
  drain points, MFU against the (configurable) peak-FLOPs table;
- recompile forensics: the ``jit:recompile`` event carries the
  abstract-signature diff (leaf path + shape/dtype delta) and
  ``compile_stats()["recompile_causes"]`` rolls it up;
- device lanes + the transfer lane render in the chrome trace (golden
  structure assertions);
- the flight-recorder report CLI reads a trace + ledger dump;
- fixed-seed BIT-parity: superstep PPO with ledger + profile_iters on
  is bitwise identical to telemetry-off, end to end through a real
  Algorithm.
"""

import json

import jax
import numpy as np
import pytest

from ray_tpu import sharding as sharding_lib
from ray_tpu.telemetry import device as device_ledger
from ray_tpu.util import tracing


def setup_function(_fn):
    device_ledger.disable()
    device_ledger.clear()
    tracing.disable()
    tracing.clear()


teardown_function = setup_function


# -- ledger rows -------------------------------------------------------


def test_ledger_records_cost_memory_and_executions():
    device_ledger.enable(analyze=True)
    fn = sharded_jit_matmul("ledger_probe")
    x = np.ones((64, 64), np.float32)
    fn(x)  # trace+compile (not a steady-state execution)
    for _ in range(3):
        fn(x)
    device_ledger.drain_point()
    snap = device_ledger.snapshot()
    (row,) = [
        p
        for p in snap["programs"]
        if p["label"] == "ledger_probe"
    ]
    assert row["traces"] == 1 and row["recompiles"] == 0
    assert row["executions"] == 3
    assert row["device_time_s"] > 0
    assert row["compile_time_s"] > 0
    # XLA cost/memory analysis captured (CPU PJRT supports both)
    assert row["flops"] and row["flops"] > 0
    assert row["bytes_accessed"] and row["bytes_accessed"] > 0
    assert row["memory"]["argument_bytes"] > 0
    # MFU is executed FLOPs over peak x busy — a real number in (0, 1]
    # territory on any sane peak table
    assert row["mfu"] is not None and row["mfu"] > 0
    assert snap["totals"]["executions"] == 3
    assert snap["totals"]["mfu"] is not None


def sharded_jit_matmul(label):
    from ray_tpu.sharding.compile import sharded_jit

    return sharded_jit(
        lambda x: (x @ x.T).sum(), label=label
    )


def test_ledger_disabled_is_inert_and_peak_flops_override():
    fn = sharded_jit_matmul("inert_probe")
    fn(np.ones((8, 8), np.float32))
    assert device_ledger.snapshot()["programs"] == []
    # peak override (the CPU-container MFU knob)
    device_ledger.set_peak_flops(123.0)
    try:
        assert device_ledger.peak_flops_per_device() == 123.0
    finally:
        device_ledger.set_peak_flops(None)


def test_traced_calls_do_not_count_as_executions():
    """Warmup/compile calls are excluded from executions and busy
    time, so steady-state MFU isn't diluted by compile wall."""
    device_ledger.enable(analyze=False)
    fn = sharded_jit_matmul("warm_probe")
    fn(np.ones((16, 16), np.float32))  # traces
    snap = device_ledger.snapshot()
    (row,) = [
        p for p in snap["programs"] if p["label"] == "warm_probe"
    ]
    assert row["executions"] == 0 and row["traces"] == 1


# -- recompile forensics -----------------------------------------------


def test_recompile_event_carries_cause_diff():
    from ray_tpu.sharding.compile import compile_stats

    device_ledger.enable(analyze=False)
    tracing.enable()
    fn = sharded_jit_matmul("forensics_probe")
    fn(np.ones((32, 8), np.float32))
    fn(np.ones((64, 8), np.float32))  # shape change → retrace
    fn(np.ones((64, 8), np.int32))  # dtype change → retrace
    events = [
        s
        for s in tracing.get_spans()
        if s["name"] == "jit:recompile"
    ]
    assert len(events) == 2
    shape_cause = events[0]["attributes"]["cause"]
    dtype_cause = events[1]["attributes"]["cause"]
    # leaf path + shape delta
    assert "float32[32,8]" in shape_cause
    assert "float32[64,8]" in shape_cause
    # dtype delta
    assert "float32[64,8]" in dtype_cause
    assert "int32[64,8]" in dtype_cause
    causes = compile_stats()["recompile_causes"]
    assert "forensics_probe" in causes
    assert sum(c["count"] for c in causes["forensics_probe"]) == 2


def test_signature_diff_reports_added_and_removed_leaves():
    sig_a = device_ledger.signature_of(
        ({"obs": np.zeros((4, 8), np.float32)},), {}
    )
    sig_b = device_ledger.signature_of(
        (
            {
                "obs": np.zeros((4, 8), np.float32),
                "extra": np.zeros((4,), np.float32),
            },
        ),
        {},
    )
    diff = device_ledger.diff_signatures(sig_a, sig_b)
    assert "added" in diff and len(diff["added"]) == 1
    assert "extra" in diff["added"][0]["path"]
    back = device_ledger.diff_signatures(sig_b, sig_a)
    assert "removed" in back
    assert device_ledger.cause_string(diff)


# -- timeline: device + transfer lanes (golden structure) ---------------


def test_chrome_trace_renders_device_and_transfer_lanes(tmp_path):
    """One exported trace shows a driver-thread span, the device
    program lane (synthetic tid + ``device:`` thread_name metadata),
    and the device_feed transfer lane — the perfetto merge the ISSUE
    tentpole names."""
    from ray_tpu.execution.device_feed import DeviceFeeder

    device_ledger.enable(analyze=False)
    tracing.enable()
    fn = sharded_jit_matmul("lane_probe")
    x = np.ones((16, 16), np.float32)
    with tracing.start_span("train:iteration"):
        fn(x)  # compile
        fn(x)
        device_ledger.drain_point()
        feeder = DeviceFeeder()
        try:
            feeder.put({"x": x}, meta=None)
            feeder.get(timeout=30)
        finally:
            feeder.stop()
    path = tracing.export_chrome_trace(str(tmp_path / "t.json"))
    events = json.load(open(path))["traceEvents"]
    x_ev = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in x_ev}
    assert "device:lane_probe" in names
    assert "feeder:transfer" in names
    assert "train:iteration" in names
    dev = next(
        e for e in x_ev if e["name"] == "device:lane_probe"
    )
    drv = next(
        e for e in x_ev if e["name"] == "train:iteration"
    )
    # the device lane is synthetic — distinct from any host thread
    assert dev["tid"] != drv["tid"]
    assert dev["dur"] >= 0
    lanes = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "device:lane_probe" in lanes
    # transfer span carries its payload size for the report CLI
    feed = next(
        e for e in x_ev if e["name"] == "feeder:transfer"
    )
    assert feed["args"]["nbytes"] == x.nbytes


def test_report_cli_renders_trace_and_ledger(tmp_path, capsys):
    from ray_tpu.telemetry import report as report_mod

    device_ledger.enable(analyze=True)
    tracing.enable()
    fn = sharded_jit_matmul("report_probe")
    fn(np.ones((32, 32), np.float32))
    fn(np.ones((32, 32), np.float32))
    fn(np.ones((48, 32), np.float32))  # one recompile with cause
    device_ledger.drain_point()
    trace = tracing.export_chrome_trace(
        str(tmp_path / "trace.json")
    )
    ledger = device_ledger.dump(str(tmp_path / "ledger.json"))
    assert report_mod.main([trace, "--ledger", ledger]) == 0
    text = capsys.readouterr().out
    assert "report_probe" in text
    assert "top programs by device time" in text
    assert "recompiles" in text
    # forensics cause made it into the report
    assert "float32[32,32]" in text
    # JSON mode is machine-parseable
    assert (
        report_mod.main([trace, "--ledger", ledger, "--json"])
        == 0
    )
    rep = json.loads(capsys.readouterr().out)
    assert rep["programs_total"] >= 1
    assert rep["programs"][0]["label"] == "report_probe"
    assert rep["programs"][0]["flops"] > 0


# -- bit parity: ledger + tracing + profiler must not touch numerics ---


@pytest.mark.slow  # ~6 s; fails at seed under jax 0.9.0, passes since
# PR 21 — moved out of tier-1 by that PR's budget rule (the newly
# passing tests compile and run where they used to fail at trace
# time; only newly passing ones may leave); tier-1 keeps
# test_superstep.py's PPO superstep bit-parity pin
def test_policy_superstep_bit_parity_with_ledger(tmp_path):
    """Fixed-seed superstep PPO chain with the full ledger (AOT
    analysis) and span tracing running is BITWISE identical to the
    bare chain — the observers wrap the dispatch path, so this is
    where a numerics leak would show. The algorithm-level run with
    ``profile_iters`` on top is the slow-marked e2e below."""
    import gymnasium as gym

    from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

    def make_policy():
        return PPOJaxPolicy(
            gym.spaces.Box(-1, 1, (8,), np.float32),
            gym.spaces.Discrete(4),
            {
                "train_batch_size": 32,
                "sgd_minibatch_size": 16,
                "num_sgd_iter": 1,
                "lr": 1e-3,
                "seed": 0,
            },
        )

    rng = np.random.default_rng(3)
    K = 2
    batches = [
        {
            "obs": rng.standard_normal((32, 8)).astype(np.float32),
            "actions": rng.integers(0, 4, 32).astype(np.int64),
            "action_logp": np.full(32, -1.3, np.float32),
            "action_dist_inputs": rng.standard_normal(
                (32, 4)
            ).astype(np.float32),
            "advantages": rng.standard_normal(32).astype(
                np.float32
            ),
            "value_targets": rng.standard_normal(32).astype(
                np.float32
            ),
        }
        for _ in range(K)
    ]
    stacked = {
        c: np.stack([b[c] for b in batches]) for c in batches[0]
    }

    def run(observed: bool):
        if observed:
            device_ledger.enable(analyze=True)
            tracing.enable()
        p = make_policy()
        for _ in range(2):
            p.learn_superstep(
                K, 32, stacked=dict(stacked), k_max=K
            )
        if observed:
            # the ledger really saw the chain it must not perturb
            assert any(
                r["label"].startswith("superstep[")
                for r in device_ledger.snapshot()["programs"]
            )
            tracing.disable()
            tracing.clear()
            device_ledger.disable()
        return jax.device_get(p.params)

    params_obs = run(True)
    params_bare = run(False)
    la = jax.tree_util.tree_leaves(params_obs)
    lb = jax.tree_util.tree_leaves(params_bare)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- end to end: superstep PPO ledger + bit parity ----------------------


def _ppo_cfg(telemetry: bool, tmp_str: str):
    from ray_tpu.algorithms.ppo import PPOConfig

    cfg = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(
            num_rollout_workers=1,
            rollout_fragment_length=32,
            sample_prefetch=1,
        )
        .training(
            train_batch_size=64,
            sgd_minibatch_size=32,
            num_sgd_iter=1,
            lr=3e-4,
            superstep=2,
        )
        .debugging(seed=0)
    )
    if telemetry:
        cfg = cfg.telemetry(
            trace=True, device_ledger=True, profile_iters=1
        )
    return cfg


@pytest.mark.slow  # two full PPO builds (~25 s on the 1-core box);
# the per-train()-result ledger surface is tier-1-covered by
# test_telemetry.test_ppo_telemetry_end_to_end and the numerics half
# by the policy-level parity test above
def test_superstep_ppo_ledger_e2e_and_bit_parity(tmp_path):
    """Acceptance: ``info/device_ledger`` on superstep PPO reports
    per-program FLOPs, HBM bytes, execution counts and MFU; the
    exported timeline contains device program lanes; and the ledger +
    ``profile_iters`` run is BITWISE identical to telemetry-off at a
    fixed seed (observability must never touch the numerics)."""
    algo = _ppo_cfg(True, str(tmp_path)).build()
    try:
        for _ in range(2):
            result = algo.train()
        ledger = result["info"]["device_ledger"]
        assert ledger["programs"], "ledger saw no programs"
        sup = next(
            p
            for p in ledger["programs"]
            if p["label"].startswith("superstep[")
        )
        assert sup["flops"] and sup["flops"] > 0
        assert sup["bytes_accessed"] and sup["bytes_accessed"] > 0
        assert sup["memory"]["temp_bytes"] >= 0
        assert sup["executions"] >= 1
        assert sup["mfu"] is not None and sup["mfu"] > 0
        assert ledger["totals"]["mfu"] is not None
        assert ledger["peak_flops_per_device"] > 0
        # Prometheus families fed
        from ray_tpu.utils.metrics import get_metric

        m = get_metric("ray_tpu_program_executions_total")
        assert m is not None and any(
            "superstep[" in dict(tags).get("program", "")
            for tags, _v in m.series()
        )
        # device lanes render in the unified timeline
        path = algo.export_timeline(
            str(tmp_path / "timeline.json")
        )
        events = json.load(open(path))["traceEvents"]
        dev_names = {
            e["name"]
            for e in events
            if e["ph"] == "X"
            and e["name"].startswith("device:")
        }
        assert any("superstep[" in n for n in dev_names)
        weights_on = algo.get_policy().get_weights()
    finally:
        algo.cleanup()
    tracing.disable()
    tracing.clear()
    device_ledger.disable()
    device_ledger.clear()

    algo_off = _ppo_cfg(False, str(tmp_path)).build()
    try:
        for _ in range(2):
            algo_off.train()
        weights_off = algo_off.get_policy().get_weights()
    finally:
        algo_off.cleanup()
    la = jax.tree_util.tree_leaves(weights_on)
    lb = jax.tree_util.tree_leaves(weights_off)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert np.array_equal(np.asarray(a), np.asarray(b))
