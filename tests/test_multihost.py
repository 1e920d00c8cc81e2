"""Multi-host (DCN) runtime tests: 2-process CPU cluster (the
reference tests multi-node with in-process clusters the same way —
python/ray/cluster_utils.py:99)."""

import os
import socket
import subprocess
import sys
import time

import pytest

from ray_tpu.fleet import (
    HeartbeatReporter,
    KVClient,
    KVServer,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_kv_put_get_blocking():
    server = KVServer(host="127.0.0.1")
    client = KVClient(f"127.0.0.1:{server.port}")
    client.put("a", {"x": 1})
    assert client.get("a") == {"x": 1}
    # blocking get: value arrives from another client after a delay
    import threading

    def later():
        time.sleep(0.3)
        KVClient(f"127.0.0.1:{server.port}").put("b", [1, 2, 3])

    threading.Thread(target=later, daemon=True).start()
    t0 = time.monotonic()
    assert client.get("b", timeout=10.0) == [1, 2, 3]
    assert time.monotonic() - t0 >= 0.25
    with pytest.raises(KeyError):
        client.get("missing", timeout=0.2)
    server.shutdown()


def test_kv_heartbeats_track_liveness():
    server = KVServer(host="127.0.0.1")
    client = KVClient(f"127.0.0.1:{server.port}")
    hb = HeartbeatReporter(client, "nodeA", interval=0.1)
    time.sleep(0.4)
    alive = client.alive_nodes(horizon=1.0)
    assert "nodeA" in alive
    hb.stop()
    # a node that stops heartbeating ages out of the horizon
    time.sleep(0.5)
    alive = client.alive_nodes(horizon=0.3)
    assert "nodeA" not in alive
    server.shutdown()


@pytest.mark.slow  # ~13 s: spins a real 2-process jax.distributed
# cluster; moved out of tier-1 by the PR-1 budget rule — tier-1 keeps
# the KV rendezvous/liveness units, and the verify recipe drives this
# file standalone as its own surface
def test_two_process_dcn_cluster(tmp_path):
    """Full rung: jax.distributed over 2 CPU processes x 2 devices,
    global-mesh psum, cross-host weight broadcast, fleet rendezvous +
    epochs, a coordinator-kill chaos stage (fenced standby failover
    mid-training), and a live resize (drain host1, survivor reshards
    onto its local mesh and learns there)."""
    coord_port = _free_port()
    kv = KVServer(host="127.0.0.1")
    repo_root = os.path.dirname(os.path.dirname(__file__))
    notice_dir = tmp_path / "notices"
    notice_dir.mkdir()
    env_base = {
        **os.environ,
        "PYTHONPATH": repo_root
        + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "RAY_TPU_COORDINATOR": f"127.0.0.1:{coord_port}",
        "RAY_TPU_NUM_PROCESSES": "2",
        "RAY_TPU_KV_ADDRESS": f"127.0.0.1:{kv.port}",
        "RAY_TPU_PREEMPTION_NOTICE_DIR": str(notice_dir),
        # short lease so the chaos stage's coordinator-kill failover
        # (standby waits out the dead incumbent's TTL) stays fast
        "RAY_TPU_FLEET_LEASE_TTL_S": "2.0",
    }
    script = os.path.join(
        os.path.dirname(__file__), "_multihost_worker.py"
    )
    procs = []
    for rank in range(2):
        env = {**env_base, "RAY_TPU_PROCESS_ID": str(rank)}
        procs.append(
            subprocess.Popen(
                [sys.executable, script],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.shutdown()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"MULTIHOST_OK rank={rank}" in out
    # fleet observability rung: rank 1's deliberately-late barrier
    # arrival was attributed by name, and the merged exposition
    # carried host= series for both hosts
    assert "FLEETOBS_STRAGGLER host1" in outs[0]
    assert "FLEETOBS_MERGED 2 hosts" in outs[0]
    # chaos stage: rank 0's coordinator died mid-training, rank 1's
    # standby won the fenced lease at term 2 within the TTL window,
    # training resumed bitwise with zero fresh compiles, and the
    # zombie's stale-term write was rejected (split-brain proof)
    assert "FAILOVER_OK term=2" in outs[1]
    assert "CHAOS_BITWISE_OK" in outs[0] and "CHAOS_BITWISE_OK" in outs[1]
    assert "FENCED_OK stale term rejected" in outs[0]
    # elastic learner-fleet case: host1 drained on notice, host0
    # finished the lockstep drain step and continued on its local mesh
    assert "ELASTIC_OK" in outs[0]
    # the resize contract: params bitwise across the reshard
    assert "RESHARD_BITWISE_OK" in outs[0]
