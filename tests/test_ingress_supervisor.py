"""Horizontal front door (docs/serving.md "Scaling the front door").

Covers the multi-process ingress scale-out contracts:

- SO_REUSEPORT distribution: N worker PROCESSES accept on ONE port
  over real sockets, and requests land on >= 2 distinct pids;
- crash -> respawn: a SIGKILLed worker is replaced and the
  replacement converges onto the bank (forwarded membership replayed,
  requests keep succeeding) with the respawn counted;
- whole-bank drain: one ``drain()`` (the provider-notice path) flips
  EVERY worker to healthz-503 at once;
- the inherited-listener fallback (one pre-fork listening socket
  shared by every worker) serves the same contract where
  SO_REUSEPORT is unavailable;
- per-policy quotas: a starved policy sheds 429/``quota`` while the
  other policies on the SAME shared admission budget keep admitting
  (the starvation counter-proof, unit-level and over real sockets);
- the flood harness smoke (``bench.py --flood --smoke``): knee found
  per config, overload answered with 200/429/503/504 (never a hang,
  never a late 200), bitwise parity across worker counts, zero
  recompiles — in a fresh subprocess so worker forks never race this
  process's XLA runtime.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from ray_tpu.ingress import (
    AdmissionController,
    CoalescingRouter,
    IngressSupervisor,
    LocalReplica,
    PolicyIngress,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _EchoReplica:
    """Pure-python replica: action = this process's pid, so responses
    prove WHICH worker process served them."""

    def __init__(self, index):
        self.name = f"echo{index}"
        self.dead = False

    def begin(self, rows, explore):
        return [
            {"action": os.getpid(), "params_version": 0}
            for _ in rows
        ]

    def finish(self, token, timeout_s):
        return token

    def alive(self):
        return True

    def queue_wait_p50_s(self):
        return None


class _StaticFeed:
    def __init__(self, members=(0, 1)):
        self._members = list(members)

    def current(self):
        return 1, self._members


def _echo_worker_init(ctx):
    feed = ctx.membership("echo")
    router = CoalescingRouter(
        "echo",
        membership=feed,
        wrap=lambda m, i: _EchoReplica(i),
        batch_wait_timeout_s=0.001,
    )
    ctx.ingress.add_policy("echo", router)


def _post(url, obs=(0.1, 0.2), timeout=10.0):
    req = urllib.request.Request(
        url,
        data=json.dumps({"obs": list(obs)}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _bank(**kw):
    kw.setdefault("num_workers", 2)
    kw.setdefault("worker_init", _echo_worker_init)
    kw.setdefault("heartbeat_s", 0.1)
    kw.setdefault("metrics_interval_s", 0.3)
    sup = IngressSupervisor(**kw)
    sup.follow_membership("echo", feed=_StaticFeed())
    return sup


@pytest.mark.skipif(
    not hasattr(socket, "SO_REUSEPORT"),
    reason="kernel lacks SO_REUSEPORT",
)
def test_reuseport_distributes_across_worker_processes():
    """One port, two real listening sockets in two PROCESSES: the
    kernel spreads connections across the bank, and any worker's
    /metrics serves the MERGED exposition with per-worker hosts."""
    sup = _bank().start()
    try:
        assert sup.stats()["reuseport"]
        url = sup.url + "/v1/policy/echo/actions"
        pids = set()
        for _ in range(50):
            status, out = _post(url)
            assert status == 200
            pids.add(out["action"])
        live = {
            p for p in sup.worker_pids() if p is not None
        }
        assert pids <= live
        assert len(pids) >= 2, (
            f"all requests served by one process: {pids}"
        )
        # merged metrics: wait for a merge cycle to reach a worker,
        # then ANY worker's scrape shows the whole bank
        deadline = time.time() + 10
        text = ""
        while time.time() < deadline:
            with urllib.request.urlopen(
                sup.url + "/metrics", timeout=5
            ) as r:
                text = r.read().decode()
            if (
                'host="ingress-w0"' in text
                and 'host="ingress-w1"' in text
            ):
                break
            time.sleep(0.2)
        assert 'host="ingress-w0"' in text
        assert 'host="ingress-w1"' in text
    finally:
        sup.stop()


def test_crash_respawn_keeps_membership_intact():
    """SIGKILL one worker: the supervisor respawns it, replays the
    forwarded membership, and the bank keeps answering on the shared
    port — zero manual re-registration."""
    sup = _bank().start()
    try:
        url = sup.url + "/v1/policy/echo/actions"
        status, _ = _post(url)
        assert status == 200
        victim = sup.worker_pids()[0]
        os.kill(victim, signal.SIGKILL)
        deadline = time.time() + 15
        while time.time() < deadline and (
            sup.respawned_total < 1 or sup.num_live() < 2
        ):
            time.sleep(0.1)
        assert sup.respawned_total >= 1, "crash never respawned"
        assert sup.num_live() == 2
        # the REPLACEMENT worker routes: its membership arrived from
        # the supervisor's replay, not from any client action
        time.sleep(0.5)
        ok = 0
        for _ in range(30):
            status, _ = _post(url)
            ok += status == 200
        assert ok == 30
        new_pids = set(sup.worker_pids())
        assert victim not in new_pids
    finally:
        sup.stop()


def test_drain_flips_the_whole_bank_to_503():
    """One drain broadcast = every worker answering healthz 503 and
    closing keep-alives (the PR-19 provider-notice path, per
    process)."""
    sup = _bank().start()
    try:
        # healthy first: poll until every worker's router has applied
        # the forwarded membership (healthz is "degraded" until then)
        deadline = time.time() + 10
        ok = 0
        while time.time() < deadline and ok < 4:
            try:
                with urllib.request.urlopen(
                    sup.url + "/healthz", timeout=5
                ) as r:
                    ok = ok + 1 if r.status == 200 else 0
            except urllib.error.HTTPError:
                ok = 0
            time.sleep(0.05)
        assert ok >= 4, "bank never became healthy"
        sup.drain(grace_s=5.0)
        assert sup.draining
        time.sleep(0.5)
        results = []
        for _ in range(8):  # fresh connections: hit both workers
            try:
                with urllib.request.urlopen(
                    sup.url + "/healthz", timeout=5
                ) as r:
                    results.append((r.status, r.read()))
            except urllib.error.HTTPError as e:
                results.append((e.code, e.read()))
        assert [s for s, _ in results] == [503] * 8, results
        for _, body in results:
            assert json.loads(body)["status"] == "draining"
    finally:
        sup.stop()


def test_inherited_listener_fallback_serves_the_bank():
    """force_inherited_listener: ONE pre-fork listening socket, every
    worker accepting from its queue — same port, same contract."""
    sup = _bank(force_inherited_listener=True).start()
    try:
        assert not sup.stats()["reuseport"]
        url = sup.url + "/v1/policy/echo/actions"
        pids = set()
        for _ in range(50):
            status, out = _post(url)
            assert status == 200
            pids.add(out["action"])
        live = {
            p for p in sup.worker_pids() if p is not None
        }
        assert pids <= live
        assert len(pids) >= 1  # shared accept queue: kernel's pick
    finally:
        sup.stop()


def test_quota_starves_one_policy_not_the_budget():
    """The starvation counter-proof, unit-level: a policy at its
    quota sheds 429/``quota`` while other policies keep admitting
    from the SAME global in-flight budget."""
    ctrl = AdmissionController(
        max_inflight=8, quotas={"hot": 2}, default_quota=None
    )
    assert ctrl.try_admit(policy="hot") is None
    assert ctrl.try_admit(policy="hot") is None
    d = ctrl.try_admit(policy="hot")  # third: past its slice
    assert d is not None and d.status == 429
    assert d.reason == "quota"
    # the bank is NOT full: other tenants admit freely
    for _ in range(6):
        assert ctrl.try_admit(policy="cold") is None
    assert ctrl.num_inflight() == 8
    # now the GLOBAL budget is exhausted: everyone sheds, reason
    # distinguishes the two
    d2 = ctrl.try_admit(policy="cold")
    assert d2 is not None and d2.reason == "inflight"
    ctrl.release(policy="hot")
    assert ctrl.try_admit(policy="hot") is None  # slice freed
    stats = ctrl.stats()
    assert stats["shed_total"]["quota"] == 1
    assert stats["quotas"] == {"hot": 2}
    assert stats["policy_inflight"]["cold"] == 6


def test_quota_starvation_counterproof_over_sockets():
    """Same proof over real sockets through ONE shared admission
    controller: the quota-starved policy gets 429s, its neighbor on
    the same ingress keeps returning 200s."""
    ingress = PolicyIngress(quotas={"hot": 0})
    ingress.add_policy(
        "hot",
        CoalescingRouter(
            "hot", [_EchoReplica(0)], batch_wait_timeout_s=0.001
        ),
    )
    ingress.add_policy(
        "cold",
        CoalescingRouter(
            "cold", [_EchoReplica(1)], batch_wait_timeout_s=0.001
        ),
    )
    ingress.start()
    try:
        status, _ = _post(
            ingress.url + "/v1/policy/cold/actions"
        )
        assert status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(ingress.url + "/v1/policy/hot/actions")
        assert ei.value.code == 429
        body = json.loads(ei.value.read())
        assert "quota" in body["error"]
        # the neighbor is untouched by the starved tenant's sheds
        status, _ = _post(
            ingress.url + "/v1/policy/cold/actions"
        )
        assert status == 200
    finally:
        ingress.stop()


def test_flood_smoke_contract(tmp_path):
    """``bench.py --flood --smoke`` is the tier-1 regression pin for
    the whole front-door stack: supervisor banks at 1 and 2 workers,
    open-loop Poisson arrivals with a deadline mix, knee per config,
    the 429/503/504-never-hang overload contract, bitwise parity
    across worker counts, zero recompiles per worker. Runs in a fresh
    subprocess: the bench forks ingress workers that initialize their
    own XLA runtimes, which must not share this process's."""
    out = tmp_path / "flood.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys; import bench; "
            "bench.bench_flood(out_path=sys.argv[1], smoke=True)",
            str(out),
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(out.read_text())
    crit = report["criteria"]
    assert crit["knee_found_per_config"]
    assert crit["overload_contract_429_503_504"]
    assert crit["parity_bitwise"]
    assert crit["zero_recompiles"]
    for cfg in report["configs"].values():
        c = cfg["overload"]["counts"]
        assert c["hang"] == 0 and c["late_200"] == 0
