"""The serving front door (docs/serving.md "the front door").

Covers the ingress-plane contracts:

- cross-replica coalescing determinism: any router merge order of a
  fixed-seed request stream is BIT-identical to sequential
  ``compute_actions`` on a 1-shard mesh, and merged dispatch causes
  zero recompiles after warmup (``compile_stats``-asserted);
- deadline-expiry drop semantics: expired requests are rejected
  BEFORE dispatch — the replica never sees them;
- dead-replica rerouting + the controller membership feed;
- admission control: bounded in-flight budget (429), queue-wait
  shedding (503 + Retry-After), dead-on-arrival refusal (504), and
  overload shedding instead of unbounded queue growth over real
  sockets;
- the shared queue-wait window accessor: ``stats()`` (the
  autoscaler's input) and the ingress shedding signal read the SAME
  numbers (the satellite regression pin);
- HTTP/ASGI protocol: real-socket POST/healthz/metrics, keep-alive,
  and the ASGI app driving the identical dispatch.

A replica's cold start on jax's persistent compilation cache is a case
of ``tests/test_compile_account.py``.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import gymnasium as gym

from ray_tpu import sharding as sharding_lib
from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy
from ray_tpu.ingress import (
    AdmissionController,
    CoalescingRouter,
    DeadlineExpired,
    LocalReplica,
    PolicyIngress,
)
from ray_tpu.resilience.discovery import MembershipFeed
from ray_tpu.serve.long_poll import LongPollHost
from ray_tpu.serve.policy_server import (
    BatchedPolicyServer,
    TrailingWindow,
)
from ray_tpu.sharding.compile import compile_stats

_OBS = gym.spaces.Box(-1.0, 1.0, (4,), np.float32)
_ACT = gym.spaces.Discrete(2)


def _one_shard_mesh():
    return sharding_lib.get_mesh(devices=jax.devices()[:1])


def _policy(seed=7):
    return PPOJaxPolicy(
        _OBS,
        _ACT,
        {
            "seed": seed,
            "num_workers": 0,
            "train_batch_size": 64,
            "sgd_minibatch_size": 32,
            "num_sgd_iter": 1,
            "lr": 3e-4,
            "model": {"fcnet_hiddens": [16, 16]},
            "_mesh": _one_shard_mesh(),
        },
    )


def _server(seed=7, name="policy", warm=True, **kw):
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("batch_wait_timeout_s", 0.002)
    kw.setdefault("explore", True)
    srv = BatchedPolicyServer(
        _policy(seed), name=name, start=False, **kw
    )
    if warm:
        srv.warmup()
    srv.start()
    return srv


def _post(url, payload, timeout=30.0):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# -- shared queue-wait window accessor (satellite regression pin) ------


def test_trailing_window_percentiles(rng):
    w = TrailingWindow(window_s=30.0)
    vals = rng.uniform(0.0, 1.0, 101)
    for v in vals:
        w.observe(float(v))
    snap = w.snapshot()
    assert snap["n"] == 101
    assert snap["p50_s"] == pytest.approx(
        float(np.percentile(vals, 50))
    )
    assert snap["p99_s"] == pytest.approx(
        float(np.percentile(vals, 99))
    )
    # decayed samples leave the window
    w2 = TrailingWindow(window_s=0.01)
    w2.observe(1.0, t=time.perf_counter() - 1.0)
    assert w2.snapshot()["n"] == 0
    assert w2.pct(50) is None


def test_queue_wait_shared_accessor_pins_stats(rng):
    """stats()['queue_wait_p50_s'] (what _Replica.stats forwards to
    the autoscale loop) and queue_wait_window()['p50_s'] (what the
    ingress shedding decision reads) are the SAME number from the
    SAME accessor — regression pin for the unification satellite."""
    server = _server()
    try:
        for o in rng.uniform(-1, 1, (9, 4)).astype(np.float32):
            server.submit(o).result(30.0)
        st = server.stats()
        qw = server.queue_wait_window()
        lat = server.latency_window()
        assert st["queue_wait_p50_s"] == qw["p50_s"]
        assert st["queue_wait_p99_s"] == qw["p99_s"]
        assert st["latency_p50_s"] == lat["p50_s"]
        assert qw["p50_s"] is not None and qw["n"] == 9
        # the router's admission feed reads the same accessor
        router = CoalescingRouter(
            "pin", [LocalReplica(server)], start=False
        )
        assert router.queue_wait_signal() == qw["p50_s"]
    finally:
        server.stop()


# -- cross-replica coalescing determinism ------------------------------


def test_router_coalescing_bitwise_parity(rng):
    """Any router merge order of a fixed-seed stream onto one replica
    is bit-identical to sequential compute_actions on a 1-shard mesh
    — actions AND extras, across several distinct chunkings."""
    obs_stream = rng.uniform(-1, 1, (13, 4)).astype(np.float32)
    ref_policy = _policy()
    refs = [
        ref_policy.compute_actions(o[None], explore=True)
        for o in obs_stream
    ]
    # two structurally distinct merge orders (mixed partial buckets;
    # uniform small merges) — each chunking rebuilds the server, so
    # the count is budget-bound; single-batch and per-row slicings
    # are already pinned at the server layer (test_serve_policy)
    for chunks in ([1, 5, 7], [2] * 6 + [1]):
        server = _server()
        router = CoalescingRouter(
            "parity",
            [LocalReplica(server)],
            max_batch_size=8,
            batch_wait_timeout_s=0.002,
        )
        try:
            futs = []
            i = 0
            for c in chunks:
                for o in obs_stream[i : i + c]:
                    futs.append(router.submit(o, explore=True))
                i += c
                time.sleep(0.02)  # let this merge dispatch
            outs = [f.result(30.0) for f in futs]
        finally:
            router.stop()
            server.stop()
        for i, (a_ref, _, ex_ref) in enumerate(refs):
            assert np.array_equal(
                outs[i]["action"], a_ref[0]
            ), (chunks, i)
            for k, v in ex_ref.items():
                assert np.array_equal(
                    outs[i]["extra"][k], v[0]
                ), (chunks, i, k)


def test_router_merges_concurrent_requests(rng):
    """Concurrent single-request clients coalesce into multi-row
    buckets (the front door's whole point), and merged dispatch is
    recompile-free after warmup."""
    server = _server(explore=False, max_batch_size=16)
    router = CoalescingRouter(
        "merge",
        [LocalReplica(server)],
        max_batch_size=16,
        batch_wait_timeout_s=0.02,
    )
    obs_stream = rng.uniform(-1, 1, (48, 4)).astype(np.float32)
    traces0 = compile_stats()["traces"]
    try:
        futs = []
        lock = threading.Lock()

        def client(rows):
            for o in rows:
                f = router.submit(o, explore=False)
                with lock:
                    futs.append(f)
                f.result(30.0)

        threads = [
            threading.Thread(target=client, args=(obs_stream[i::8],))
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = router.stats()
        assert stats["merged_rows_total"] == 48
        assert stats["batches_total"] < 48
        assert stats["mean_merged_rows"] > 1.0
        assert compile_stats()["traces"] == traces0
    finally:
        router.stop()
        server.stop()


# -- deadlines ---------------------------------------------------------


def test_router_deadline_expiry_drops_before_dispatch(rng):
    """Requests whose deadline passes while queued are dropped AT
    COLLECTION, before dispatch: the replica never sees them and no
    device work is computed for them."""
    server = _server()
    served0 = server.requests_total
    # long coalesce wait + short deadlines: the requests expire in
    # the router queue before a bucket ever forms
    router = CoalescingRouter(
        "deadline",
        [LocalReplica(server)],
        max_batch_size=8,
        batch_wait_timeout_s=0.25,
    )
    try:
        futs = [
            router.submit(
                rng.uniform(-1, 1, 4).astype(np.float32),
                explore=True,
                deadline_s=0.01,
            )
            for _ in range(3)
        ]
        for f in futs:
            with pytest.raises(DeadlineExpired):
                f.result(30.0)
        assert router.expired_total == 3
        assert server.requests_total == served0  # never dispatched
        # an unexpired request still flows normally afterwards
        out = router.submit(
            rng.uniform(-1, 1, 4).astype(np.float32),
            explore=True,
            deadline_s=30.0,
        ).result(30.0)
        assert "action" in out
    finally:
        router.stop()
        server.stop()


# -- dead replicas / membership ----------------------------------------


def test_router_routes_around_dead_replica(rng):
    """A replica that dies mid-dispatch is marked dead and its bucket
    re-queues onto the survivor — requests complete, rerouted_total
    counts them."""

    class _DiesOnFinish:
        name = "corpse"

        def __init__(self):
            self.dead = False
            self.begun = 0

        def begin(self, rows, explore):
            self.begun += len(rows)
            return list(rows)

        def finish(self, token, timeout_s):
            raise RuntimeError("replica actor died")

        def alive(self):
            return not self.dead

        def queue_wait_p50_s(self):
            return None

    server = _server(explore=False)
    corpse = _DiesOnFinish()
    router = CoalescingRouter(
        "failover",
        [corpse, LocalReplica(server, name="survivor")],
        max_batch_size=4,
        batch_wait_timeout_s=0.002,
    )
    try:
        obs_stream = rng.uniform(-1, 1, (8, 4)).astype(np.float32)
        futs = [
            router.submit(o, explore=False) for o in obs_stream
        ]
        outs = [f.result(30.0) for f in futs]
        assert len(outs) == 8
        assert corpse.dead
        assert router.num_dead() == 1
        assert router.rerouted_total >= corpse.begun > 0
        # parity even through the failover (greedy = deterministic)
        ref = _policy()
        for i, o in enumerate(obs_stream):
            a_ref, _, _ = ref.compute_actions(
                o[None], explore=False
            )
            assert np.array_equal(outs[i]["action"], a_ref[0])
    finally:
        router.stop()
        server.stop()


def test_router_follows_membership_feed(rng):
    """The router adopts the controller's republished membership
    (scale-up / dead-replica replacement) between batches — the
    serve long-poll feed surfaced via resilience.discovery."""
    host = LongPollHost()
    feed = MembershipFeed(host, "replicas:feedtest")
    s1 = _server(name="feed1")
    s2 = _server(name="feed2")
    host.notify("replicas:feedtest", [s1])
    router = CoalescingRouter(
        "feedtest",
        membership=feed,
        max_batch_size=4,
        batch_wait_timeout_s=0.002,
    )
    try:
        assert router.num_replicas() == 1
        out = router.submit(
            rng.uniform(-1, 1, 4).astype(np.float32), explore=True
        ).result(30.0)
        assert "action" in out
        # controller publishes a scale-up; the next dispatch adopts it
        host.notify("replicas:feedtest", [s1, s2])
        deadline = time.time() + 5
        while time.time() < deadline and router.num_replicas() != 2:
            router.submit(
                rng.uniform(-1, 1, 4).astype(np.float32),
                explore=True,
            ).result(30.0)
        assert router.num_replicas() == 2
    finally:
        router.stop()
        s1.stop()
        s2.stop()


# -- admission control -------------------------------------------------


def test_admission_inflight_budget():
    ctrl = AdmissionController(max_inflight=2)
    assert ctrl.try_admit() is None
    assert ctrl.try_admit() is None
    decision = ctrl.try_admit()
    assert decision is not None
    assert decision.status == 429
    assert decision.reason == "inflight"
    assert decision.retry_after_s > 0
    ctrl.release()
    assert ctrl.try_admit() is None
    assert ctrl.stats()["shed_total"]["inflight"] == 1
    assert ctrl.stats()["admitted_total"] == 3


def test_admission_queue_wait_shed():
    """Waits above the target shed with 503 + a Retry-After sized to
    the observed congestion; the signal is cached between polls."""
    calls = []

    def signal():
        calls.append(1)
        return 2.0

    ctrl = AdmissionController(
        max_inflight=100,
        shed_queue_wait_s=0.5,
        wait_signal=signal,
        signal_interval_s=60.0,
    )
    d1 = ctrl.try_admit()
    d2 = ctrl.try_admit()
    assert d1.status == d2.status == 503
    assert d1.reason == "queue_wait"
    assert d1.retry_after_s == pytest.approx(4.0)  # 2x observed
    assert len(calls) == 1  # cached within signal_interval_s
    # a healthy signal admits
    ok = AdmissionController(
        shed_queue_wait_s=0.5, wait_signal=lambda: 0.01
    )
    assert ok.try_admit() is None


def test_admission_dead_on_arrival():
    ctrl = AdmissionController()
    decision = ctrl.try_admit(deadline_s=0.0)
    assert decision is not None
    assert decision.status == 504
    assert decision.reason == "deadline"
    assert ctrl.num_inflight() == 0


# -- the HTTP/ASGI front door over real sockets ------------------------


def test_http_ingress_socket_e2e(rng):
    """POST /v1/policy/<name>/actions over a real socket: bitwise
    parity with sequential compute_actions, healthz, the Prometheus
    /metrics passthrough, and HTTP keep-alive."""
    server = _server()
    router = CoalescingRouter(
        "cartpole",
        [LocalReplica(server)],
        max_batch_size=8,
        batch_wait_timeout_s=0.002,
    )
    ingress = PolicyIngress().start()
    ingress.add_policy("cartpole", router)
    try:
        obs_stream = rng.uniform(-1, 1, (9, 4)).astype(np.float32)
        outs = []
        for o in obs_stream:
            status, out = _post(
                ingress.url + "/v1/policy/cartpole/actions",
                {"obs": o.tolist()},
            )
            assert status == 200
            outs.append(out)
        ref = _policy()
        for i, o in enumerate(obs_stream):
            a_ref, _, ex_ref = ref.compute_actions(
                o[None], explore=True
            )
            assert int(outs[i]["action"]) == int(a_ref[0])
            assert np.float32(outs[i]["logp"]) == np.float32(
                ex_ref["action_logp"][0]
            )
            assert outs[i]["params_version"] == 1

        # keep-alive: two requests on ONE connection
        import http.client

        conn = http.client.HTTPConnection(
            ingress.host, ingress.port, timeout=30
        )
        for _ in range(2):
            conn.request(
                "POST",
                "/v1/policy/cartpole/actions",
                body=json.dumps(
                    {"obs": obs_stream[0].tolist()}
                ),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
        conn.close()

        with urllib.request.urlopen(
            ingress.url + "/healthz", timeout=10
        ) as r:
            health = json.loads(r.read())
            assert r.status == 200
            assert health["status"] == "ok"
            assert health["policies"]["cartpole"]["replicas"] == 1
        with urllib.request.urlopen(
            ingress.url + "/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        assert "ray_tpu_ingress_requests_total" in text
        assert "ray_tpu_router_batches_total" in text
        # protocol errors
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(
                ingress.url + "/v1/policy/nope/actions",
                {"obs": [0, 0, 0, 0]},
            )
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                ingress.url + "/v1/policy/cartpole/actions",
                timeout=10,
            )
        assert ei.value.code == 405
    finally:
        ingress.stop()
        router.stop()
        server.stop()


def test_http_ingress_coalesces_concurrent_clients(rng):
    """Tier-1 sibling of the slow socket sweep: concurrent socket
    clients coalesce into multi-row buckets through the full
    HTTP -> router -> replica stack with zero recompiles (the
    recompile-free merge contract, asserted at small scale)."""
    server = _server(explore=False, max_batch_size=16)
    router = CoalescingRouter(
        "cartpole",
        [LocalReplica(server)],
        max_batch_size=16,
        batch_wait_timeout_s=0.02,
    )
    ingress = PolicyIngress().start()
    ingress.add_policy("cartpole", router)
    obs_stream = rng.uniform(-1, 1, (32, 4)).astype(np.float32)
    traces0 = compile_stats()["traces"]
    try:
        results = [None] * len(obs_stream)

        def client(idxs):
            for i in idxs:
                _, out = _post(
                    ingress.url + "/v1/policy/cartpole/actions",
                    {"obs": obs_stream[i].tolist()},
                )
                results[i] = out

        threads = [
            threading.Thread(
                target=client, args=(range(i, 32, 8),)
            )
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r is not None for r in results)
        assert router.stats()["mean_merged_rows"] > 1.0
        assert compile_stats()["traces"] == traces0
        ref = _policy()
        for i, o in enumerate(obs_stream):
            a_ref, _, _ = ref.compute_actions(
                o[None], explore=False
            )
            assert int(results[i]["action"]) == int(a_ref[0])
    finally:
        ingress.stop()
        router.stop()
        server.stop()


def test_http_ingress_overload_sheds_429_503(rng):
    """Synthetic overload: more concurrent requests than the
    admission budget against a deliberately slow replica. The ingress
    answers 429/503 with Retry-After instead of queueing without
    bound, and the queue stays bounded by the budget."""

    class _Slow:
        name = "slow"
        dead = False

        def __init__(self, server):
            self.server = server

        def begin(self, rows, explore):
            return self.server.submit_many(rows, explore=explore)

        def finish(self, token, timeout_s):
            time.sleep(0.15)  # a slow mesh forward
            out = []
            for fut in token:
                action, extra = fut.result(timeout_s)
                out.append(
                    {
                        "action": action,
                        "params_version": fut.params_version,
                        "extra": extra,
                    }
                )
            return out

        def alive(self):
            return True

        def queue_wait_p50_s(self):
            return None

    server = _server(explore=False)
    router = CoalescingRouter(
        "cartpole",
        [_Slow(server)],
        max_batch_size=4,
        batch_wait_timeout_s=0.001,
        dispatch_workers=1,
    )
    ingress = PolicyIngress(max_inflight=4).start()
    ingress.add_policy("cartpole", router)
    statuses = []
    retry_after = []
    lock = threading.Lock()
    try:
        def client(i):
            try:
                status, _ = _post(
                    ingress.url + "/v1/policy/cartpole/actions",
                    {"obs": [0.0, 0.0, 0.0, 0.0]},
                    timeout=60.0,
                )
            except urllib.error.HTTPError as e:
                with lock:
                    statuses.append(e.code)
                    if e.headers.get("Retry-After"):
                        retry_after.append(
                            int(e.headers["Retry-After"])
                        )
                return
            with lock:
                statuses.append(status)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        served = statuses.count(200)
        shed = [s for s in statuses if s in (429, 503)]
        assert served >= 1
        assert len(shed) >= 1, statuses
        assert len(retry_after) == len(shed)
        assert all(r >= 1 for r in retry_after)
        assert served + len(shed) == 24
        st = ingress.stats()["policies"]["cartpole"]
        assert st["admission"]["shed_total"]["inflight"] >= 1
        # the admitted queue never grew past the budget
        assert st["admission"]["max_inflight"] == 4
    finally:
        ingress.stop()
        router.stop()
        server.stop()


def test_asgi_app_contract(rng):
    """The ASGI 3 app drives the IDENTICAL dispatch: scripted
    receive/send for healthz and a POST round-trip."""
    import asyncio

    server = _server()
    router = CoalescingRouter(
        "cartpole",
        [LocalReplica(server)],
        max_batch_size=8,
        batch_wait_timeout_s=0.002,
    )
    ingress = PolicyIngress()  # NOT started: no socket needed
    ingress.add_policy("cartpole", router)
    app = ingress.asgi_app()

    async def call(method, path, body=b""):
        sent = []
        received = [
            {"type": "http.request", "body": body, "more_body": False}
        ]

        async def receive():
            return received.pop(0)

        async def send(msg):
            sent.append(msg)

        await app(
            {"type": "http", "method": method, "path": path},
            receive,
            send,
        )
        start = sent[0]
        payload = b"".join(
            m.get("body", b"") for m in sent[1:]
        )
        return start["status"], json.loads(payload)

    try:
        loop = asyncio.new_event_loop()
        try:
            status, health = loop.run_until_complete(
                call("GET", "/healthz")
            )
            assert status == 200 and health["status"] == "ok"
            obs = rng.uniform(-1, 1, 4).astype(np.float32)
            status, out = loop.run_until_complete(
                call(
                    "POST",
                    "/v1/policy/cartpole/actions",
                    json.dumps({"obs": obs.tolist()}).encode(),
                )
            )
            assert status == 200
            ref = _policy()
            a_ref, _, _ = ref.compute_actions(
                obs[None], explore=True
            )
            assert int(out["action"]) == int(a_ref[0])
            status, err = loop.run_until_complete(
                call("POST", "/v1/policy/cartpole/actions", b"{}")
            )
            assert status == 400
        finally:
            loop.close()
    finally:
        router.stop()
        server.stop()


@pytest.mark.slow
def test_ingress_fronts_serve_deployment_actors(tmp_path, rng):
    """serve_deployment resolves a RunningDeployment through the
    serve core and routes coalesced buckets to its ACTOR replicas
    (ActorReplica.begin → PolicyDeployment.handle_rows) — the
    multi-process fleet path, fed by the controller membership feed."""
    import os

    import ray_tpu as ray
    from ray_tpu.algorithms.ppo.ppo import PPO
    from ray_tpu.serve import serve
    from ray_tpu.serve.policy_server import policy_deployment

    cfg = {
        "env": "CartPole-v1",
        "seed": 7,
        "num_workers": 0,
        "train_batch_size": 64,
        "sgd_minibatch_size": 32,
        "num_sgd_iter": 1,
        "model": {"fcnet_hiddens": [16, 16]},
    }
    algo = PPO(config=cfg)
    ckpt_root = str(tmp_path / "ckpts")
    try:
        algo.save(os.path.join(ckpt_root, "checkpoint_000001"))
    finally:
        algo.cleanup()
    ingress = None
    try:
        serve.run(
            policy_deployment(
                ckpt_root, name="cartpole", watch=False
            )
        )
        ingress = PolicyIngress().start()
        ingress.serve_deployment(
            "cartpole", max_batch_size=8,
            batch_wait_timeout_s=0.01,
        )
        obs_stream = rng.uniform(-1, 1, (6, 4)).astype(np.float32)
        outs = []
        for o in obs_stream:
            status, out = _post(
                ingress.url + "/v1/policy/cartpole/actions",
                {"obs": o.tolist()},
                timeout=120.0,
            )
            assert status == 200
            outs.append(out)
        assert all(o["action"] in (0, 1) for o in outs)
        assert all(o["params_version"] == 1 for o in outs)
        assert all("logp" in o for o in outs)
        st = ingress.stats()["policies"]["cartpole"]["router"]
        assert st["replicas"] == 1
        assert st["merged_rows_total"] == 6
        # the router follows the controller's membership feed
        serve.update_deployment("cartpole", num_replicas=2)
        deadline = time.time() + 30
        n_now = 1
        while time.time() < deadline and n_now < 2:
            status, out = _post(
                ingress.url + "/v1/policy/cartpole/actions",
                {"obs": obs_stream[0].tolist()},
                timeout=120.0,
            )
            assert status == 200
            n_now = ingress.stats()["policies"]["cartpole"][
                "router"
            ]["replicas"]
        assert n_now == 2
    finally:
        if ingress is not None:
            ingress.stop()
        serve.shutdown()
        ray.shutdown()


# -- the slow socket sweep (tier-1 sibling above) ----------------------


@pytest.mark.slow
def test_ingress_throughput_vs_per_request_http_slow(tmp_path, rng):
    """E2E acceptance at reduced container scale: batched ingress
    throughput over real sockets vs the per-request HTTP path (the
    serve-core one-request-per-actor-call server) at 32 concurrent
    clients, with bitwise response parity and zero recompiles in the
    timed window. The full sweep + cold-start A/B artifact is
    bench.py --ingress."""
    import ray_tpu as ray
    from ray_tpu.serve import serve

    n_requests = 128
    obs_stream = rng.uniform(-1, 1, (n_requests, 4)).astype(
        np.float32
    )

    def sweep(full_url, clients):
        latencies = [None] * n_requests
        results = [None] * n_requests
        next_i = [0]
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    i = next_i[0]
                    if i >= n_requests:
                        return
                    next_i[0] += 1
                t0 = time.perf_counter()
                _, out = _post(
                    full_url,
                    {"obs": obs_stream[i].tolist()},
                    timeout=120.0,
                )
                latencies[i] = time.perf_counter() - t0
                # the serve-core HTTP path wraps results in
                # {"result": ...}; the ingress answers the row itself
                results[i] = out.get("result", out)
        threads = [
            threading.Thread(target=worker) for _ in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return n_requests / wall, results

    # batched side: the front door over an in-process replica
    server = _server(explore=False, max_batch_size=32)
    router = CoalescingRouter(
        "cartpole",
        [LocalReplica(server)],
        max_batch_size=32,
        batch_wait_timeout_s=0.005,
    )
    ingress = PolicyIngress().start()
    ingress.add_policy("cartpole", router)
    traces0 = compile_stats()["traces"]
    try:
        batched_rps, batched_results = sweep(
            ingress.url + "/v1/policy/cartpole/actions", clients=32
        )
        assert compile_stats()["traces"] == traces0
    finally:
        ingress.stop()
        router.stop()
        server.stop()

    # per-request side: the old serve-core HTTP path — one request
    # per actor call through a deployment replica
    try:
        from ray_tpu.algorithms.ppo.ppo import PPO

        cfg = {
            "env": "CartPole-v1",
            "seed": 7,
            "num_workers": 0,
            "train_batch_size": 64,
            "sgd_minibatch_size": 32,
            "num_sgd_iter": 1,
            "model": {"fcnet_hiddens": [16, 16]},
        }
        algo = PPO(config=cfg)
        ckpt_root = str(tmp_path / "ckpts")
        try:
            import os

            algo.save(
                os.path.join(ckpt_root, "checkpoint_000001")
            )
        finally:
            algo.cleanup()
        from ray_tpu.serve.policy_server import policy_deployment

        serve.run(
            policy_deployment(
                ckpt_root,
                name="cartpole_naive",
                max_batch_size=1,
                watch=False,
            ),
            http_host="127.0.0.1",
        )
        naive_url = (
            f"http://127.0.0.1:{serve.http_port()}/cartpole_naive"
        )
        naive_rps, naive_results = sweep(naive_url, clients=32)
    finally:
        serve.shutdown()
        ray.shutdown()

    # bitwise response parity between the two paths (greedy)
    for i in range(n_requests):
        assert int(batched_results[i]["action"]) == int(
            naive_results[i]["action"]
        ), i
    assert batched_rps >= 4.0 * naive_rps, (
        batched_rps,
        naive_rps,
    )
