"""Continuous-batching policy serving: the inference plane.

The serve core (``serve/serve.py``) routes ONE request per actor call —
the same per-call dispatch overhead the training superstep killed on
the learner path (docs/data_plane.md). This module applies the
identical optimization to inference (the Orca-style continuous-batching
pattern): concurrent ``compute_actions`` requests coalesce into ONE
mesh-sharded jit'd forward, so a replica's throughput scales with batch
rows instead of dispatches.

Three pieces:

- :class:`BatchedPolicyServer` — the in-process engine. A batcher
  thread drains up to ``max_batch_size`` queued requests (or
  ``batch_wait_timeout_s`` after the first, whichever first), pads the
  batch into a small set of static **bucket** shapes (powers of two →
  zero recompiles after warmup, ``compile_stats``-asserted), and runs
  one ``sharded_jit`` forward on the policy's mesh: replicated params,
  row-sharded observations, a **donated rng carry**. Results scatter
  back to per-request futures.

  **Determinism contract** (docs/serving.md): the program advances the
  rng carry exactly once per REAL request — padded rows consume no
  splits — and maps the policy's ``_action_step_body`` over
  per-request keys at batch-1 shapes (``lax.map``), so a fixed-seed
  request stream produces BIT-identical actions/extras to sequential
  ``compute_actions`` calls on a 1-shard mesh, no matter how the
  batcher happened to slice it. ``vectorized=True`` swaps the map for
  a vmap over row-sharded obs (the wide-hardware throughput mode;
  batched matmuls round the last ulp differently).

- **Checkpoint hot-reload**: :class:`CheckpointWatcher` polls a
  training run's ``checkpoint_root`` through
  ``resilience.discovery`` — the SAME newest-of stream-tail/periodic
  preference ``RecoveryManager.restore_latest`` uses — and stages the
  new policy state on the server's long-poll host. The batcher applies
  it atomically BETWEEN batches: in-flight requests finish under the
  params they started with, queued requests see the new version, and
  every response reports the ``params_version`` that computed it (no
  dropped, no blended requests). A trainer and a server pointed at the
  same root form the closed train→serve→refresh loop.

- :class:`PolicyDeployment` — the serve-core deployment wrapper:
  restores a policy from a checkpoint, owns a server + watcher, and
  surfaces queue/latency stats through ``_Replica.stats`` for the
  queue-wait autoscaler (``serve.serve.RunningDeployment``).
"""

from __future__ import annotations

import collections
import os
import pickle
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID
from ray_tpu.resilience import discovery
from ray_tpu.serve.long_poll import LongPollHost
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing


def device_ledger_summary() -> Optional[Dict[str, Any]]:
    """The device-ledger slice of ``stats()``: aggregate MFU from the
    telemetry ledger (``telemetry.device.snapshot()["totals"]``) plus
    the fraction of HBM still free on this replica's device. This is
    the serve autoscaler's SECOND signal source
    (``autoscaling_config={"signal": "ledger"}``) — batch fill says
    how hard the buckets run, this says whether another replica could
    even fit. Returns None when neither number is knowable (ledger
    disabled AND no memory stats), so stats() payloads stay honest.

    ``RAY_TPU_HBM_HEADROOM`` overrides the measured headroom (CPU
    hosts report no HBM; tests pin the gate with it)."""
    mfu = None
    try:
        from ray_tpu.telemetry import device as device_ledger

        if device_ledger.enabled():
            mfu = device_ledger.snapshot()["totals"]["mfu"]
    except Exception:
        pass
    headroom = None
    env = os.environ.get("RAY_TPU_HBM_HEADROOM")
    if env:
        try:
            headroom = float(env)
        except ValueError:
            headroom = None
    if headroom is None:
        try:
            import jax

            ms = jax.devices()[0].memory_stats()
            in_use = (ms or {}).get("bytes_in_use")
            limit = (ms or {}).get("bytes_limit")
            if in_use is not None and limit:
                headroom = max(0.0, 1.0 - in_use / limit)
        except Exception:
            pass
    if mfu is None and headroom is None:
        return None
    return {"mfu": mfu, "hbm_headroom": headroom}


def default_buckets(max_batch_size: int) -> Tuple[int, ...]:
    """Powers of two up to (and including) ``max_batch_size`` — the
    static batch shapes the server compiles. log2(B_max)+1 programs
    cover every occupancy with ≤ 2x padding waste."""
    out = []
    b = 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return tuple(out)


class TrailingWindow:
    """THE trailing-window percentile accessor of the serve plane.

    One implementation computes every queue-wait/latency signal —
    ``BatchedPolicyServer.stats()`` (what ``_Replica.stats`` forwards
    to the ``_autoscale_loop`` queue-wait targeting), the ingress
    admission controller's shedding decision, and the router's own
    wait tracking all read the SAME windowed numbers
    (regression-pinned by tests/test_ingress.py). Samples older than
    ``window_s`` decay out, so the signal relaxes once load does."""

    def __init__(self, window_s: float = 30.0, maxlen: int = 8192):
        self.window_s = float(window_s)
        self._samples: collections.deque = collections.deque(
            maxlen=maxlen
        )
        self._lock = threading.Lock()

    def observe(self, value: float, t: Optional[float] = None) -> None:
        with self._lock:
            self._samples.append(
                (time.perf_counter() if t is None else t, value)
            )

    def values(self) -> List[float]:
        cutoff = time.perf_counter() - self.window_s
        with self._lock:
            return [v for (t, v) in self._samples if t >= cutoff]

    def pct(self, q: float) -> Optional[float]:
        vals = self.values()
        if not vals:
            return None
        return float(np.percentile(np.asarray(vals), q))

    def snapshot(self) -> Dict[str, Any]:
        vals = self.values()
        arr = np.asarray(vals) if vals else None
        return {
            "p50_s": float(np.percentile(arr, 50))
            if arr is not None
            else None,
            "p99_s": float(np.percentile(arr, 99))
            if arr is not None
            else None,
            "n": len(vals),
            "window_s": self.window_s,
        }


class ServeFuture:
    """Per-request future a :meth:`BatchedPolicyServer.submit` returns.
    ``result()`` blocks for ``(action, extra)``; ``params_version``
    records which weights computed it (the hot-reload audit field)."""

    __slots__ = (
        "_event", "_value", "_error", "params_version", "latency_s",
    )

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._error: Optional[BaseException] = None
        self.params_version: Optional[int] = None
        self.latency_s: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = 60.0):
        if not self._event.wait(timeout):
            raise TimeoutError("policy-server request did not complete")
        if self._error is not None:
            raise self._error
        return self._value

    # -- server side ----------------------------------------------------

    def _resolve(self, value, version: int, latency_s: float) -> None:
        self._value = value
        self.params_version = version
        self.latency_s = latency_s
        self._event.set()

    def _reject(self, err: BaseException) -> None:
        self._error = err
        self._event.set()


class _Request:
    __slots__ = (
        "obs",
        "explore",
        "future",
        "t_submit",
        "flush",
        "trace",
    )

    def __init__(
        self, obs, explore, future, t_submit, flush=False, trace=None
    ):
        self.obs = obs
        self.explore = explore
        self.future = future
        self.t_submit = t_submit
        # flush hint: the tail of a router-coalesced bucket — the
        # batcher drains immediately instead of waiting out the batch
        # timeout for rows that are not coming
        self.flush = flush
        # trace context riding batch formation: the serve:batch span
        # joins the trace of its first traced request, so an ingress
        # request's spans stitch end to end
        self.trace = trace


class BatchedPolicyServer:
    """Coalesces concurrent single-observation requests into fused
    batched forwards on ``policy``'s mesh.

    The policy object is owned by the batcher thread after
    construction: param swaps, coefficient updates, and forwards all
    happen there, so no policy-level locking exists or is needed.
    """

    def __init__(
        self,
        policy,
        *,
        name: str = "policy",
        max_batch_size: int = 32,
        batch_wait_timeout_s: float = 0.002,
        explore: bool = False,
        buckets: Optional[Sequence[int]] = None,
        vectorized: bool = False,
        obs_filter=None,
        preprocessor=None,
        stats_window_s: float = 30.0,
        start: bool = True,
    ):
        self.policy = policy
        self.name = name
        self.max_batch_size = int(max_batch_size)
        if self.max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self.batch_wait_timeout_s = float(batch_wait_timeout_s)
        self.explore = bool(explore)
        self.buckets: Tuple[int, ...] = tuple(
            sorted(set(int(b) for b in buckets))
            if buckets
            else default_buckets(self.max_batch_size)
        )
        if self.buckets[-1] < self.max_batch_size:
            raise ValueError(
                "largest bucket must cover max_batch_size"
            )
        # exact (default): lax.map of the batch-1 action body —
        # bit-identical per row to sequential compute_actions (the
        # docs/serving.md determinism contract; batched matmuls round
        # the last ulp differently, measured on this backend).
        # vectorized: vmap + row-sharded obs — the wide-hardware
        # throughput mode, parity within ~1 ulp.
        self.vectorized = bool(vectorized)
        self.obs_filter = obs_filter
        self.preprocessor = preprocessor
        # the fused path needs a feedforward model + stateless
        # exploration; anything else serves sequentially (still
        # batched at the queue, one compute_actions per request)
        self.fused = bool(
            getattr(policy, "supports_batched_serve", False)
        )
        obs_space = policy.observation_space
        self._row_shape = tuple(obs_space.shape)
        self._row_dtype = np.dtype(obs_space.dtype)

        import jax

        from ray_tpu import sharding as sharding_lib
        from ray_tpu.utils.platform import ensure_compile_cache

        ensure_compile_cache()
        self._rep = sharding_lib.replicated(policy.mesh)
        # params enter the fused forward per their live placement tree
        # (replicated for ordinary policies; per-leaf model-axis
        # shardings for partitioned ones — the supports_batched_serve
        # gate already guaranteed the placement matches the rules)
        self._param_spec = (
            getattr(policy, "param_shardings", None) or self._rep
        )
        # the rng carry CONTINUES the policy's own stream: a reference
        # policy built from the same seed makes the same splits
        # sequentially — the parity contract's anchor
        self._carry = jax.device_put(policy._rng, self._rep)
        self._fns: Dict[Tuple[int, bool], Any] = {}
        # per-bucket program specs (sharding/registry.py): warmup()
        # walks this registry, and an algorithm-owned registry can
        # absorb the same rows so the driver's coverage sweep sees
        # serve programs alongside the learn-side ones
        self.program_registry = self._build_program_registry()

        # hot-reload staging rides a long-poll host: the watcher (any
        # thread) notifies, the batcher adopts between batches
        self._swap_host = LongPollHost()
        self._applied_swap = 0
        self.params_version = 1
        self.reload_info: Optional[Dict[str, Any]] = None
        telemetry_metrics.set_serve_params_version(
            self.name, self.params_version
        )

        self._queue: "collections.deque[_Request]" = collections.deque()
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._flush_hints = 0
        self.error: Optional[BaseException] = None
        self.requests_total = 0
        self.batches_total = 0
        self.batch_rows_total = 0
        self.padded_rows_total = 0
        # trailing-window percentile accessors — the ONE windowing
        # implementation the autoscaler (via stats()) and the ingress
        # shedding decision both read, so the signal decays once load
        # does (a lifetime p50 would pin scale-down forever)
        self.stats_window_s = float(stats_window_s)
        self._lat = TrailingWindow(self.stats_window_s)
        self._queue_wait = TrailingWindow(self.stats_window_s)

        self._thread: Optional[threading.Thread] = None
        if start:
            self.start()

    def start(self) -> None:
        """Start the batcher thread (idempotent). Deferred start lets
        a caller warm every bucket before traffic can race the carry."""
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"serve_batcher_{self.name}",
        )
        self._thread.start()

    # -- client side -----------------------------------------------------

    def _transform_obs(self, obs) -> np.ndarray:
        """Preprocessor + observation filter (``update=False`` —
        serving traffic must not mutate training filter statistics) +
        shape/dtype validation, shared by submit and submit_many."""
        if self.preprocessor is not None:
            obs = self.preprocessor.transform(obs)
        if self.obs_filter is not None:
            obs = self.obs_filter(obs, update=False)
        obs = np.asarray(obs, dtype=self._row_dtype)
        if obs.shape != self._row_shape:
            raise ValueError(
                f"obs shape {obs.shape} != policy row shape "
                f"{self._row_shape}"
            )
        return obs

    def submit(
        self,
        obs,
        explore: Optional[bool] = None,
        trace: Optional[Dict[str, Any]] = None,
    ) -> ServeFuture:
        """Enqueue ONE observation; returns its future. No flush hint:
        singleton submits rely on the batcher's timeout coalescing
        (the PR-9 continuous-batching contract)."""
        return self._enqueue([obs], explore, flush=False, trace=trace)[0]

    def submit_many(
        self,
        obs_rows,
        explore: Optional[bool] = None,
        trace: Optional[Dict[str, Any]] = None,
    ) -> List[ServeFuture]:
        """Enqueue a pre-coalesced run of observations ATOMICALLY (one
        lock acquisition, one batcher wakeup): the ingress router's
        dispatch path. The last request carries a flush hint, so the
        batcher drains the run immediately instead of waiting out
        ``batch_wait_timeout_s`` for rows that are not coming — a
        router-formed bucket turns into exactly one forward (plus
        whatever was already queued, which can only round UP to a
        bigger warm bucket, never retrace)."""
        return self._enqueue(obs_rows, explore, flush=True, trace=trace)

    def _enqueue(
        self, obs_rows, explore, flush: bool, trace=None
    ) -> List[ServeFuture]:
        if self._stop.is_set():
            raise RuntimeError("policy server is stopped")
        obs_rows = list(obs_rows)
        if not obs_rows:
            return []  # no rows → no flush hint to pop, don't arm one
        explore = self.explore if explore is None else bool(explore)
        now = time.perf_counter()
        reqs = []
        for i, obs in enumerate(obs_rows):
            reqs.append(
                _Request(
                    self._transform_obs(obs),
                    explore,
                    ServeFuture(),
                    now,
                    flush=flush and i == len(obs_rows) - 1,
                    trace=trace,
                )
            )
        with self._cv:
            self._queue.extend(reqs)
            depth = len(self._queue)
            self.requests_total += len(reqs)
            if flush:
                self._flush_hints += 1
            self._cv.notify_all()
        telemetry_metrics.inc_serve_requests(self.name, len(reqs))
        telemetry_metrics.set_serve_queue_depth(self.name, depth)
        return [r.future for r in reqs]

    def compute_actions(
        self, obs_batch, explore: Optional[bool] = None
    ):
        """Blocking convenience: submit every row of ``obs_batch`` and
        gather ``(actions, extras)`` numpy results in order."""
        futs = [self.submit(o, explore=explore) for o in obs_batch]
        outs = [f.result() for f in futs]
        actions = np.stack([a for a, _ in outs])
        extras = {
            k: np.stack([e[k] for _, e in outs])
            for k in (outs[0][1] if outs else {})
        }
        return actions, extras

    # -- hot reload ------------------------------------------------------

    def update_params(
        self, state, *, info: Optional[Dict[str, Any]] = None
    ) -> None:
        """Stage a new policy state (a ``Policy.get_state`` dict, a
        stream-snapshot policy entry, or a bare weights tree). The
        batcher thread adopts it atomically between batches; a fresh
        stage replaces an unadopted one (the server only ever wants
        the newest params)."""
        self._swap_host.notify("params", (state, info))
        with self._cv:
            self._cv.notify_all()

    # ray-tpu: thread=batcher
    def _maybe_apply_params(self) -> None:
        """Batcher-thread only: adopt the newest staged state, if any.
        Runs strictly between forwards, which is what makes the swap
        atomic per request."""
        ver, staged = self._swap_host.current("params")
        if ver <= self._applied_swap or staged is None:
            return
        state, info = staged
        policy = self.policy
        if isinstance(state, dict) and "weights" in state:
            policy.set_state(state)
        elif (
            isinstance(state, dict)
            and set(state.keys()) == {"state"}
        ):
            # bespoke-policy stream snapshot wrapper
            policy.set_state(state["state"])
        else:
            policy.set_weights(state)
        self._applied_swap = ver
        self.params_version += 1
        self.reload_info = info
        telemetry_metrics.set_serve_params_version(
            self.name, self.params_version
        )
        tracing.event(
            "serve:hot_reload",
            version=self.params_version,
            **{
                k: str(v)
                for k, v in (info or {}).items()
                if k in ("kind", "path")
            },
        )

    # -- fused forward ---------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _build_serve_fn(self, bucket: int, explore: bool):
        import jax
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib

        policy = self.policy
        rep = self._rep
        # exact mode computes replicated (every shard runs the same
        # row scan — no resharding collectives around a sequential
        # scan); vectorized mode shards rows across the mesh
        rows = rep
        if self.vectorized and (
            bucket % sharding_lib.num_shards(policy.mesh) == 0
        ):
            rows = sharding_lib.batch_sharded(policy.mesh)

        def fn(params, carry, obs, n_real, coeffs):
            # sequential per-request key stream: request i consumes
            # split i of the carry, EXACTLY like i sequential
            # compute_actions calls; padded rows (i >= n_real) leave
            # the carry untouched so occupancy never skews the stream
            def split_body(c, i):
                ks = jax.random.split(c)
                return jnp.where(i < n_real, ks[0], c), ks[1]

            carry, keys = jax.lax.scan(
                split_body, carry, jnp.arange(bucket)
            )

            def row(obs_i, key_i):
                actions, _, extra, _ = policy._action_step_body(
                    params,
                    obs_i[None],
                    key_i,
                    coeffs,
                    explore=explore,
                    expl_state=(),
                )
                return actions[0], {
                    k: v[0] for k, v in extra.items()
                }

            if self.vectorized:
                actions, extra = jax.vmap(row)(obs, keys)
            else:
                # scan of the EXACT batch-1 ops the sequential path
                # jits — the formulation that keeps per-row results
                # bitwise (vmap/batched matmuls do not, measured)
                actions, extra = jax.lax.map(
                    lambda t: row(*t), (obs, keys)
                )
            return actions, extra, carry

        return sharding_lib.sharded_jit(
            fn,
            in_specs=(self._param_spec, rep, rows, rep, rep),
            out_specs=(rows, rows, rep),
            donate_argnums=(1,),
            label=(
                f"serve[{self.name}:{bucket}"
                f":{'explore' if explore else 'greedy'}]"
            ),
        )

    # ray-tpu: thread=batcher hot-path
    def forward_padded(
        self, obs_rows: np.ndarray, explore: Optional[bool] = None
    ):
        """ONE fused forward for ``len(obs_rows)`` already-transformed
        rows, padded to the smallest covering bucket. Batcher-thread
        API (also driven directly by warmup/bench); returns
        ``(actions, extras)`` trimmed to the real rows."""
        explore = self.explore if explore is None else bool(explore)
        n = int(obs_rows.shape[0])
        bucket = self._bucket_for(n)
        padded = np.zeros(
            (bucket,) + self._row_shape, self._row_dtype
        )
        padded[:n] = obs_rows
        policy = self.policy
        policy.exploration.update_coeffs(
            policy.coeff_values, policy.global_timestep
        )
        params = policy.exploration.params_for_inference(
            policy, explore
        )
        coeffs = policy._coeff_array()
        key = (bucket, explore)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_serve_fn(
                bucket, explore
            )
        telemetry_metrics.add_h2d_bytes("serve", padded.nbytes)
        with tracing.start_span(
            "serve:forward", bucket=bucket, rows=n
        ):
            actions, extra, self._carry = fn(
                params, self._carry, padded, np.int32(n), coeffs
            )
        # ray-tpu: allow[RTA005] the serve forward's ONE counted drain: result materialization closes the ledger interval (drain_point below)
        actions = np.asarray(actions)[:n]
        extra = {k: np.asarray(v)[:n] for k, v in extra.items()}  # ray-tpu: allow[RTA005] same counted drain
        # results materialized host-side → the serve program finished;
        # close its ledger interval (timestamps only, no extra sync)
        from ray_tpu.telemetry import device as device_ledger

        device_ledger.drain_point()
        return actions, extra

    def _build_program_registry(self):
        """One warmable :class:`~ray_tpu.sharding.registry.ProgramSpec`
        per bucket (plus the explore-variant pattern): the registry IS
        the warmup plan."""
        import functools

        from ray_tpu.sharding import registry as registry_lib

        reg = registry_lib.ProgramRegistry()
        if not self.fused:
            return reg
        for b in self.buckets:
            reg.add_program(
                rf"serve\[{re.escape(self.name)}:{b}"
                rf":(?:explore|greedy)\]",
                kind="serve",
                regex=True,
                warm=functools.partial(self._warm_bucket, b, None),
                meta={"bucket": b},
            )
        return reg

    def warmup(self, explore: Optional[bool] = None) -> int:
        """Compile every bucket for ``explore`` (default: the server's
        flag) by walking the per-bucket program registry with
        zero-occupancy forwards — ``n_real=0`` leaves the rng carry
        bitwise untouched, so warmup never perturbs the request
        stream. Returns the bucket count; after this, steady traffic
        is recompile-free (``compile_stats``-asserted)."""
        if not self.fused:
            return 0
        if explore is None:
            # the registry's warm callables carry explore=None (the
            # server flag) — the common sweep the driver also runs
            report = self.program_registry.sweep(kind="serve")
            return report["warmed"]
        for b in self.buckets:
            self._warm_bucket(b, explore)
        return len(self.buckets)

    def _warm_bucket(self, bucket, explore):
        explore = self.explore if explore is None else bool(explore)
        # force THIS bucket (forward_padded would pick the smallest)
        padded = np.zeros(
            (bucket,) + self._row_shape, self._row_dtype
        )
        policy = self.policy
        policy.exploration.update_coeffs(
            policy.coeff_values, policy.global_timestep
        )
        params = policy.exploration.params_for_inference(
            policy, explore
        )
        coeffs = policy._coeff_array()
        key = (bucket, explore)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_serve_fn(
                bucket, explore
            )
        _, _, self._carry = fn(
            params, self._carry, padded, np.int32(0), coeffs
        )

    # -- batcher thread --------------------------------------------------

    # ray-tpu: thread=batcher
    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    while (
                        not self._queue
                        and not self._stop.is_set()
                        and not self._swap_pending()
                    ):
                        self._cv.wait()
                    if self._stop.is_set() and not self._queue:
                        break
                self._maybe_apply_params()
                batch = self._collect_batch()
                if batch:
                    self._process_batch(batch)
            # drain: adopt any final swap so stop() leaves a coherent
            # version, then exit
            self._maybe_apply_params()
        except BaseException as e:  # pragma: no cover - defensive
            self.error = e
            with self._cv:
                pending = list(self._queue)
                self._queue.clear()
            for req in pending:
                req.future._reject(e)

    # ray-tpu: thread=batcher
    def _swap_pending(self) -> bool:
        ver, _ = self._swap_host.current("params")
        return ver > self._applied_swap

    # ray-tpu: thread=batcher
    def _collect_batch(self) -> List[_Request]:
        """Drain up to ``max_batch_size`` same-explore requests, FIFO;
        a partial batch flushes ``batch_wait_timeout_s`` after its
        FIRST request arrived (whichever comes first — the
        timeout-flush contract)."""
        with self._cv:
            if not self._queue:
                return []
            deadline = (
                self._queue[0].t_submit + self.batch_wait_timeout_s
            )
            while (
                len(self._queue) < self.max_batch_size
                and not self._stop.is_set()
                # a flush hint means a pre-coalesced run's tail is
                # already queued — drain now, nothing more is coming
                and self._flush_hints == 0
            ):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cv.wait(remaining)
            batch: List[_Request] = []
            flag = self._queue[0].explore
            while (
                self._queue
                and len(batch) < self.max_batch_size
                and self._queue[0].explore == flag
            ):
                req = self._queue.popleft()
                if req.flush:
                    self._flush_hints -= 1
                batch.append(req)
            telemetry_metrics.set_serve_queue_depth(
                self.name, len(self._queue)
            )
            return batch

    # ray-tpu: thread=batcher hot-path
    def _process_batch(self, batch: List[_Request]) -> None:
        t0 = time.perf_counter()
        n = len(batch)
        explore = batch[0].explore
        version = self.params_version
        # the forward's span joins the trace of the batch's first
        # traced request (ingress→router→replica stitching); untraced
        # batches keep their own fresh span as before
        trace = next(
            (
                r.trace
                for r in batch
                if getattr(r, "trace", None) is not None
            ),
            None,
        )
        with tracing.context_span(
            trace, "serve:batch", rows=n, version=version
        ):
            try:
                if self.fused:
                    obs = np.stack([r.obs for r in batch])
                    actions, extra = self.forward_padded(
                        obs, explore=explore
                    )
                    results = [
                        (
                            actions[i],
                            {k: v[i] for k, v in extra.items()},
                        )
                        for i in range(n)
                    ]
                else:
                    # sequential fallback (recurrent / stateful
                    # exploration): correctness over coalescing
                    results = []
                    for r in batch:
                        a, _, ex = self.policy.compute_actions(
                            r.obs[None], explore=explore
                        )
                        results.append(
                            (a[0], {k: v[0] for k, v in ex.items()})
                        )
            except BaseException as e:
                for r in batch:
                    r.future._reject(e)
                raise
        t1 = time.perf_counter()
        self.batches_total += 1
        self.batch_rows_total += n
        self.padded_rows_total += self._bucket_for(n) - n
        telemetry_metrics.observe_serve_batch(self.name, n)
        # bucket occupancy of the forward that just ran: real rows /
        # executed rows (the fused path pads to a power-of-two bucket;
        # the sequential fallback runs exactly its rows)
        executed = self._bucket_for(n) if self.fused else n
        telemetry_metrics.set_serve_batch_fill(
            self.name, n / executed if executed else 0.0
        )
        for req, value in zip(batch, results):
            lat = t1 - req.t_submit
            wait = t0 - req.t_submit
            self._lat.observe(lat, t=t1)
            self._queue_wait.observe(wait, t=t1)
            telemetry_metrics.observe_serve_latency(self.name, lat)
            telemetry_metrics.observe_serve_queue_wait(
                self.name, wait
            )
            req.future._resolve(value, version, lat)

    # -- introspection ---------------------------------------------------

    def queue_wait_window(self) -> Dict[str, Any]:
        """THE queue-wait signal: trailing-window percentiles of how
        long requests sat queued before their forward launched. One
        accessor feeds BOTH consumers — ``stats()`` (whose
        ``queue_wait_p50_s`` the serve-core ``_autoscale_loop``
        targets) and the ingress admission controller's shedding
        decision — so the two planes can never act on different
        numbers (regression-pinned by tests/test_ingress.py)."""
        return self._queue_wait.snapshot()

    def latency_window(self) -> Dict[str, Any]:
        """Trailing-window end-to-end latency percentiles (same
        accessor discipline as :meth:`queue_wait_window`)."""
        return self._lat.snapshot()

    def stats(self) -> Dict[str, Any]:
        """Queue/latency surface (exact percentiles over the trailing
        ``stats_window_s``) — what ``_Replica.stats`` forwards to the
        queue-wait autoscaler and what the bench curves read."""
        with self._cv:
            depth = len(self._queue)
        lat = self.latency_window()
        qw = self.queue_wait_window()
        return {
            "queue_depth": depth,
            "requests_total": self.requests_total,
            "batches_total": self.batches_total,
            "mean_batch_rows": (
                self.batch_rows_total / self.batches_total
                if self.batches_total
                else 0.0
            ),
            "padded_rows_total": self.padded_rows_total,
            # cumulative bucket occupancy: of every row the fused
            # forwards executed, the fraction that was real work
            "batch_fill_fraction": (
                self.batch_rows_total
                / (self.batch_rows_total + self.padded_rows_total)
                if self.batch_rows_total
                else 0.0
            ),
            "latency_p50_s": lat["p50_s"],
            "latency_p99_s": lat["p99_s"],
            "queue_wait_p50_s": qw["p50_s"],
            "queue_wait_p99_s": qw["p99_s"],
            "params_version": self.params_version,
            "fused": self.fused,
            "vectorized": self.vectorized,
            # the ledger autoscale signal rides the same stats pull
            # the queue-wait targeting already makes (None when the
            # host can report neither MFU nor HBM headroom)
            "device": device_ledger_summary(),
            "buckets": list(self.buckets),
        }

    def stop(self, join_timeout: float = 30.0) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=join_timeout)


# -- checkpoint restore / hot-reload sources ----------------------------


def load_policy_state(
    kind: str, path: str, policy_id: str = DEFAULT_POLICY_ID
) -> Dict[str, Any]:
    """Policy state dict out of a restore target — a periodic
    checkpoint directory (``algorithm_state.pkl`` worker state) or a
    continuous-stream snapshot (``snapshot_*.pkl`` payload). Raises on
    torn/pruned targets; pollers retry next round."""
    if kind == "stream":
        with open(path, "rb") as f:
            payload = pickle.load(f)
        states = payload.get("policy_states", {})
    else:
        with open(
            os.path.join(path, "algorithm_state.pkl"), "rb"
        ) as f:
            state = pickle.load(f)
        states = state.get("worker", {}).get("policy_states", {})
    if policy_id not in states:
        raise KeyError(
            f"policy {policy_id!r} not in {kind} target {path!r} "
            f"(has {sorted(states)})"
        )
    return states[policy_id]


def restore_policy(
    checkpoint: str,
    *,
    policy_id: str = DEFAULT_POLICY_ID,
    config_overrides: Optional[Dict[str, Any]] = None,
    mesh=None,
):
    """Build a standalone serving policy from a periodic checkpoint.

    ``checkpoint`` is a checkpoint directory or a ``checkpoint_root``
    containing ``checkpoint_*`` ones (newest wins). The stored config
    names the algorithm (→ its default policy class) and the env (→
    observation/action spaces); the stored worker state provides
    weights and observation-filter statistics. Returns
    ``(policy, preprocessor, obs_filter, info)``.
    """
    path = checkpoint
    if not os.path.exists(
        os.path.join(path, "algorithm_state.pkl")
    ):
        latest = discovery.latest_periodic(path)
        if latest is None:
            raise ValueError(
                f"no checkpoint under {checkpoint!r} "
                "(expected algorithm_state.pkl or checkpoint_* dirs)"
            )
        path = latest
    import json

    from ray_tpu.algorithms.registry import get_algorithm_class
    from ray_tpu.core import serialization as _ser

    with open(
        os.path.join(path, "rllib_checkpoint.json")
    ) as f:
        meta = json.load(f)
    with open(
        os.path.join(path, "algorithm_config.pkl"), "rb"
    ) as f:
        config = _ser.loads(f.read())
    config = dict(config)
    config.update(config_overrides or {})
    config["num_workers"] = 0
    config.pop("_mesh", None)
    if mesh is not None:
        config["_mesh"] = mesh

    algo_cls = get_algorithm_class(meta["algorithm_name"])
    # class-level lookup only: no Algorithm (workers, telemetry, ...)
    # is built for serving
    policy_cls = algo_cls.get_default_policy_class(
        object.__new__(algo_cls), config
    )

    obs_space = config.get("observation_space")
    act_space = config.get("action_space")
    if obs_space is None or act_space is None:
        from ray_tpu.env.env_context import EnvContext
        from ray_tpu.env.registry import get_env_creator

        env = get_env_creator(config["env"])(
            EnvContext(config.get("env_config") or {}, worker_index=0)
        )
        obs_space = obs_space or env.observation_space
        act_space = act_space or env.action_space
        if hasattr(env, "close"):
            try:
                env.close()
            except Exception:
                pass

    from ray_tpu.models.catalog import ModelCatalog
    from ray_tpu.utils.filter import get_filter

    prep = ModelCatalog.get_preprocessor_for_space(obs_space)
    eff_obs_space = prep.observation_space
    policy = policy_cls(eff_obs_space, act_space, config)

    with open(
        os.path.join(path, "algorithm_state.pkl"), "rb"
    ) as f:
        worker_state = pickle.load(f).get("worker", {})
    pol_state = worker_state.get("policy_states", {}).get(policy_id)
    if pol_state is None:
        raise KeyError(
            f"policy {policy_id!r} not in checkpoint {path!r}"
        )
    policy.set_state(pol_state)

    obs_filter = get_filter(
        config.get("observation_filter", "NoFilter"),
        eff_obs_space.shape,
    )
    saved_filter = worker_state.get("filters", {}).get(policy_id)
    if saved_filter is not None:
        obs_filter.sync(saved_filter)
    info = {
        "checkpoint": path,
        "algorithm": meta["algorithm_name"],
        "policy_cls": policy_cls.__name__,
    }
    return policy, prep, obs_filter, info


class CheckpointWatcher:
    """Polls a training run's ``checkpoint_root`` and pushes every new
    restore target into ``apply_fn(state, info)``. Target selection is
    ``resilience.discovery``'s newest-of stream-tail/periodic
    preference — the same snapshot a recovering trainer would restore.
    Prune-safe: targets deleted between discovery and read are skipped
    and retried on the next poll."""

    def __init__(
        self,
        checkpoint_root: str,
        apply_fn: Callable[[Dict, Dict], None],
        *,
        policy_id: str = DEFAULT_POLICY_ID,
        poll_interval_s: float = 0.5,
        initial_version: Tuple[int, int] = (-1, -1),
        start: bool = True,
    ):
        self.checkpoint_root = checkpoint_root
        self.apply_fn = apply_fn
        self.policy_id = policy_id
        self.poll_interval_s = float(poll_interval_s)
        self.version = tuple(initial_version)
        self.num_reloads = 0
        self.last_target: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name="serve_ckpt_watcher",
            )
            self._thread.start()

    def poll_once(self) -> bool:
        """One discovery round; True when a newer target was applied."""
        kind, path = discovery.discover(self.checkpoint_root)
        if path is None:
            return False
        if kind == "checkpoint" and not os.path.exists(
            os.path.join(path, "algorithm_state.pkl")
        ):
            return False  # save in progress (state lands before meta)
        try:
            ver = discovery.target_version(kind, path)
        except Exception:
            return False  # pruned/torn between listdir and read
        if tuple(ver) <= tuple(self.version):
            return False
        try:
            state = load_policy_state(kind, path, self.policy_id)
        except Exception:
            return False
        self.apply_fn(
            state,
            {"kind": kind, "path": path, "version": tuple(ver)},
        )
        self.version = tuple(ver)
        self.last_target = path
        self.num_reloads += 1
        return True

    def _run(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            try:
                self.poll_once()
            except Exception:
                pass  # discovery must never kill the watcher

    def stats(self) -> Dict[str, Any]:
        return {
            "version": tuple(self.version),
            "num_reloads": self.num_reloads,
            "last_target": self.last_target,
        }

    def stop(self, join_timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=join_timeout)


class PolicyDeployment:
    """The serve-core deployment class for policy serving: restore →
    batch-serve → hot-reload. Deploy via :func:`policy_deployment`
    (replica actors behind a DeploymentHandle) or instantiate directly
    for in-process serving (tests, bench, notebooks).

    Calls take ``{"obs": [...], "explore": bool?}`` (or a bare obs
    array) and return ``{"action", "params_version", "logp"?}`` with
    JSON-friendly types, so the HTTP ingress can route them as-is.
    """

    def __init__(
        self,
        checkpoint: str,
        *,
        policy_id: str = DEFAULT_POLICY_ID,
        name: str = "policy",
        max_batch_size: int = 32,
        batch_wait_timeout_s: float = 0.002,
        explore: bool = False,
        watch: bool = True,
        poll_interval_s: float = 0.5,
        warmup: bool = True,
        config_overrides: Optional[Dict[str, Any]] = None,
    ):
        policy, prep, obs_filter, info = restore_policy(
            checkpoint,
            policy_id=policy_id,
            config_overrides=config_overrides,
        )
        self.info = info
        self.policy_id = policy_id
        self.server = BatchedPolicyServer(
            policy,
            name=name,
            max_batch_size=max_batch_size,
            batch_wait_timeout_s=batch_wait_timeout_s,
            explore=explore,
            obs_filter=obs_filter,
            preprocessor=prep,
            start=False,
        )
        if warmup:
            self.server.warmup()
        self.server.start()
        # the watcher follows the ROOT the checkpoint came from, so a
        # live trainer writing new checkpoints (or stream snapshots)
        # refreshes this replica continuously
        ckpt = info["checkpoint"]
        self.checkpoint_root = (
            os.path.dirname(ckpt)
            if os.path.basename(ckpt).startswith(
                discovery.PERIODIC_PREFIX
            )
            else ckpt
        )
        self.watcher = None
        if watch:
            try:
                init_ver = discovery.target_version(
                    "checkpoint", ckpt
                )
            except ValueError:
                init_ver = (-1, -1)
            self.watcher = CheckpointWatcher(
                self.checkpoint_root,
                lambda state, inf: self.server.update_params(
                    state, info=inf
                ),
                policy_id=policy_id,
                poll_interval_s=poll_interval_s,
                initial_version=init_ver,
            )

    def __call__(self, payload=None):
        if isinstance(payload, dict):
            obs = payload.get("obs")
            explore = payload.get("explore")
        else:
            obs, explore = payload, None
        fut = self.server.submit(
            np.asarray(obs), explore=explore
        )
        action, extra = fut.result()
        out = {
            "action": np.asarray(action).tolist(),
            "params_version": fut.params_version,
        }
        logp = extra.get("action_logp")
        if logp is not None:
            out["logp"] = float(np.asarray(logp))
        return out

    def compute_actions(self, obs_batch, explore=None):
        return self.server.compute_actions(
            obs_batch, explore=explore
        )

    def handle_rows(self, rows, explore=None, timeout_s: float = 60.0):
        """Batch entry point for the ingress coalescing router: one
        pre-coalesced bucket in, one JSON-friendly result row per
        request out (same fields as ``__call__``). The rows enqueue
        atomically (``submit_many``) so a router bucket becomes
        exactly one fused forward on this replica."""
        futs = self.server.submit_many(
            [np.asarray(r) for r in rows], explore=explore
        )
        out = []
        for fut in futs:
            action, extra = fut.result(timeout_s)
            row = {
                "action": np.asarray(action).tolist(),
                "params_version": fut.params_version,
            }
            logp = extra.get("action_logp")
            if logp is not None:
                row["logp"] = float(np.asarray(logp))
            out.append(row)
        return out

    def reconfigure(self, user_config) -> None:
        """Serve-core live config push: an explicit
        ``{"checkpoint": path}`` loads that target immediately (the
        push-based alternative to the polling watcher)."""
        if not user_config:
            return
        path = user_config.get("checkpoint")
        if path:
            kind = (
                "stream"
                if path.endswith(".pkl")
                else "checkpoint"
            )
            state = load_policy_state(kind, path, self.policy_id)
            self.server.update_params(
                state, info={"kind": kind, "path": path}
            )

    def preemption_notice(self):
        """Provider eviction probe — the SAME mechanism rollout
        workers poll (resilience/provider_notice.py), so one notice
        surface drains training and serving fleets alike."""
        from ray_tpu.resilience import provider_notice

        return provider_notice.probe()

    def stats(self) -> Dict[str, Any]:
        out = self.server.stats()
        if self.watcher is not None:
            out["reload"] = self.watcher.stats()
        out["checkpoint_root"] = self.checkpoint_root
        return out

    def stop(self) -> None:
        if self.watcher is not None:
            self.watcher.stop()
        self.server.stop()


def policy_deployment(
    checkpoint: str,
    *,
    name: str = "policy",
    num_replicas: int = 1,
    autoscaling_config: Optional[Dict] = None,
    **kwargs,
):
    """A ready-to-``serve.run`` Deployment serving ``checkpoint``:
    each replica actor restores the policy, batches its own requests,
    and hot-reloads from the checkpoint root independently."""
    from ray_tpu.serve.serve import Deployment

    return Deployment(
        PolicyDeployment,
        name,
        num_replicas=num_replicas,
        init_args=(checkpoint,),
        init_kwargs=dict(kwargs, name=name),
        autoscaling_config=autoscaling_config,
    )
