"""Worker process main loop.

The ray_tpu counterpart of the reference worker executable
(``python/ray/_private/workers/default_worker.py`` +
``_raylet.pyx execute_task :487``): a spawned process that executes stateless
tasks and hosts actor instances, exchanging commands/results with the driver
over a duplex pipe and large payloads through shared memory.

Workers pin JAX to the CPU platform — the single TPU chip belongs to the
driver/learner; rollout actors do inference with CPU XLA.
"""

from __future__ import annotations

import os
import sys
import traceback
from typing import Any, Dict


def _resolve_args(args, kwargs, shm_cache):
    """Replace _ObjArg markers with actual values (attaching shm)."""

    def resolve(v):
        if isinstance(v, _ObjArg):
            return v.load(shm_cache)
        return v

    return [resolve(a) for a in args], {k: resolve(v) for k, v in kwargs.items()}


class _ObjArg:
    """Marker for an object-store argument passed to a worker."""

    __slots__ = (
        "obj_id", "shm_name", "inline", "has_inline", "spill_loc",
        "remote_loc",
    )

    def __init__(
        self, obj_id, shm_name=None, inline=None, has_inline=False,
        spill_loc=None, remote_loc=None,
    ):
        self.obj_id = obj_id
        self.shm_name = shm_name
        self.inline = inline
        self.has_inline = has_inline
        # (spill_uri, path): the object lives in spill storage; the
        # worker reads it from there directly
        self.spill_loc = spill_loc
        # (host, port): the object's primary copy is NODE-RESIDENT on
        # a fleet agent; the worker pulls from its data server
        # directly — the driver never materializes the bytes
        self.remote_loc = remote_loc

    def _read_spill(self, loc):
        from ray_tpu.core import serialization as ser
        from ray_tpu.core.external_storage import storage_from_uri

        blob = storage_from_uri(loc[0]).get(loc[1])
        return ser.read_from_buffer(memoryview(blob))

    def load(self, shm_cache: Dict[str, Any]):
        from ray_tpu.core import serialization as ser

        if self.obj_id in shm_cache:
            return shm_cache[self.obj_id][1]
        if self.has_inline:
            shm_cache[self.obj_id] = (None, self.inline)
            return self.inline
        if self.spill_loc is not None:
            try:
                value = self._read_spill(self.spill_loc)
            except Exception:
                # spill file gone (freed / restored+evicted between
                # marshal and here): fall back to a driver-API get
                from ray_tpu.core.worker_api import worker_client

                client = worker_client()
                if client is None:
                    raise
                value = client.get(self.obj_id, timeout=120.0)
            shm_cache[self.obj_id] = (None, value)
            return value
        if self.remote_loc is not None:
            try:
                from ray_tpu.core.cluster import fetch_remote_object

                blob = fetch_remote_object(
                    self.remote_loc[0],
                    self.remote_loc[1],
                    self.obj_id,
                )
                value = ser.loads(blob)
            except Exception:
                # node died / object freed between marshal and here:
                # the driver get surfaces the canonical error (or the
                # value, if it was re-homed)
                from ray_tpu.core.worker_api import worker_client

                client = worker_client()
                if client is None:
                    raise
                value = client.get(self.obj_id, timeout=120.0)
            shm_cache[self.obj_id] = (None, value)
            return value
        from ray_tpu.core.object_store import Segment

        try:
            shm = Segment(name=self.shm_name)
        except FileNotFoundError:
            # the driver's LRU spilled (and unlinked) this segment
            # after the task marshalled its args — at-volume runs hit
            # this when the working set exceeds the store cap. Read
            # the spilled bytes straight from the storage backend when
            # possible (no driver round trip for the data), falling
            # back to a driver-API get (which restores transparently).
            from ray_tpu.core.worker_api import worker_client

            client = worker_client()
            if client is None:
                raise
            value = None
            try:
                loc = client.spill_location(self.obj_id)
                if loc is not None:
                    value = self._read_spill(loc)
            except Exception:
                value = None
            if value is None:
                value = client.get(self.obj_id, timeout=120.0)
            shm_cache[self.obj_id] = (None, value)
            return value
        value = ser.read_from_buffer(shm.buf)
        # Keep the segment mapped as long as the value is cached: the
        # deserialized arrays are zero-copy views into it.
        shm_cache[self.obj_id] = (shm, value)
        return value


def worker_main(conn, worker_id: str, env_overrides: Dict[str, str]):
    """Entry point for spawned worker processes."""
    os.environ.update(env_overrides or {})
    # per-worker log files (reference: per-process files in the session
    # dir, tailed by the LogMonitor)
    log_dir = os.environ.get("RAY_TPU_LOG_DIR")
    if log_dir:
        try:
            os.makedirs(log_dir, exist_ok=True)
            sys.stdout = open(
                os.path.join(log_dir, f"worker-{worker_id}.out"),
                "a",
                buffering=1,
            )
            sys.stderr = open(
                os.path.join(log_dir, f"worker-{worker_id}.err"),
                "a",
                buffering=1,
            )
        except OSError:
            pass
    # Workers run jax on the CPU: a chip belongs to one process at a
    # time, and that process is the driver/learner — a worker that
    # initialized the default backend would fail or hang on a chip its
    # parent holds. The inherited env may name the TPU and jax may
    # already be imported (it reads JAX_PLATFORMS at import), so the
    # platform is pinned at the config level too, before anything
    # initializes a backend. Override via
    # worker_env={"RAY_TPU_WORKER_PLATFORM": ...} in ray.init for
    # workers that legitimately own a device.
    platform = (env_overrides or {}).get(
        "RAY_TPU_WORKER_PLATFORM", "cpu"
    )
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)

    from ray_tpu.core import serialization as ser

    func_cache: Dict[str, Any] = {}
    shm_cache: Dict[str, Any] = {}
    actors: Dict[str, Any] = {}
    result_shms = []  # keep created segments alive until driver owns them

    # Bulk-result data plane: a persistent native SPSC ring to the driver
    # (the plasma role for produced-once/consumed-once payloads, e.g.
    # rollout SampleBatches — reference src/ray/object_manager/plasma/
    # store.h:55). Size-routed like plasma vs inline objects: tiny
    # results stay on the pipe; [ring_min, ring_max] rides the ring
    # (zero syscalls/record beats per-record segment churn — measured
    # 1.3-1.7x faster at 64KB-512KB); larger records go to a dedicated
    # shm segment whose lazy zero-copy driver views win once the
    # per-record copy costs more than mmap+unlink (~1MB+). Gate via
    # worker_env RAY_TPU_DISABLE_RING=1.
    ring = None
    # 16MB default: ~21 max-band (768KB) records of headroom, and small
    # enough that the create-side MAP_POPULATE prefault stays cheap.
    ring_cap = int(
        os.environ.get("RAY_TPU_RING_CAPACITY", 16 * 1024 * 1024)
    )
    ring_min = int(os.environ.get("RAY_TPU_RING_MIN_BYTES", 32 * 1024))
    ring_max = min(
        int(os.environ.get("RAY_TPU_RING_MAX_BYTES", 768 * 1024)),
        ring_cap // 2,
    )
    if os.environ.get("RAY_TPU_DISABLE_RING") != "1":
        try:
            from ray_tpu.core.shm_ring import ShmRing

            ring = ShmRing.create(f"rtring_{worker_id}", ring_cap)
            conn.send({"status": "ring", "ring_name": ring.name})
        except Exception:
            ring = None

    import threading

    # one logical producer: concurrent actor threads serialize their
    # sends (pipe AND ring — the ring is SPSC; the lock keeps this
    # process a single producer)
    send_lock = threading.Lock()
    actor_pools: Dict[str, Any] = {}  # actor_id -> ThreadPoolExecutor

    def send_error(msg, e, tb):
        from ray_tpu.util import tracing as _tracing

        _err_spans = _tracing.drain_finished()
        with send_lock:
            conn.send(
                {
                    "task_id": msg.get("task_id"),
                    "status": "err",
                    "error": str(e),
                    "error_cls": type(e).__name__,
                    "traceback": tb,
                    **({"spans": _err_spans} if _err_spans else {}),
                }
            )

    def send_value(msg, value):
        # Serialize result; bulk payloads ride the ring, very large
        # ones a fresh shm segment, small ones the pipe.
        meta, buffers = ser.serialize(value)
        size = ser.serialized_size(meta, buffers)
        # finished spans ride the result message back to the driver's
        # tracer (the reference exports via its OTel pipeline instead)
        from ray_tpu.util import tracing

        spans = tracing.drain_finished()
        extra = {"spans": spans} if spans else {}
        with send_lock:
            if ring is not None and ring_min <= size <= ring_max:
                try:
                    # Zero-copy: the serializer writes straight into
                    # the mapped ring memory (reserve→write→commit).
                    pushed = ring.push_serialized(
                        meta, buffers, size, timeout=5.0
                    )
                except (BrokenPipeError, ValueError):
                    pushed = False
                if pushed:
                    conn.send(
                        {
                            "task_id": msg["task_id"],
                            "status": "ok_ring",
                            "nbytes": size,
                            **extra,
                        }
                    )
                    return
                # ring congested/unusable: fall through
            if size >= 256 * 1024:
                from ray_tpu.core.object_store import Segment

                shm = Segment(
                    create=True,
                    size=size,
                    name=f"rt_{msg['task_id'][:24]}",
                )
                ser.write_to_buffer(shm.buf, meta, buffers)
                conn.send(
                    {
                        "task_id": msg["task_id"],
                        "status": "ok_shm",
                        "shm_name": shm.name,
                        **extra,
                    }
                )
                shm.close()  # driver owns the segment now
            else:
                conn.send(
                    {
                        "task_id": msg["task_id"],
                        "status": "ok",
                        "value_blob": ser.dumps(value),
                        **extra,
                    }
                )

    while True:
        try:
            msg = conn.recv()
        except (EOFError, KeyboardInterrupt):
            break
        mtype = msg["type"]
        if mtype == "shutdown":
            break
        try:
            if mtype == "register_func":
                func_cache[msg["func_id"]] = ser.loads(msg["func"])
                continue
            elif mtype == "runtime_env":
                # job-level env from ray.init(runtime_env=...)
                from ray_tpu.core.runtime_env import apply_runtime_env

                apply_runtime_env(msg.get("packed"))
                continue
            elif mtype == "task":
                from ray_tpu.util import tracing

                fn = func_cache[msg["func_id"]]
                args, kwargs = _resolve_args(
                    *ser.loads(msg["payload"]), shm_cache
                )
                _span = tracing.remote_span(
                    msg.get("trace_ctx"),
                    f"task:{getattr(fn, '__name__', 'fn')}",
                )
                renv = msg.get("runtime_env")
                if renv:
                    # pooled workers: the WHOLE env (vars, cwd,
                    # sys.path) applies only around the call, so a
                    # later unrelated task on this worker doesn't
                    # inherit another task's working_dir or modules.
                    # Extracted archives persist via the cache. Actors
                    # get dedicated processes, so theirs persist
                    # wholesale.
                    from ray_tpu.core.runtime_env import (
                        apply_runtime_env,
                    )

                    saved = {
                        k: os.environ.get(k)
                        for k in (renv.get("env_vars") or {})
                    }
                    saved_cwd = os.getcwd()
                    saved_path = list(sys.path)
                    apply_runtime_env(renv)
                    try:
                        with _span:
                            value = fn(*args, **kwargs)
                    finally:
                        for k, old in saved.items():
                            if old is None:
                                os.environ.pop(k, None)
                            else:
                                os.environ[k] = old
                        try:
                            os.chdir(saved_cwd)
                        except OSError:
                            pass
                        sys.path[:] = saved_path
                else:
                    with _span:
                        value = fn(*args, **kwargs)
            elif mtype == "actor_init":
                if msg.get("runtime_env"):
                    from ray_tpu.core.runtime_env import (
                        apply_runtime_env,
                    )

                    apply_runtime_env(msg["runtime_env"])
                cls = ser.loads(msg["cls"])
                args, kwargs = _resolve_args(
                    *ser.loads(msg["payload"]), shm_cache
                )
                actors[msg["actor_id"]] = cls(*args, **kwargs)
                mc = int(msg.get("max_concurrency", 1))
                if mc > 1:
                    # threaded actor (reference max_concurrency,
                    # actor.py:options): calls dispatch to a pool and
                    # may complete out of order; the user class is
                    # responsible for its own thread safety — same
                    # contract as the reference
                    from concurrent.futures import ThreadPoolExecutor

                    actor_pools[msg["actor_id"]] = ThreadPoolExecutor(
                        max_workers=mc,
                        thread_name_prefix=f"actor_{msg['actor_id'][:8]}",
                    )
                value = None
            elif mtype == "actor_call":
                from ray_tpu.util import tracing

                actor = actors[msg["actor_id"]]
                args, kwargs = _resolve_args(
                    *ser.loads(msg["payload"]), shm_cache
                )
                pool = actor_pools.get(msg["actor_id"])
                if pool is not None:

                    def _run_concurrent(
                        msg=msg, actor=actor, args=args, kwargs=kwargs
                    ):
                        try:
                            with tracing.remote_span(
                                msg.get("trace_ctx"),
                                f"actor:{type(actor).__name__}."
                                f"{msg['method']}",
                            ):
                                out = getattr(actor, msg["method"])(
                                    *args, **kwargs
                                )
                        except BaseException as e:  # noqa: BLE001
                            send_error(
                                msg, e, traceback.format_exc()
                            )
                            return
                        send_value(msg, out)

                    pool.submit(_run_concurrent)
                    continue
                with tracing.remote_span(
                    msg.get("trace_ctx"),
                    f"actor:{type(actor).__name__}.{msg['method']}",
                ):
                    value = getattr(actor, msg["method"])(
                        *args, **kwargs
                    )
            elif mtype == "free":
                for oid in msg["obj_ids"]:
                    ent = shm_cache.pop(oid, None)
                    if ent and ent[0] is not None:
                        ent[0].close()
                continue
            else:
                raise ValueError(f"unknown message type {mtype}")
        except BaseException as e:  # noqa: BLE001 — report, don't die
            tb = traceback.format_exc()
            try:
                send_error(msg, e, tb)
            except Exception:
                break
            continue

        if msg.get("task_id") is None:
            continue
        send_value(msg, value)

    for pool in actor_pools.values():
        pool.shutdown(wait=False)
    if ring is not None:
        try:
            ring.mark_closed()
            ring.close()
        except Exception:
            pass
    for shm, _ in (v for v in shm_cache.values() if v[0] is not None):
        try:
            shm.close()
        except Exception:
            pass
    try:
        conn.close()
    except Exception:
        pass
    sys.exit(0)
