"""Ray-like task/actor API over a process-based local backend.

Counterpart of the reference's Python core API
(``python/ray/_private/worker.py:984`` init, ``:2086`` get, remote_function /
actor decorator machinery ``remote_function.py:34`` / ``actor.py:377``) and,
underneath, the roles of raylet scheduling + CoreWorker submission
(``src/ray/core_worker/core_worker.h:462``), scoped to one host.

TPU-first disposition (SURVEY §2.1 table note): the heavy C++ process fabric
(GCS, raylet, gRPC transports) is replaced by a driver-resident scheduler +
spawned CPU worker processes + a shared-memory object plane. On a TPU pod
the accelerator-side "scheduling" is static SPMD placement via jax meshes;
this API exists for the CPU rollout fleet around the learner. Multi-host
fan-out rides jax.distributed (DCN) rather than a bespoke RPC stack.
"""

from __future__ import annotations

import atexit
import functools
import os
import queue
import threading
import time
import traceback
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import multiprocessing as mp

from ray_tpu.core import serialization as ser
from ray_tpu.core.object_store import (
    ObjectRef,
    ObjectStore,
    RayActorError,
    RayOutOfMemoryError,
    RayTaskError,
    WorkerCrashedError,
)
from ray_tpu.core.worker_proc import worker_main, _ObjArg

_INLINE_ARG_MAX = 256 * 1024


class _WorkerHandle:
    def __init__(self, proc, conn, worker_id: str, dedicated: bool):
        self.proc = proc
        self.conn = conn
        self.worker_id = worker_id
        self.dedicated = dedicated  # actor-owned process
        self.idle = True
        self.dead = False
        self.registered_funcs = set()
        self.inflight: Dict[str, "_TaskRecord"] = {}
        self.send_lock = threading.Lock()
        self.recv_thread: Optional[threading.Thread] = None
        self.ring = None  # bulk-result ShmRing (attached lazily)
        self.ring_results = 0


class _TaskRecord:
    def __init__(self, task_id, msg, retries_left, name,
                 num_cpus: float = 1.0, resources=None,
                 placement_group=None, bundle_index: int = -1):
        self.task_id = task_id
        self.msg = msg
        self.retries_left = retries_left
        self.name = name
        self.num_cpus = float(num_cpus)
        self.resources = dict(resources or {})
        self.placement_group = placement_group
        self.bundle_index = int(bundle_index)
        self.acquired_bundle = -1  # set at admission
        self.submit_time = time.time()


class _ActorRecord:
    def __init__(self, actor_id, worker, cls_blob, init_msg, max_restarts,
                 daemon: bool = True):
        self.actor_id = actor_id
        self.worker = worker
        self.cls_blob = cls_blob
        self.init_msg = init_msg
        self.max_restarts = max_restarts
        self.daemon = daemon
        self.restarts = 0
        self.name: Optional[str] = None
        self.dead = False


class _Runtime:
    """Global driver state (reference: the global ``Worker`` in
    ``_private/worker.py:397``)."""

    def __init__(self, num_cpus: int, object_store_memory=None,
                 resources=None):
        self.num_cpus = num_cpus
        # Resource-aware scheduling (reference ClusterResourceScheduler
        # cluster_resource_scheduler.h:45, fixed-point bookkeeping):
        # dispatch admits a task only when its CPU + custom-resource
        # demand fits; placement groups carve out their own pools.
        self.available_cpus = float(num_cpus)
        self.total_resources = dict(resources or {})
        self.available_resources = dict(self.total_resources)
        self.store = ObjectStore(max_bytes=object_store_memory)
        # workers currently parked in a nested blocking get — they
        # lend their CPU and pool slot to their children
        self.blocked_workers = 0
        self.ctx = mp.get_context("spawn")
        self.lock = threading.RLock()
        self.pool: List[_WorkerHandle] = []
        self.actors: Dict[str, _ActorRecord] = {}
        self.named_actors: Dict[str, str] = {}
        self.pending: "queue.deque" = None
        import collections

        self.pending = collections.deque()
        self.timeline_events: List[Dict] = []
        self.shutting_down = False
        self._worker_env = {}
        self._job_runtime_env = None
        # Cross-host fleet (core/cluster.py): the head's listener and
        # the map of actors placed on remote agents
        self.cluster = None
        self.remote_actors: Dict[str, Any] = {}
        # actor_id -> (pg, num_cpus, bundle_index) for actors charged
        # against a placement-group bundle (released at kill)
        self._actor_pg_charges: Dict[str, Any] = {}
        # Durable job/actor metadata tables (the gcs_job_manager /
        # gcs_actor_manager storage role, reference
        # gcs/gcs_table_storage.cc): enabled via ray.init(state_path=)
        # or RAY_TPU_STATE_PATH. Driver death keeps the record; a
        # restarted driver (or `list_jobs`) can inspect prior runs.
        self.state_store = None
        self.job_id = f"job_{uuid.uuid4().hex[:8]}"
        state_path = os.environ.get("RAY_TPU_STATE_PATH")
        if state_path:
            self._open_state_store(state_path)

    def _open_state_store(self, path: str) -> None:
        import json as _json
        import time as _time

        from ray_tpu.core.store_client import make_store_client

        self.state_store = make_store_client(path)
        self.state_store.put(
            "jobs",
            self.job_id,
            _json.dumps(
                {
                    "job_id": self.job_id,
                    "status": "RUNNING",
                    "start_time": _time.time(),
                    "pid": os.getpid(),
                }
            ).encode(),
        )

    def _record_named_actor(self, name: str, actor_id: str, cls_name: str):
        if self.state_store is None:
            return
        import json as _json
        import time as _time

        self.state_store.put(
            "actors",
            name,
            _json.dumps(
                {
                    "name": name,
                    "actor_id": actor_id,
                    "class": cls_name,
                    "job_id": self.job_id,
                    "time": _time.time(),
                }
            ).encode(),
        )

    # -- worker lifecycle ------------------------------------------------

    def _worker_api_server(self):
        """Lazy singleton worker-API listener (nested ray.* calls)."""
        with self.lock:
            if getattr(self, "_api_server", None) is None:
                from ray_tpu.core.worker_api import WorkerAPIServer

                self._api_server = WorkerAPIServer(self)
            return self._api_server

    def _spawn_worker(
        self, dedicated: bool = False, daemon: bool = True
    ) -> _WorkerHandle:
        worker_id = uuid.uuid4().hex[:12]
        parent_conn, child_conn = self.ctx.Pipe(duplex=True)
        env = dict(self._worker_env)
        # nested ray.* calls inside this worker route back here over
        # the worker-API channel (core/worker_api.py)
        env.setdefault(
            "RAY_TPU_DRIVER_API", self._worker_api_server().address
        )
        env["RAY_TPU_WORKER_ID"] = worker_id
        # daemon=False is for actors that must spawn children of their
        # own (e.g. tune trial actors hosting an Algorithm with rollout
        # workers) — daemonic processes cannot have children.
        proc = self.ctx.Process(
            target=worker_main,
            args=(child_conn, worker_id, env),
            daemon=daemon,
            name=f"ray_tpu_worker_{worker_id}",
        )
        proc.start()
        child_conn.close()
        w = _WorkerHandle(proc, parent_conn, worker_id, dedicated)
        if self._job_runtime_env:
            # job-level runtime_env (ray.init) reaches every worker
            # before any task does (pipe ordering)
            w.conn.send(
                {
                    "type": "runtime_env",
                    "packed": self._job_runtime_env,
                }
            )
        t = threading.Thread(
            target=self._recv_loop, args=(w,), daemon=True,
            name=f"recv_{worker_id}",
        )
        w.recv_thread = t
        t.start()
        return w

    def _recv_loop(self, w: _WorkerHandle):
        while True:
            try:
                msg = w.conn.recv()
            except (EOFError, OSError):
                self._on_worker_death(w)
                return
            self._on_result(w, msg)

    def _on_result(self, w: _WorkerHandle, msg: Dict):
        status = msg["status"]
        if status == "ring":
            # Worker announced its bulk-result ring: attach as consumer.
            try:
                from ray_tpu.core.shm_ring import ShmRing

                w.ring = ShmRing.attach(msg["ring_name"])
            except Exception:
                w.ring = None
            return
        if msg.get("spans"):
            from ray_tpu.util import tracing

            tracing.record_spans(msg["spans"])
        task_id = msg.get("task_id")
        with self.lock:
            rec = w.inflight.pop(task_id, None)
        if status == "ok":
            self.store.put(
                task_id, ser.loads(msg["value_blob"]), use_shm=False
            )
        elif status == "ok_ring":
            # The record was pushed before the control message was sent,
            # so the next ring record is this task's payload (SPSC FIFO).
            data = w.ring.pop_bytes(timeout=30.0) if w.ring else None
            if data is None:
                self.store.put_error(
                    task_id,
                    WorkerCrashedError(
                        "bulk result missing from worker ring"
                    ),
                )
            else:
                w.ring_results += 1
                self.store.put(
                    task_id,
                    ser.read_from_buffer(memoryview(data)),
                    use_shm=False,
                )
        elif status == "ok_shm":
            self.store.attach_shm(task_id, msg["shm_name"])
        else:
            name = rec.name if rec else "unknown"
            err: BaseException = RayTaskError(name, msg["traceback"])
            self.store.put_error(task_id, err)
        if rec:
            self._record_event(rec, w)
            with self.lock:
                self._release(rec)
        with self.lock:
            if not w.dedicated:
                w.idle = True
        self._dispatch_pending()

    def _on_worker_death(self, w: _WorkerHandle):
        with self.lock:
            if w.dead:
                return
            w.dead = True
            if w.ring is not None:
                try:
                    w.ring.close()
                except Exception:
                    pass
                w.ring = None
            inflight = list(w.inflight.values())
            w.inflight.clear()
            for trec in inflight:
                self._release(trec)
            if not w.dedicated:
                if w in self.pool:
                    self.pool.remove(w)
            actor_rec = None
            for rec in self.actors.values():
                if rec.worker is w:
                    actor_rec = rec
                    break
        if self.shutting_down:
            return
        oom_reason = getattr(w, "oom_reason", None)
        for trec in inflight:
            if trec.retries_left > 0 and trec.msg["type"] == "task":
                trec.retries_left -= 1
                self._enqueue(trec)
            else:
                err: BaseException
                if oom_reason is not None:
                    err = RayOutOfMemoryError(
                        f"Task {trec.name} was killed by the memory "
                        f"monitor.\n{oom_reason}"
                    )
                elif actor_rec is not None:
                    err = RayActorError(
                        f"Actor {actor_rec.actor_id} died executing "
                        f"{trec.name}"
                    )
                else:
                    err = WorkerCrashedError(
                        f"Worker died executing {trec.name}"
                    )
                self.store.put_error(trec.task_id, err)
        if actor_rec is not None:
            self._maybe_restart_actor(actor_rec)
        self._dispatch_pending()

    def _maybe_restart_actor(self, rec: _ActorRecord):
        with self.lock:
            if rec.restarts >= rec.max_restarts or self.shutting_down:
                rec.dead = True
                return
            rec.restarts += 1
            w = self._spawn_worker(dedicated=True, daemon=rec.daemon)
            rec.worker = w
        with w.send_lock:
            w.conn.send(rec.init_msg)

    # -- scheduling ------------------------------------------------------

    def _enqueue(self, trec: _TaskRecord):
        with self.lock:
            self.pending.append(trec)
        self._dispatch_pending()

    def _fits(self, trec) -> bool:
        """Lock held: does the task's resource demand fit right now?"""
        pg = trec.placement_group
        if pg is not None:
            # head dispatch only admits against HEAD-hosted bundles;
            # bundles reserved on fleet agents admit via _try_spill
            return pg._fits(
                trec.num_cpus, trec.bundle_index, node_id=None
            )
        if trec.num_cpus > self.available_cpus + 1e-9:
            return False
        for k, v in trec.resources.items():
            if v > self.available_resources.get(k, 0.0) + 1e-9:
                return False
        return True

    def _acquire(self, trec) -> bool:
        """→ False when a placement-group charge lost the race between
        _fits and here (an actor creation filled the bundle): the
        caller requeues instead of dispatching an uncharged task."""
        pg = trec.placement_group
        if pg is not None:
            trec.acquired_bundle = pg._acquire(
                trec.num_cpus, trec.bundle_index
            )
            return trec.acquired_bundle >= 0
        self.available_cpus -= trec.num_cpus
        for k, v in trec.resources.items():
            self.available_resources[k] = (
                self.available_resources.get(k, 0.0) - v
            )
        return True

    def _release(self, trec) -> None:
        pg = trec.placement_group
        if pg is not None:
            pg._release(trec.num_cpus, trec.acquired_bundle)
            return
        self.available_cpus += trec.num_cpus
        for k, v in trec.resources.items():
            self.available_resources[k] = (
                self.available_resources.get(k, 0.0) + v
            )

    def _dispatch_pending(self):
        while True:
            spill = False
            with self.lock:
                if not self.pending:
                    return
                w = None
                for cand in self.pool:
                    if cand.idle and not cand.dead:
                        w = cand
                        break
                # workers parked in a nested ray.get lend out both
                # their CPU and their pool slot (worker_api.py)
                cap = self.num_cpus + getattr(
                    self, "blocked_workers", 0
                )
                if w is None and len(self.pool) < cap:
                    w = self._spawn_worker()
                    self.pool.append(w)
                if w is None:
                    spill = True
                else:
                    # FIFO with skip: the first pending task whose
                    # resource demand fits (reference
                    # cluster_task_manager queueing)
                    trec = None
                    for i, cand_t in enumerate(self.pending):
                        if self._fits(cand_t):
                            trec = cand_t
                            del self.pending[i]
                            break
                    if trec is None:
                        spill = True
                    elif not self._acquire(trec):
                        # pg bundle filled between _fits and the
                        # charge: requeue, try the spill path
                        self.pending.appendleft(trec)
                        trec = None
                        spill = True
                    else:
                        w.idle = False
                        w.inflight[trec.task_id] = trec
            if spill:
                # local head is saturated: push queued work to fleet
                # agents (the reference's lease spillback —
                # cluster_resource_scheduler.h:45)
                self._try_spill()
                return
            self._send_task(w, trec)

    def _try_spill(self):
        """Ship queued stateless tasks to fleet agents with free CPU
        capacity. Plain CPU tasks spill to the freest node;
        placement-group tasks spill to THE node hosting a fitting
        bundle (cross-node gang scheduling,
        ``raylet/placement_group_resource_manager.h`` commit side).
        Custom-resource tasks stay head-local — agents register CPUs
        only. Args marshal through the node's once-per-node pool."""
        cluster = getattr(self, "cluster", None)
        if cluster is None:
            return
        while True:
            nodes = [
                n for n in cluster.nodes.values() if not n.dead
            ]
            if not nodes:
                return
            pick = None
            with self.lock:
                for i, t in enumerate(self.pending):
                    if (
                        t.resources
                        or t.msg.get("type") != "task"
                        or getattr(t, "orig_args", None) is None
                    ):
                        continue
                    pg = t.placement_group
                    if pg is not None:
                        # the bundle's node is fixed at reservation:
                        # admit against it, run on it (CPUs already
                        # reserved there — no node-ledger charge)
                        for node in nodes:
                            if pg._fits(
                                t.num_cpus,
                                t.bundle_index,
                                node_id=node.node_id,
                            ):
                                t.acquired_bundle = pg._acquire(
                                    t.num_cpus,
                                    t.bundle_index,
                                    node_id=node.node_id,
                                )
                                if t.acquired_bundle >= 0:
                                    t.pg_spilled = True
                                    pick = (t, node)
                                    del self.pending[i]
                                break
                        if pick is not None:
                            break
                        continue
                    node = max(nodes, key=lambda n: n.free_cpus())
                    if node.free_cpus() >= t.num_cpus:
                        pick = (t, node)
                        del self.pending[i]
                        break
                if pick is None:
                    return
            t, node = pick
            try:
                m_args, m_kwargs = node.marshal_args(
                    t.orig_args, t.orig_kwargs
                )
                payload = ser.dumps((m_args, m_kwargs))
                sent = node.submit_task(t, payload)
            except BaseException:
                sent = False
            if not sent:
                # un-charge before requeue — the retry re-acquires,
                # and a leaked charge would shrink the bundle forever
                if getattr(t, "pg_spilled", False):
                    t.placement_group._release(
                        t.num_cpus, t.acquired_bundle
                    )
                    t.pg_spilled = False
                    t.acquired_bundle = -1
                with self.lock:
                    self.pending.appendleft(t)
                return

    def _send_task(self, w: _WorkerHandle, trec: _TaskRecord):
        msg = trec.msg
        try:
            with w.send_lock:
                if (
                    msg["type"] == "task"
                    and msg["func_id"] not in w.registered_funcs
                ):
                    w.conn.send(
                        {
                            "type": "register_func",
                            "func_id": msg["func_id"],
                            "func": msg["func_blob"],
                        }
                    )
                    w.registered_funcs.add(msg["func_id"])
                wire = {k: v for k, v in msg.items() if k != "func_blob"}
                w.conn.send(wire)
        except (BrokenPipeError, OSError):
            self._on_worker_death(w)

    def _record_event(self, trec: _TaskRecord, w: _WorkerHandle):
        now = time.time()
        self.timeline_events.append(
            {
                "name": trec.name,
                "cat": "task",
                "ph": "X",
                "ts": trec.submit_time * 1e6,
                "dur": (now - trec.submit_time) * 1e6,
                "pid": 1,
                "tid": hash(w.worker_id) % 10000,
            }
        )

    # -- argument marshalling --------------------------------------------

    def _marshal_arg(self, v):
        if isinstance(v, ObjectRef):
            if not self.store.is_ready(v.id):
                raise _UnreadyDep(v.id)
            shm = self.store.shm_name(v.id)
            if shm:
                return _ObjArg(v.id, shm_name=shm)
            # already spilled: ship the storage location, not the
            # bytes — the worker reads the spill file directly instead
            # of this path restoring the value into driver memory and
            # inlining it over the pipe
            loc = self.store.spill_location(v.id)
            if loc is not None:
                return _ObjArg(v.id, spill_loc=loc)
            # node-resident (fleet data plane): ship the node's data
            # server address — a local worker pulls peer-style, and
            # the driver never materializes the bytes (pulling here
            # would defeat the per-node store for every head-executed
            # task naming a fleet-produced ref)
            rloc = self.store.remote_loc(v.id)
            if rloc is not None:
                return _ObjArg(
                    v.id,
                    remote_loc=(rloc["host"], rloc["port"]),
                )
            return _ObjArg(
                v.id, inline=self.store.get(v.id), has_inline=True
            )
        return v

    def submit_task(
        self, func, func_id, func_blob, args, kwargs, options
    ) -> List[ObjectRef]:
        num_returns = options.get("num_returns", 1)
        task_id = uuid.uuid4().hex
        name = options.get("name") or getattr(func, "__name__", "task")
        # NOTE: no base-task_id ObjectRef in the multi-return case —
        # a created-then-discarded handle would refcount the base
        # entry to zero and free the tuple out from under the split
        if num_returns > 1:
            refs = [
                ObjectRef(f"{task_id}_{i}", self.store)
                for i in range(num_returns)
            ]
            self._register_split(task_id, refs)
        else:
            refs = [ObjectRef(task_id, self.store)]

        pg = None
        bundle_index = -1
        strategy = options.get("scheduling_strategy")
        if strategy is not None and hasattr(
            strategy, "placement_group"
        ):
            pg = strategy.placement_group
            bundle_index = getattr(
                strategy, "placement_group_bundle_index", -1
            )
        from ray_tpu.core.runtime_env import pack_runtime_env
        from ray_tpu.util import tracing

        trec = _TaskRecord(
            task_id,
            {
                "type": "task",
                "task_id": task_id,
                "func_id": func_id,
                "func_blob": func_blob,
                "runtime_env": (
                    options["runtime_env_packed"]
                    if "runtime_env_packed" in options
                    else pack_runtime_env(options.get("runtime_env"))
                ),
                "trace_ctx": tracing.inject_context(),
                "args": args,
                "kwargs": kwargs,
            },
            retries_left=options.get("max_retries", 3),
            name=name,
            num_cpus=(
                1 if options.get("num_cpus") is None
                else options["num_cpus"]
            ),
            resources=options.get("resources"),
            placement_group=pg,
            bundle_index=bundle_index,
        )
        # spillover needs this: an agent executing a multi-return task
        # splits the tuple NODE-SIDE (one node-resident object per
        # return) so the parts never transit the head
        trec.num_returns = num_returns
        self._submit_when_ready(trec, args, kwargs)
        return refs

    def _register_split(self, task_id: str, refs: List[ObjectRef]):
        def split():
            try:
                values = self.store.get(task_id)
            except BaseException as e:  # propagate error to all returns
                for r in refs:
                    self.store.put_error(r.id, e)
                self.store.free([task_id])
                return
            for r, v in zip(refs, values):
                self.store.put(r.id, v, use_shm=False)
            # nothing holds a handle to the base tuple entry
            self.store.free([task_id])

        self.store.on_ready(task_id, split)

    def _submit_when_ready(self, trec: _TaskRecord, args, kwargs):
        """Marshal args; if some ObjectRef deps are unready, wait for them."""
        deps = [
            a.id
            for a in list(args) + list(kwargs.values())
            if isinstance(a, ObjectRef) and not self.store.is_ready(a.id)
        ]
        if not deps:
            # pin the argument refs on the record: marshalling strips
            # them from the msg, but the entries (shm segments) must
            # outlive dispatch AND any retries — the task record is
            # exactly that lifetime (reference_count.h's
            # task-dependency references)
            trec.arg_refs = [
                a
                for a in list(trec.msg["args"])
                + list(trec.msg["kwargs"].values())
                if isinstance(a, ObjectRef)
            ]
            # keep the unmarshalled args: spillover to a fleet agent
            # must re-marshal for the remote object plane (shm names
            # in the local payload mean nothing off-host)
            trec.orig_args = list(trec.msg["args"])
            trec.orig_kwargs = dict(trec.msg["kwargs"])
            m_args = [self._marshal_arg(a) for a in trec.msg["args"]]
            m_kwargs = {
                k: self._marshal_arg(v) for k, v in trec.msg["kwargs"].items()
            }
            trec.msg["payload"] = ser.dumps((m_args, m_kwargs))
            del trec.msg["args"], trec.msg["kwargs"]
            self._enqueue(trec)
            return
        remaining = {"n": len(deps)}
        lk = threading.Lock()

        def on_dep():
            with lk:
                remaining["n"] -= 1
                done = remaining["n"] == 0
            if done:
                self._submit_when_ready(trec, trec.msg["args"], trec.msg["kwargs"])

        for d in deps:
            self.store.on_ready(d, on_dep)

    # -- actors ----------------------------------------------------------

    def _local_actor_saturated(self, options) -> bool:
        """Would placing one more dedicated-CPU actor locally
        oversubscribe the head? (Actors run on dedicated workers
        outside the task pool's CPU ledger, so they keep their own
        count.)"""
        req = options.get("num_cpus")
        req = 1.0 if req is None else float(req)
        if req <= 0:
            return False
        with self.lock:
            used = sum(
                getattr(rec, "num_cpus", 1.0)
                for rec in self.actors.values()
                if not rec.dead
            )
        return used + req > self.num_cpus

    def create_actor(self, cls, args, kwargs, options) -> "ActorHandle":
        from ray_tpu.core.runtime_env import pack_runtime_env

        # pack path-based runtime_env pieces HERE (driver-side), so
        # the spec ships host-independently — including to remote node
        # agents (reference runtime_env URI upload at submission time)
        renv_packed = options.get("runtime_env_packed")
        if renv_packed is None:
            renv_packed = pack_runtime_env(
                options.get("runtime_env")
            )
        # placement-group actors: charge a bundle and run ON the
        # bundle's node (the reference's pg-aware actor scheduling —
        # gcs_actor_scheduler honoring the bundle's node commit)
        pg_strategy = options.get("scheduling_strategy")
        pg = getattr(pg_strategy, "placement_group", None)
        pg_charge = None
        if pg is not None:
            if not pg.ready(timeout=30.0):
                raise TimeoutError(
                    f"placement group {pg.id} not ready"
                )
            ncpus = (
                1.0
                if options.get("num_cpus") is None
                else float(options["num_cpus"])
            )
            bidx = getattr(
                pg_strategy, "placement_group_bundle_index", -1
            )
            # under the runtime lock: task dispatch does its
            # _fits/_acquire pair there, so actor charges must not
            # interleave between them
            with self.lock:
                bundle, pg_node = pg._acquire_any(ncpus, bidx)
            if bundle < 0:
                raise ValueError(
                    f"placement group {pg.id} cannot admit actor "
                    f"(num_cpus={ncpus}, bundle_index={bidx})"
                )
            pg_charge = (pg, ncpus, bundle)
            options = dict(options)
            if pg_node is not None:
                # agent bundle: pin there; CPUs are paid by the pg
                # ledger, not the node's actor ledger
                options["placement_node"] = pg_node
                options["pg_charged"] = True
        if pg_charge is not None:
            # any failure between the charge and a registered actor
            # (duplicate name, node send error, unpicklable class)
            # must give the bundle back or the group bleeds capacity
            try:
                return self._create_actor_placed(
                    cls, args, kwargs, options, renv_packed,
                    pg_charge,
                )
            except BaseException:
                pgx, ncpusx, bundlex = pg_charge
                for aid, ch in list(
                    self._actor_pg_charges.items()
                ):
                    if ch is pg_charge:
                        self._actor_pg_charges.pop(aid, None)
                pgx._release(ncpusx, bundlex)
                raise
        return self._create_actor_placed(
            cls, args, kwargs, options, renv_packed, None
        )

    def _create_actor_placed(
        self, cls, args, kwargs, options, renv_packed, pg_charge
    ) -> "ActorHandle":
        node_name = options.get("placement_node")
        pg = (
            pg_charge[0] if pg_charge is not None else None
        )
        if (
            node_name is None
            and pg is None  # pg decides placement, not saturation
            and self.cluster is not None
            and self._local_actor_saturated(options)
        ):
            # automatic spillover: unpinned actors spread to fleet
            # agents once the head's CPUs are spoken for (the hybrid
            # local-first/spillback policy of the reference's
            # cluster_resource_scheduler.h:45, scoped to actors+CPUs)
            node_name = "any"
        if node_name is not None and self.cluster is not None:
            try:
                node = self.cluster.pick_node(
                    None if node_name == "any" else node_name
                )
            except ValueError:
                # requested node is gone (e.g. recreate_failed_workers
                # after a host death): fall back to local placement so
                # the fault-tolerance path keeps the run alive rather
                # than throwing (reference: dead-node leases respawn
                # wherever the cluster scheduler finds room)
                import warnings

                warnings.warn(
                    f"cluster node {node_name!r} unavailable; placing "
                    "actor locally"
                )
                node = None
            if node is not None:
                actor_id = uuid.uuid4().hex
                name = options.get("name")
                if renv_packed is not None:
                    options = dict(
                        options, runtime_env_packed=renv_packed
                    )
                r_args, r_kwargs = node.marshal_args(args, kwargs)
                with self.lock:
                    if name:
                        if name in self.named_actors:
                            raise ValueError(
                                f"Actor name {name} already taken"
                            )
                        self.named_actors[name] = actor_id
                        self._record_named_actor(
                            name, actor_id, cls.__name__
                        )
                    self.remote_actors[actor_id] = node
                    if pg_charge is not None:
                        self._actor_pg_charges[actor_id] = pg_charge
                node.create_actor(
                    actor_id, cls, r_args, r_kwargs, options
                )
                return ActorHandle(actor_id, cls.__name__)
        actor_id = uuid.uuid4().hex
        # serialize BEFORE spawning: an unpicklable class or argument
        # must not leak a freshly spawned (possibly non-daemon) worker
        # process — an orphaned non-daemon child wedges interpreter
        # exit in multiprocessing's atexit join
        cls_blob = ser.dumps(cls)
        payload = ser.dumps(
            (
                [self._marshal_arg(a) for a in args],
                {k: self._marshal_arg(v) for k, v in kwargs.items()},
            )
        )
        w = self._spawn_worker(
            dedicated=True,
            daemon=bool(options.get("daemon", True)),
        )
        init_msg = {
            "type": "actor_init",
            "actor_id": actor_id,
            "task_id": None,
            "cls": cls_blob,
            "max_concurrency": int(
                options.get("max_concurrency", 1)
            ),
            "runtime_env": renv_packed,
            "payload": payload,
        }
        rec = _ActorRecord(
            actor_id, w, cls_blob, init_msg,
            options.get("max_restarts", 0),
            daemon=bool(options.get("daemon", True)),
        )
        if pg_charge is not None:
            self._actor_pg_charges[actor_id] = pg_charge
        rec.num_cpus = (
            1.0
            if options.get("num_cpus") is None
            else float(options["num_cpus"])
        )
        # constructor ref args stay pinned for the actor's LIFETIME:
        # a restart replays init_msg, which re-attaches their shm
        rec.arg_refs = [
            a
            for a in list(args) + list(kwargs.values())
            if isinstance(a, ObjectRef)
        ]
        name = options.get("name")
        with self.lock:
            self.actors[actor_id] = rec
            if name:
                if name in self.named_actors:
                    raise ValueError(f"Actor name {name} already taken")
                self.named_actors[name] = actor_id
                rec.name = name
                self._record_named_actor(name, actor_id, cls.__name__)
        with w.send_lock:
            w.conn.send(init_msg)
        return ActorHandle(actor_id, cls.__name__)

    def call_actor(self, actor_id, method, args, kwargs, num_returns=1):
        node = self.remote_actors.get(actor_id)
        if node is not None:
            if node.dead:
                ref = ObjectRef(uuid.uuid4().hex, self.store)
                self.store.put_error(
                    ref.id,
                    RayActorError(
                        f"Actor {actor_id}'s node {node.node_id} is dead"
                    ),
                )
                return [ref] * num_returns
            # ObjectRef args ride the once-per-node pool: the value
            # ships on first use per node, the id alone afterwards
            # (cluster._PoolObj) — weight broadcast to K actors on one
            # agent moves one copy, not K
            r_args, r_kwargs = node.marshal_args(args, kwargs)
            return node.call(
                actor_id, method, r_args, r_kwargs, num_returns
            )
        with self.lock:
            rec = self.actors.get(actor_id)
        if rec is None or rec.dead:
            ref = ObjectRef(uuid.uuid4().hex, self.store)
            self.store.put_error(
                ref.id, RayActorError(f"Actor {actor_id} is dead")
            )
            return [ref]
        from ray_tpu.util import tracing

        task_id = uuid.uuid4().hex
        trec = _TaskRecord(
            task_id,
            {
                "type": "actor_call",
                "task_id": task_id,
                "actor_id": actor_id,
                "method": method,
                "trace_ctx": tracing.inject_context(),
                "payload": ser.dumps(
                    (
                        [self._marshal_arg(a) for a in args],
                        {
                            k: self._marshal_arg(v)
                            for k, v in kwargs.items()
                        },
                    )
                ),
            },
            retries_left=0,
            name=f"{method}",
            # actor calls run on the actor's dedicated process: they
            # neither acquire nor release scheduler CPUs
            num_cpus=0,
        )
        # pin shm-backed argument refs until the call completes (see
        # _submit_when_ready)
        trec.arg_refs = [
            a
            for a in list(args) + list(kwargs.values())
            if isinstance(a, ObjectRef)
        ]
        w = rec.worker
        with self.lock:
            w.inflight[task_id] = trec
        self._send_task(w, trec)
        if num_returns > 1:
            refs = [
                ObjectRef(f"{task_id}_{i}", self.store)
                for i in range(num_returns)
            ]
            self._register_split(task_id, refs)
        else:
            refs = [ObjectRef(task_id, self.store)]
        return refs

    def kill_actor(self, actor_id: str, no_restart: bool = True):
        charge = self._actor_pg_charges.pop(actor_id, None)
        if charge is not None:
            pg, ncpus, bundle = charge
            pg._release(ncpus, bundle)
        node = self.remote_actors.pop(actor_id, None)
        if node is not None:
            node.kill(actor_id)
            return
        with self.lock:
            rec = self.actors.get(actor_id)
            if rec is None:
                return
            rec.dead = True
            if no_restart:
                rec.max_restarts = 0
            w = rec.worker
        try:
            w.proc.terminate()
        except Exception:
            pass

    # -- shutdown --------------------------------------------------------

    def shutdown(self):
        self.shutting_down = True
        with self.lock:
            workers = list(self.pool) + [
                rec.worker for rec in self.actors.values()
            ]
        for w in workers:
            try:
                with w.send_lock:
                    w.conn.send({"type": "shutdown"})
            except Exception:
                pass
        deadline = time.time() + 2.0
        for w in workers:
            w.proc.join(max(0.0, deadline - time.time()))
            if w.proc.is_alive():
                w.proc.terminate()
        self.store.clear()
        if self.state_store is not None:
            import json as _json

            try:
                rec = self.state_store.get("jobs", self.job_id)
                if rec:
                    job = _json.loads(rec.decode())
                    job["status"] = "FINISHED"
                    job["end_time"] = time.time()
                    self.state_store.put(
                        "jobs", self.job_id, _json.dumps(job).encode()
                    )
            finally:
                self.state_store.close()
                self.state_store = None
        srv = getattr(self, "_api_server", None)
        if srv is not None:
            srv.shutdown()
            self._api_server = None
        mon = getattr(self, "memory_monitor", None)
        if mon is not None:
            mon.stop()
            self.memory_monitor = None
        dash = getattr(self, "dashboard", None)
        if dash is not None:
            try:
                dash.shutdown()
            except Exception:
                pass
            self.dashboard = None


class _UnreadyDep(Exception):
    def __init__(self, obj_id):
        self.obj_id = obj_id


_runtime: Optional[_Runtime] = None


def init(
    num_cpus: Optional[int] = None,
    num_gpus: Optional[int] = None,
    object_store_memory: Optional[int] = None,
    ignore_reinit_error: bool = False,
    local_mode: bool = False,
    worker_env: Optional[Dict[str, str]] = None,
    log_dir: Optional[str] = None,
    address: Optional[str] = None,
    runtime_env: Optional[Dict] = None,
    **kwargs,
) -> Dict:
    """Start the local runtime (reference ray.init,
    ``_private/worker.py:984``).

    address="host:port" JOINS an existing head's fleet as a worker
    agent: this process's runtime hosts actors the head places here
    (reference: ray start --address joining a raylet to the GCS). The
    head enables its listener with
    ``ray_tpu.core.cluster.start_cluster_server()``."""
    global _runtime, _client_mode
    if address and address.startswith("ray://"):
        # LIVE remote-driver client (reference ray.util.client,
        # python/ray/util/client/__init__.py:214): this process keeps
        # NO runtime — every ray.* verb routes over the driver-API
        # wire to the head (the same channel nested worker calls use;
        # core/worker_api.py). The head exposes it with
        # ``start_client_server()``. Trust model: the channel carries
        # pickled payloads — loopback/SSH-tunnel or trusted-network
        # use, like the reference's client server.
        if _runtime is not None:
            raise RuntimeError(
                "ray://: this process already runs a local runtime"
            )
        from ray_tpu.core import worker_api

        os.environ[worker_api.ENV_ADDR] = address[len("ray://"):]
        _client_mode = True
        client = worker_api.worker_client()
        if client is None:  # pragma: no cover - env just set
            raise ConnectionError(f"cannot reach {address}")
        return {"address": address, "mode": "client"}
    if _runtime is not None:
        if ignore_reinit_error:
            return {"address": "local"}
        raise RuntimeError(
            "ray_tpu.init() called twice; pass ignore_reinit_error=True"
        )
    n = num_cpus if num_cpus is not None else max(4, os.cpu_count() or 1)
    resources = kwargs.get("resources")
    _runtime = _Runtime(n, object_store_memory, resources=resources)
    if worker_env:
        _runtime._worker_env.update(worker_env)
    if log_dir:
        _runtime._worker_env.setdefault("RAY_TPU_LOG_DIR", log_dir)
    if runtime_env:
        from ray_tpu.core.runtime_env import pack_runtime_env

        _runtime._job_runtime_env = pack_runtime_env(runtime_env)
    state_path = kwargs.get("state_path")
    if state_path and _runtime.state_store is None:
        _runtime._open_state_store(state_path)
    if (
        kwargs.get("enable_memory_monitor")
        or os.environ.get("RAY_TPU_MEMORY_MONITOR") == "1"
    ):
        from ray_tpu.core.memory_monitor import MemoryMonitor

        _runtime.memory_monitor = MemoryMonitor(_runtime)
    if kwargs.get("dashboard"):
        from ray_tpu.dashboard.dashboard import DashboardLite
        from ray_tpu.job.job_manager import JobManager

        _runtime.dashboard = DashboardLite(
            port=int(kwargs.get("dashboard_port") or 0),
            job_manager=JobManager(state_path=state_path),
        )
    if address and address not in ("local", "auto"):
        from ray_tpu.core.cluster import NodeAgent

        _runtime.node_agent = NodeAgent(
            address,
            node_id=kwargs.get("node_id"),
            num_cpus=num_cpus,
        )
        return {
            "address": address,
            "num_cpus": n,
            "node_id": _runtime.node_agent.node_id,
        }
    return {"address": "local", "num_cpus": n}


def start_client_server(host: str = "127.0.0.1", port: int = 0) -> str:
    """Expose this head's driver API for ``ray://`` remote drivers
    (reference ``ray.util.client.server``): returns "host:port" for
    ``ray_tpu.init(address="ray://host:port")`` in another process or
    host. Loopback by default; front with an SSH tunnel / trusted
    network for remote use (pickled payloads ride this channel)."""
    from ray_tpu.core.worker_api import WorkerAPIServer

    rt = _require_runtime()
    if getattr(rt, "client_server", None) is None:
        rt.client_server = WorkerAPIServer(rt, host=host, port=port)
    return rt.client_server.address


def list_jobs(state_path: Optional[str] = None) -> List[Dict]:
    """Jobs recorded in the durable state store — including those of
    PREVIOUS (dead) drivers, which is the point (reference
    gcs_job_manager.cc job table + `ray job list`). Reads the running
    runtime's store, or the file at ``state_path``/RAY_TPU_STATE_PATH
    without a runtime."""
    import json as _json

    if _runtime is not None and _runtime.state_store is not None:
        store = _runtime.state_store
        close = False
    else:
        path = state_path or os.environ.get("RAY_TPU_STATE_PATH")
        if not path or not os.path.exists(path):
            return []
        from ray_tpu.core.store_client import make_store_client

        store = make_store_client(path)
        close = True
    try:
        return sorted(
            (
                _json.loads(v.decode())
                for v in store.all("jobs").values()
            ),
            key=lambda j: j.get("start_time", 0),
        )
    finally:
        if close:
            store.close()


_client_mode = False


def is_initialized() -> bool:
    return _runtime is not None or _client_mode


def shutdown():
    global _runtime, _client_mode
    if _client_mode:
        from ray_tpu.core import worker_api

        os.environ.pop(worker_api.ENV_ADDR, None)
        _client_mode = False
    if _runtime is not None:
        _runtime.shutdown()
        _runtime = None


atexit.register(shutdown)


def _require_runtime() -> _Runtime:
    if _runtime is None:
        if _client_mode:
            raise RuntimeError(
                "this operation needs the head's runtime and is not "
                "proxied over the ray:// client channel"
            )
        init()
    return _runtime


def _ambient_client():
    """Worker-context driver-API client, if this process is a worker
    (nested ray.* calls route to the driver instead of booting a
    private runtime inside the worker — reference: every worker is a
    CoreWorker and submits through its own task path)."""
    if _runtime is not None:
        return None
    from ray_tpu.core.worker_api import worker_client

    return worker_client()


def put(value: Any) -> ObjectRef:
    client = _ambient_client()
    if client is not None:
        return ObjectRef(client.put(value))
    rt = _require_runtime()
    ref = ObjectRef(uuid.uuid4().hex, rt.store)
    rt.store.put(ref.id, value)
    return ref


def get(
    refs: Union[ObjectRef, Sequence[ObjectRef]],
    *,
    timeout: Optional[float] = None,
):
    client = _ambient_client()
    if client is not None:
        if isinstance(refs, ObjectRef):
            return client.get(refs.id, timeout)
        return [client.get(r.id, timeout) for r in refs]
    rt = _require_runtime()
    if isinstance(refs, ObjectRef):
        return rt.store.get(refs.id, timeout)
    return [rt.store.get(r.id, timeout) for r in refs]


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
    fetch_local: bool = True,
) -> Tuple[List[ObjectRef], List[ObjectRef]]:
    """reference ray.wait (worker.py)."""
    client = _ambient_client()
    if client is not None:
        refs = list(refs)
        by_id = {r.id: r for r in refs}
        ready_ids, pending_ids = client.wait(
            [r.id for r in refs], num_returns, timeout
        )
        return (
            [by_id[i] for i in ready_ids],
            [by_id[i] for i in pending_ids],
        )
    rt = _require_runtime()
    refs = list(refs)
    deadline = None if timeout is None else time.time() + timeout
    ready: List[ObjectRef] = []
    evt = threading.Event()

    def notify():
        evt.set()

    registered: set = set()
    try:
        while True:
            # Clear BEFORE scanning: a ref completing after the scan
            # sets the event, so the wakeup cannot be lost between the
            # scan and the wait.
            evt.clear()
            ready = [r for r in refs if rt.store.is_ready(r.id)]
            if len(ready) >= num_returns:
                break
            if deadline is not None and time.time() >= deadline:
                break
            for r in refs:
                if r.id not in registered and not rt.store.is_ready(
                    r.id
                ):
                    rt.store.on_ready(r.id, notify)
                    registered.add(r.id)
            remaining_t = (
                None
                if deadline is None
                else max(0.0, deadline - time.time())
            )
            evt.wait(remaining_t)
    finally:
        # Deregister: repeated wait() polls on long-pending refs must
        # not accumulate callbacks on the store entries.
        for rid in registered:
            rt.store.discard_callback(rid, notify)
    ready, not_ready = [], []
    for r in refs:
        if rt.store.is_ready(r.id) and len(ready) < num_returns:
            ready.append(r)
        else:
            not_ready.append(r)
    return ready, not_ready


class RemoteFunction:
    """reference ``remote_function.py:34``."""

    def __init__(self, func, options: Dict):
        self._func = func
        self._options = dict(options)
        self._func_id = uuid.uuid4().hex[:16]
        self._func_blob = None
        functools.update_wrapper(self, func)

    def options(self, **kwargs) -> "RemoteFunction":
        rf = RemoteFunction(self._func, {**self._options, **kwargs})
        rf._func_id = self._func_id
        rf._func_blob = self._func_blob
        return rf

    def remote(self, *args, **kwargs) -> Union[ObjectRef, List[ObjectRef]]:
        if self._func_blob is None:
            self._func_blob = ser.dumps(self._func)
        client = _ambient_client()
        if client is not None:  # nested submission from a worker
            ids = client.submit(
                self._func,
                self._func_id,
                self._func_blob,
                list(args),
                dict(kwargs),
                self._options,
            )
            refs = [ObjectRef(i) for i in ids]
        else:
            rt = _require_runtime()
            refs = rt.submit_task(
                self._func,
                self._func_id,
                self._func_blob,
                list(args),
                dict(kwargs),
                self._options,
            )
        if self._options.get("num_returns", 1) == 1:
            return refs[0]
        return refs

    def __call__(self, *args, **kwargs):
        raise TypeError(
            "Remote functions cannot be called directly; use .remote()"
        )


class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str, num_returns: int = 1):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns

    def options(self, num_returns: int = 1, **kwargs) -> "ActorMethod":
        return ActorMethod(self._handle, self._name, num_returns)

    def remote(self, *args, **kwargs):
        client = _ambient_client()
        if client is not None:  # actor call from inside a worker
            ids = client.call_actor(
                self._handle._actor_id,
                self._name,
                list(args),
                dict(kwargs),
                self._num_returns,
            )
            refs = [ObjectRef(i) for i in ids]
        else:
            rt = _require_runtime()
            refs = rt.call_actor(
                self._handle._actor_id, self._name, list(args),
                dict(kwargs), self._num_returns,
            )
        if self._num_returns == 1:
            return refs[0]
        return refs


class ActorHandle:
    """reference ``actor.py:950``."""

    def __init__(self, actor_id: str, class_name: str = "Actor"):
        self._actor_id = actor_id
        self._class_name = class_name

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return ActorMethod(self, name)

    def __repr__(self):
        return f"ActorHandle({self._class_name}, {self._actor_id[:8]})"

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._class_name))


class ActorClass:
    """reference ``actor.py:377``."""

    def __init__(self, cls, options: Dict):
        self._cls = cls
        self._options = dict(options)

    def options(self, **kwargs) -> "ActorClass":
        return ActorClass(self._cls, {**self._options, **kwargs})

    def remote(self, *args, **kwargs) -> ActorHandle:
        client = _ambient_client()
        if client is not None:  # actor creation from inside a worker
            actor_id, class_name = client.create_actor(
                ser.dumps(self._cls), list(args), dict(kwargs),
                self._options,
            )
            return ActorHandle(actor_id, class_name)
        rt = _require_runtime()
        return rt.create_actor(self._cls, list(args), dict(kwargs),
                               self._options)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            "Actor classes cannot be instantiated directly; use .remote()"
        )


def remote(*args, **options):
    """``@ray.remote`` decorator (reference ``worker.py`` remote)."""

    def decorate(obj):
        if isinstance(obj, type):
            return ActorClass(obj, options)
        return RemoteFunction(obj, options)

    if len(args) == 1 and callable(args[0]) and not options:
        return decorate(args[0])
    return decorate


def method(num_returns: int = 1, **kwargs):
    """``@ray.method`` decorator — annotates num_returns on actor methods."""

    def decorate(m):
        m.__ray_num_returns__ = num_returns
        return m

    return decorate


def kill(actor: ActorHandle, *, no_restart: bool = True):
    client = _ambient_client()
    if client is not None:
        client.kill_actor(actor._actor_id, no_restart)
        return
    rt = _require_runtime()
    rt.kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    # Best-effort: mark as errored if not yet done.
    rt = _require_runtime()
    if not rt.store.is_ready(ref.id):
        rt.store.put_error(ref.id, TaskCancelledError("cancelled"))


class TaskCancelledError(RuntimeError):
    pass


def get_actor(name: str) -> ActorHandle:
    client = _ambient_client()
    if client is not None:  # named-actor lookup from inside a worker
        return ActorHandle(client.get_actor(name))
    rt = _require_runtime()
    with rt.lock:
        actor_id = rt.named_actors.get(name)
    if actor_id is None:
        raise ValueError(f"No actor named {name!r}")
    return ActorHandle(actor_id)


class RuntimeContext:
    def __init__(self):
        self.node_id = "local"
        self.job_id = (
            _runtime.job_id if _runtime is not None else "job_local"
        )

    def get(self):
        return {"node_id": self.node_id, "job_id": self.job_id}


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext()


def available_resources() -> Dict[str, float]:
    rt = _require_runtime()
    with rt.lock:
        out = {"CPU": float(rt.available_cpus)}
        out.update(rt.available_resources)
    return out


def cluster_resources() -> Dict[str, float]:
    rt = _require_runtime()
    res = {"CPU": float(rt.num_cpus)}
    res.update(rt.total_resources)
    import jax

    tpus = len([d for d in jax.devices() if d.platform != "cpu"])
    if tpus:
        res["TPU"] = float(tpus)
    return res


def nodes() -> List[Dict]:
    return [
        {
            "NodeID": "local",
            "Alive": True,
            "Resources": cluster_resources(),
        }
    ]


def timeline() -> List[Dict]:
    """Chrome-trace events (reference ``_private/state.py:435``)."""
    rt = _require_runtime()
    return list(rt.timeline_events)


def free(refs: Sequence[ObjectRef]):
    client = _ambient_client()
    if client is not None:
        client.free([r.id for r in refs])
        return
    rt = _require_runtime()
    rt.store.free([r.id for r in refs])
