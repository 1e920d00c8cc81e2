"""Ahead-of-time compiled-program cache: cold starts that skip XLA.

A fresh serve replica (or an elastic joiner's learn program) pays the
full warmup compile storm before its first useful dispatch — one XLA
compile per bucket shape for a policy server, seconds each at real
geometry. The programs are identical across the fleet: same policy,
same mesh topology, same bucket contract. This module makes that
redundancy a cache hit.

The mechanism is the ``Lowered``/pjit-AOT machinery (SNIPPETS [1],
``jax.experimental.serialize_executable``): a ``sharded_jit`` program
is lowered and compiled ahead of time, the **compiled XLA executable**
is serialized (not StableHLO — deserialization skips XLA entirely,
measured ~20x faster than a live compile even for toy programs), and
the payload lands in a persistent on-disk cache shared across the
fleet. ``ShardedFunction.aot_warmup`` restores it; on a hit the
executable is installed as the function's dispatch path with ZERO
fresh compiles, ledger-registered with ``compile_s=0`` and
``source="aot_cache"`` so MFU/compile accounting stays honest.

Keying and the fallback contract (docs/serving.md "the front door"):

- entries are keyed by a **fingerprint** (jax/jaxlib version, backend
  platform, device kind, device count — serialized executables are
  only valid on the topology+toolchain that built them), the program
  label, and the abstract input signature — which carries the mesh
  geometry of the program's shardings (``compile.py``
  ``_mesh_geometry_token``), so one process can hold entries for
  SEVERAL mesh geometries at once (the fleet pre-seeds its ±1-host
  resize geometries ahead of a preemption, PR 17);
- ANY mismatch — different version, different topology, a torn or
  corrupt file, an API that refuses to deserialize — is a plain cache
  miss: the caller compiles live (and repopulates the cache), never
  errors. A stale executable that slips through keying and fails at
  dispatch falls back the same way (``ShardedFunction.__call__``);
- writes go through a background cache-writer thread with the PR-2
  atomic-write discipline (temp + fsync + ``os.replace``), so a
  replica killed mid-write never leaves a torn entry for the fleet.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import queue
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu.util import tracing

# bump when the entry layout changes: old entries become misses
# (2: mesh-geometry token joined the signature — pre-format entries
# would collide across geometries, so they must miss)
FORMAT = 2


def fingerprint() -> Dict[str, Any]:
    """The validity domain of a serialized executable: the toolchain
    that compiled it and the device topology it was compiled for. Any
    component moving invalidates every entry (by key)."""
    import jax
    import jaxlib

    try:
        devices = jax.devices()
        kind = devices[0].device_kind
        platform = devices[0].platform
        n = len(devices)
    except Exception:
        kind, platform, n = "unknown", "unknown", 0
    return {
        "format": FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": platform,
        "device_kind": kind,
        "n_devices": n,
    }


def entry_key(label: str, signature: Any, fp: Dict[str, Any]) -> str:
    """Stable digest naming one cache entry: fingerprint + program
    label + abstract input signature (the same signature unit the
    device ledger's recompile forensics diff)."""
    h = hashlib.sha256()
    h.update(repr(sorted(fp.items())).encode())
    h.update(b"\x00")
    h.update(label.encode())
    h.update(b"\x00")
    h.update(repr(signature).encode())
    return h.hexdigest()


class AOTCompileCache:
    """Persistent on-disk cache of serialized compiled executables,
    shared across the fleet (point every replica at the same
    directory — NFS/GCS-fuse at fleet scale, tmpdir in tests).

    ``load`` returns a ready-to-dispatch executable or None (every
    failure mode is a miss); ``save`` serializes on the cache-writer
    thread so warmup never blocks on pickling + fsync.
    """

    def __init__(self, root: str, *, writer: bool = True):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._fp = fingerprint()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.saves = 0
        self.load_errors = 0
        self.save_errors = 0
        self._writer_q: "queue.Queue[Optional[Tuple]]" = queue.Queue()
        self._writer: Optional[threading.Thread] = None
        if writer:
            self._writer = threading.Thread(
                target=self._writer_run, daemon=True,
                name="aot_cache_writer",
            )
            self._writer.start()

    # -- keying ----------------------------------------------------------

    @property
    def fingerprint_dict(self) -> Dict[str, Any]:
        return dict(self._fp)

    def path_for(self, label: str, signature: Any) -> str:
        return os.path.join(
            self.root, entry_key(label, signature, self._fp) + ".aot"
        )

    # -- load (any failure is a miss) ------------------------------------

    def load(self, label: str, signature: Any):
        """Deserialize the cached executable for (label, signature) on
        the CURRENT fingerprint, or None. Version/topology mismatches
        never reach this far (they key to different paths); torn or
        corrupt files and deserialization refusals count as
        ``load_errors`` and fall through to a miss."""
        path = self.path_for(label, signature)
        if not os.path.exists(path):
            self._count("misses")
            return None
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            # defense in depth beyond the keyed filename: a hand-moved
            # or hash-colliding entry still must match exactly
            if entry.get("fingerprint") != self._fp:
                raise ValueError("fingerprint mismatch")
            if entry.get("label") != label:
                raise ValueError("label mismatch")
            from jax.experimental import serialize_executable as se

            loaded = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"]
            )
        except Exception:
            self._count("load_errors")
            self._count("misses")
            _metric("load_error")
            return None
        self._count("hits")
        _metric("hit")
        tracing.event("aot:restore", label=label, path=path)
        return loaded

    # -- save (cache-writer thread) --------------------------------------

    def save(self, label: str, signature: Any, compiled) -> None:
        """Queue one compiled executable for serialization + atomic
        write. Returns immediately; ``flush()`` joins the queue (bench
        and tests; a serving replica never needs to)."""
        self._writer_q.put((label, signature, compiled))
        if self._writer is None:
            self._drain_one()

    def flush(self, timeout_s: float = 30.0) -> None:
        """Block until every queued save hit the disk (unfinished
        TASKS, not just an empty queue — the writer may be mid-write
        on the last entry)."""
        deadline = time.monotonic() + timeout_s
        while (
            self._writer_q.unfinished_tasks > 0
            and time.monotonic() < deadline
        ):
            if self._writer is None:
                self._drain_one()
            else:
                time.sleep(0.01)

    # ray-tpu: thread=aot-writer
    def _writer_run(self) -> None:
        while True:
            item = self._writer_q.get()
            try:
                if item is None:
                    return
                self._write_entry(*item)
            finally:
                self._writer_q.task_done()

    def _drain_one(self) -> None:
        try:
            item = self._writer_q.get_nowait()
        except queue.Empty:
            return
        try:
            if item is not None:
                self._write_entry(*item)
        finally:
            self._writer_q.task_done()

    def _write_entry(self, label, signature, compiled) -> None:
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps(
                {
                    "fingerprint": self._fp,
                    "label": label,
                    "signature": repr(signature),
                    "created": time.time(),
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                }
            )
            from ray_tpu.util.atomic_io import atomic_write

            path = self.path_for(label, signature)
            atomic_write(path, lambda f: f.write(blob))
        except Exception:
            self._count("save_errors")
            _metric("save_error")
            return
        self._count("saves")
        _metric("save")

    # -- introspection ---------------------------------------------------

    def _count(self, field: str) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "root": self.root,
                "hits": self.hits,
                "misses": self.misses,
                "saves": self.saves,
                "load_errors": self.load_errors,
                "save_errors": self.save_errors,
                "entries": sum(
                    1
                    for n in os.listdir(self.root)
                    if n.endswith(".aot")
                )
                if os.path.isdir(self.root)
                else 0,
            }

    def stop(self, join_timeout: float = 10.0) -> None:
        if self._writer is not None and self._writer.is_alive():
            self._writer_q.put(None)
            self._writer.join(timeout=join_timeout)
            self._writer = None


def _metric(event: str) -> None:
    try:
        from ray_tpu.telemetry import metrics as tm

        tm.inc_aot_cache_event(event)
    except Exception:
        pass


def resolve_cache(cache) -> Optional[AOTCompileCache]:
    """Accept an :class:`AOTCompileCache`, a directory path, or None
    (also reading ``RAY_TPU_AOT_CACHE`` as the no-config activation
    path, mirroring the device ledger's env knob)."""
    if cache is None:
        env = os.environ.get("RAY_TPU_AOT_CACHE")
        if not env:
            return None
        cache = env
    if isinstance(cache, AOTCompileCache):
        return cache
    return AOTCompileCache(str(cache))
