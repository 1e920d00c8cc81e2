"""Mesh construction for the sharding runtime.

One mesh per process (cached), built from the devices the backend
exposes: real TPU cores, or simulated host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the standard
way to test multi-device layouts without hardware — tests/conftest.py
forces 8).

Axis conventions:
  - ``"batch"``: data parallelism over the train-batch leading dim —
    the only axis the learner uses today.
  - ``"model"``: reserved for tensor parallelism of large learner
    models (multi-chip PRs add shapes here; the name is fixed now so
    specs written against it won't churn).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

BATCH_AXIS = "batch"
MODEL_AXIS = "model"

# (device ids, axis names, axis sizes) -> Mesh. Mesh construction is
# cheap but identity matters: jit caches key on sharding objects, and
# two equal-but-distinct meshes would recompile every learn program.
_MESH_CACHE: dict = {}


def available_devices(platform: Optional[str] = None):
    """Devices to build meshes from. ``platform`` filters ("tpu",
    "cpu"); asking for a platform the backend does not expose is an
    error — a learner configured for TPU never comes up on the CPU
    unannounced."""
    devs = jax.devices()
    if not platform:
        return devs
    matched = [d for d in devs if d.platform == platform]
    if not matched:
        found = sorted({d.platform for d in devs})
        raise RuntimeError(
            f"no {platform!r} devices: jax exposes {found} "
            f"({len(devs)} device(s))"
        )
    return matched


def all_cpu(mesh: Optional[Mesh] = None) -> bool:
    """Whether the learner's devices (the mesh's, else the default
    backend's) are all host CPUs — the question every ``"auto"`` knob
    asks. If the backend cannot be asked, that is the error."""
    devices = mesh.devices.flat if mesh is not None else jax.devices()
    return all(d.platform == "cpu" for d in devices)


def get_mesh(
    devices=None,
    axis_shapes: Optional[Sequence[Tuple[str, int]]] = None,
    platform: Optional[str] = None,
) -> Mesh:
    """Build (or fetch the cached) mesh.

    Default shape is a 1-D ``("batch",)`` data mesh over all available
    devices — simulated host devices from
    ``--xla_force_host_platform_device_count`` count like real ones.
    ``axis_shapes`` opts into richer layouts, e.g.
    ``[("batch", 4), ("model", 2)]``.
    """
    if devices is None:
        devices = available_devices(platform)
    devices = list(devices)
    if axis_shapes is None:
        axis_shapes = [(BATCH_AXIS, len(devices))]
    names = tuple(n for n, _ in axis_shapes)
    shape = tuple(int(s) for _, s in axis_shapes)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {dict(axis_shapes)} needs {n} devices, "
            f"have {len(devices)}"
        )
    key = (tuple(id(d) for d in devices[:n]), names, shape)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = Mesh(np.asarray(devices[:n]).reshape(shape), names)
        _MESH_CACHE[key] = mesh
    return mesh


def clear_mesh_cache() -> None:
    _MESH_CACHE.clear()


def data_axis(mesh: Mesh) -> str:
    """The data-parallel axis of a mesh: its first axis (``"batch"``
    on every mesh this package builds by default)."""
    return mesh.axis_names[0]


def num_shards(mesh: Mesh) -> int:
    return int(mesh.shape[data_axis(mesh)])


def model_axis(mesh: Mesh) -> Optional[str]:
    """The tensor-parallel axis name when the mesh carries one
    (2-D data x model layouts built by ``resolve_mesh`` with
    ``model_parallel`` set), else None. Presence — even at size 1 —
    activates per-leaf param placement in the learn programs; size 1
    keeps every leaf whole (the parity geometry)."""
    return MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None


def model_shards(mesh: Mesh) -> int:
    """Size of the model axis (1 when the mesh has none)."""
    if MODEL_AXIS in mesh.axis_names:
        return int(mesh.shape[MODEL_AXIS])
    return 1


def resolve_model_parallel(config, devices=None, strict: bool = False) -> int:
    """Resolve ``AlgorithmConfig.model_parallel`` (None | "auto" |
    int) to the model-axis size M of this run's mesh.

    Returns 0 when unset — the legacy 1-D data mesh, no model axis at
    all — so existing runs are untouched. Any non-zero M (including
    an explicit 1) builds the 2-D ``[("batch", D//M), ("model", M)]``
    mesh and routes params through the per-leaf rule placement.
    ``"auto"`` resolves to 1 on the CPU client (tensor parallelism
    buys nothing without an accelerator memory wall) and to 2 behind
    a real accelerator when the device count is even."""
    mode = config.get("model_parallel")
    if mode in (None, False, 0):
        return 0
    if devices is None:
        devices = jax.devices()
    n = len(list(devices))
    if mode == "auto":
        if all(d.platform == "cpu" for d in devices):
            return 1
        return 2 if (n >= 2 and n % 2 == 0) else 1
    m = int(mode)
    if m < 1:
        return 0 if m == 0 else 1
    if n % m:
        if strict:
            raise ValueError(
                f"model_parallel={m} does not divide the {n} learner "
                "devices"
            )
        # non-strict callers (rollout workers resolving their own
        # 1-device CPU mesh from the shipped config) degrade to the
        # 1-D data mesh — inference replicas never split params
        return 0
    return m


def resolve_hosts(config, strict: bool = False) -> int:
    """Resolve ``AlgorithmConfig.hosts`` (None | "auto" | int) to the
    number of jax processes the learner mesh spans.

    Returns 1 when unset — the single-process mesh, unchanged
    behavior. ``"auto"`` adopts however many processes the
    jax.distributed runtime brought up (``dist.initialize`` ran first
    in Algorithm.setup). An explicit N asserts the runtime actually
    spans N processes when ``strict`` — a mesh silently smaller than
    the config promised is the hardest multi-host bug to notice."""
    mode = config.get("hosts")
    if mode in (None, False, 0):
        return 1
    if mode == "auto":
        return int(jax.process_count())
    h = int(mode)
    if h < 1:
        return 1
    if strict and h != jax.process_count():
        raise ValueError(
            f"sharding(hosts={h}) but the jax runtime spans "
            f"{jax.process_count()} process(es) — set "
            "RAY_TPU_COORDINATOR/RAY_TPU_NUM_PROCESSES/"
            "RAY_TPU_PROCESS_ID (or hosts='auto') so the fleet "
            "geometry and the runtime agree"
        )
    return h


def global_devices(hosts: int):
    """The devices a ``hosts``-process learner mesh is built from:
    every process's devices when the mesh spans hosts (the DCN × ICI
    global view — XLA routes collectives over ICI within a host and
    DCN across), this process's local devices otherwise."""
    if hosts > 1:
        return list(jax.devices())
    return list(jax.local_devices())


def simulated_device_env(n: int) -> dict:
    """Env-var dict that makes a fresh process expose ``n`` simulated
    CPU devices (must be set before jax initializes its backend; use
    for subprocess tests and docs examples)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags}
