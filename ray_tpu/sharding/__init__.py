"""ray_tpu.sharding — the mesh-based sharding runtime of the learner
(docs/sharding.md):

  - :mod:`~ray_tpu.sharding.mesh`    mesh construction (cached,
    simulated devices), ``("batch",)`` data mesh today with the
    ``"model"`` axis name reserved;
  - :mod:`~ray_tpu.sharding.specs`   NamedSharding builders: replicated
    param trees, row-sharded batch columns, per-leaf trees with the
    ragged-leading-dim fallback;
  - :mod:`~ray_tpu.sharding.compile` ``sharded_jit`` — jit with
    shardings + donation + compile-cache stats.

Every learn program lowers through ``sharded_jit`` with explicit
shardings on the mesh :func:`resolve_mesh` builds from the config.
"""

from ray_tpu.sharding.compile import (
    ShardedFunction,
    compile_stats,
    dispatch_count,
    f64_scope,
    sharded_jit,
)
from ray_tpu.sharding.mesh import (
    BATCH_AXIS,
    MODEL_AXIS,
    available_devices,
    clear_mesh_cache,
    data_axis,
    get_mesh,
    global_devices,
    model_axis,
    model_shards,
    num_shards,
    resolve_hosts,
    resolve_model_parallel,
    simulated_device_env,
)
from ray_tpu.sharding.specs import (
    batch_sharded,
    clear_sharding_caches,
    default_partition_rules,
    leaf_sharding,
    manual_pspecs,
    mesh_spans_processes,
    named_tree,
    param_pspecs,
    param_sharding,
    put_global,
    replicated,
    shard_batch,
    sharding_tree,
    state_pspecs,
    tree_nbytes,
    tree_shard_nbytes,
    varying,
    vma_barrier,
    vma_of,
)
from ray_tpu.sharding.registry import (
    ProgramRegistry,
    ProgramSpec,
    for_algorithm as registry_for_algorithm,
)
from ray_tpu.sharding.superstep import (
    build_stack_fn,
    build_superstep_fn,
    resolve_superstep,
)


def refuse_removed_options(options) -> None:
    """Fail on an option this package no longer reads (the config
    setters and :func:`resolve_mesh` call it, so no spelling of a
    config passes one silently)."""
    if "sharding_backend" in options:
        raise ValueError(
            "sharding_backend was removed in PR 30: the 'pmap' backend "
            "reached no fused lane and every learn program now lowers "
            "through the mesh runtime; drop the option "
            "(docs/MIGRATION.md)"
        )


def resolve_mesh(config):
    """The mesh a policy should learn on, per config: an injected
    ``_mesh`` (Algorithm.setup, multi-host tests) wins; otherwise it
    is built here. ``sharding(hosts=N)`` builds over the GLOBAL device
    view (every process of the jax.distributed runtime — the DCN × ICI
    mesh of docs/fleet.md) instead of this process's local devices."""
    refuse_removed_options(config)
    m = config.get("_mesh")
    if m is not None:
        return m
    hosts = resolve_hosts(config)
    mp = resolve_model_parallel(config)
    if hosts > 1:
        devs = global_devices(hosts)
        if mp:
            return get_mesh(
                devices=devs,
                axis_shapes=[
                    (BATCH_AXIS, len(devs) // mp),
                    (MODEL_AXIS, mp),
                ],
            )
        return get_mesh(devices=devs)
    if mp:
        devs = list(available_devices())
        return get_mesh(
            devices=devs,
            axis_shapes=[
                (BATCH_AXIS, len(devs) // mp),
                (MODEL_AXIS, mp),
            ],
        )
    return get_mesh()


__all__ = [
    "BATCH_AXIS",
    "MODEL_AXIS",
    "ProgramRegistry",
    "ProgramSpec",
    "ShardedFunction",
    "registry_for_algorithm",
    "available_devices",
    "batch_sharded",
    "build_stack_fn",
    "build_superstep_fn",
    "default_partition_rules",
    "resolve_superstep",
    "clear_mesh_cache",
    "compile_stats",
    "data_axis",
    "dispatch_count",
    "f64_scope",
    "get_mesh",
    "global_devices",
    "leaf_sharding",
    "manual_pspecs",
    "mesh_spans_processes",
    "model_axis",
    "model_shards",
    "named_tree",
    "num_shards",
    "param_pspecs",
    "param_sharding",
    "put_global",
    "refuse_removed_options",
    "replicated",
    "resolve_hosts",
    "resolve_mesh",
    "resolve_model_parallel",
    "shard_batch",
    "sharded_jit",
    "sharding_tree",
    "simulated_device_env",
    "state_pspecs",
    "tree_nbytes",
    "tree_shard_nbytes",
    "varying",
    "vma_barrier",
    "vma_of",
]
