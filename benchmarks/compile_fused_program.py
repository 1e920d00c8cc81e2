"""Compile a cell's fused rollout+learn program at its real size for a
described TPU v5e, from the CPU: what the chip's compiler says about
memory, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=. python benchmarks/compile_fused_program.py \\
        qwen3next_ppo.fused_tokens.1chip

Builds the cell's Algorithm on the CPU as ``perf/run.py`` does, lets one
``train()`` run as far as the fused dispatch to take its argument
shapes, builds the same program again over a mesh of the described
chip, lowers and compiles it, and prints ``memory_analysis()``
(``temp_size_in_bytes`` is what ``device.program_scratch_gb`` reads on
the chip). About 3 minutes for the Qwen3-Next cell and 7 for the Xing4
cell on 8 cores, 8 GB of host memory for the weights. With
``XLA_FLAGS="--xla_dump_to=<dir>
--xla_dump_hlo_module_re=.*rollout_superstep.*"`` the compiler also
leaves ``*buffer-assignment.txt`` (every buffer of the program's temp
allocation with its size: diff two trees' lists). Code that asks
``jax.default_backend()`` takes its CPU branch here (the one-token
delta rule lowers to its ``jax.numpy`` body, not the kernel). Only one
process at a time may hold libtpu: side by side only under
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, and never inside the tests.

With a second argument, a file name, it stops before the described chip:
the program as lowered on the CPU mesh is written there as StableHLO
text without locations, for ``cmp`` against the same cell's text from
another checkout (a change that must leave a cell's program as it was:
PR 39 held three cells to that).
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh


class _Taken(Exception):
    """Raised out of ``train()`` once the piece looked for is in hand."""


def main(cell_name: str, text_to: str = "") -> None:
    from perf import manifest, run as perf_run
    from ray_tpu.sharding import compile as compile_lib
    from ray_tpu.sharding import superstep as superstep_lib

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    start = time.time()
    algo = perf_run.build_algorithm(manifest.load_cell(cell_name), 1, 1, 1)
    policy = algo.get_policy()
    taken = {}

    # 1. the dispatch's argument shapes, from a real train() on the CPU mesh
    def shapes_only(self, *args, **kwargs):
        if "rollout_superstep" not in self.label:
            return dispatch(self, *args, **kwargs)
        taken["args"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x,
            (args, kwargs),
        )
        taken["cpu_fn"] = self
        raise _Taken()

    dispatch = compile_lib.ShardedFunction.__call__
    compile_lib.ShardedFunction.__call__ = shapes_only
    try:
        algo.train()
    except _Taken:
        print(f"built and traced as far as the dispatch in {time.time() - start:.0f} s",
              flush=True)

    if text_to:
        args, kwargs = taken["args"]
        with open(text_to, "w") as f:
            f.write(taken["cpu_fn"]._jitted.lower(*args, **kwargs).as_text())
        print(f"{cell_name}: lowered text in {text_to}", flush=True)
        os._exit(0)

    # 2. the same program over the described chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    described = Mesh(
        np.array(topo.devices[:1]).reshape(policy.mesh.devices.shape),
        policy.mesh.axis_names,
    )
    policy.mesh = algo._jax_rollout_engine.mesh = described
    policy.__dict__.pop("_superstep_fns", None)
    build = superstep_lib.build_superstep_fn

    def build_only(**kwargs):
        taken["fn"] = build(**kwargs)
        raise _Taken()

    superstep_lib.build_superstep_fn = build_only
    try:
        algo.train()
    except _Taken:
        pass

    start = time.time()
    args, kwargs = taken["args"]
    compiled = taken["fn"]._jitted.lower(*args, **kwargs).compile()
    memory = compiled.memory_analysis()
    gib = 2.0 ** 30
    sizes = {
        name: getattr(memory, name + "_size_in_bytes")
        for name in ("argument", "output", "alias", "temp")
    }
    in_all = sizes["argument"] + sizes["output"] - sizes["alias"] + sizes["temp"]
    print(f"compiled in {time.time() - start:.0f} s")
    print(cell_name, {k: round(v / gib, 3) for k, v in sizes.items()},
          f"in all {in_all / gib:.3f} GiB of 15.75",
          f"(temp {sizes['temp'] / 1e9:.3f} GB)", flush=True)
    os._exit(0)  # the Algorithm's threads hold nothing worth a clean exit


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        sys.exit(__doc__)
    main(*sys.argv[1:])
