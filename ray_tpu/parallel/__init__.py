"""Legacy parallel namespace — an adapter over the sharding runtime
(``ray_tpu.sharding``). The mesh helpers re-exported here keep the
historical ``("data",)`` axis naming for the pmap-backend learn
programs; new code targets ``ray_tpu.sharding``."""

from ray_tpu.parallel.mesh import (
    make_mesh,
    data_sharding,
    replicated,
    num_data_shards,
    DATA_AXIS,
    MODEL_AXIS,
)

__all__ = [
    "make_mesh",
    "data_sharding",
    "replicated",
    "num_data_shards",
    "DATA_AXIS",
    "MODEL_AXIS",
]
