"""Mesh-sharded concurrent Tune trials (VERDICT r3 #9).

``resources_per_trial={"TPU": k}`` no longer forces time-slicing when
the mesh is big enough: the device pool partitions into disjoint
k-device submeshes and trials run concurrently on threads, each
jitting its own shard_map programs onto its own devices (the
reference's fractional-GPU trial packing, done the TPU way)."""

import json
import os
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.tune import (
    PopulationBasedTraining,
    Trainable,
    grid_search,
    run,
)

_BARRIER = threading.Barrier(2)
_MESH_DEVICES = []


class _MeshQuadratic(Trainable):
    """The PBT toy quadratic, but every step runs a jitted shard_map
    program on the trial's OWN submesh and proves overlap with a
    2-party barrier (both trials must be inside step() at once for it
    to pass)."""

    def setup(self, config):
        self.mesh = config["_mesh"]
        _MESH_DEVICES.append(
            tuple(d.id for d in self.mesh.devices.ravel())
        )
        self.x = float(config.get("x", 0.0))
        self.lr = float(config.get("lr", 0.1))
        mesh = self.mesh

        def dist_sq_err(xs):
            return jax.shard_map(
                lambda a: jax.lax.psum(
                    ((a - 3.0) ** 2).sum(), "batch"
                ),
                mesh=mesh,
                in_specs=P("batch"),
                out_specs=P(),
            )(xs)

        self._jit = jax.jit(dist_sq_err)
        self._concurrent = False

    def step(self):
        try:
            _BARRIER.wait(timeout=30)
            self._concurrent = True
        except threading.BrokenBarrierError:
            pass
        n = len(self.mesh.devices.ravel())
        err = float(self._jit(jnp.full((n * 2,), self.x)))
        self.x = self.x + self.lr * 2 * (3.0 - self.x)
        return {
            "episode_reward_mean": -err,
            "concurrent": self._concurrent,
        }

    def save_checkpoint(self, d):
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"x": self.x, "lr": self.lr}, f)
        return d

    def load_checkpoint(self, path):
        with open(os.path.join(path, "state.json")) as f:
            s = json.load(f)
        self.x, self.lr = s["x"], s["lr"]


def test_pbt_mesh_sharded_concurrent_trials():
    _MESH_DEVICES.clear()
    scheduler = PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"lr": [0.05, 0.1, 0.3]},
    )
    analysis = run(
        _MeshQuadratic,
        config={"x": grid_search([0.0, 20.0]), "lr": 0.1},
        stop={"training_iteration": 6},
        scheduler=scheduler,
        resources_per_trial={"TPU": 4},
        verbose=0,
    )
    # the two trials ran on DISJOINT 4-device submeshes of the
    # 8-device test mesh
    meshes = set(_MESH_DEVICES)
    assert len(meshes) == 2, meshes
    a, b = sorted(meshes)
    assert len(a) == 4 and len(b) == 4
    assert not set(a) & set(b), (a, b)
    # and genuinely overlapped inside step() (the barrier passed)
    best = analysis.get_best_trial()
    assert best is not None
    assert best.last_result.get("concurrent") is True
    # the optimization still works end to end
    assert best.last_result["episode_reward_mean"] > -10.0


def test_single_slot_falls_back_to_time_slicing():
    """One slot's worth of devices → the round-3 sequential
    time-slicing path still works (1-chip hosts)."""
    analysis = run(
        _MeshQuadratic2,
        config={"x": grid_search([0.0, 10.0]), "lr": 0.2},
        stop={"training_iteration": 3},
        resources_per_trial={"TPU": 8},  # all 8 devices per trial
        verbose=0,
    )
    best = analysis.get_best_trial()
    assert best is not None


class _MeshQuadratic2(Trainable):
    """Sequential-mode variant: no _mesh key arrives (time-slicing
    path), so it just runs the quadratic."""

    def setup(self, config):
        assert "_mesh" not in config  # sequential mode: no submesh
        self.x = float(config.get("x", 0.0))
        self.lr = float(config.get("lr", 0.1))

    def step(self):
        self.x = self.x + self.lr * 2 * (3.0 - self.x)
        return {"episode_reward_mean": -((self.x - 3.0) ** 2)}

    def save_checkpoint(self, d):
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"x": self.x}, f)
        return d

    def load_checkpoint(self, path):
        with open(os.path.join(path, "state.json")) as f:
            self.x = json.load(f)["x"]
