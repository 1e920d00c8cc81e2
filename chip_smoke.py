"""The quickest proof that the system still starts on the chip.

Drives the repo's main path once, through the entry points a user
would call, at the full width the repo ships: the PPO learner on the
Nature-CNN pixel policy (VisionNet 32/64/64 convs + 512 dense on
84x84x4 uint8; train batch 2048, minibatch 512, 6 epochs), fed

  - by CPU rollout actors  (tuned_examples/ppo/ponglite-ppo.yaml,
    3 training iterations), and
  - by the fused device lane (tuned_examples/ppo/ponglitejax-ppo.yaml,
    2 training iterations of K=8 rollout+learn updates per dispatch),

each yaml loaded through the loader and config assembly
``python -m ray_tpu.train -f`` uses, with only ``stop`` replaced.
Weights are random, made from the yaml's seed. It checks what comes
out (steps counted, finite losses, params and batch shards on every
TPU device of the mesh, rollout workers on the CPU, zero retraces
after each lane's first iteration), prints wall time per phase with
jax's own compile seconds apart from step seconds, and prints as the
LAST line of stdout one JSON object naming the device.

    python chip_smoke.py          # on a machine with a TPU

ONE process owns the chip: this one. It exits non-zero at once,
naming what it found, unless ``jax.devices()[0].platform == "tpu"``;
no phase is wrapped in ``try/except`` — any failure is a non-zero
exit and no result line. Run it twice in one place and the second
run's compile seconds show the persistent compile cache hitting
(``ray_tpu/utils/platform.ensure_compile_cache``).
"""

from __future__ import annotations

import json
import os
import sys
import time

ACTOR_YAML = "tuned_examples/ppo/ponglite-ppo.yaml"
FUSED_YAML = "tuned_examples/ppo/ponglitejax-ppo.yaml"
ACTOR_ITERS = 3
FUSED_ITERS = 2
FUSED_K = 8  # what superstep="auto" must resolve to behind a chip

_HERE = os.path.dirname(os.path.abspath(__file__))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


class CompileMeter:
    """jax's own account of compiling in THIS process (the chip's
    owner): seconds inside the backend compile call — a persistent-
    cache hit spends its retrieval there instead of a compile — plus
    the cache's hit/miss events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.compile_s, self.hits, self.misses)

    def since(self, snap):
        c, h, m = snap
        return {
            "compile_s": round(self.compile_s - c, 2),
            "cache_hits": self.hits - h,
            "cache_misses": self.misses - m,
        }


def worker_backend(worker):
    """Runs inside a rollout worker process."""
    import jax

    return {"pid": os.getpid(), "backend": jax.default_backend()}


def build_algorithm(yaml_path: str):
    """The Algorithm ``python -m ray_tpu.train -f yaml_path`` would
    run as its one in-process trial (tune.run instantiates exactly
    ``get_algorithm_class(run)(config=config)``); ``stop`` is dropped —
    the caller steps ``train()`` itself."""
    from ray_tpu.algorithms.registry import get_algorithm_class
    from ray_tpu.train.__main__ import experiment_args, load_experiments

    (spec,) = load_experiments(os.path.join(_HERE, yaml_path)).values()
    run, config, _stop = experiment_args(spec)
    return get_algorithm_class(run)(config=config), config


def check_params_on_every_device(policy, devices) -> None:
    import jax

    want = set(devices)
    leaves = jax.tree_util.tree_leaves(policy.params)
    check(bool(leaves), "policy has no params")
    for leaf in leaves:
        check(
            set(leaf.sharding.device_set) == want
            and leaf.is_fully_replicated,
            f"param leaf {leaf.shape} lives on "
            f"{sorted(d.id for d in leaf.sharding.device_set)}, not "
            f"replicated over all of {sorted(d.id for d in want)}",
        )
        check(
            all(d.platform == "tpu" for d in leaf.sharding.device_set),
            "param leaf off the TPU",
        )


def check_rows_on_every_device(tree, rows: int, devices, what) -> int:
    """Every column of ``tree`` with ``rows`` leading rows holds one
    equal shard on EACH device — what catches everything-on-device-0.
    Returns how many such columns were seen."""
    want = set(devices)
    seen = 0
    for name, col in tree.items():
        if not col.shape or col.shape[0] != rows:
            continue  # the replicated frame pool, scalars
        seen += 1
        shards = col.addressable_shards
        check(
            {s.device for s in shards} == want,
            f"{what} column {name!r} has shards on "
            f"{sorted(s.device.id for s in shards)} only",
        )
        check(
            all(s.data.shape[0] == rows // len(want) for s in shards),
            f"{what} column {name!r} is not split evenly: "
            f"{[s.data.shape for s in shards]}",
        )
    return seen


def loss_of(result) -> float:
    return float(
        result["info"]["learner"]["default_policy"]["total_loss"]
    )


def with_walls(out: dict, t0: float) -> dict:
    out["wall_s"] = round(time.perf_counter() - t0, 2)
    out["step_s"] = round(out["wall_s"] - out["compile_s"], 2)
    return out


def actor_lane(meter, devices) -> dict:
    """CPU rollout workers -> shm -> H2D -> the learner's SGD nest."""
    import numpy as np

    import ray_tpu as ray
    from ray_tpu import native
    from ray_tpu.sharding.compile import compile_stats

    # the ring library is built on THIS machine from the committed
    # shm_ring.cpp (build artifacts are gitignored); without it the
    # workers' bulk-result ring quietly drops to pipes
    check(native.available(), "native shm ring did not build")

    t0 = time.perf_counter()
    snap = meter.snapshot()
    algo, config = build_algorithm(ACTOR_YAML)
    try:
        setup_s = time.perf_counter() - t0
        policy = algo.get_policy()
        mesh_devices = list(policy.mesh.devices.flat)
        check(
            set(mesh_devices) == set(devices),
            f"learner mesh {policy.mesh} does not span every device",
        )
        rows = int(config["train_batch_size"])

        # observe (not alter) what the learn program is handed
        seen_batches = []
        learn = policy.learn_on_device_batch

        def spy(dev, bsize, *a, **kw):
            seen_batches.append(
                check_rows_on_every_device(
                    dev, bsize, mesh_devices, "train batch"
                )
            )
            check(bsize == rows, f"train batch of {bsize} != {rows}")
            return learn(dev, bsize, *a, **kw)

        policy.learn_on_device_batch = spy

        iters = []
        traces_after_first = None
        for i in range(ACTOR_ITERS):
            ti = time.perf_counter()
            result = algo.train()
            wall = time.perf_counter() - ti
            loss = loss_of(result)
            check(np.isfinite(loss), f"iteration {i + 1} loss {loss}")
            check(
                result["num_env_steps_sampled"] == (i + 1) * rows
                and result["num_env_steps_trained"] == (i + 1) * rows,
                f"iteration {i + 1}: sampled "
                f"{result['num_env_steps_sampled']}, trained "
                f"{result['num_env_steps_trained']}, want "
                f"{(i + 1) * rows} each",
            )
            iters.append({"wall_s": round(wall, 2), "total_loss": loss})
            if i == 0:
                traces_after_first = compile_stats()["traces"]
        retraces = compile_stats()["traces"] - traces_after_first
        check(retraces == 0, f"{retraces} trace(s) after iteration 1")
        check(
            len(seen_batches) == ACTOR_ITERS and all(seen_batches),
            f"learn program saw {seen_batches} row-sharded columns",
        )
        check_params_on_every_device(policy, mesh_devices)

        # every rollout worker is a CPU process; this one kept the chip
        workers = algo.workers.remote_workers()
        check(
            len(workers) == int(config["num_workers"]),
            f"{len(workers)} rollout workers",
        )
        reports = ray.get([w.apply.remote(worker_backend) for w in workers])
        for r in reports:
            check(
                r["backend"] == "cpu" and r["pid"] != os.getpid(),
                f"rollout worker reports {r}",
            )
        import jax

        check(
            jax.default_backend() == "tpu",
            "driver lost the TPU backend",
        )

        # each worker's result ring is attached driver-side (not
        # dropped to pipes). Pixel fragments are MBs — above the
        # ring's 32-768 KB band — so they ride dedicated shm segments
        # by design; what rode the ring is reported, not asserted.
        rt = ray.core.api._require_runtime()
        recs = [
            rec.worker
            for rec in rt.actors.values()
            if rec.worker is not None
        ]
        check(
            len(recs) >= len(workers)
            and all(w.ring is not None for w in recs),
            "a worker's shm ring is not attached",
        )
        out = {
            "setup_s": round(setup_s, 2),
            "iterations": iters,
            "retraces_after_first": retraces,
            "rollout_workers": reports,
            "rings_attached": len(recs),
            "results_over_ring": sum(w.ring_results for w in recs),
            **meter.since(snap),
        }
    finally:
        algo.cleanup()
        ray.shutdown()
    return with_walls(out, t0)


def fused_lane(meter, devices) -> dict:
    """rollout(T) + GAE + the SGD nest, K=8 per dispatch, on device."""
    import numpy as np

    import ray_tpu as ray
    from ray_tpu import telemetry
    from ray_tpu.sharding.compile import compile_stats

    t0 = time.perf_counter()
    snap = meter.snapshot()
    algo, config = build_algorithm(FUSED_YAML)
    try:
        setup_s = time.perf_counter() - t0
        policy = algo.get_policy()
        mesh_devices = list(policy.mesh.devices.flat)
        check(
            set(mesh_devices) == set(devices),
            f"learner mesh {policy.mesh} does not span every device",
        )
        k = algo._resolve_superstep_k()
        check(k == FUSED_K, f"superstep 'auto' resolved to K={k}")
        rows = int(config["train_batch_size"])

        def updates():
            return telemetry.metrics.counter_total(
                telemetry.metrics.SUPERSTEP_UPDATES_TOTAL
            )

        def dispatches():
            return sum(
                f["calls"]
                for f in compile_stats()["per_function"]
                if "superstep[" in f["label"]
            )

        iters = []
        traces_after_first = None
        for i in range(FUSED_ITERS):
            u0, d0 = updates(), dispatches()
            ti = time.perf_counter()
            result = algo.train()
            wall = time.perf_counter() - ti
            loss = loss_of(result)
            check(np.isfinite(loss), f"iteration {i + 1} loss {loss}")
            du, dd = updates() - u0, dispatches() - d0
            check(
                dd == 1 and du == FUSED_K,
                f"iteration {i + 1}: {du} updates in {dd} dispatch(es)"
                f", want {FUSED_K} in 1",
            )
            check(
                result["num_env_steps_trained"]
                == (i + 1) * FUSED_K * rows,
                f"iteration {i + 1}: trained "
                f"{result['num_env_steps_trained']} env steps",
            )
            iters.append(
                {
                    "wall_s": round(wall, 2),
                    "total_loss": loss,
                    "updates_per_dispatch": int(du // dd),
                }
            )
            if i == 0:
                traces_after_first = compile_stats()["traces"]
        retraces = compile_stats()["traces"] - traces_after_first
        check(retraces == 0, f"{retraces} trace(s) after iteration 1")
        check_params_on_every_device(policy, mesh_devices)
        # the env carry IS this lane's batch source: one equal slice of
        # the env slots on every device
        eng = algo._jax_engine()
        check(
            check_rows_on_every_device(
                {"obs": eng._carry["obs"], "ep_ret": eng._carry["ep_ret"]},
                eng.N,
                mesh_devices,
                "env carry",
            )
            == 2,
            "env carry columns missing",
        )
        out = {
            "setup_s": round(setup_s, 2),
            "k": k,
            "iterations": iters,
            "retraces_after_first": retraces,
            **meter.since(snap),
        }
    finally:
        algo.cleanup()
        ray.shutdown()
    return with_walls(out, t0)


def main() -> int:
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but jax found {len(devices)} "
            f"{dev.platform!r} device(s) ({dev.device_kind}); "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}",
            file=sys.stderr,
        )
        return 2

    import importlib.metadata

    import jaxlib

    from ray_tpu.utils.platform import device_info, ensure_compile_cache

    cache_dir = ensure_compile_cache()
    meter = CompileMeter()
    device = device_info()
    say(
        "device",
        **device,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache_dir=cache_dir,
        compile_cache_entries=(
            len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir)
            else 0
        ),
    )
    say("actor_lane", **actor_lane(meter, devices))
    say("fused_lane", **fused_lane(meter, devices))
    say("total", wall_s=round(time.perf_counter() - t0, 2))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
