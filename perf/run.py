"""Run one cell of the benchmark once.

    python3 -m perf.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process, and the only one that touches JAX (rollout workers,
where a traffic mix has any, are spawned and pinned to the CPU by
``ray_tpu/core/worker_proc.py``). It refuses to start without a TPU,
builds the Algorithm as ``python -m ray_tpu.train -f`` would
(``experiment_args`` + the registry), loads weights made from the
seed, runs the ``correct`` comparison, warms the cell's own programs,
measures ``Algorithm.train()`` iterations for ``--seconds`` and prints
one JSON object as the last line of stdout. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from perf import correct as correct_lib  # noqa: E402
from perf import flops as flops_lib  # noqa: E402
from perf import manifest as manifest_lib  # noqa: E402
from perf.trace_reduce import TRAIN_ANNOTATION  # noqa: E402


class CompileMeter:
    """jax's own account of compiling in this process: seconds inside
    the backend compile call (a persistent-cache hit spends its
    retrieval there) and the cache's hit / miss events. Copy of
    ``chip_smoke.py``'s."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Window:
    """What the measured window saw. ``walls`` are host-clock seconds
    of each ``Algorithm.train()`` call; ``seconds`` runs from the
    first dispatch to a final ``block_until_ready``."""

    def __init__(self):
        self.walls: List[float] = []
        self.failed = 0
        self.seconds = 0.0
        self.before: Dict[str, Any] = {}
        self.after: Dict[str, Any] = {}

    def delta(self, key: str) -> float:
        if isinstance(self.after[key], dict):  # a count per program label
            return _grown(self.before[key], self.after[key])
        return self.after[key] - self.before[key]

    def updates(self) -> float:
        """Optimizer updates: the superstep's counter, or learn calls
        where no superstep runs."""
        return self.delta("updates") or self.delta("learn_steps")

    def env_steps(self, trained_per_sampled: float = 1) -> float:
        """Env steps that were sampled AND entered training."""
        return min(
            self.delta("sampled"), self.delta("trained") / trained_per_sampled
        )

    def h2d_delta(self, path: Optional[str] = None) -> float:
        a, b = self.after["h2d"], self.before["h2d"]
        paths = [path] if path else set(a) | set(b)
        return sum(a.get(p, 0.0) - b.get(p, 0.0) for p in paths)


class Context:
    """Everything a per-layer reader may read."""

    def __init__(self, cell, algo, window, chips, device_kind, num_actions):
        self.cell = cell
        self.algo = algo
        self.window: Window = window
        self.chips = chips
        self.device_kind = device_kind
        self.num_actions = num_actions
        self.setup: Dict[str, float] = {}
        self.memory_peak_bytes: Optional[int] = None
        self.program_temp_bytes: Dict[str, int] = {}
        self.trace = None  # perf.trace_reduce.Trace of the traced span
        self.traced: Optional[Window] = None  # counters over that span

    def env_steps(self) -> float:
        """Of the untraced window, by the traffic mix's own ratio of
        trained rows to sampled steps."""
        ratio = (self.cell.traffic.get("expect") or {}).get(
            "trained_per_sampled", 1
        )
        return self.window.env_steps(ratio)


def _counters(algo) -> Dict[str, Any]:
    from ray_tpu import telemetry
    from ray_tpu.sharding.compile import compile_stats

    stats = compile_stats()
    c = algo._counters
    return {
        "sampled": int(c["num_env_steps_sampled"]),
        "trained": int(c["num_env_steps_trained"]),
        "updates": telemetry.metrics.counter_total(
            telemetry.metrics.SUPERSTEP_UPDATES_TOTAL
        ),
        "learn_steps": telemetry.metrics.learn_steps_total(),
        "traces": _by_label(stats["per_function"], "traces"),
        "calls": _by_label(stats["per_function"], "calls"),
        "h2d": dict(telemetry.metrics.h2d_bytes_by_path()),
    }


def _by_label(per_function: List[Dict], key: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for f in per_function:
        out[f["label"]] = out.get(f["label"], 0) + int(f[key])
    return out


def _grown(before: Dict[str, int], after: Dict[str, int], label_part="") -> int:
    """Sum of the per-label increases. ``compile_stats()`` sums over
    LIVE programs only, so a program that was garbage-collected in
    between (the ``correct`` comparison's one-step learn program) must
    not read as a negative count."""
    return sum(
        max(0, n - before.get(label, 0))
        for label, n in after.items()
        if label_part in label
    )


def _dispatches(before: Dict, after: Dict, label_part: str) -> int:
    return _grown(before["calls"], after["calls"], label_part)


def _loss_of(result) -> float:
    info = result["info"]["learner"].get("default_policy") or {}
    return float(info.get("total_loss", float("nan")))


def _block(policy) -> None:
    import jax

    jax.block_until_ready(policy.params)


def build_algorithm(cell, seed: int, chips: int, n_devices: int):
    """The Algorithm ``python -m ray_tpu.train -f <yaml>`` would run
    as its one in-process trial, from the cell's files."""
    from ray_tpu.algorithms.registry import get_algorithm_class
    from ray_tpu.train.__main__ import experiment_args

    spec = cell.experiment_spec(seed)
    if n_devices > chips:
        # a 1-chip cell on a larger host keeps to its chips
        spec["config"]["learner_devices"] = chips
    run, config, _stop = experiment_args(spec)
    return get_algorithm_class(run)(config=config)


def load_seeded_weights(cell, policy, ref, seed32: int, num_actions: int):
    """Weights from ``--seed``, made by the benchmark on the device in
    one jitted call and handed to the policy (and its target network);
    returns them in the reference's names."""
    import jax

    params = ref.init_params(
        jax.random.fold_in(jax.random.PRNGKey(seed32), 7), cell.config, num_actions
    )
    tree = ref.to_policy_tree(params, cell.config)
    have = jax.tree_util.tree_map(lambda x: tuple(x.shape), policy.params)
    want = jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)
    if have != want:
        raise SystemExit(
            "perf: the configuration file's model does not match the "
            f"policy's parameters:\n  file:   {want}\n  policy: {have}"
        )
    policy.set_weights(tree)
    if hasattr(policy, "update_target"):
        policy.update_target()
    return params


def measure(algo, seconds: float, expect: Dict, annotate=None,
            max_iterations: Optional[int] = None) -> Window:
    """``Algorithm.train()`` back to back for ``seconds`` (the
    iteration in flight at the deadline finishes and counts), or for
    ``max_iterations`` calls. Inside the timed region an iteration
    costs two clock reads, two dictionary reads and one float check;
    the program's counter tables are read once before and once after."""
    import contextlib

    import numpy as np

    policy = algo.get_policy()
    ratio = expect.get("trained_per_sampled")
    c = algo._counters
    win = Window()
    _block(policy)
    win.before = _counters(algo)
    sampled, trained = win.before["sampled"], win.before["trained"]
    t0 = time.perf_counter()
    while True:
        ti = time.perf_counter()
        with annotate(TRAIN_ANNOTATION) if annotate else contextlib.nullcontext():
            result = algo.train()
        now = time.perf_counter()
        win.walls.append(now - ti)
        s, t = int(c["num_env_steps_sampled"]), int(c["num_env_steps_trained"])
        ok = np.isfinite(_loss_of(result)) and s > sampled and t > trained
        if ratio is not None:
            ok = ok and t - trained == ratio * (s - sampled)
        win.failed += not ok
        sampled, trained = s, t
        if now - t0 >= seconds or len(win.walls) == max_iterations:
            break
    _block(policy)
    win.seconds = time.perf_counter() - t0
    win.after = _counters(algo)
    # updates and dispatches add up over the whole window, or the
    # iterations short of the claim count as failed
    n = len(win.walls)
    short = 0
    want_updates = expect.get("updates_per_iteration")
    if want_updates is not None:
        short = max(short, abs(n - win.delta("updates") / want_updates))
    want_dispatch = expect.get("dispatches_per_iteration")
    if want_dispatch is not None:
        calls = _dispatches(win.before, win.after, expect["dispatch_label"])
        short = max(short, abs(n - calls / want_dispatch))
    win.failed = min(n, win.failed + int(-(-short // 1)))
    return win


def _iteration_ok(prev, cur, result, expect, np) -> bool:
    """A finite loss and step counts that add up to what the traffic
    mix claims for one iteration."""
    ok = bool(np.isfinite(_loss_of(result)))
    want_updates = expect.get("updates_per_iteration")
    if want_updates is not None:
        ok &= cur["updates"] - prev["updates"] == want_updates
    want_dispatch = expect.get("dispatches_per_iteration")
    if want_dispatch is not None:
        ok &= (
            _dispatches(prev, cur, expect["dispatch_label"]) == want_dispatch
        )
    ratio = expect.get("trained_per_sampled")
    sampled = cur["sampled"] - prev["sampled"]
    trained = cur["trained"] - prev["trained"]
    ok &= sampled > 0 and trained > 0
    if ratio is not None:
        ok &= trained == ratio * sampled
    return bool(ok)


def warm_up(algo, iterations: int) -> int:
    for _ in range(int(iterations)):
        algo.train()
    _block(algo.get_policy())
    return int(iterations)


def run_correct(state):
    """The comparisons of set-up: the layout the configuration states,
    then the checks the cell's files list for before the program's own
    first iterations (``warmup.first_iterations``: they define the
    ring's columns) and for after them. Returns the iterations run."""
    cell = state.cell
    correct_lib.layout_checks(
        state.checks, state.policy, state.devices, cell.param_layout
    )
    for _, check in cell.checks("before_first_iterations"):
        check.run(state)
    warm = cell.traffic.get("warmup") or {}
    n = warm_up(state.algo, warm.get("first_iterations", 0))
    for _, check in cell.checks("after_first_iterations"):
        check.run(state)
    n += warm_up(state.algo, warm.get("then_iterations", 1))
    return n


def run_correct_after_warmup(state) -> None:
    """The comparisons that need a warmed system, around one more real
    iteration: what holds for any cell (K updates in one dispatch, the
    env carry split over the chips, workers on the CPU) and the cell's
    own ``after_warmup`` checks."""
    import numpy as np

    cell, algo, checks = state.cell, state.algo, state.checks
    expect = cell.traffic.get("expect") or {}
    after_warmup = cell.checks("after_warmup")
    for name, check in after_warmup:
        if hasattr(check, "prepare"):
            state.prepared[name] = check.prepare(state)
    before = _counters(algo)
    result = algo.train()
    after = _counters(algo)
    state.iteration = {"before": before, "after": after, "result": result}
    checks.true(
        "iteration_adds_up",
        _iteration_ok(before, after, result, expect, np),
        f"{after['updates'] - before['updates']} update(s) in "
        f"{_dispatches(before, after, expect.get('dispatch_label', 'superstep['))} "
        f"dispatch(es); sampled {after['sampled'] - before['sampled']}, "
        f"trained {after['trained'] - before['trained']}; "
        f"loss {_loss_of(result):.6g}",
    )
    label = expect.get("dispatch_label")
    if label:
        traced = sum(n for k, n in after["traces"].items() if label in k)
        checks.equal(
            "dispatch_program_traced_once", traced, 1,
            "the program the comparison ran is the one the iterations run",
        )
    eng = algo.__dict__.get("_jax_rollout_engine")
    if eng is not None:
        checks.true(
            "env_carry_split_over_every_chip",
            correct_lib.rows_split_evenly(
                {"obs": eng._carry["obs"], "ep_ret": eng._carry["ep_ret"]},
                eng.N,
                state.devices,
            ),
        )
    for _, check in after_warmup:
        check.run(state)
    workers = algo.workers.remote_workers()
    if workers:
        import ray_tpu as ray

        backends = ray.get(
            [w.apply.remote(_worker_backend) for w in workers]
        )
        checks.true(
            "rollout_workers_on_cpu",
            all(b == "cpu" for b in backends),
            f"{backends}",
        )


def _worker_backend(worker):
    import jax

    return jax.default_backend()


def program_temp_bytes(ledger_snapshot: Dict) -> Dict[str, int]:
    """``{program label: temp bytes per chip}`` from the program's
    ledger (XLA's ``memory_analysis().temp_size_in_bytes`` of each
    compiled program, which is per device)."""
    out = {}
    for entry in ledger_snapshot.get("programs") or []:
        mem = entry.get("memory") or {}
        if mem.get("temp_bytes"):
            out[entry["label"]] = int(mem["temp_bytes"])
    return out


def _quartile_note(walls: List[float]) -> Dict[str, float]:
    if len(walls) < 2:
        return {"n": len(walls)}
    q = statistics.quantiles(walls, n=4)
    return {"n": len(walls), "q1_ms": q[0] * 1e3, "median_ms": q[1] * 1e3,
            "q3_ms": q[2] * 1e3, "max_ms": max(walls) * 1e3}


def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-95 * len(s) // 100) - 1))]


def end_to_end_values(ctx: Context) -> Dict[str, float]:
    """The benchmark's own end-to-end numbers, by name."""
    win = ctx.window
    return {
        "env_steps_per_s": ctx.env_steps() / win.seconds,
        "iter_p95_ms": p95(win.walls) * 1e3,
        "setup_s": ctx.setup["setup_s"],
    }


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> Dict[str, Any]:
    """Build, check, warm, measure, reduce. Returns the result object
    (``main`` prints it). ``require_tpu=False`` is for the CPU
    rehearsal in ``perf/tests`` only."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(
            f"perf: needs a TPU, but jax found {len(devices)} "
            f"{platform!r} device(s) ({devices[0].device_kind}); "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
        )
    if len(devices) < cell.chips:
        raise SystemExit(
            f"perf: cell {cell.name!r} needs {cell.chips} chip(s), "
            f"jax found {len(devices)}"
        )
    if require_tpu:
        flops_lib.load_peaks(devices[0].device_kind)  # unknown kind: error now

    import ray_tpu as ray
    from ray_tpu.utils.platform import ensure_compile_cache

    # every program into the persistent cache, however quick its
    # compile, so that set-up repeats from the second run on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = ensure_compile_cache()
    meter = CompileMeter()
    seed32 = int(seed) % (2**31 - 1)

    # the program's own ledger of compiled programs, on for set-up only:
    # it records each program's memory_analysis() as it first traces
    from ray_tpu.telemetry import device as device_ledger

    device_ledger.enable(analyze=True)
    phases = {"imports_s": time.time() - _PROCESS_T0}
    clock = time.perf_counter()

    def lap(name):
        nonlocal clock
        now = time.perf_counter()
        phases[name] = now - clock
        clock = now

    algo = build_algorithm(cell, seed32, cell.chips, len(devices))
    try:
        policy = algo.get_policy()
        cell_devices = list(policy.mesh.devices.flat)
        num_actions = int(policy.action_space.n)
        ref = cell.reference()
        ref_params = load_seeded_weights(cell, policy, ref, seed32, num_actions)
        lap("build_s")

        checks = correct_lib.Checks()
        state = correct_lib.CheckState(
            cell, algo, policy, ref, ref_params, seed, num_actions,
            cell_devices, checks,
        )
        warm_iters = run_correct(state)
        lap("correct_and_warm_s")
        run_correct_after_warmup(state)
        _block(policy)
        program_temp = program_temp_bytes(device_ledger.snapshot())
        device_ledger.disable()  # the window runs the lean dispatch path
        # free what set-up built and dropped (the comparison's one-step
        # learn program and its executable) now, not at some moment
        # inside the window
        gc.collect()
        lap("check_iteration_s")

        ctx = Context(cell, algo, None, cell.chips, devices[0].device_kind,
                      num_actions)
        ctx.setup = {
            "compile_backend_s": meter.compile_s,
            "cache_hits": meter.hits,
            "cache_misses": meter.misses,
            "warm_iterations": warm_iters,
            **phases,
        }
        ctx.setup["setup_s"] = time.time() - _PROCESS_T0
        expect = cell.traffic.get("expect") or {}
        print(f"[setup] {json.dumps(ctx.setup)} cache_dir={cache_dir}", flush=True)

        window = measure(algo, seconds, expect)
        ctx.window = window
        print(
            f"[window] seconds={window.seconds:.3f} iterations="
            f"{len(window.walls)} failed={window.failed} "
            f"walls={json.dumps(_quartile_note(window.walls))}",
            flush=True,
        )
        print(f"[window] traces_in_window={window.delta('traces')}", flush=True)

        breakdown = None
        if trace:
            from perf import trace_reduce

            ctx.trace, ctx.traced = trace_reduce.traced_span(
                algo, int(cell.traffic.get("trace_iterations", 1)), measure,
                expect, os.path.join(cell.root, ".perf_trace"), cell.chips,
            )
            breakdown = ctx.trace.breakdown()

        # as the runtime measures it. On this runtime that is live
        # buffers (params, optimizer, ring, carry); the scratch a
        # running program holds is XLA's own estimate and is reported
        # apart (device.program_scratch_gb), never added in
        ctx.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in cell_devices
        )
        ctx.program_temp_bytes = program_temp

        metrics: Dict[str, Dict[str, Any]] = {}
        if trace:
            for m in cell.per_layer:
                value = cell.reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = end_to_end_values(ctx)
            for m in cell.end_to_end:
                metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]
                }

        device = {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": ctx.memory_peak_bytes,
            "program_temp_bytes": program_temp,
        }
        if trace:
            device["busy_s"] = ctx.trace.busy_s()
            device["window_s"] = ctx.trace.span_s()
        out = {
            "correct": checks.ok,
            "attempted": len(window.walls),
            "failed": window.failed,
            "metrics": metrics,
            "device": device,
            "workload": cell.name,
            "seed": int(seed),
            "checks": checks.rows,
        }
        if breakdown is not None:
            out["breakdown"] = breakdown
        return out
    finally:
        algo.cleanup()
        ray.shutdown()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python3 -m perf.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = manifest_lib.load_cell(args.workload)
    if not os.path.isdir(os.path.join(cell.root, "ray_tpu")):
        print("perf: no system under test beside the benchmark "
              f"({cell.root}/ray_tpu is missing)", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
