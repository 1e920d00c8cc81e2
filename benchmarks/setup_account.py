"""Where a benchmark run's set-up went, from the program's own records.

    python3 benchmarks/setup_account.py <out.jsonl> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``perf.run`` unchanged in this process and appends one JSON line
to ``<out.jsonl>``: ``compile_stats()["families"]`` (every compile by
program family and phase, the persistent cache's hits and misses; no
``JAX_LOG_COMPILES``), the live programs' rows and ``tracing.phases()``
as they stood when the measured window began, the harness's own
``[setup]`` line, the result line, the lowering counters at exit
(``ray_tpu_*_lowerings_total``) and the newest iteration's
``LEARN_STATS`` (``attn_key_blocks_skipped_share``,
``attn_decode_key_blocks_skipped_share``, ...), and ``host_reads``
(``ray_tpu_rollout_drains_total{kind}``,
``ray_tpu_weight_pulls_skipped_total``). ``benchmarks/chip/setup_account.sh``
runs it cold and warm for each cell; ``--table`` prints PERF.md's
"Where set-up goes" from such lines.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys


# the newest iteration's learn statistics kept beside a run's result
LEARN_STATS = ("attn_key_blocks_skipped_share",
               "attn_decode_key_blocks_skipped_share", "window_rows_seen_mean",
               "moe_decode_held_experts_touched_share", "moe_rows_computed_share")


def run(out_path: str, argv) -> int:
    from perf import run as perf_run

    from ray_tpu.sharding.compile import compile_stats
    from ray_tpu.util import tracing

    record = {"argv": list(argv)}
    measure = perf_run.measure

    def measure_after_snapshot(*args, **kwargs):
        first = "programs" not in record  # the window's call, not the traced span's
        if first:
            stats = compile_stats()
            record["families"] = stats.get("families")  # None: an older tree
            record["programs"] = stats["per_function"]
            record["phases"] = getattr(tracing, "phases", list)()
        window = measure(*args, **kwargs)
        if first:
            # does an iteration's cost grow through the window?
            walls = sorted(window.walls[:100]), sorted(window.walls[-100:])
            record["walls_ms"] = {
                "n": len(window.walls),
                "first_100_median": walls[0][len(walls[0]) // 2] * 1e3,
                "last_100_median": walls[1][len(walls[1]) // 2] * 1e3,
            }
        return window

    perf_run.measure = measure_after_snapshot
    # the newest iteration's learn stats that say which path a layer took
    from ray_tpu.algorithms.algorithm import Algorithm

    train = Algorithm.train

    def train_and_keep(self):
        result = train(self)
        found = _find(result, LEARN_STATS)
        if found:
            record["learn_stats"] = found
        return result

    Algorithm.train = train_and_keep
    stdout = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, text):
            sys.__stdout__.write(text)
            return stdout.write(text)

        def flush(self):
            sys.__stdout__.flush()

    with contextlib.redirect_stdout(Tee()):
        rc = perf_run.main(argv)
    lines = stdout.getvalue().splitlines()
    for line in lines:
        if line.startswith("[setup] "):
            record["harness"] = json.loads(line[8:line.rindex("}") + 1])
    try:
        record["result"] = json.loads(lines[-1])
        record["result"].pop("checks", None)
        record["result"].pop("breakdown", None)
    except (IndexError, ValueError):
        pass
    record["families_at_exit"] = compile_stats().get("families")
    from ray_tpu.telemetry import metrics

    record["lowerings"] = {
        name: getattr(metrics, name)()
        for name in ("attention_fragment_lowerings", "attention_step_lowerings",
                     "attention_layer_lowerings",
                     "window_cache_lowerings", "moe_product_lowerings",
                     "deltanet_step_lowerings", "ssm_step_lowerings",
                     "mla_decode_lowerings")
        if hasattr(metrics, name)  # an older tree lacks the newest
    }
    if hasattr(metrics, "rollout_drains"):
        # the device lane's host half since the process began: reads of
        # a rollout's metrics by kind, weight pulls nobody needed
        record["host_reads"] = {
            "rollout_drains": metrics.rollout_drains(),
            "weight_pulls_skipped": metrics.counter_total(
                metrics.WEIGHT_PULLS_SKIPPED_TOTAL),
        }
    with open(out_path, "a") as f:
        f.write(json.dumps(record) + "\n")
    return rc


def _find(tree, names):
    """``{name: value}`` of the first entry of each name anywhere in a
    result's nested dicts."""
    found = {}
    if isinstance(tree, dict):
        for key, value in tree.items():
            if key in names:
                found.setdefault(key, float(value))
            else:
                for k, v in _find(value, names).items():
                    found.setdefault(k, v)
    return found


def table(paths) -> None:
    """One block a run: ``setup_s`` as imports + build (model init in
    it) + the program's families by phase + ``other`` + the rest."""
    for path in paths:
        for raw in open(path):
            rec = json.loads(raw)
            if not rec.get("families"):
                continue
            h, fam = rec["harness"], rec["families"]
            phase = {}
            for row in rec["phases"]:
                phase[row["name"]] = phase.get(row["name"], 0.0) + row["seconds"]
            prog = {k: v for k, v in fam.items() if k != "other"}
            other = fam.get("other", {})
            rows = list(prog.values())

            def tot(rows, key):
                return sum(r[key] for r in rows)

            print(f"== {rec['argv']}  setup_s {h['setup_s']:.1f}  "
                  f"hits/misses (harness's meter) {h['cache_hits']}/{h['cache_misses']}  "
                  f"compile.backend_s {h['compile_backend_s']:.1f}")
            print(f"   harness laps: imports {h['imports_s']:.1f} build {h['build_s']:.1f} "
                  f"correct_and_warm {h['correct_and_warm_s']:.1f} "
                  f"check_iteration {h['check_iteration_s']:.1f}")
            print("   phases: " + ", ".join(
                f"{k} {v:.2f}" for k, v in phase.items()))
            print(f"   program: trace {tot(rows, 'trace_s'):.1f} lower {tot(rows, 'lower_s'):.1f} "
                  f"backend {tot(rows, 'backend_s'):.1f} analysis {tot(rows, 'analysis_s'):.1f} "
                  f"hits {tot(rows, 'cache_hits')} misses {tot(rows, 'cache_misses')}")
            top = sorted(prog.items(), key=lambda kv: -(
                kv[1]["compile_time_s"] + kv[1]["analysis_s"]))[:5]
            for name, r in top:
                print(f"     {name}: trace {r['trace_s']:.2f} lower {r['lower_s']:.2f} "
                      f"backend {r['backend_s']:.2f} analysis {r['analysis_s']:.2f} "
                      f"hits {r['cache_hits']} misses {r['cache_misses']}")
            missed = [k for k, r in prog.items() if r["cache_misses"]]
            print(f"   families that missed: {missed}")
            if other:
                print(f"   other: trace {other['trace_s']:.1f} lower {other['lower_s']:.1f} "
                      f"backend {other['backend_s']:.1f} hits {other['cache_hits']} "
                      f"misses {other['cache_misses']}")
            compiled = tot(rows, "compile_time_s") + tot(rows, "analysis_s") + other.get(
                "compile_time_s", 0.0)
            print(f"   all compiling, every thread: {compiled:.1f} s of setup_s {h['setup_s']:.1f}")
            metrics = (rec.get("result") or {}).get("metrics") or {}
            print("   metrics: " + ", ".join(
                f"{k} {v['value']:.3f}" for k, v in metrics.items()
                if k.startswith(("compile.", "entry.", "setup_s"))))


if __name__ == "__main__":
    if sys.argv[1] == "--table":
        table(sys.argv[2:])
        sys.exit(0)
    sys.exit(run(sys.argv[1], sys.argv[2:]))
