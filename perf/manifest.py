"""BENCHMARK.json and the files it names, found by name.

A cell is one entry of ``workloads``. Whatever the harness knows about
one configuration, one model family, one env, one traffic mix, one
cell or one per-layer metric sits in a file of its own, found by a
name that the configuration file, the traffic file or the workload
entry states. A later PR adds a cell, of any family, by adding files
and entries:

    perf/configs/<config>.json        sizes, source, hyper-parameters. Keys read:
                                      run, algo_config, model, reference,
                                      flops_family, checks, limits (default: the
                                      file's own name), learner_check {rows,
                                      batches} (default 512 / 4),
                                      control_precisions (default int8, fp8),
                                      param_layout (default "replicated", or
                                      {mesh, rules, fullest_chip_share})
    perf/reference/<reference>.py     its plain reference: init_params,
                                      to_policy_tree, from_policy_tree,
                                      make_batch, loss
    perf/flop_rules/<flops_family>.py train_flops_per_env_step(config, num_actions)
    perf/limits/<limits>.json         every ``correct`` limit with the readings
                                      it was set from (class Limits)
    perf/checks/<check>.py            one comparison: STAGE, LIMITS, run(state)
                                      (perf/correct.py, CheckState)
    perf/traffic/<traffic>.json       the traffic mix's parameters. Keys read:
                                      env (perf/envs.py), algo_config, warmup,
                                      expect, trace_iterations, checks, and
                                      whatever its checks read (ring_fill)
    perf/cells/<workload>.json        optional: {"metrics": [...]}, entries of
                                      end_to_end / per_layer the cell takes
                                      besides those whose ``workloads`` list
                                      is absent or names it
    perf/layer_metrics/<metric>.py    the metric's reader: read(ctx)

A name that finds no file is an error when the cell is loaded, not in
the middle of a run. Nothing here, in perf/run.py or in
perf/correct.py branches on a family, an env or a cell, and no limit
is a number in code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# when a comparison runs: before the program's first iterations, after
# them (they define the ring's columns), or after warm-up, around one
# more real iteration
STAGES = ("before_first_iterations", "after_first_iterations", "after_warmup")
SETUP_STAGES = STAGES[:2]


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def check_name(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


class Limits:
    """``perf/limits/<name>.json``: every limit of a configuration's
    comparisons beside the two readings it was set from. An entry is
    ``{limit, sound_max, control_min: {<precision>: x}, separates,
    read}`` and may add ``floor`` (the least denominator of a relative
    number). ``separates`` says whether the controls come out as not
    correct on this number; where they do, the limit lies between
    ``sound_max`` and the smallest ``control_min`` (perf/tests holds
    every committed file to that)."""

    ENTRY_KEYS = ("limit", "sound_max", "control_min", "separates", "read")

    def __init__(self, path: str):
        self.path = path
        self.entries: Dict[str, Dict] = _load_json(path)["limits"]
        for name, entry in self.entries.items():
            missing = [k for k in self.ENTRY_KEYS if k not in entry]
            if missing:
                raise KeyError(f"{path}: limit {name!r} lacks {missing}")

    def __contains__(self, name: str) -> bool:
        return name in self.entries

    def _entry(self, name: str) -> Dict:
        if name not in self.entries:
            raise KeyError(f"{self.path} has no limit {name!r}")
        return self.entries[name]

    def limit(self, name: str):
        return self._entry(name)["limit"]

    def floor(self, name: str, default: Optional[float] = None) -> float:
        entry = self._entry(name)
        if "floor" not in entry and default is None:
            raise KeyError(f"{self.path}: limit {name!r} states no floor")
        return float(entry.get("floor", default))


class Cell:
    """One workload with everything the run needs, loaded from files."""

    def __init__(self, manifest: Dict, workload: Dict, root: str):
        self.root = root
        self.manifest = manifest
        self._modules: Dict[str, Any] = {}
        self.name = check_name(workload["name"])
        self.chips = int(workload["chips"])
        self.why = workload["why"]
        entry = next(
            (
                c
                for c in manifest["configs"]
                if c["name"] == workload["config"]
            ),
            None,
        )
        if entry is None:
            raise KeyError(
                f"cell {self.name!r} names configuration "
                f"{workload['config']!r}, which BENCHMARK.json lacks"
            )
        self.config_entry = entry
        self.config = _load_json(os.path.join(root, entry["file"]))
        self.traffic = _load_json(
            self._path("traffic", workload["traffic"], ".json")
        )
        self.run_seconds = int(manifest["run_seconds"])
        self.limits = Limits(
            self._path("limits", self.config.get("limits", entry["name"]), ".json")
        )
        self.flop_rule()  # an unknown family is an error now
        chosen = self._path("cells", self.name, ".json")
        self.chosen_metrics: Tuple[str, ...] = tuple(
            _load_json(chosen).get("metrics", ()) if os.path.isfile(chosen) else ()
        )
        known = {
            m["name"] for s in ("end_to_end", "per_layer") for m in manifest[s]
        }
        unknown = [m for m in self.chosen_metrics if m not in known]
        if unknown:
            raise KeyError(
                f"{chosen} takes metrics BENCHMARK.json lacks: {unknown}"
            )
        self._checks = [
            (name, self._module("checks", name))
            for name in list(self.config.get("checks") or [])
            + list(self.traffic.get("checks") or [])
        ]
        for name, module in self._checks:
            if module.STAGE not in STAGES:
                raise ValueError(
                    f"check {name!r} states stage {module.STAGE!r}; "
                    f"the stages are {STAGES}"
                )
            for limit in module.LIMITS:
                self.limits.limit(limit)  # a name the file lacks: error now

    # -- files by name ---------------------------------------------------

    def _path(self, kind: str, name: str, suffix: str) -> str:
        return os.path.join(self.root, "perf", kind, check_name(name) + suffix)

    def _module(self, kind: str, name: str):
        """``perf/<kind>/<name>.py`` of THIS cell's root, loaded from
        its path (the names have dots, and a temporary copy of the
        benchmark brings files the installed package lacks)."""
        path = self._path(kind, name, ".py")
        if path in self._modules:
            return self._modules[path]
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"cell {self.name!r} names {kind[:-1].replace('_', ' ')} "
                f"{name!r}, but there is no {path}"
            )
        spec = importlib.util.spec_from_file_location(
            f"perf_{kind}_" + re.sub(r"\W", "_", name), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        self._modules[path] = module
        return module

    # -- metrics ---------------------------------------------------------

    def _metrics(self, section: str) -> List[Dict]:
        return [
            m
            for m in self.manifest[section]
            if "workloads" not in m
            or self.name in m["workloads"]
            or m["name"] in self.chosen_metrics
        ]

    @property
    def end_to_end(self) -> List[Dict]:
        return self._metrics("end_to_end")

    @property
    def per_layer(self) -> List[Dict]:
        return self._metrics("per_layer")

    def reader(self, metric_name: str) -> Callable[[Any], Optional[float]]:
        """``read(ctx)`` of ``perf/layer_metrics/<metric_name>.py``. A
        reader that finds nothing to read returns None and the metric
        is left out of the result line."""
        return self._module("layer_metrics", metric_name).read

    def flop_rule(self) -> Callable[[Dict, int], float]:
        """``train_flops_per_env_step(config, num_actions)`` of
        ``perf/flop_rules/<flops_family>.py``."""
        return self._module(
            "flop_rules", self.config["flops_family"]
        ).train_flops_per_env_step

    def reference(self):
        return self._module("reference", self.config["reference"])

    # -- the comparisons -------------------------------------------------

    def checks(self, stage: str) -> List[Tuple[str, Any]]:
        """``(name, module)`` of the comparisons the configuration and
        the traffic mix list, that run at ``stage``, in listed order."""
        return [(n, m) for n, m in self._checks if m.STAGE == stage]

    def limit(self, name: str):
        return self.limits.limit(name)

    @property
    def learner_check_shape(self) -> Tuple[int, int]:
        """``(rows, seeded minibatches)`` of the learner comparison;
        the reference's ``make_batch`` decides what a row is."""
        shape = self.config.get("learner_check") or {}
        return int(shape.get("rows", 512)), int(shape.get("batches", 4))

    @property
    def control_precisions(self) -> Sequence[str]:
        """The precision steps below the configuration's stated one
        that the controls take."""
        return tuple(self.config.get("control_precisions") or ("int8", "fp8"))

    @property
    def param_layout(self):
        return self.config.get("param_layout", "replicated")

    def experiment_spec(self, seed: int) -> Dict:
        """The tuned-example style spec ``python -m ray_tpu.train -f``
        would load: the configuration's hyper-parameters, then the
        traffic mix's lane geometry over them, then the seed."""
        from perf import envs  # needs the program; the manifest does not

        config = dict(self.config["algo_config"])
        config.update(self.traffic.get("algo_config") or {})
        config["seed"] = int(seed)
        return {
            "run": self.config["run"],
            "env": envs.resolve(self.traffic["env"]),
            "config": config,
        }


def load_manifest(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_manifest(root)
    for workload in manifest["workloads"]:
        if workload["name"] == name:
            return Cell(manifest, workload, root)
    raise KeyError(
        f"BENCHMARK.json has no workload {name!r}; it has "
        f"{[w['name'] for w in manifest['workloads']]}"
    )
