"""BENCHMARK.json and the files it names, found by name.

A cell is one entry of ``workloads``. Whatever belongs to one
configuration, one traffic mix or one per-layer metric sits in a file
of its own, so a later PR adds a cell by adding files and entries:

    perf/configs/<config>.json         sizes, source, hyper-parameters
    perf/reference/<reference>.py      its plain reference
    perf/traffic/<traffic>.json        the traffic mix's parameters
                                       (its env: perf/envs.py)
    perf/layer_metrics/<metric>.py     the metric's reader: read(ctx)

Nothing here branches on a cell's name.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def check_name(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a valid benchmark name: {name!r}")
    return name


class Cell:
    """One workload with everything the run needs, loaded from files."""

    def __init__(self, manifest: Dict, workload: Dict, root: str):
        self.root = root
        self.manifest = manifest
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
            os.path.join(
                root,
                "perf",
                "traffic",
                check_name(workload["traffic"]) + ".json",
            )
        )
        self.run_seconds = int(manifest["run_seconds"])

    # -- metrics ---------------------------------------------------------

    def _metrics(self, section: str) -> List[Dict]:
        return [
            m
            for m in self.manifest[section]
            if "workloads" not in m or self.name in m["workloads"]
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
        path = os.path.join(
            self.root, "perf", "layer_metrics", check_name(metric_name) + ".py"
        )
        spec = importlib.util.spec_from_file_location(
            "perf_layer_metric_" + re.sub(r"\W", "_", metric_name), path
        )
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def reference(self):
        return importlib.import_module(
            "perf.reference." + check_name(self.config["reference"])
        )

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
