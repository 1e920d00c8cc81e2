"""ray_tpu.sharding.registry — the compiled-program registry.

Every executable an AlgorithmConfig lowers — learn nests, superstep
bodies, the device rollout engine, replay insert/sample/draw programs,
prioritized-tree programs, serve buckets — carries a ``sharded_jit``
label (the same label the compile-cache stats and the PR-13 device
ledger report). This module makes that inventory a first-class object:
a :class:`ProgramRegistry` of :class:`ProgramSpec` rows, predicted
up-front from the config rather than discovered after the fact, so
warmup sweeps and dispatch-diet coverage checks are ONE walk over the
same list.

Three consumers (docs/API.md "program registry"):

- ``Algorithm.setup`` builds ``algo.program_registry`` via
  :func:`for_algorithm`;
- ``serve.BatchedPolicyServer.warmup`` walks its per-bucket specs
  (registered by the server itself) instead of an ad-hoc loop;
- ``tests/test_dispatch_diet.py`` asserts completeness: every label
  ``compile_stats()`` observed after a run matches some spec — a new
  program that forgets to register here fails CI, which is what keeps
  the warmup sweep exhaustive.

Labels with data-dependent components (batch sizes resolved at the
first learn call, draw widths, bucket sizes) register as anchored
regexes; fully static labels register exact. Specs may carry a
zero-arg ``warm`` callable — build + lower the program without
dispatching — which is what the sweep runs.
"""

from __future__ import annotations

import dataclasses
import re
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional


@dataclasses.dataclass
class ProgramSpec:
    """One predicted executable: an exact label or an anchored regex
    over the ``sharded_jit`` label space, plus where it comes from and
    (optionally) how to warm it ahead of first dispatch."""

    label: str
    kind: str = "other"  # learn | superstep | rollout | replay | tree | serve | grads | stack | keys | other
    policy_id: str = ""
    regex: bool = False
    warm: Optional[Callable[[], Any]] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self._pattern = (
            re.compile(self.label) if self.regex else None
        )

    def matches(self, label: str) -> bool:
        if self._pattern is not None:
            return self._pattern.fullmatch(label) is not None
        return label == self.label

    def describe(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "kind": self.kind,
            "policy_id": self.policy_id,
            "regex": self.regex,
            "warmable": self.warm is not None,
            **({"meta": dict(self.meta)} if self.meta else {}),
        }


class ProgramRegistry:
    """The mutable spec list + the sweeps over it. Built once on the
    driver (Algorithm.setup / server init) and only read afterwards;
    the lock covers late additions (a server attaching its buckets to
    an algorithm's registry)."""

    # ray-tpu: thread=driver

    def __init__(self) -> None:
        self._specs: List[ProgramSpec] = []
        self._lock = threading.Lock()

    # -- building -------------------------------------------------------

    def add(self, spec: ProgramSpec) -> ProgramSpec:
        with self._lock:
            self._specs.append(spec)
        return spec

    def add_program(self, label: str, **kwargs) -> ProgramSpec:
        return self.add(ProgramSpec(label=label, **kwargs))

    def extend(self, specs) -> None:
        with self._lock:
            self._specs.extend(specs)

    # -- reading --------------------------------------------------------

    def __len__(self) -> int:
        return len(self._specs)

    def __iter__(self) -> Iterator[ProgramSpec]:
        return iter(list(self._specs))

    def specs(self, kind: Optional[str] = None) -> List[ProgramSpec]:
        with self._lock:
            out = list(self._specs)
        if kind is not None:
            out = [s for s in out if s.kind == kind]
        return out

    def match(self, label: str) -> Optional[ProgramSpec]:
        """First spec covering ``label`` (exact specs are checked
        before regex ones so a static row wins over its family
        pattern)."""
        specs = self.specs()
        for s in specs:
            if not s.regex and s.matches(label):
                return s
        for s in specs:
            if s.regex and s.matches(label):
                return s
        return None

    # -- the sweeps -----------------------------------------------------

    def coverage(
        self, observed: Optional[List[str]] = None
    ) -> Dict[str, Any]:
        """Dispatch-diet coverage: which observed program labels the
        registry predicted. ``observed`` defaults to every live
        ``ShardedFunction`` label (``compile_stats()``); pass the
        device ledger's program labels for a device-time view."""
        if observed is None:
            from ray_tpu.sharding.compile import compile_stats

            observed = [
                s["label"]
                for s in compile_stats()["per_function"]
            ]
        matched: Dict[str, str] = {}
        unmatched: List[str] = []
        for label in observed:
            spec = self.match(label)
            if spec is None:
                unmatched.append(label)
            else:
                matched[label] = spec.kind
        return {
            "specs": len(self),
            "observed": len(observed),
            "matched": matched,
            "unmatched": unmatched,
        }

    def sweep(
        self, *, kind: Optional[str] = None, warm: bool = True
    ) -> Dict[str, Any]:
        """Walk the specs (optionally one ``kind``), running each
        ``warm`` callable — the one-pass bucket warmup.
        Errors are collected, not raised: a spec whose program can't
        build yet (batch size unknown until the first train call) must
        not abort the specs after it."""
        warmed, skipped, errors = 0, 0, []
        for spec in self.specs(kind):
            if not warm or spec.warm is None:
                skipped += 1
                continue
            try:
                spec.warm()
                warmed += 1
            except Exception as e:  # pragma: no cover - defensive
                errors.append({"label": spec.label, "error": repr(e)})
        return {
            "specs": len(self.specs(kind)),
            "warmed": warmed,
            "skipped": skipped,
            "errors": errors,
        }

    def snapshot(self) -> Dict[str, Any]:
        """The ledger-backed view: every spec row joined against the
        device ledger's per-program device time (empty columns when
        the ledger is off) and the compile-cache stats."""
        from ray_tpu.sharding.compile import compile_stats
        from ray_tpu.telemetry import device as device_ledger

        per_fn = {
            s["label"]: s
            for s in compile_stats()["per_function"]
        }
        ledger_rows: Dict[str, Dict[str, Any]] = {}
        if device_ledger.enabled():
            for row in device_ledger.snapshot().get("programs", []):
                ledger_rows[row.get("label", "")] = row
        rows = []
        for spec in self.specs():
            row = spec.describe()
            observed = [
                lbl for lbl in per_fn if spec.matches(lbl)
            ]
            row["observed"] = observed
            row["calls"] = sum(
                per_fn[lbl]["calls"] for lbl in observed
            )
            row["traces"] = sum(
                per_fn[lbl]["traces"] for lbl in observed
            )
            dev = [
                ledger_rows[lbl]
                for lbl in ledger_rows
                if spec.matches(lbl)
            ]
            if dev:
                row["device_time_s"] = sum(
                    d.get("device_time_s", 0.0) for d in dev
                )
                row["executions"] = sum(
                    d.get("executions", 0) for d in dev
                )
            rows.append(row)
        return {"specs": rows}


# -- predictive enumeration ------------------------------------------------

_NUM = r"\d+"


def _cls(policy) -> str:
    return re.escape(type(policy).__name__)


def for_policy(
    policy, policy_id: str = "default_policy", config=None
) -> List[ProgramSpec]:
    """The executables ONE policy's config lowers. Batch sizes are
    data-dependent (resolved on first dispatch), so the learn-side
    rows are anchored regexes over the class-name label families the
    policy builds (``jax_policy._build_*``)."""
    config = config if config is not None else getattr(
        policy, "config", {}
    )
    cls = _cls(policy)
    specs: List[ProgramSpec] = [
        # the per-update learn nest (multi_learn: SAC's fused actor/
        # critic pair; learn[QMIX] has no batch suffix)
        ProgramSpec(
            rf"(?:multi_)?learn\[{cls}(?::{_NUM}(?:x{_NUM})?)?\]",
            kind="learn",
            policy_id=policy_id,
            regex=True,
        ),
        # split-phase gradient API (compute_gradients/apply_gradients)
        ProgramSpec(
            rf"grads\[{cls}\]",
            kind="grads",
            policy_id=policy_id,
            regex=True,
        ),
        ProgramSpec(
            rf"apply_grads\[{cls}\]",
            kind="grads",
            policy_id=policy_id,
            regex=True,
        ),
        # a lane's host key schedule (JaxPolicy._split_chain): slots,
        # then the splits of each stream of a slot
        ProgramSpec(
            rf"(?:rollout_learn|rollout|learn)_keys"
            rf"\[(?:{_NUM}of{_NUM}x)?{_NUM}(?:x{_NUM})*\]",
            kind="keys",
            policy_id=policy_id,
            regex=True,
        ),
    ]
    if config.get("superstep", "auto") != 0:
        specs += [
            ProgramSpec(
                rf"superstep\[{cls}:{_NUM}x{_NUM}\]",
                kind="superstep",
                policy_id=policy_id,
                regex=True,
            ),
            # host-side minibatch re-stack feeding the scan
            ProgramSpec(
                rf"superstep_stack\[{_NUM}\]",
                kind="stack",
                policy_id=policy_id,
                regex=True,
            ),
        ]
    if config.get("jax_fused_rollout", True) or (
        config.get("env_backend") == "jax"
    ):
        specs += [
            ProgramSpec(
                rf"rollout_superstep\[{cls}:{_NUM}x{_NUM}\]",
                kind="rollout",
                policy_id=policy_id,
                regex=True,
            ),
            ProgramSpec(
                rf"jax_rollout\[\w+:{_NUM}x{_NUM}\]",
                kind="rollout",
                policy_id=policy_id,
                regex=True,
            ),
        ]
    return specs


def _replay_specs(policy_id: str, prioritized: bool) -> List[ProgramSpec]:
    pid = re.escape(policy_id)
    specs = [
        ProgramSpec(
            rf"replay_insert\[{pid}\]",
            kind="replay",
            policy_id=policy_id,
            regex=True,
        ),
        ProgramSpec(
            rf"replay_sample\[{pid}\]",
            kind="replay",
            policy_id=policy_id,
            regex=True,
        ),
    ]
    if prioritized:
        specs += [
            ProgramSpec(
                rf"replay_draw_sample\[{pid}:{_NUM}\]",
                kind="replay",
                policy_id=policy_id,
                regex=True,
            ),
            ProgramSpec(
                rf"tree_draw_sets\[{pid}:{_NUM}x{_NUM}\]",
                kind="tree",
                policy_id=policy_id,
                regex=True,
            ),
            ProgramSpec(
                rf"tree_update\[{pid}:{_NUM}x{_NUM}\]",
                kind="tree",
                policy_id=policy_id,
                regex=True,
            ),
            ProgramSpec(
                rf"tree_draw\[{pid}:{_NUM}(?:x{_NUM})*\]",
                kind="tree",
                policy_id=policy_id,
                regex=True,
            ),
        ]
    return specs


def _uses_replay(config) -> bool:
    # replay-driven algorithms all size a ring through one of these
    return bool(
        config.get("buffer_size")
        or config.get("replay_buffer_size")
        or (config.get("replay_buffer_config") or {}).get("capacity")
    )


def for_algorithm(algo) -> ProgramRegistry:
    """Enumerate every program the algorithm's current config lowers:
    one spec family per (policy × subsystem). Serve buckets attach
    later — ``BatchedPolicyServer`` registers its own exact rows when
    it is constructed against this algorithm."""
    reg = ProgramRegistry()
    config = getattr(algo, "config", {}) or {}
    try:
        lw = algo.workers.local_worker()
        policy_map = getattr(lw, "policy_map", None) or {}
    except Exception:  # pragma: no cover - partially built algos
        policy_map = {}
    replay = _uses_replay(config)
    prioritized = bool(
        config.get("prioritized_replay")
        or (config.get("replay_buffer_config") or {}).get(
            "prioritized_replay"
        )
    )
    for pid, pol in policy_map.items():
        reg.extend(
            for_policy(pol, policy_id=pid, config=config)
        )
        if replay:
            reg.extend(_replay_specs(pid, prioritized))
        if replay and pid != "default_policy":
            # shared single-buffer algorithms keep the default label
            reg.extend(
                _replay_specs("default_policy", prioritized)
            )
    # APEX shards its ring: one insert/sample family per shard label
    if replay and "apex" in type(algo).__name__.lower():
        reg.add_program(
            r"replay_(?:insert|sample|draw_sample)\[apex_shard_\d+(?::\d+)?\]",
            kind="replay",
            regex=True,
        )
        reg.add_program(
            r"tree_(?:update|draw|draw_sets)\[apex_shard_\d+(?::\d+(?:x\d+)*)?\]",
            kind="tree",
            regex=True,
        )
    # QMIX's episode stacker rides its own label
    reg.add_program(
        r"qmix_episodes", kind="stack", regex=False
    ) if "qmix" in type(algo).__name__.lower() else None
    return reg
