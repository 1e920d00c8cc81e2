"""Device-plane observability: the compiled-program ledger.

PR 3's telemetry instruments the *host* hot path; by now the actual
work lives in opaque device programs — a ``train:learn_on_batch`` span
covers a K-update superstep, fused rollout never surfaces per-program
cost, and ``jit:recompile`` says *that* a retrace happened, not *why*.
This module is the device-side counterpart: a process-wide ledger,
hooked into the ``sharding/compile.sharded_jit`` cache, that records
per compiled program

- identity: label, donation flags, in/out shardings, creation time;
- compile cost: wall time per trace, abstract signatures;
- program cost (``Lowered.compile()`` substrate, the AOT machinery of
  SNIPPETS [1]): ``cost_analysis()`` FLOPs and bytes accessed,
  ``memory_analysis()`` HBM footprint (argument/output/temp/alias
  bytes);
- runtime: execution count and cumulative device-busy wall time,
  closed out at the policy drain points (the RTA005-annotated ONE
  counted drain per superstep) so async dispatch doesn't under-report;
- **recompile forensics**: on a trace beyond the first, the new
  abstract signature is diffed against the cached ones and the
  differing leaf path / shape / dtype rides the ``jit:recompile``
  event and the ``compile_stats()["recompile_causes"]`` rollup;
- **MFU / bandwidth accounting** against a per-device-kind peak-FLOPs
  table (``RAY_TPU_PEAK_FLOPS`` / ``telemetry(peak_flops=...)``
  override it, so the CPU container reports meaningful numbers).

Execution spans land in the trace buffer on synthetic ``device:`` +
program lanes, so ``Algorithm.export_timeline`` renders driver
threads, worker spans, and device programs in ONE perfetto file.

The ledger is off by default (one flag check per dispatch). The
telemetry runtime enables it (``AlgorithmConfig.telemetry(...)``), or
``RAY_TPU_DEVICE_LEDGER=1`` does with no config at all. The cost /
memory analysis pays one extra ahead-of-time compile per traced
signature (jit's execution cache does not serve ``lower().compile()``);
``device_ledger="light"`` keeps the counters and forensics without it.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util import tracing

# -- activation ---------------------------------------------------------

_LOCK = threading.Lock()
_enabled = os.environ.get("RAY_TPU_DEVICE_LEDGER") == "1"
# capture cost/memory analysis (one extra AOT compile per signature)
_analyze = os.environ.get("RAY_TPU_DEVICE_LEDGER_LIGHT") != "1"

# label -> _ProgramEntry, insertion-ordered (dict is)
_entries: Dict[str, "_ProgramEntry"] = {}
# thread id -> [(entry, t_wall0, t_wall_ret)] dispatches not yet
# closed by a drain point (flushed lazily — see drain_point)
_pending: Dict[int, List[Tuple["_ProgramEntry", float, float]]] = {}

# synthetic chrome-trace lane block for device program spans: far away
# from any real thread id, one sub-lane per program label
_DEVICE_TID_BASE = 0x0DE00000
_span_seq = itertools.count()


def enable(analyze: Optional[bool] = None) -> None:
    global _enabled, _analyze
    _enabled = True
    if analyze is not None:
        _analyze = bool(analyze)


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def analyzing() -> bool:
    return _enabled and _analyze


def clear() -> None:
    """Drop all ledger state (tests)."""
    with _LOCK:
        _entries.clear()
        _pending.clear()


# -- peak-FLOPs / peak-bandwidth tables ---------------------------------

# per-chip peak FLOPs (bf16 where the chip has it) and peak HBM
# bytes/s, keyed by device_kind substring (public specs). The CPU
# entry is a placeholder a container overrides (it keeps the ledger's
# arithmetic testable on the CPU); a kind with no row is an error.
PEAK_FLOPS_TABLE: Tuple[Tuple[str, float], ...] = (
    ("v6", 918e12),      # v6e (Trillium)
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("cpu", 5e10),
)
PEAK_HBM_TABLE: Tuple[Tuple[str, float], ...] = (
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
    ("cpu", 20e9),
)

_peak_flops_override: Optional[float] = None
_peak_hbm_override: Optional[float] = None


def set_peak_flops(
    flops: Optional[float], hbm_bytes_per_s: Optional[float] = None
) -> None:
    """Override the per-device peak (``telemetry(peak_flops=...)``) —
    the CPU-container knob that makes container MFU meaningful."""
    global _peak_flops_override, _peak_hbm_override
    _peak_flops_override = float(flops) if flops else None
    if hbm_bytes_per_s is not None:
        _peak_hbm_override = float(hbm_bytes_per_s) or None


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def table_peak(table, kind: Optional[str], what: str) -> float:
    """The table row whose key is a substring of the device kind; a
    kind the table does not hold is an error, not a default — a
    utilization against a guessed peak is a wrong number with a
    device's name on it."""
    k = kind or device_kind()
    for key, peak in table:
        if key in k.lower():
            return peak
    raise ValueError(
        f"unknown device_kind {k!r}: no {what} row for it in "
        "ray_tpu/telemetry/device.py — add the chip's published peak"
    )


def peak_flops_per_device(kind: Optional[str] = None) -> float:
    env = os.environ.get("RAY_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if _peak_flops_override:
        return _peak_flops_override
    return table_peak(PEAK_FLOPS_TABLE, kind, "peak-FLOPs")


def peak_hbm_bytes_per_s(kind: Optional[str] = None) -> float:
    env = os.environ.get("RAY_TPU_PEAK_HBM_BPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if _peak_hbm_override:
        return _peak_hbm_override
    return table_peak(PEAK_HBM_TABLE, kind, "peak-HBM-bandwidth")


# -- abstract signatures / forensics ------------------------------------


def _leaf_desc(x: Any) -> str:
    """Compact shape/dtype descriptor of one abstract leaf:
    ``f32[128,4]`` (jax's own notation)."""
    dtype = getattr(x, "dtype", None)
    shape = getattr(x, "shape", None)
    if dtype is None or shape is None:
        return f"py:{type(x).__name__}={x!r}"[:64]
    try:
        import jax

        short = jax.ShapeDtypeStruct(shape, dtype).str_short()
    except Exception:
        short = f"{dtype}[{','.join(str(d) for d in shape)}]"
    return short


def signature_of(args, kwargs, static_argnames=()) -> Tuple:
    """Abstract (path → shape/dtype) signature of one call, the unit
    the forensics diff operates on. Static kwargs compare by value."""
    import jax

    statics = {
        k: kwargs[k] for k in static_argnames if k in kwargs
    }
    dyn_kwargs = {
        k: v for k, v in kwargs.items() if k not in statics
    }
    leaves = []
    flat = jax.tree_util.tree_flatten_with_path(
        (args, dyn_kwargs)
    )[0]
    for path, leaf in flat:
        leaves.append(
            (jax.tree_util.keystr(path), _leaf_desc(leaf))
        )
    for k in sorted(statics):
        leaves.append((f"static:{k}", repr(statics[k])[:64]))
    return tuple(leaves)


def diff_signatures(old: Tuple, new: Tuple) -> Dict[str, Any]:
    """What changed between two abstract signatures: the leaf paths
    whose shape/dtype differ, plus added/removed paths. This IS the
    recompile cause — jit retraced because some leaf's abstract value
    (or the tree structure itself) moved."""
    a, b = dict(old), dict(new)
    changed = [
        {"path": p, "from": a[p], "to": b[p]}
        for p in a
        if p in b and a[p] != b[p]
    ]
    added = [{"path": p, "to": b[p]} for p in b if p not in a]
    removed = [{"path": p, "from": a[p]} for p in a if p not in b]
    out: Dict[str, Any] = {}
    if changed:
        out["changed"] = changed
    if added:
        out["added"] = added
    if removed:
        out["removed"] = removed
    return out


def cause_string(diff: Dict[str, Any], limit: int = 6) -> str:
    """One-line human rendering of a signature diff (what the
    ``jit:recompile`` event carries)."""
    parts = []
    for c in diff.get("changed", ())[:limit]:
        parts.append(f"{c['path']}: {c['from']} -> {c['to']}")
    for c in diff.get("added", ())[:limit]:
        parts.append(f"+{c['path']}: {c['to']}")
    for c in diff.get("removed", ())[:limit]:
        parts.append(f"-{c['path']}: {c['from']}")
    n = sum(len(diff.get(k, ())) for k in ("changed", "added", "removed"))
    if n > limit:
        parts.append(f"(+{n - limit} more)")
    return "; ".join(parts) if parts else "identical abstract signature (static/config retrace)"


# -- the ledger ---------------------------------------------------------


class _ProgramEntry:
    """One compiled program's ledger row."""

    __slots__ = (
        "label",
        "created",
        "donate_argnums",
        "in_shardings",
        "out_shardings",
        "traces",
        "compile_time_s",
        "executions",
        "device_time_s",
        "signatures",
        "causes",
        "flops",
        "bytes_accessed",
        "memory",
        "n_devices",
        "tid",
        "abstract",
    )

    def __init__(self, label: str, donate_argnums=(), in_specs=None,
                 out_specs=None):
        self.label = label
        self.created = time.time()
        self.donate_argnums = tuple(donate_argnums or ())
        self.in_shardings = _spec_str(in_specs)
        self.out_shardings = _spec_str(out_specs)
        self.traces = 0
        self.compile_time_s = 0.0
        self.executions = 0
        self.device_time_s = 0.0
        self.signatures: List[Tuple] = []
        self.causes: List[Dict[str, Any]] = []
        # per-execution program cost (None until analyzed)
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.memory: Optional[Dict[str, float]] = None
        self.n_devices = 1
        # (abstract args, abstract kwargs, x64) of the analysed
        # signature: shapes and shardings, no array. What a later
        # request for the compiled text lowers again
        # (sharding/compile.ShardedFunction.compiled_text)
        self.abstract: Optional[Tuple[Any, Any, bool]] = None
        # stable synthetic chrome-trace lane for this program
        self.tid = _DEVICE_TID_BASE + (
            zlib.crc32(label.encode()) % 0x10000
        )

    def to_dict(self) -> Dict[str, Any]:
        peak = peak_flops_per_device()
        out: Dict[str, Any] = {
            "label": self.label,
            "traces": self.traces,
            "recompiles": max(0, self.traces - 1),
            "compile_time_s": round(self.compile_time_s, 6),
            "executions": self.executions,
            "device_time_s": round(self.device_time_s, 6),
            "donate_argnums": list(self.donate_argnums),
            "in_shardings": self.in_shardings,
            "out_shardings": self.out_shardings,
            "n_devices": self.n_devices,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "memory": self.memory,
            "recompile_causes": [
                c["cause"] for c in self.causes
            ],
        }
        out["mfu"] = program_mfu(
            self.flops, self.executions, self.device_time_s,
            self.n_devices, peak,
        )
        out["bandwidth_util"] = program_bandwidth_util(
            self.bytes_accessed, self.executions,
            self.device_time_s, self.n_devices,
        )
        return out


def _spec_str(spec, limit: int = 800) -> Optional[str]:
    if spec is None:
        return None
    s = str(spec)
    return s if len(s) <= limit else s[: limit - 3] + "..."


def program_mfu(
    flops, executions, device_time_s, n_devices, peak=None
) -> Optional[float]:
    """Model-FLOPs utilization of one program: executed FLOPs over the
    peak the busy interval could have delivered. ``flops`` is the
    compiled module's per-execution cost (``cost_analysis``); peak is
    per device × the devices the program spans."""
    if not flops or not executions or device_time_s <= 0:
        return None
    peak = peak or peak_flops_per_device()
    return float(flops) * executions / (
        device_time_s * peak * max(1, n_devices)
    )


def program_bandwidth_util(
    bytes_accessed, executions, device_time_s, n_devices, peak=None
) -> Optional[float]:
    if not bytes_accessed or not executions or device_time_s <= 0:
        return None
    peak = peak or peak_hbm_bytes_per_s()
    return float(bytes_accessed) * executions / (
        device_time_s * peak * max(1, n_devices)
    )


def _entry_for(sf) -> "_ProgramEntry":
    e = _entries.get(sf.label)
    if e is None:
        e = _entries[sf.label] = _ProgramEntry(
            sf.label,
            donate_argnums=getattr(sf, "donate_argnums", ()),
            in_specs=getattr(sf, "in_specs", None),
            out_specs=getattr(sf, "out_specs", None),
        )
    return e


def _sharding_devices(x) -> Optional[int]:
    sh = getattr(x, "sharding", None)
    ds = getattr(sh, "device_set", None)
    return len(ds) if ds else None


def _abstractify(args, kwargs, static_argnames=()):
    """(args, kwargs) with every array leaf replaced by its
    ``ShapeDtypeStruct`` (sharding preserved for committed jax
    arrays): what the AOT ``lower()`` consumes — no data read, so
    donated/deleted buffers are fine."""
    import jax
    import numpy as np

    statics = set(static_argnames)

    def to_sds(x):
        if isinstance(x, jax.Array):
            try:
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                )
            except Exception:
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    sds_args = jax.tree_util.tree_map(to_sds, args)
    sds_kwargs = {
        k: (v if k in statics else jax.tree_util.tree_map(to_sds, v))
        for k, v in kwargs.items()
    }
    return sds_args, sds_kwargs


def _analyze_program(entry: "_ProgramEntry", sf, args, kwargs) -> None:
    """Capture ``cost_analysis``/``memory_analysis`` for the signature
    just traced. Pays ONE ahead-of-time compile (jit's execution
    cache does not serve ``lower().compile()``); the guard in
    ``ShardedFunction`` keeps that abstract retrace out of the
    recompile counters."""
    import jax

    try:
        sds_args, sds_kwargs = _abstractify(
            args, kwargs, getattr(sf, "static_argnames", ())
        )
        with sf.uncounted_traces(analysis=True):
            compiled = sf._jitted.lower(
                *sds_args, **sds_kwargs
            ).compile()
    except Exception:
        return
    entry.abstract = (
        sds_args, sds_kwargs, bool(jax.config.jax_enable_x64)
    )
    n = None
    for leaf in jax.tree_util.tree_leaves(args):
        n = _sharding_devices(leaf)
        if n:
            break
    if n:
        entry.n_devices = n
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            entry.flops = float(ca.get("flops", 0.0)) or None
            entry.bytes_accessed = (
                float(ca.get("bytes accessed", 0.0)) or None
            )
        if entry.flops:
            from ray_tpu.telemetry import metrics as tm

            tm.set_program_flops(entry.label, entry.flops)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            entry.memory = {
                "argument_bytes": float(
                    getattr(ma, "argument_size_in_bytes", 0)
                ),
                "output_bytes": float(
                    getattr(ma, "output_size_in_bytes", 0)
                ),
                "temp_bytes": float(
                    getattr(ma, "temp_size_in_bytes", 0)
                ),
                "alias_bytes": float(
                    getattr(ma, "alias_size_in_bytes", 0)
                ),
                "generated_code_bytes": float(
                    getattr(ma, "generated_code_size_in_bytes", 0)
                ),
            }
    except Exception:
        pass


# -- hooks called by sharding/compile.ShardedFunction -------------------


def on_traced(sf, args, kwargs, compile_s: float) -> Optional[str]:
    """One trace (compile) just happened on ``sf``. Records the
    signature, runs the forensics diff against the cached ones, and
    (full mode) captures the program's cost/memory analysis. Returns
    the cause string for retraces beyond the first, else None.
    ``compile_s``: jax's own seconds of that compile
    (``ShardedFunction.account``)."""
    sig = None
    try:
        sig = signature_of(
            args, kwargs, getattr(sf, "static_argnames", ())
        )
    except Exception:
        pass
    if not _enabled:
        # off: record nothing; a retrace of a program the ledger saw
        # while it ran still learns what moved since
        with _LOCK:
            seen = getattr(_entries.get(sf.label), "signatures", None)
            if sig is None or not seen:
                return None
            return cause_string(diff_signatures(seen[-1], sig))
    with _LOCK:
        entry = _entry_for(sf)
        entry.traces += 1
        entry.compile_time_s += compile_s
        cause = None
        if sig is not None:
            if entry.signatures:
                diff = diff_signatures(entry.signatures[-1], sig)
                cause = cause_string(diff)
                entry.causes.append(
                    {"cause": cause, "diff": diff, "ts": time.time()}
                )
            entry.signatures.append(sig)
            # bound memory on pathological retrace storms
            del entry.signatures[:-16]
            del entry.causes[:-32]
    # one successful analysis per program: the first signature's
    # cost/memory stands for the program (a retrace storm must not pay
    # an extra AOT compile per retrace on top of jit's own)
    if _analyze and entry.flops is None:
        _analyze_program(entry, sf, args, kwargs)
    return cause


def on_call(sf, t_wall0: float, dt: float, traced: bool = False) -> None:
    """One dispatch of ``sf`` returned after ``dt`` seconds
    (dispatch-side wall; async backends return before the device
    finishes — the next :func:`drain_point` on this thread extends
    the interval to the drain, which is when the work provably
    ended). Calls that traced are compile calls: they don't count as
    executions or busy time, so steady-state MFU stays honest."""
    if not _enabled:
        return
    tid = threading.get_ident()
    now = t_wall0 + dt
    with _LOCK:
        entry = _entry_for(sf)
        stale = _pending.pop(tid, ())
        if not traced:
            entry.executions += 1
            _pending[tid] = [(entry, t_wall0, now)]
    for e, t0, t1 in stale:
        _close(e, t0, t1)
    if not traced:
        _prom_executions(sf.label)


def drain_point() -> None:
    """Close this thread's open program interval at the drain that
    just completed (the RTA005-annotated ONE counted drain): the
    device work is provably finished NOW, so busy time extends from
    dispatch start to here."""
    if not _enabled:
        return
    tid = threading.get_ident()
    with _LOCK:
        open_ = _pending.pop(tid, ())
    now = time.time()
    for e, t0, _t1 in open_:
        _close(e, t0, now)


def _close(entry: "_ProgramEntry", t0: float, t1: float) -> None:
    """Finish one execution interval: accrue busy time, export the
    chrome-trace span on the program's synthetic device lane."""
    t1 = max(t1, t0)
    with _LOCK:
        entry.device_time_s += t1 - t0
    _prom_seconds(entry.label, t1 - t0)
    if tracing.is_enabled():
        tracing.record_spans(
            [
                {
                    "trace_id": "device",
                    "span_id": f"dev-{entry.tid:x}-{next(_span_seq)}",
                    "parent_id": None,
                    "name": f"device:{entry.label}",
                    "start": t0,
                    "end": t1,
                    "attributes": {"program": entry.label},
                    "pid": os.getpid(),
                    "tid": entry.tid,
                    "thread_name": f"device:{entry.label}",
                }
            ]
        )


def _prom_executions(label: str) -> None:
    try:
        from ray_tpu.telemetry import metrics as tm

        tm.inc_program_execution(label)
    except Exception:
        pass


def _prom_seconds(label: str, dt: float) -> None:
    try:
        from ray_tpu.telemetry import metrics as tm

        tm.add_program_device_seconds(label, dt)
    except Exception:
        pass


# -- reads --------------------------------------------------------------


def _flush_all_pending() -> None:
    """Close every thread's open interval at its dispatch-return
    stamp (a snapshot must not leave busy time parked in _pending)."""
    with _LOCK:
        items = list(_pending.items())
        _pending.clear()
    for _tid, open_ in items:
        for e, t0, t1 in open_:
            _close(e, t0, t1)


def abstract_signature(label: str) -> Optional[Tuple[Any, Any, bool]]:
    """``(abstract args, abstract kwargs, x64)`` of the signature the
    ledger analysed for the program ``label``; ``None`` where it
    analysed none (the ledger off or light when the program traced)."""
    with _LOCK:
        entry = _entries.get(label)
    return entry.abstract if entry is not None else None


def recompile_causes() -> Dict[str, List[Dict[str, Any]]]:
    """``{label: [{"cause", "count"}...]}`` rollup of every forensics
    diff recorded so far (``compile_stats()["recompile_causes"]``)."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    with _LOCK:
        entries = list(_entries.values())
    for e in entries:
        if not e.causes:
            continue
        counts: Dict[str, int] = {}
        for c in e.causes:
            counts[c["cause"]] = counts.get(c["cause"], 0) + 1
        out[e.label] = [
            {"cause": k, "count": v} for k, v in counts.items()
        ]
    return out


def snapshot() -> Dict[str, Any]:
    """The ``info/device_ledger`` payload: per-program rows plus the
    aggregate MFU/bytes view. Flushes open execution intervals first."""
    _flush_all_pending()
    kind = device_kind()
    peak = peak_flops_per_device(kind)
    peak_bw = peak_hbm_bytes_per_s(kind)
    with _LOCK:
        entries = list(_entries.values())
    programs = [e.to_dict() for e in entries]
    flops_total = sum(
        (p["flops"] or 0.0) * p["executions"] for p in programs
    )
    bytes_total = sum(
        (p["bytes_accessed"] or 0.0) * p["executions"]
        for p in programs
    )
    busy = sum(
        p["device_time_s"]
        for p in programs
        if p["flops"] is not None and p["executions"]
    )
    n_dev = max((p["n_devices"] for p in programs), default=1)
    totals = {
        "programs": len(programs),
        "executions": sum(p["executions"] for p in programs),
        "device_time_s": round(
            sum(p["device_time_s"] for p in programs), 6
        ),
        "compile_time_s": round(
            sum(p["compile_time_s"] for p in programs), 6
        ),
        "recompiles": sum(p["recompiles"] for p in programs),
        "flops_executed": flops_total,
        "bytes_accessed": bytes_total,
        "mfu": (
            flops_total / (busy * peak * n_dev) if busy > 0 else None
        ),
        "bandwidth_util": (
            bytes_total / (busy * peak_bw * n_dev)
            if busy > 0
            else None
        ),
    }
    return {
        "device_kind": kind,
        "peak_flops_per_device": peak,
        "peak_hbm_bytes_per_s": peak_bw,
        "analyzed": _analyze,
        "programs": programs,
        "totals": totals,
        "recompile_causes": recompile_causes(),
    }


def dump(path: str) -> str:
    """Write the snapshot as JSON (the report CLI's --ledger input)."""
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=1)
    return path
