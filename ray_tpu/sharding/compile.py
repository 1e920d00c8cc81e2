"""``sharded_jit``: the compile layer of the sharding runtime.

Wraps ``jax.jit`` with explicit input/output shardings and buffer
donation (the modern spelling of the retrieved ``pjit`` pattern:
``in_axis_resources``/``donate_argnums``), and instruments the compile
cache: every retrace is counted and its wall time recorded, so "did
this step recompile?" is a metric instead of a profiler session.

Every program of the learner plane comes through here (learn bodies
with ``NamedSharding`` trees attached, key schedules and tree programs
as plain jits), so compile stats cover all of them.

``ShardedFunction.__call__`` has two paths, chosen by what it can
observe: with neither ``tracing`` nor the device ledger on, a warmed-up
dispatch costs one perf-clock pair and an unlocked counter bump; with
either on, every call takes the wall stamps, the span and the ledger
hook they consume.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
import weakref
from typing import Any, Dict, Optional, Sequence, Tuple

import jax

from ray_tpu.telemetry import device as device_ledger
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing

_LOCK = threading.Lock()
# live ShardedFunctions, for process-wide stats aggregation
_REGISTRY: "weakref.WeakSet" = weakref.WeakSet()

def label_family(label: str) -> str:
    """What a program is called in a profiler trace: its label up to
    the first ``[``, reduced to ``[A-Za-z0-9_]``
    (``replay_insert[buf:...]`` -> ``replay_insert``). The function
    handed to ``jax.jit`` carries it as its name, so the trace reads
    ``jit_replay_insert(<fingerprint>)`` on ``XLA Modules`` and
    ``PjitFunction(replay_insert)`` on the host's plane; the label,
    with its sizes, stays the key of ``compile_stats()`` and of the
    device ledger."""
    return re.sub(r"[^A-Za-z0-9_]", "_", label.split("[", 1)[0]) or "sharded_fn"


def _mesh_geometry_token(tree) -> Tuple:
    """Stable token naming every mesh geometry the tree's shardings
    reference: ((axis, size) pairs, participating device ids) per
    distinct mesh. The AOT cache keys on it (aot.FORMAT 2) — a program
    lowered on a 2-host (dcn=2, batch=k) mesh and its 1-host resize
    twin share label AND abstract shapes but not executables, so the
    geometry must be part of the entry identity for the fleet's
    pre-seeded ±1-host entries to coexist."""
    toks = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        for holder in (leaf, getattr(leaf, "sharding", None)):
            mesh = getattr(holder, "mesh", None)
            if mesh is None:
                continue
            try:
                axes = tuple(
                    (str(a), int(s))
                    for a, s in dict(mesh.shape).items()
                )
                ids = tuple(
                    int(d.id) for d in mesh.devices.flat
                )
            except Exception:
                continue
            toks.add((axes, ids))
    return tuple(sorted(toks))


class ShardedFunction:
    """A compiled, partitioned callable.

    Callable like the underlying jitted function. ``stats()`` reports
    the compile-cache behavior:

      - ``traces``: distinct (shape, dtype, static-arg) signatures
        compiled so far — 1 after warmup means shape-stable;
      - ``recompiles``: traces beyond the first (should be 0 across
        steps with constant shapes);
      - ``calls``: total invocations;
      - ``compile_time_s``: wall time of the calls that traced
        (compile + first dispatch); steady-state calls add nothing.
    """

    def __init__(
        self,
        fn,
        in_specs=None,
        out_specs=None,
        donate_argnums: Sequence[int] = (),
        static_argnames: Sequence[str] = (),
        label: Optional[str] = None,
    ):
        self.label = label or getattr(fn, "__name__", "sharded_fn")
        self.traces = 0
        self.calls = 0
        self.compile_time_s = 0.0
        # AOT-installed dispatch path (sharding/aot.py): a compiled
        # executable restored from the persistent cache ("aot_cache")
        # or compiled ahead of time here ("aot_live"); None = plain jit
        self._aot = None
        self.aot_source: Optional[str] = None
        self.aot_fallbacks = 0
        # ledger-visible program identity (telemetry/device.py)
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.donate_argnums = tuple(donate_argnums)
        self.static_argnames = tuple(static_argnames)
        # donation pre-validation, ONCE at wrap time: jax re-checks the
        # donate/static interaction on every trace, but a donate index
        # that is not a non-negative int (or collides with nothing it
        # could ever donate) is a wiring bug worth failing at
        # construction, not at first dispatch
        for i in self.donate_argnums:
            if not isinstance(i, int) or i < 0:
                raise ValueError(
                    f"donate_argnums must be non-negative ints, got "
                    f"{self.donate_argnums!r} for {self.label!r}"
                )
        self._lock = threading.Lock()
        self._uncounted = threading.local()

        def _counted(*args, **kwargs):
            # the ledger's ahead-of-time analysis compile re-traces
            # abstractly; that must not count as a (re)trace of the
            # execution path
            if not getattr(self._uncounted, "on", False):
                with self._lock:
                    self.traces += 1
            return fn(*args, **kwargs)

        _counted.__name__ = _counted.__qualname__ = label_family(self.label)

        kw: Dict[str, Any] = {}
        if in_specs is not None:
            kw["in_shardings"] = in_specs
        if out_specs is not None:
            kw["out_shardings"] = out_specs
        if static_argnames:
            kw["static_argnames"] = tuple(static_argnames)
        if donate_argnums:
            kw["donate_argnums"] = tuple(donate_argnums)
        self._jitted = jax.jit(_counted, **kw)
        with _LOCK:
            _REGISTRY.add(self)

    @contextlib.contextmanager
    def uncounted_traces(self):
        """Scope in which re-traces don't bump ``traces`` (the device
        ledger's AOT analysis compile — same function, abstract args)."""
        self._uncounted.on = True
        try:
            yield
        finally:
            self._uncounted.on = False

    def aot_warmup(self, cache, *args, **kwargs) -> str:
        """Install an ahead-of-time compiled executable for the ONE
        abstract signature ``(*args, **kwargs)`` describes (the serve
        bucket contract: one ShardedFunction = one static shape).

        Tries the persistent cache first — a hit installs the
        deserialized executable with ZERO fresh compiles and registers
        it in the device ledger with ``compile_s=0`` /
        ``source="aot_cache"``. A miss compiles ahead of time (counted
        as this function's one trace), installs the result, and queues
        the serialized executable for the cache writer so the NEXT
        replica hits. Returns ``"hit"`` / ``"compiled"`` /
        ``"disabled"`` (no cache — the caller falls back to plain jit
        warmup).

        The cache signature carries the MESH GEOMETRY of the program's
        shardings on top of the ledger's shape/dtype signature: the
        same label at the same shapes lowers to different collectives
        on different meshes (a 2-host fleet pre-seeding its 1-host
        resize geometry is the motivating case — without the token the
        two entries would collide on one key).
        """
        from ray_tpu.sharding import aot as aot_lib

        cache = aot_lib.resolve_cache(cache)
        if cache is None:
            return "disabled"
        try:
            sig = device_ledger.signature_of(
                args, kwargs, self.static_argnames
            )
            geo = _mesh_geometry_token(
                (args, kwargs, self.in_specs, self.out_specs)
            )
            if geo:
                sig = (sig, ("mesh", geo))
        except Exception:
            return "disabled"
        loaded = cache.load(self.label, sig)
        if loaded is not None:
            self._aot = loaded
            self.aot_source = "aot_cache"
            device_ledger.on_aot(self, 0.0, "aot_cache")
            return "hit"
        t0 = time.perf_counter()
        try:
            with self.uncounted_traces():
                compiled = self._jitted.lower(
                    *args, **kwargs
                ).compile()
        except Exception:
            return "disabled"
        dt = time.perf_counter() - t0
        with self._lock:
            # a real XLA compile: count it exactly like a jit trace so
            # compile_stats stays honest about cold-start cost
            self.traces += 1
            self.compile_time_s += dt
        self._aot = compiled
        self.aot_source = "aot_live"
        device_ledger.on_aot(self, dt, "aot_live")
        cache.save(self.label, sig, compiled)
        return "compiled"

    def _call_aot(self, args, kwargs):
        """Dispatch through the installed AOT executable; any failure
        (signature drift, an executable a stale cache slipped past the
        keying) drops the AOT path and falls back to plain jit — the
        graceful-fallback contract. Shape/dtype mismatches raise
        BEFORE execution, so donated buffers are still intact for the
        fallback call."""
        ledger_on = device_ledger.enabled()
        trace_on = tracing.is_enabled()
        if not (ledger_on or trace_on):
            # steady-path diet: nobody consumes the wall/perf stamps,
            # so don't take them (the ledger hook below early-returns)
            try:
                out = self._aot(*args, **kwargs)
            except Exception:
                self._aot = None
                with self._lock:
                    self.aot_fallbacks += 1
                tracing.event("aot:fallback", label=self.label)
                try:
                    telemetry_metrics.inc_aot_cache_event("fallback")
                except Exception:
                    pass
                return None
            self.calls += 1
            return (out,)
        t_wall0 = time.time()
        t0 = time.perf_counter()
        try:
            if trace_on:
                with tracing.start_span("jit:" + self.label) as sp:
                    out = self._aot(*args, **kwargs)
                    sp.set_attribute("aot", self.aot_source)
            else:
                out = self._aot(*args, **kwargs)
        except Exception:
            self._aot = None
            with self._lock:
                self.aot_fallbacks += 1
            tracing.event("aot:fallback", label=self.label)
            try:
                telemetry_metrics.inc_aot_cache_event("fallback")
            except Exception:
                pass
            return None
        dt = time.perf_counter() - t0
        with self._lock:
            self.calls += 1
        device_ledger.on_call(self, t_wall0, dt, traced=False)
        return (out,)

    def __call__(self, *args, **kwargs):
        if self._aot is not None:
            boxed = self._call_aot(args, kwargs)
            if boxed is not None:
                return boxed[0]
        before = self.traces
        # fast path: after warmup, with neither tracing nor the device
        # ledger consuming the per-call stamps, dispatch costs one
        # perf-clock pair and an unlocked counter bump — no
        # time.time(), no lock, no span, no ledger hook. A retrace
        # detected after the fact (shape drift, a genuinely changed
        # sharding) gets the full bookkeeping for THIS call, so compile
        # stats and forensics stay exact on every path that compiles.
        if (
            before > 0
            and not tracing.is_enabled()
            and not device_ledger.enabled()
        ):
            t0 = time.perf_counter()
            out = self._jitted(*args, **kwargs)
            if self.traces == before:
                self.calls += 1
                return out
            dt = time.perf_counter() - t0
            device_ledger.on_traced(self, args, kwargs, dt)
            with self._lock:
                self.calls += 1
                self.compile_time_s += dt
            return out
        t_wall0 = time.time()
        t0 = time.perf_counter()
        if tracing.is_enabled():
            # trace-vs-cached-execute span: "did this step recompile?"
            # shows up as a lane in the chrome trace, and a retrace
            # after warmup additionally records a recompile event —
            # with the ledger on, carrying the forensics cause (which
            # abstract leaf's shape/dtype moved)
            with tracing.start_span("jit:" + self.label) as sp:
                out = self._jitted(*args, **kwargs)
                traced = self.traces != before
                sp.set_attribute("traced", traced)
                if traced:
                    cause = device_ledger.on_traced(
                        self, args, kwargs,
                        time.perf_counter() - t0,
                    )
                    if before > 0:
                        ev = {"label": self.label}
                        if cause:
                            ev["cause"] = cause
                        tracing.event("jit:recompile", **ev)
        else:
            out = self._jitted(*args, **kwargs)
            if self.traces != before:
                device_ledger.on_traced(
                    self, args, kwargs, time.perf_counter() - t0
                )
        dt = time.perf_counter() - t0
        with self._lock:
            self.calls += 1
            if self.traces != before:
                self.compile_time_s += dt
        device_ledger.on_call(
            self, t_wall0, dt, traced=self.traces != before
        )
        return out

    @property
    def recompiles(self) -> int:
        return max(0, self.traces - 1)

    def stats(self) -> Dict[str, Any]:
        out = {
            "label": self.label,
            "traces": self.traces,
            "recompiles": self.recompiles,
            "calls": self.calls,
            "compile_time_s": self.compile_time_s,
        }
        if self.aot_source is not None or self.aot_fallbacks:
            out["aot_source"] = self.aot_source
            out["aot_fallbacks"] = self.aot_fallbacks
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)


def sharded_jit(
    fn,
    in_specs=None,
    out_specs=None,
    donate_argnums: Sequence[int] = (),
    static_argnames: Sequence[str] = (),
    label: Optional[str] = None,
) -> ShardedFunction:
    """Compile ``fn`` partitioned across the mesh its shardings name.

    ``in_specs``/``out_specs`` are per-argument shardings (a single
    ``NamedSharding`` broadcasts over that argument's pytree leaves);
    ``None`` leaves placement to jit (the legacy-fallback mode).
    ``donate_argnums`` releases those input buffers to the output —
    opt-state double-buffering for free."""
    return ShardedFunction(
        fn,
        in_specs=in_specs,
        out_specs=out_specs,
        donate_argnums=donate_argnums,
        static_argnames=static_argnames,
        label=label,
    )


def f64_scope():
    """The x64 scope the device segment-tree programs build and run
    in (``ops/segment_tree.DeviceSumTree``). Priorities are float64
    state — the host sum tree the device tree must reproduce
    bit-exactly is numpy f64 — but this process keeps jax's default
    x64-off canonicalization for every learner program. The scope is
    thread-local and wraps ONLY the tree programs: their f64 arrays
    stay f64 across calls (a jit traced outside the scope would
    silently downcast them to f32), while their f32/i32 outputs (IS
    weights, drawn indices) feed the ordinary f32 learner world
    outside."""
    return jax.enable_x64(True)


def compile_stats() -> Dict[str, Any]:
    """Process-wide compile-cache summary across every live
    ShardedFunction (benchmarks and the acceptance test read this)."""
    with _LOCK:
        fns = list(_REGISTRY)
    per_fn = [f.stats() for f in fns]
    return {
        "functions": len(per_fn),
        "traces": sum(s["traces"] for s in per_fn),
        "recompiles": sum(s["recompiles"] for s in per_fn),
        "calls": sum(s["calls"] for s in per_fn),
        "compile_time_s": sum(s["compile_time_s"] for s in per_fn),
        "per_function": per_fn,
        # forensics rollup (telemetry/device.py): per-label recompile
        # causes — the abstract-signature diffs of every retrace seen
        # while the device ledger ran ({} with the ledger off)
        "recompile_causes": device_ledger.recompile_causes(),
    }
