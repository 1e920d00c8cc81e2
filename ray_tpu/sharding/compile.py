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
from typing import Any, Dict, Optional, Sequence

import jax

from ray_tpu.sharding import async_pairs as async_pairs_lib
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


class ShardedFunction:
    """A compiled, partitioned callable.

    Callable like the underlying jitted function. ``stats()`` reports
    the compile-cache behavior:

      - ``traces``: distinct (shape, dtype, static-arg) signatures
        compiled so far — 1 after warmup means shape-stable;
      - ``recompiles``: traces beyond the first (should be 0 across
        steps with constant shapes);
      - ``calls``: total invocations;
      - ``compile_time_s``: ``trace_s + lower_s + backend_s``, jax's
        own seconds of this program's compiles (the account below).
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
        self.account = CompileAccount(label_family(self.label))
        self.retrace_causes: list = []  # what moved, the last few
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
            _now_tracing(self)
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
    def uncounted_traces(self, analysis: bool = False):
        """Scope whose compiles are this function's without bumping
        ``traces``; ``analysis``: the device ledger's second compile
        of the same program, kept apart as ``analysis_s``."""
        self._uncounted.on = "analysis" if analysis else True
        _TLS.claim = self
        try:
            yield
        finally:
            self._uncounted.on = _TLS.claim = False

    def __call__(self, *args, **kwargs):
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
            # found after the fact: the listeners already gave this
            # compile's seconds to the account, by phase
            self._compiled(args, kwargs)
            with self._lock:
                self.calls += 1
            return out
        t_wall0 = time.time()
        t0 = time.perf_counter()
        if tracing.is_enabled():
            # trace-vs-cached-execute span: "did this step recompile?"
            # shows up as a lane in the chrome trace, and a retrace
            # after warmup additionally records a recompile event with
            # the forensics cause (which abstract leaf's shape/dtype
            # moved) where the ledger saw the program's last trace
            with tracing.start_span("jit:" + self.label) as sp:
                out = self._jitted(*args, **kwargs)
                traced = self.traces != before
                sp.set_attribute("traced", traced)
                if traced:
                    # the compile itself: a ``compile:<family>`` span
                    # over jax's own three phases (trace, lower,
                    # backend), inside this one
                    cause = self._compiled(args, kwargs)
                    if before > 0:
                        ev = {"label": self.label}
                        if cause:
                            ev["cause"] = cause
                        tracing.event("jit:recompile", **ev)
        else:
            out = self._jitted(*args, **kwargs)
            if self.traces != before:
                self._compiled(args, kwargs)
        dt = time.perf_counter() - t0
        with self._lock:
            self.calls += 1
        device_ledger.on_call(
            self, t_wall0, dt, traced=self.traces != before
        )
        return out

    def _compiled(self, args, kwargs) -> Optional[str]:
        """The bookkeeping of a call that compiled, on whichever path
        found it: the ledger's row takes jax's seconds of this
        compile, the forensics say what moved (None on a first trace),
        and while tracing is on or a profiler session is live the
        compile becomes its spans."""
        cause = device_ledger.on_traced(
            self, args, kwargs, self.account.pending_s()
        )
        self.account.settle(self.label, cause)
        if cause:
            with self._lock:
                self.retrace_causes = self.retrace_causes[-7:] + [cause]
        return cause

    @property
    def recompiles(self) -> int:
        return max(0, self.traces - 1)

    @property
    def compile_time_s(self) -> float:
        return self.account.compile_time_s

    def stats(self) -> Dict[str, Any]:
        out = {
            "label": self.label,
            "traces": self.traces,
            "recompiles": self.recompiles,
            "calls": self.calls,
            **self.account.row(),
        }
        if self.retrace_causes:
            out["retrace_causes"] = list(self.retrace_causes)
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def compiled_text(self) -> Optional[str]:
        """The program's scheduled, optimized HLO as the backend
        compiled it, made on request: the signature the device ledger
        analysed is lowered and compiled once more (through jax's
        persistent cache where there is one: a retrieval, not a
        compile) as the ledger's own analysis is, uncounted and with
        its seconds under ``analysis_s``. ``None`` where the ledger
        analysed no signature of this program."""
        abstract = device_ledger.abstract_signature(self.label)
        if abstract is None:
            return None
        args, kwargs, x64 = abstract
        with self.uncounted_traces(analysis=True), jax.enable_x64(x64):
            text = self._jitted.lower(*args, **kwargs).compile().as_text()
        self.account.settle(self.label)
        return text


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


def dispatch_count() -> int:
    """Invocations of every live ShardedFunction so far. Two readings
    that differ had a program dispatched between them (host code that
    puts off a read asks whether it waited for anything)."""
    with _LOCK:
        return sum(f.calls for f in _REGISTRY)


def compile_stats() -> Dict[str, Any]:
    """Process-wide compile-cache summary across every live
    ShardedFunction (benchmarks and the acceptance test read this)."""
    with _LOCK:
        fns = list(_REGISTRY)
    per_fn = [f.stats() for f in fns]
    with _LOCK:
        families = {k: v.row() for k, v in _FAMILIES.items()}
    return {
        "functions": len(per_fn),
        "traces": sum(s["traces"] for s in per_fn),
        "recompiles": sum(s["recompiles"] for s in per_fn),
        "calls": sum(s["calls"] for s in per_fn),
        **{k: sum(s[k] for s in per_fn) for k in _ROW},
        "per_function": per_fn,
        # the same account by program family since the process began:
        # it keeps the seconds of programs that are gone, and under
        # ``other`` what no ShardedFunction compiled
        "families": families,
        # forensics rollup (telemetry/device.py): per-label recompile
        # causes — the abstract-signature diffs of every retrace seen
        # while the device ledger ran ({} with the ledger off)
        "recompile_causes": device_ledger.recompile_causes(),
    }


def async_pairs(family: str) -> Dict[str, Any]:
    """``{label: rows}`` for the live programs of ``family`` (a label
    up to its first ``[``): the asynchronous pairs the compiler put
    into each (``sharding/async_pairs.pairs``: a row a ``*-done`` with
    the loop it sits in, what consumes it, what its start reads and
    the room the scheduler gave it). Made when asked for, from
    ``ShardedFunction.compiled_text``; a program the device ledger
    analysed no signature of is left out. Nothing is kept: a second
    request compiles (retrieves) again."""
    with _LOCK:
        fns = [f for f in _REGISTRY if f.account.family == family]
    out: Dict[str, Any] = {}
    for sf in fns:
        text = sf.compiled_text()
        if text is not None:
            out[sf.label] = async_pairs_lib.pairs(text)
    return out


# -- the compile account -------------------------------------------------
#
# jax times the three phases of every compile itself (tracing to a
# jaxpr, lowering to a module, the backend's compile or the persistent
# cache's retrieval) and says whether the cache hit. The listeners
# below give each event to the ShardedFunction that is compiling on
# that thread, so "where did set-up go" is read from the program's own
# tables instead of a log. ALWAYS ON: a listener runs only when
# something compiles (a steady dispatch raises no event), a handful of
# times a process, and set-up is over before anyone could switch
# tracing on for it.

OTHER = "other"
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# phases of one function's compiles that nobody settled yet (a bare
# ``lower()`` from outside): the oldest go
_MAX_PENDING = 48
_TLS = threading.local()
# the columns of an account's row, in stats() and compile_stats() alike
_ROW = (
    "compile_time_s", "trace_s", "lower_s", "backend_s", "analysis_s",
    "cache_hits", "cache_misses",
)


class CompileAccount:
    """jax's own seconds of one program's compiles: ``trace_s``,
    ``lower_s``, ``backend_s`` (the compile, or the retrieval on a
    cache hit) of the execution path, each exclusive of what ran
    inside it, ``analysis_s`` (all three phases of the device ledger's
    second compile for its cost figures, where the ledger analyzes),
    and the persistent cache's hits and misses (a miss is counted when
    the entry is written)."""

    __slots__ = (
        "family", "trace_s", "lower_s", "backend_s", "analysis_s",
        "cache_hits", "cache_misses", "_pending",
    )

    def __init__(self, family: str):
        self.family = family
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.analysis_s = 0.0
        self.cache_hits = self.cache_misses = 0
        # (phase, start, end, cache, analysis) of the outermost phases
        # since the last settle()
        self._pending: list = []

    @property
    def compile_time_s(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def row(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in _ROW}

    @staticmethod
    def _seconds(entries) -> float:
        return sum(
            end - start
            for _, start, end, _, analysis in entries
            if not analysis
        )

    def pending_s(self) -> float:
        """Seconds of the compile that just ran and was not settled."""
        with _LOCK:
            return self._seconds(self._pending)

    def settle(self, label: str, cause: Optional[str] = None) -> float:
        """Close the books on the compile that just ran: its seconds,
        and while tracing is on or a profiler session is live a
        ``compile:<family>`` span over its ``compile:trace`` /
        ``compile:lower`` / ``compile:backend`` (the ledger's
        analysis compile a second such span, ``analysis=True``), from
        jax's own ``time.time()`` stamps."""
        with _LOCK:
            entries, self._pending = self._pending, []
        if tracing.is_enabled() or tracing.profiling():
            for analysis in (False, True):
                phases = [e for e in entries if e[4] == analysis]
                if not phases:
                    continue
                attrs = {"label": label}
                cache = [e[3] for e in phases if e[3]]
                if cache:
                    attrs["cache"] = cache[-1]
                if analysis:
                    attrs["analysis"] = True
                elif cause:
                    attrs["cause"] = cause
                ctx = tracing.record_span(
                    "compile:" + self.family,
                    min(e[1] for e in phases),
                    max(e[2] for e in phases),
                    **attrs,
                )
                for phase, start, end, _, _ in phases:
                    tracing.record_span(
                        "compile:" + phase, start, end, ctx=ctx
                    )
        return self._seconds(entries)


_FAMILIES: Dict[str, CompileAccount] = {}


def _accounts(sf):
    """The accounts an event on this thread goes to: the compiling
    function's own and its family's row, or ``other``'s alone."""
    family = sf.account.family if sf is not None else OTHER
    row = _FAMILIES.get(family) or _FAMILIES.setdefault(
        family, CompileAccount(family)
    )
    return (row,) if sf is None else (sf.account, row)


def _now_tracing(sf) -> None:
    """jax is tracing ``sf`` on this thread (its traced wrapper calls
    this): the compile events that follow on the thread are its own. A
    program traced INSIDE another's trace leaves the outer's claim."""
    if len(getattr(_TLS, "open", ())) <= 1:
        _TLS.sf = weakref.ref(sf)


def _compiling():
    """The ShardedFunction compiling on this thread: the one inside
    its ``uncounted_traces`` scope (an ahead-of-time compile, which
    may trace nothing anew), else the one jax last traced here."""
    claim = getattr(_TLS, "claim", None)
    if claim:
        return claim
    ref = getattr(_TLS, "sf", None)
    return ref() if ref is not None else None


def _on_phase_start(event: str, value, **_) -> None:
    if event not in _PHASES:
        return
    open_ = _TLS.__dict__.setdefault("open", [])
    if not open_ and _PHASES[event] == "trace":
        # a new compile begins: whoever is traced claims it
        _TLS.sf = None
    open_.append([event, 0.0])


def _on_phase_end(
    event: str, start: float, end: float, fun_name: str = "", **_
) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    open_ = getattr(_TLS, "open", None) or []
    inner = 0.0
    while open_:
        opened, inner = open_.pop()
        if opened == event:
            break
    # exclusive of the phases that ran inside this one (a program
    # traced inside a trace, an eager op compiled while tracing)
    seconds = max(0.0, (end - start) - inner)
    if open_:
        open_[-1][1] += end - start
    sf = _compiling()
    cache = None
    if phase == "backend":
        cache, _TLS.cache = getattr(_TLS, "cache", None), None
    analysis = (
        sf is not None
        and getattr(sf._uncounted, "on", False) == "analysis"
    )
    field = "analysis_s" if analysis else phase + "_s"
    accounts = _accounts(sf)
    with _LOCK:
        for a in accounts:
            setattr(a, field, getattr(a, field) + seconds)
        if sf is not None and not open_:
            sf.account._pending.append(
                (phase, start, end, cache, analysis)
            )
            del sf.account._pending[:-_MAX_PENDING]
    telemetry_metrics.add_compile_phase_seconds(
        accounts[-1].family, "analysis" if analysis else phase, seconds
    )
    if not open_:
        if sf is None and (tracing.is_enabled() or tracing.profiling()):
            tracing.record_span(
                "compile:" + phase, start, end,
                family=OTHER, fun_name=fun_name,
            )
        if phase == "backend":
            _TLS.sf = None  # this compile is over


def _on_cache_event(event: str, **_) -> None:
    result = _CACHE_EVENTS.get(event)
    if result is None:
        return
    _TLS.cache = result
    accounts = _accounts(_compiling())
    field = "cache_hits" if result == "hit" else "cache_misses"
    with _LOCK:
        for a in accounts:
            setattr(a, field, getattr(a, field) + 1)
    telemetry_metrics.inc_compile_cache_event(accounts[-1].family, result)


jax.monitoring.register_scalar_listener(_on_phase_start)
jax.monitoring.register_event_time_span_listener(_on_phase_end)
jax.monitoring.register_event_listener(_on_cache_event)
