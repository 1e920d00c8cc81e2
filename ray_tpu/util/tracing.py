"""Distributed span tracing across task/actor boundaries.

Counterpart of the reference's OpenTelemetry integration
(``python/ray/util/tracing/tracing_helper.py``: every remote
function/actor method is wrapped with span-propagating proxies,
``_inject_tracing_into_function :324``, ``_inject_tracing_into_class
:449``). Same shape without the OTel dependency: when tracing is
enabled, submissions carry a trace context (trace_id + parent span
id), workers open a child span around execution — user code can open
nested spans via :func:`start_span` and they parent correctly — and
finished spans ride back on the result message into the driver's
tracer, exportable as a span list or a chrome://tracing file.

Usage::

    from ray_tpu.util import tracing
    tracing.enable()
    with tracing.start_span("rollout-phase"):
        ray.get(worker.sample.remote())   # worker span is a child
    spans = tracing.get_spans()
    tracing.export_chrome_trace("/tmp/trace.json")

Enable for every process with ``RAY_TPU_TRACE=1`` (workers inherit the
env), or per-driver with :func:`enable`.

Two outputs, two clocks:

- the span list (:func:`get_spans`, :func:`export_chrome_trace`, the
  spans that ride back from workers) is stamped with ``time.time()``:
  wall-clock seconds, comparable across processes up to their skew;
- while a ``jax.profiler`` session is live in this process
  (``profile_iters``, any ``jax.profiler.start_trace``) every span
  site is ALSO a ``jax.profiler.TraceAnnotation`` of the same name and
  attributes. It lands on the ``/host:CPU`` plane of the session's
  ``.xplane.pb`` on the profiler's own clock (nanoseconds from about
  ``start_trace``), the clock the device's operations are on, so a
  program span lies over the device's idle gaps with no conversion.

The profiler session is the switch for the second output; there is no
other. Three states: :func:`enable` on and no session, the span list
only; a session and :func:`enable` never called, the annotation only
(no ``Span`` object, no uuid, nothing appended); neither, the null
span, at the cost of one ``TraceAnnotation.is_enabled()`` per span
site and of nothing in a process that has not imported jax. This
module is imported by the CPU rollout workers and never imports jax
itself: it looks the class up once jax is in ``sys.modules``.

What is kept with tracing OFF: the sites opened with :func:`phase`
and nothing else. Those are the steps of building an Algorithm
(``setup:algorithm``, ``setup:workers``, ``setup:policy``,
``setup:model_init``, ``setup:optimizer_init``,
``setup:rollout_engine``, ``setup:replay``): each runs once a process,
before anyone could have switched tracing on for it, and what an
operator asks after a slow restart is where those seconds went. A
``phase`` costs a clock pair and one row of a bounded table
(:func:`phases`; the first ``train()`` result carries it under
``info/setup``). No site on the path of an iteration is a ``phase``:
a hot site is a :func:`start_span`, which costs two flag checks when
off. The other account that is always on, the seconds of every
compile by phase and program family, lives with the compile layer
(``sharding/compile.py``) because it is fed by jax's own events.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

_enabled = os.environ.get("RAY_TPU_TRACE") == "1"
_current: contextvars.ContextVar[Optional["Span"]] = (
    contextvars.ContextVar("ray_tpu_span", default=None)
)
_finished: List[Dict] = []
# spans ever appended: the clock of ``spans_since``'s cursor
_appended = 0
_lock = threading.Lock()
# bound the span buffer: long-running jobs must not grow driver memory
# monotonically — oldest spans drop first (export/inspect regularly,
# or raise via RAY_TPU_TRACE_BUFFER)
_MAX_SPANS = int(os.environ.get("RAY_TPU_TRACE_BUFFER", 100_000))


def _append_bounded(records: List[Dict]) -> None:
    global _appended
    with _lock:
        _finished.extend(records)
        _appended += len(records)
        if len(_finished) > _MAX_SPANS:
            del _finished[: len(_finished) - _MAX_SPANS]


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


# -- the profiler's trace as a second output ---------------------------

# jax.profiler.TraceAnnotation extended with the span interface, built
# the first time a span site runs in a process that has imported jax
_annotation = None


def _annotation_class():
    global _annotation
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None

    class ProfilerSpan(profiler.TraceAnnotation):
        """What a span site yields under a profiler-only session: the
        annotation itself, with the null span's ids."""

        __slots__ = ()
        trace_id = None
        span_id = None
        parent_id = None

        def set_attribute(self, key: str, value: Any) -> None:
            self.set_metadata(**{key: value})

    _annotation = ProfilerSpan
    return ProfilerSpan


def profiling() -> bool:
    """Whether a ``jax.profiler`` session is live in this process."""
    cls = _annotation or _annotation_class()
    return cls is not None and cls.is_enabled()


def _annotate(name: str, attributes: Dict[str, Any]):
    """A context manager that is the profiler's annotation while a
    session is live, and nothing otherwise."""
    if profiling():
        return _annotation(name, **attributes)
    return contextlib.nullcontext()


class Span:
    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "name",
        "start",
        "end",
        "attributes",
        "process",
        "thread",
        "thread_name",
        "note",
    )

    def __init__(self, name: str, trace_id=None, parent_id=None):
        # the profiler's annotation of this span, while it is open
        # under a live session (start_span sets it)
        self.note = None
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_id = parent_id
        self.name = name
        self.start = time.time()
        self.end: Optional[float] = None
        self.attributes: Dict[str, Any] = {}
        self.process = os.getpid()
        # thread identity so prefetcher/feeder/learner threads render
        # as separate chrome-trace lanes instead of one flat tid 0
        t = threading.current_thread()
        self.thread = t.ident or 0
        self.thread_name = t.name

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value
        if self.note is not None:
            self.note.set_metadata(**{key: value})

    def finish(self, end: Optional[float] = None) -> Dict:
        self.end = time.time() if end is None else end
        record = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "attributes": dict(self.attributes),
            "pid": self.process,
            "tid": self.thread,
            "thread_name": self.thread_name,
        }
        if _enabled:  # disabled tracing records nothing
            _append_bounded([record])
        return record


class _NullSpan:
    """Returned by start_span when tracing is off and no profiler
    session is live: every operation is a no-op, so the disabled hot
    path costs two flag checks (no uuid, no clock reads, no
    allocation)."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def set_attribute(self, key: str, value: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


@contextlib.contextmanager
def _open(span: "Span", attributes: Dict[str, Any]):
    """Run the body under ``span`` as the current one (and under the
    profiler's annotation of it, where a session is live)."""
    for k, v in attributes.items():
        span.set_attribute(k, v)
    token = _current.set(span)
    try:
        with _annotate(span.name, attributes) as span.note:
            yield span
    finally:
        span.note = None
        _current.reset(token)
        span.finish()


def _child(name: str) -> "Span":
    parent = _current.get()
    return Span(
        name,
        trace_id=parent.trace_id if parent else None,
        parent_id=parent.span_id if parent else None,
    )


@contextlib.contextmanager
def start_span(name: str, **attributes):
    """Open a span under the current one (driver or worker side)."""
    if not _enabled:
        if profiling():
            with _annotation(name, **attributes) as note:
                yield note
        else:
            yield _NULL_SPAN
        return
    with _open(_child(name), attributes) as span:
        yield span


def event(name: str, **attributes) -> None:
    """Record a zero-duration span (dead worker, recompile, ...)
    parented under the current span. No-op when tracing is off and no
    profiler session is live."""
    if profiling():
        with _annotation(name, **attributes):
            pass
    if not _enabled:
        return
    span = _child(name)
    span.attributes.update(attributes)
    span.finish(end=span.start)


def record_span(
    name: str, start: float, end: float, ctx: Optional[Dict] = None,
    **attributes,
) -> Optional[Dict]:
    """Record a span whose interval was measured out-of-band (e.g. a
    queue wait that ended when ``get()`` returned). ``start``/``end``
    are ``time.time()`` stamps. The profiler's clock cannot be
    back-dated: under a live session the span is an annotation of no
    length at the moment of this call, with the interval's length as
    its ``seconds`` attribute. No-op when tracing is off and no
    session is live. Returns the context a further ``record_span``
    takes as ``ctx`` to lie under this one (None when nothing was
    recorded); without ``ctx`` the span lies under the current one."""
    if profiling():
        with _annotation(name, seconds=end - start, **attributes):
            pass
    if not _enabled:
        return None
    if ctx is None:
        span = _child(name)
    else:
        span = Span(name, ctx["trace_id"], ctx["parent_span_id"])
    span.start = start
    span.attributes.update(attributes)
    span.finish(end=end)
    return {"trace_id": span.trace_id, "parent_span_id": span.span_id}


# -- the sites that are kept with tracing off ---------------------------

_MAX_PHASES = 256
_phases: List[Dict[str, Any]] = []
_phase: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "ray_tpu_phase", default=None
)


@contextlib.contextmanager
def phase(name: str, **attributes):
    """A span site that runs once a process (a step of building the
    Algorithm): a :func:`start_span`, and whether or not tracing is on
    one row ``{name, seconds, parent}`` of :func:`phases`. Not for a
    site an iteration reaches."""
    token = _phase.set(name)
    t0 = time.perf_counter()
    try:
        with start_span(name, **attributes) as span:
            yield span
    finally:
        _phase.reset(token)
        row = {
            "name": name,
            "seconds": time.perf_counter() - t0,
            "parent": _phase.get(),
        }
        with _lock:
            _phases.append(row)
            del _phases[:-_MAX_PHASES]


def phases() -> List[Dict[str, Any]]:
    """The :func:`phase` sites that finished in this process, oldest
    first (the last ``_MAX_PHASES``): ``name``, ``seconds`` on the
    host's clock, and ``parent``, the name of the phase it ran inside
    (None at the top)."""
    with _lock:
        return [dict(row) for row in _phases]


def phase_seconds(name: str) -> Optional[float]:
    """Seconds under every finished phase called ``name``; None when
    there is none."""
    found = [r["seconds"] for r in phases() if r["name"] == name]
    return sum(found) if found else None


# -- boundary plumbing (called by core/api.py and core/worker_proc.py) --


def inject_context() -> Optional[Dict]:
    """Driver-side: the context a submission carries
    (tracing_helper's span injection role)."""
    if not _enabled:
        return None
    parent = _current.get()
    if parent is not None:
        return {
            "trace_id": parent.trace_id,
            "parent_span_id": parent.span_id,
        }
    return {"trace_id": uuid.uuid4().hex[:16], "parent_span_id": None}


@contextlib.contextmanager
def remote_span(ctx: Optional[Dict], name: str):
    """Worker-side: execution span as a child of the submitted
    context; no-op when the submission carried none. A present
    context IS the worker's enable signal (the driver's enable() flag
    doesn't cross the process boundary; the injected context does),
    so nested user spans inside the execution record too."""
    global _enabled
    if ctx is None:
        yield None
        return
    span = Span(
        name,
        trace_id=ctx.get("trace_id"),
        parent_id=ctx.get("parent_span_id"),
    )
    token = _current.set(span)
    was_enabled = _enabled
    _enabled = True
    try:
        yield span
    finally:
        _current.reset(token)
        span.finish()
        _enabled = was_enabled


@contextlib.contextmanager
def context_span(ctx: Optional[Dict], name: str, **attributes):
    """Open a span under an EXPLICIT trace context (the serving path's
    ``x-ray-tpu-trace`` propagation: ingress → router → replica spans
    stitch into one trace even though they run on different threads,
    where contextvars can't carry the parent). Unlike
    :func:`remote_span` this never force-enables tracing — when the
    process has tracing off and no profiler session is live it costs
    two flag checks and yields the null span, so it is safe on the
    serve hot path. ``ctx`` is an
    :func:`inject_context`-shaped dict; ``None`` falls back to the
    calling context's current span (plain :func:`start_span`
    semantics)."""
    if not _enabled:
        if profiling():
            with _annotation(name, **attributes) as note:
                yield note
        else:
            yield _NULL_SPAN
        return
    if ctx is None:
        with _open(_child(name), attributes) as span:
            yield span
        return
    span = Span(
        name,
        trace_id=ctx.get("trace_id"),
        parent_id=ctx.get("parent_span_id"),
    )
    with _open(span, attributes):
        yield span


def drain_finished() -> List[Dict]:
    """Worker-side: hand finished spans to the result pipe."""
    with _lock:
        out = list(_finished)
        _finished.clear()
    return out


def record_spans(spans: List[Dict]) -> None:
    """Driver-side: absorb spans shipped back from a worker."""
    if not spans:
        return
    _append_bounded(spans)


def get_spans() -> List[Dict]:
    with _lock:
        return list(_finished)


def spans_since(cursor: int = 0):
    """``(spans, cursor)``: the spans appended since ``cursor`` (what
    an earlier call returned; 0 reads from the start) and the cursor
    to pass next. A consumer that runs every iteration reads each span
    once, whatever the buffer holds; what the bounded buffer dropped in
    between is gone."""
    with _lock:
        n = min(len(_finished), max(0, _appended - cursor))
        return (_finished[len(_finished) - n:] if n else []), _appended


def clear() -> None:
    with _lock:
        _finished.clear()
        _phases.clear()


def _clamped_intervals(spans: List[Dict]) -> Dict[str, tuple]:
    """Per-span [start, end] intervals with cross-actor clock skew
    contained: a child span is clamped into its parent's (clamped)
    interval, and end never precedes start. Worker clocks are plain
    ``time.time()`` — a worker ahead of the driver used to render its
    execution span outside (or "before") the submitting span, which
    chrome://tracing draws as negative-duration garbage. Parentage is
    ground truth (the submission carried the context), so the parent
    interval bounds the child."""
    by_id = {
        s["span_id"]: s for s in spans if s.get("span_id")
    }
    out: Dict[str, tuple] = {}

    def resolve(s, seen) -> tuple:
        sid = s.get("span_id")
        if sid in out:
            return out[sid]
        start = s["start"]
        end = s["end"] if s["end"] is not None else start
        end = max(end, start)
        pid = s.get("parent_id")
        parent = by_id.get(pid)
        if parent is not None and pid not in seen:
            ps, pe = resolve(parent, seen | {pid})
            start = min(max(start, ps), pe)
            end = min(max(end, start), pe)
        if sid:
            out[sid] = (start, end)
        return (start, end)

    for s in spans:
        resolve(s, {s.get("span_id")})
    return out


def export_chrome_trace(
    path: str, since: Optional[float] = None
) -> str:
    """chrome://tracing JSON (the reference's ray.timeline format,
    _private/state.py:435, with span parent/trace ids attached).
    ``since`` keeps only spans that END at or after that
    ``time.time()`` stamp (Algorithm.export_timeline's last-N-iteration
    window). Each (pid, tid) lane carries a thread_name metadata event
    so prefetcher/feeder/learner threads are labeled in the viewer.
    Child spans are clamped into their parent's interval so cross-actor
    clock skew can't produce negative durations or out-of-parent
    rendering (raw stamps stay available in the span list API)."""
    with _lock:
        spans = list(_finished)
    if since is not None:
        spans = [
            s for s in spans if (s["end"] or s["start"]) >= since
        ]
    clamped = _clamped_intervals(spans)
    events = []
    for s in spans:
        start, end = clamped.get(
            s.get("span_id"),
            (s["start"], s["end"] or s["start"]),
        )
        events.append(
            {
                "name": s["name"],
                "cat": "span",
                "ph": "X",
                "ts": start * 1e6,
                "dur": (end - start) * 1e6,
                "pid": s["pid"],
                "tid": s.get("tid", 0),
                "args": {
                    "trace_id": s["trace_id"],
                    "span_id": s["span_id"],
                    "parent_id": s["parent_id"],
                    **s["attributes"],
                },
            }
        )
    lanes = {}
    for s in spans:
        lanes.setdefault(
            (s["pid"], s.get("tid", 0)), s.get("thread_name")
        )
    for (pid, tid), tname in sorted(lanes.items()):
        if tname:
            events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path
