"""RTA010 — metric/span catalog consistency against the docs.

The Prometheus catalog is 60+ hand-maintained families and the span
map another two dozen names; dashboards, the report CLI, and the
roll-up all key on them by STRING. A renamed family or an
undocumented span silently orphans a dashboard panel — the exact
drift class the "one place, so docs/tests/dashboards can't drift"
comment in ``telemetry/metrics.py`` hoped convention would prevent.
This rule makes the doc the enforced source of truth:

- every metric family name constructed in code — a string literal
  matching ``ray_tpu_[a-z0-9_]+`` assigned at module level or passed
  to an instrument constructor — must appear in
  ``docs/observability.md``;
- for instrument declarations with an explicit ``tag_keys=(...)``,
  every tag key must appear on the doc line(s) that mention the
  family (the catalog table row documents the label set — a tag the
  row doesn't name is an undocumented cardinality axis);
- every literal span name opened via ``start_span("...")``,
  ``phase("...")`` or ``context_span(ctx, "...")`` must be documented: the full name
  appears in the doc, a documented ``prefix:*`` glob covers it, or it
  starts with a stage prefix of ``telemetry/rollup.py``'s
  ``STAGE_PREFIXES`` map (when that module is in the scan). Dynamic
  names (``"jit:" + label``) are checked by their constant prefix;
- fleet-scoped families (``ray_tpu_fleet_*`` / ``ray_tpu_kv_*``) must
  additionally name the ``host`` label in their catalog row: every
  fleet-plane series is host-attributed — either tagged at the source
  or ``host=``-injected by the fleetview aggregator — and a row that
  doesn't say so misdocuments the merged exposition's cardinality.

The doc is read once per scan; with no ``docs/observability.md``
under the scan root the rule is silent (fixture scans anchor
``root`` at the repo, so fixtures exercise it against the real doc).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.analysis.engine import Finding, ModuleModel
from ray_tpu.analysis.rules._common import call_name, keyword

RULE_ID = "RTA010"

_FAMILY_RE = re.compile(r"^ray_tpu_[a-z0-9_]+$")
# fleet-plane families: their doc rows must name the `host` label
_HOST_SCOPED_RE = re.compile(r"^ray_tpu_(fleet|kv)_")
_INSTRUMENT_CTORS = {
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "timer_histogram", "get_metric",
}
# opener -> index of the span-name argument (context_span takes the
# propagated context first, the name second; phase is the span site
# that is kept with tracing off)
_SPAN_OPENERS = {"start_span": 0, "context_span": 1, "phase": 0}


def _doc(program) -> Optional[str]:
    path = os.path.join(program.root, "docs", "observability.md")
    try:
        with open(path, encoding="utf-8") as f:
            return f.read()
    except OSError:
        return None


def _doc_globs(doc: str) -> List[str]:
    """Documented ``prefix:*`` globs (e.g. ``recovery:*``)."""
    return re.findall(r"([a-z_]+:)\*", doc)


def _rollup_prefixes(program) -> List[str]:
    m = program.by_name.get("ray_tpu.telemetry.rollup")
    if m is None:
        return []
    out: List[str] = []
    for node in ast.walk(m.tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "STAGE_PREFIXES"
            for t in node.targets
        ):
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Constant) and isinstance(
                sub.value, str
            ):
                out.append(sub.value)
    return out


def _literal_prefix(node: ast.AST) -> Optional[Tuple[str, bool]]:
    """(text, is_full) for a span-name argument: a constant string is
    full; the constant LEFT side of ``"p:" + x`` or an f-string's
    leading literal is a prefix."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, True
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _literal_prefix(node.left)
        if left is not None:
            return left[0], False
    if isinstance(node, ast.JoinedStr) and node.values:
        first = node.values[0]
        if isinstance(first, ast.Constant) and isinstance(
            first.value, str
        ):
            return first.value, False
    return None


def check_program(program) -> List[Finding]:
    doc = _doc(program)
    if doc is None:
        return []
    doc_lines = doc.splitlines()
    globs = _doc_globs(doc)
    stage_prefixes = _rollup_prefixes(program)
    findings: List[Finding] = []

    def add(model: ModuleModel, node, msg):
        f = model.finding(RULE_ID, node, msg)
        if f:
            findings.append(f)

    _row_cache: Dict[str, List[str]] = {}

    def family_rows(name: str) -> List[str]:
        rows = _row_cache.get(name)
        if rows is None:
            rows = [ln for ln in doc_lines if name in ln]
            _row_cache[name] = rows
        return rows

    # metric family names: module-level constants + ctor args ---------
    for m in program.modules:
        if m.module_name.startswith("ray_tpu.analysis"):
            continue
        if not program.in_scope(m):
            continue
        # module-level NAME = "ray_tpu_..."
        consts: Dict[str, Tuple[str, ast.AST]] = {}
        for node in m.tree.body:
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Constant
            ):
                val = node.value.value
                if isinstance(val, str) and _FAMILY_RE.match(val):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            consts[tgt.id] = (val, node.value)
        for name, (val, node) in consts.items():
            rows = family_rows(val)
            if not rows:
                add(
                    m,
                    node,
                    f"metric family `{val}` is not documented in "
                    "docs/observability.md — add a catalog row (the "
                    "doc is the enforced source of truth for "
                    "dashboards)",
                )
            elif _HOST_SCOPED_RE.match(val) and "host" not in " ".join(
                rows
            ):
                add(
                    m,
                    node,
                    f"fleet-plane family `{val}` has a catalog row "
                    "that never mentions the `host` label — every "
                    "ray_tpu_fleet_*/ray_tpu_kv_* series is "
                    "host-attributed in the merged exposition "
                    "(tagged at the source or injected by the "
                    "fleetview aggregator); document it",
                )

        # instrument constructions: name + tag_keys
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call):
                continue
            last = call_name(node).split(".")[-1]
            if last not in _INSTRUMENT_CTORS or not node.args:
                continue
            arg = node.args[0]
            family: Optional[str] = None
            if isinstance(arg, ast.Constant) and isinstance(
                arg.value, str
            ):
                if _FAMILY_RE.match(arg.value):
                    family = arg.value
            elif isinstance(arg, ast.Name) and arg.id in consts:
                family = consts[arg.id][0]
            if family is None:
                continue
            rows = family_rows(family)
            if not rows:
                add(
                    m,
                    node,
                    f"metric family `{family}` is not documented in "
                    "docs/observability.md — add a catalog row",
                )
                continue
            if (
                isinstance(arg, ast.Constant)
                and _HOST_SCOPED_RE.match(family)
                and "host" not in " ".join(rows)
            ):
                # literal ctor names never went through the
                # module-const check above — same host-label contract
                add(
                    m,
                    node,
                    f"fleet-plane family `{family}` has a catalog "
                    "row that never mentions the `host` label — "
                    "document it (merged-exposition cardinality)",
                )
            tags = keyword(node, "tag_keys")
            if tags is None:
                continue
            tag_names = [
                n.value
                for n in ast.walk(tags)
                if isinstance(n, ast.Constant)
                and isinstance(n.value, str)
            ]
            row_text = " ".join(rows)
            for t in tag_names:
                if t not in row_text:
                    add(
                        m,
                        node,
                        f"metric family `{family}` declares tag "
                        f"`{t}` but its docs/observability.md row "
                        "does not name it — document the full label "
                        "set (undocumented tags are unbudgeted "
                        "cardinality)",
                    )

    # span names -------------------------------------------------------
    def span_covered(text: str, is_full: bool) -> bool:
        if is_full and text in doc:
            return True
        if not is_full and text and text in doc:
            return True
        for g in globs:
            if text.startswith(g):
                return True
        for p in stage_prefixes:
            if text.startswith(p) or (not is_full and p.startswith(text)):
                return True
        return False

    for m in program.modules:
        if m.module_name.startswith("ray_tpu.analysis"):
            continue
        if not program.in_scope(m):
            continue
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            idx = _SPAN_OPENERS.get(call_name(node).split(".")[-1])
            if idx is None or len(node.args) <= idx:
                continue
            lit = _literal_prefix(node.args[idx])
            if lit is None:
                continue
            text, is_full = lit
            if span_covered(text, is_full):
                continue
            kind = "span" if is_full else "span prefix"
            add(
                m,
                node.args[0],
                f"{kind} `{text}` is not in the documented span map "
                "(docs/observability.md) nor covered by a rollup "
                "stage prefix — document it (or fold it into an "
                "existing stage) so timelines and the report CLI "
                "stay navigable",
            )
    return findings
