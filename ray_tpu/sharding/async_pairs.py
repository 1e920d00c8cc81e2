"""The asynchronous pairs of a compiled program, read from its text.

The compiler's memory-space assignment and scheduler put pairs into a
program that its author never wrote: ``copy-start``/``copy-done``,
``slice-start``/``slice-done``, ``async-start``/``async-done`` (any
``*-start``/``*-done``). The start issues a transfer, the done WAITS
for it, and the done carries no scope of the program, so a profile
shows the wait under no name. The scheduled, optimized HLO
(``Compiled.as_text()``, or an ``--xla_dump_to`` file) knows what the
profile does not: which instruction consumes each done, in which loop
it sits, what the start reads and how many instructions the scheduler
put between the two.

``pairs(text)`` gives one row a done:

    name, opcode, shape, bytes      ``copy-done.294``, ``copy-done``,
                                    ``f32[2560]``, 10240; ``space`` the
                                    memory space of the result's layout
                                    (``S(1)``), where it states one
    start                           the start's name (``copy-start.294``)
    computation                     the computation that holds the done
    under                           the call graph upward, innermost
                                    first: ``{kind, name, op_name}`` of
                                    each ``while`` / ``conditional`` /
                                    ``call`` the computation runs under,
                                    then ``{kind: "entry"}``; ``op_name``
                                    is that instruction's own metadata
                                    path as the text prints it
    consumers                       ``op_name`` paths of the
                                    instructions that use the done's
                                    result, looked for through
                                    ``bitcast``, ``get-tuple-element``,
                                    ``tuple``, a ``ConcatBitcast`` and a
                                    fusion's parameter; ``consumer_ops``
                                    their names
    source                          what the start reads: ``{parameter,
                                    shape, of}`` where that is element
                                    ``parameter`` of the tuple the loop
                                    (``of``) carries, followed upward
                                    through loops that hand it on
                                    unchanged to ``{entry_parameter,
                                    name}``; else ``{op, opcode,
                                    op_name}`` of the producer
    room                            instructions between start and done
                                    in the computation's schedule order
    hoisted                         the start lies in the PREVIOUS
                                    iteration of the loop: the done
                                    reads the loop's carried tuple and
                                    the start feeds the body's root

Plain text in, plain data out: nothing here imports jax, and the rows
are JSON as they stand. ``format_table`` prints them by loop and
consumer, which is what ``python -m ray_tpu.telemetry.report --pairs``
shows.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

# what a done's value (or a start's operand) passes through unchanged
_THROUGH = ("bitcast", "get-tuple-element", "tuple", "opt-barrier")
_CONCAT = 'custom_call_target="ConcatBitcast"'
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}
_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_NAME = re.compile(r"%?([A-Za-z_][\w.\-]*)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_SPACE = re.compile(r"S\((\d+)\)")


class Instruction:
    __slots__ = (
        "name", "shape", "opcode", "operands", "called", "op_name",
        "index", "at", "concat", "root",
    )

    def __init__(self, name, shape, opcode, operands, rest, at, root):
        self.name = name
        self.shape = shape
        self.opcode = opcode
        self.operands: List[str] = operands
        self.at = at  # place in the computation's schedule order
        self.root = root
        self.called: List[Tuple[str, str]] = []  # (role, computation)
        self.op_name = ""
        self.index: Optional[int] = None
        self.concat = False
        if not rest:
            return
        if "op_name=" in rest:
            m = _OP_NAME.search(rest)
            if m:
                self.op_name = m.group(1).replace("\\'", "'")
        if opcode in ("get-tuple-element", "parameter"):
            m = re.match(r",? ?index=(\d+)", rest)
            if m:
                self.index = int(m.group(1))
        if opcode == "custom-call":
            self.concat = _CONCAT in rest
        elif "=" in rest and opcode not in ("constant", "parameter"):
            head = rest.split("metadata=", 1)[0]
            self.called = _CALLED.findall(head)
            m = _BRANCHES.search(head)
            if m:
                self.called += [
                    ("branch", n.strip().lstrip("%"))
                    for n in m.group(1).split(",") if n.strip()
                ]


class Computation:
    __slots__ = ("name", "entry", "instructions", "by_name", "users")

    def __init__(self, name: str, entry: bool):
        self.name = name
        self.entry = entry
        self.instructions: List[Instruction] = []
        self.by_name: Dict[str, Instruction] = {}
        self.users: Optional[Dict[str, List[Instruction]]] = None

    def users_of(self, name: str) -> List[Instruction]:
        if self.users is None:
            self.users = {}
            for ins in self.instructions:
                for operand in ins.operands:
                    self.users.setdefault(operand, []).append(ins)
        return self.users.get(name, [])

    def root(self) -> Optional[Instruction]:
        for ins in reversed(self.instructions):
            if ins.root:
                return ins
        return self.instructions[-1] if self.instructions else None


def _closing(text: str, at: int) -> int:
    """Index of the bracket that closes the ``(`` at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i
    raise ValueError(f"unbalanced brackets in an HLO line: {text[:120]!r}")


def _instruction(line: str, at: int) -> Optional[Instruction]:
    body = line.strip()
    root = body.startswith("ROOT ")
    if root:
        body = body[5:]
    if " = " not in body:
        return None
    lhs, rhs = body.split(" = ", 1)
    if rhs.startswith("("):  # a tuple's shape
        end = _closing(rhs, 0) + 1
    else:
        end = rhs.find(" ")
    if end <= 0:
        return None
    shape, rest = rhs[:end], rhs[end:].lstrip()
    opened = rest.find("(")
    if opened <= 0:
        return None
    opcode = rest[:opened]
    closed = _closing(rest, opened)
    inside = rest[opened + 1:closed]
    if opcode == "parameter":
        operands: List[str] = []
        tail = f"index={inside}" + rest[closed + 1:]
    else:
        tail = rest[closed + 1:]
        operands = (
            [] if opcode == "constant"
            else [m.group(1) for m in re.finditer(r"%([\w.\-]+)", inside)]
        )
    return Instruction(
        lhs.lstrip("%"), shape, opcode, operands, tail, at, root
    )


def parse(text: str) -> Dict[str, Computation]:
    """``{computation name: Computation}`` of an HLO module's printed
    form, each computation's instructions in the order printed (the
    schedule's, where the module says ``is_scheduled=true``)."""
    out: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " \t":
            if line.rstrip().endswith("{") and not line.startswith("HloModule"):
                entry = line.startswith("ENTRY ")
                name = _NAME.match(line[6:] if entry else line).group(1)
                current = out[name] = Computation(name, entry)
            elif line.startswith("}"):
                current = None
            continue
        if current is None:
            continue
        ins = _instruction(line, len(current.instructions))
        if ins is not None:
            current.instructions.append(ins)
            current.by_name[ins.name] = ins
    return out


def shape_bytes(shape: str) -> int:
    """Bytes of a printed shape (the arrays of a tuple added up)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def plain_shape(shape: str) -> str:
    """``f32[2560]{0:T(1024)S(1)}`` -> ``f32[2560]``: a shape as a
    profile's operation names print it."""
    return re.sub(r"\{[^{}]*\}", "", shape)


class _Module:
    """The call graph of a parsed module and the walks the rows need."""

    def __init__(self, computations: Dict[str, Computation]):
        self.computations = computations
        # computation -> (caller computation, instruction, role)
        self.callers: Dict[str, Tuple[Computation, Instruction, str]] = {}
        for comp in computations.values():
            for ins in comp.instructions:
                for role, called in ins.called:
                    self.callers.setdefault(called, (comp, ins, role))

    def under(self, comp: Computation) -> List[Dict[str, str]]:
        chain: List[Dict[str, str]] = []
        seen = set()
        while comp.name in self.callers and comp.name not in seen:
            seen.add(comp.name)
            parent, ins, _ = self.callers[comp.name]
            kind = ins.opcode if ins.opcode in ("while", "conditional") else "call"
            chain.append(
                {"kind": kind, "name": ins.name, "op_name": ins.op_name}
            )
            comp = parent
        chain.append({"kind": "entry", "name": comp.name, "op_name": ""})
        return chain

    # -- what a done is for ------------------------------------------------

    def consumers(self, comp: Computation, ins: Instruction,
                  depth: int = 0) -> List[Tuple[str, str]]:
        """``(instruction name, op_name)`` of what uses ``ins``."""
        out: List[Tuple[str, str]] = []
        if depth > 6:
            return out
        for user in comp.users_of(ins.name):
            if user.opcode in _THROUGH or user.concat:
                if user.opcode == "tuple" and user.root:
                    out.append((user.name, "(the computation's result)"))
                else:
                    out += self.consumers(comp, user, depth + 1)
            elif user.opcode == "fusion":
                inner = self._inside_fusion(user, ins.name)
                out += inner or [(user.name, user.op_name)]
            else:
                out.append((user.name, user.op_name))
        return out

    def _inside_fusion(self, fusion: Instruction,
                       operand: str) -> List[Tuple[str, str]]:
        """The instructions of a fused computation that read the
        parameter ``operand`` arrives as, with their own paths."""
        called = next((c for r, c in fusion.called if r == "calls"), None)
        comp = self.computations.get(called or "")
        if comp is None:
            return []
        found: List[Tuple[str, str]] = []
        for k, name in enumerate(fusion.operands):
            if name != operand:
                continue
            for ins in comp.instructions:
                if ins.opcode == "parameter" and ins.index == k:
                    found += [
                        (fusion.name, path) for _, path in
                        self.consumers(comp, ins, 5) if path
                    ]
        return found

    # -- what a start reads --------------------------------------------------

    @staticmethod
    def _origin(comp: Computation, name: str, index: Optional[int] = None
                ) -> Tuple[Optional[Instruction], Optional[int]]:
        """Back from ``name`` (element ``index`` of it, where given)
        through ``get-tuple-element``, ``bitcast``, ``opt-barrier`` and
        the ``tuple`` an element was put into: ``(the instruction that
        made the value, the element of it that is meant or None)``."""
        ins = comp.by_name.get(name)
        for _ in range(64):
            if ins is None or not ins.operands:
                break
            if ins.opcode == "get-tuple-element":
                index = ins.index
            elif ins.opcode == "tuple" and index is not None \
                    and index < len(ins.operands):
                ins, index = comp.by_name.get(ins.operands[index]), None
                continue
            elif ins.opcode not in ("bitcast", "opt-barrier"):
                break
            ins = comp.by_name.get(ins.operands[0])
        return ins, index

    def source(self, comp: Computation, name: str,
               index: Optional[int] = None) -> Dict[str, Any]:
        ins, index = self._origin(comp, name, index)
        if ins is None:
            return {}
        if ins.opcode != "parameter":
            return {"op": ins.name, "opcode": ins.opcode,
                    "op_name": ins.op_name}
        if comp.entry:
            return {"entry_parameter": ins.index, "name": ins.op_name,
                    "shape": plain_shape(ins.shape)}
        caller = self.callers.get(comp.name)
        out: Dict[str, Any] = {
            "parameter": index, "of": caller[1].name if caller else "",
            "shape": plain_shape(ins.shape) if index is None
            else _element(ins.shape, index),
        }
        # a loop that hands the element on unchanged: look above it
        if index is not None and caller is not None \
                and caller[1].opcode == "while" and caller[2] == "body" \
                and caller[1].operands and self._carried_unchanged(comp, index):
            above = self.source(caller[0], caller[1].operands[0], index)
            if above:
                out["from"] = above
        return out

    def _carried_unchanged(self, comp: Computation, index: int) -> bool:
        root = comp.root()
        if root is None or root.opcode != "tuple" or index >= len(root.operands):
            return False
        ins, element = self._origin(comp, root.operands[index])
        return ins is not None and ins.opcode == "parameter" and element == index

    # -- a done whose start lies in the previous iteration ------------------

    def hoisted_start(self, comp: Computation,
                      done: Instruction) -> Optional[Instruction]:
        """The start of a done that reads the loop's carried tuple:
        the ``*-start`` that feeds the same element of the body's
        root."""
        if not done.operands:
            return None
        ins, index = self._origin(comp, done.operands[0])
        root = comp.root()
        if ins is None or ins.opcode != "parameter" or index is None \
                or root is None or root.opcode != "tuple" \
                or index >= len(root.operands):
            return None
        ins, _ = self._origin(comp, root.operands[index])
        if ins is not None and ins.opcode.endswith("-start"):
            return ins
        return None


def _element(shape: str, index: int) -> str:
    """Element ``index`` of a printed tuple shape, plain."""
    if not shape.startswith("("):
        return plain_shape(shape)
    parts, depth, start = [], 0, 1
    for i, c in enumerate(shape):
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == "," and depth == 1:
            parts.append(shape[start:i])
            start = i + 1
    parts.append(shape[start:-1])
    if index >= len(parts):
        return ""
    return plain_shape(re.sub(r"/\*.*?\*/", "", parts[index]).strip())


def pairs(text: str) -> List[Dict[str, Any]]:
    """One row a ``*-done`` of the module ``text`` prints (module
    docstring), in the order printed."""
    module = _Module(parse(text))
    rows: List[Dict[str, Any]] = []
    for comp in module.computations.values():
        under = None
        for done in comp.instructions:
            if not done.opcode.endswith("-done"):
                continue
            if under is None:
                under = module.under(comp)
            start = comp.by_name.get(done.operands[0]) if done.operands else None
            hoisted = False
            if start is None or not start.opcode.endswith("-start"):
                start = module.hoisted_start(comp, done)
                hoisted = start is not None
            if start is None:
                room = None
            elif hoisted:
                room = (len(comp.instructions) - start.at - 1) + done.at
            else:
                room = done.at - start.at - 1
            used = module.consumers(comp, done)
            space = _SPACE.search(done.shape)
            rows.append({
                "name": done.name,
                "opcode": done.opcode,
                "shape": plain_shape(done.shape),
                "bytes": shape_bytes(done.shape),
                "space": int(space.group(1)) if space else None,
                "start": start.name if start is not None else None,
                "computation": comp.name,
                "under": under,
                "consumers": _unique(path for _, path in used if path),
                "consumer_ops": _unique(name for name, _ in used),
                "source": (
                    module.source(comp, start.operands[0])
                    if start is not None and start.operands else {}
                ),
                "room": room,
                "hoisted": hoisted,
            })
    return rows


def _unique(items, limit: int = 6) -> List[str]:
    out: List[str] = []
    for item in items:
        if item not in out:
            out.append(item)
    return out[:limit]


# -- the printed table ------------------------------------------------------


def loop_of(row: Dict[str, Any]) -> str:
    """The path of the innermost ``while`` a row sits under, as the
    text prints it; ``(entry)`` where it sits under none."""
    for level in row["under"]:
        if level["kind"] == "while":
            return level["op_name"] or level["name"]
    return "(entry)"


def _short(path: str, keep: int = 4) -> str:
    parts = [p for p in path.rstrip(":").split("/") if p]
    return "/".join(parts[-keep:]) if len(parts) > keep else "/".join(parts)


def source_text(source: Dict[str, Any]) -> str:
    if not source:
        return "-"
    if "entry_parameter" in source:
        return f"argument {source['entry_parameter']} {source['name']}"
    if "parameter" in source:
        text = (f"carried [{source['parameter']}] {source['shape']} "
                f"of {source['of']}")
        if source.get("from"):
            text += " <- " + source_text(source["from"])
        return text
    return f"{source.get('opcode')} {_short(source.get('op_name', ''))}".strip()


def format_table(rows: List[Dict[str, Any]], top: int = 0) -> str:
    """The rows by loop and consumer: one line a (loop, consumer,
    opcode) with the dones it holds, their bytes, and the least and
    greatest room; then ``top`` largest rows one by one."""
    groups: Dict[Tuple[str, str, str], List[Dict[str, Any]]] = {}
    for row in rows:
        consumer = _short(row["consumers"][0]) if row["consumers"] else "-"
        groups.setdefault((loop_of(row), consumer, row["opcode"]), []).append(row)
    lines = [f"{'loop':52s} {'consumer':44s} {'kind':11s} "
             f"{'n':>4s} {'bytes':>12s} {'room':>9s} hoisted"]
    for (loop, consumer, opcode), held in sorted(
        groups.items(), key=lambda kv: (kv[0][0], -sum(r["bytes"] for r in kv[1]))
    ):
        rooms = [r["room"] for r in held if r["room"] is not None]
        room = f"{min(rooms)}-{max(rooms)}" if rooms else "-"
        lines.append(
            f"{_short(loop, 6)[-52:]:52s} {consumer[-44:]:44s} {opcode:11s} "
            f"{len(held):>4d} {sum(r['bytes'] for r in held):>12d} "
            f"{room:>9s} {sum(1 for r in held if r['hoisted'])}"
        )
    if top:
        lines.append("")
        for row in sorted(rows, key=lambda r: -r["bytes"])[:top]:
            lines.append(
                f"{row['name']} {row['shape']} {row['bytes']} B, room "
                f"{row['room']}, in {_short(loop_of(row), 6)}, for "
                f"{', '.join(_short(c) for c in row['consumers']) or '-'}; "
                f"reads {source_text(row['source'])}"
            )
    return "\n".join(lines)
