"""Which program scope each device op of a compiled executable ran under.

A profiler trace of the TPU names each device op by its HLO instruction
(``fusion.1``, ``copy.22``) and each executable by its module
(``jit_dwt_forward(<fingerprint>)``); it carries no scope.  The program
names its layers with ``jax.named_scope`` (``dwt.level0``,
``dwt.to_planes``, ``dwt.pad``, ...), which reach the compiled HLO as
each instruction's ``metadata={op_name="jit(dwt_forward)/dwt.level0/
dwt.to_planes/slice"}``.  :func:`record_op_scopes` reads that text once
per compile and keeps ``{module: {instruction: scope}}``, so a reader of
the trace can put each device op's time on a program layer.

Rules, in order, for one instruction of a computation that runs as
such (the entry, a loop body; not a fusion's body):

* its scope is the innermost ``dwt.*`` component of its ``op_name``;
* a fusion takes the scope that its fused instructions carry most often
  (its own metadata is only its root's);
* an instruction with no scope of its own, one the compiler inserted
  (a layout copy, a bitcast, an async slice and the concatenate it
  feeds), takes the scope of its first operand's producer;
* one still without a scope (it reads a parameter) takes the scope of
  its users, where they all share one.

An instruction left without a scope is absent from the map.
"""
from __future__ import annotations

import re
import threading
from collections import Counter
from typing import Dict, Optional

#: the prefix of the program's own scope names
PREFIX = "dwt."

_MODULE = re.compile(r"^HloModule ([\w.-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_OPERAND = re.compile(r"%([\w.-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

_LOCK = threading.Lock()
_SCOPES: Dict[str, Dict[str, str]] = {}


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``dwt.*`` component of an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return None


def _operands(text: str) -> list:
    """Operand names of ``opcode(...)``: everything up to the paren that
    closes the opcode's own (types carry parens of their own)."""
    depth = 0
    for i, ch in enumerate(text):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(text[:i])
    return _OPERAND.findall(text)


def parse(hlo_text: str):
    """``(module, {instruction: scope})`` of a compiled module's text."""
    m = _MODULE.match(hlo_text)
    module = m.group(1) if m else "?"
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            c = _COMPUTATION.match(line)
            cur = comps.setdefault(c.group(1), []) if c else None
            continue
        ins = _INSTRUCTION.match(line) if cur is not None else None
        if ins is None:
            continue
        name, rest = ins.groups()
        op = _OPCODE.search(rest)
        named = _OP_NAME.search(rest)
        calls = _CALLS.search(rest) if op and op.group(1) == "fusion" \
            else None
        cur.append((name,
                    _operands(rest[op.end() - 1:]) if op else [],
                    calls.group(1) if calls else None,
                    scope_of(named.group(1)) if named else None))
    fused = {c for ins in comps.values() for *_, c, _ in ins if c}

    def body_scopes(comp: str) -> Counter:
        n = Counter()
        for _, _, calls, scope in comps.get(comp, ()):
            if calls:
                n.update(body_scopes(calls))
            elif scope:
                n[scope] += 1
        return n

    out: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        users: Dict[str, list] = {}
        for name, operands, calls, scope in instrs:
            for o in operands:
                users.setdefault(o, []).append(name)
            if calls:
                common = body_scopes(calls).most_common(1)
                scope = common[0][0] if common else scope
            if scope is None and operands:
                scope = out.get(operands[0])
            if scope is not None:
                out[name] = scope
        for name, *_ in reversed(instrs):
            seen = {out.get(u) for u in users.get(name, ())}
            if name not in out and len(seen) == 1 and None not in seen:
                out[name] = seen.pop()
    return module, out


def record_op_scopes(hlo_text: str) -> str:
    """Parse one compiled module's HLO text and keep its map (a later
    compile under the same module name replaces it); returns the
    module name."""
    module, scopes = parse(hlo_text)
    with _LOCK:
        _SCOPES[module] = scopes
    return module


def op_scopes() -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: scope}}`` of every executable compiled
    under ``REPRO_TELEMETRY=spans`` in this process."""
    with _LOCK:
        return {k: dict(v) for k, v in _SCOPES.items()}


def clear() -> None:
    with _LOCK:
        _SCOPES.clear()
