"""Dataframe-operator scopes of a compiled program's HLO instructions.

``planner.physical.eval_node`` evaluates each plan node under
``jax.named_scope(node.op)`` and ``dataframe.shuffle.shuffle`` runs under
``jax.named_scope("shuffle")``.  XLA keeps the name stack in each
instruction's ``metadata={op_name="..."}``, so the optimized HLO text of a
program says which operator every device op it runs belongs to: the map a
profiler trace's per-op events (named by instruction) are attributed with.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

#: the scope the shuffle body runs under, wherever it is called from
SHUFFLE = "shuffle"
#: scopes ``eval_node`` opens: one per logical plan operator
OPERATOR_SCOPES = frozenset({
    "scan", "noop", "project", "filter", "with_columns", "add_scalar",
    "recode", "shuffle", "join", "groupby", "sort", "limit"})

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")


def scope_of(op_name: str) -> str:
    """The dataframe scope of one ``op_name`` (name stack, then the
    primitive): ``shuffle`` anywhere in the stack wins, else the operator
    scope in it, else ""."""
    stack = op_name.split("/")[:-1]
    if SHUFFLE in stack:
        return SHUFFLE
    return next((c for c in stack if c in OPERATOR_SCOPES), "")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> dataframe scope ("" for none) of every
    instruction in ``hlo_text`` (``Compiled.as_text()``).  An instruction
    without an ``op_name`` (a fusion XLA built) takes the first scope
    found among the instructions of the computations it calls."""
    comps: Dict[str, List[Tuple[str, Optional[str], List[str]]]] = {}
    body: Optional[List] = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and body is not None:
            name, rest = m.groups()
            op = _OP_NAME.search(rest)
            body.append((name, op.group(1) if op else None,
                         _CALLS.findall(rest)))
            continue
        m = _COMPUTATION.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
    memo: Dict[str, str] = {}

    def called_scope(callees: List[str], seen: frozenset) -> str:
        for c in callees:
            if c in seen:
                continue
            for name, op, calls in comps.get(c, ()):
                s = (scope_of(op) if op is not None
                     else called_scope(calls, seen | {c}))
                if s:
                    return s
        return ""

    for instrs in comps.values():
        for name, op, calls in instrs:
            memo[name] = (scope_of(op) if op is not None
                          else called_scope(calls, frozenset()))
    return memo


def merge_scopes(maps: Iterable[Dict[str, str]]) -> Dict[str, str]:
    """The union of several programs' maps; a name they scope differently
    maps to ""."""
    out: Dict[str, str] = {}
    for m in maps:
        for name, scope in m.items():
            out[name] = scope if out.get(name, scope) == scope else ""
    return out
