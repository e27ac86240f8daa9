"""Device self time per named scope of one program, from a reduced trace.

``devtrace.reduce`` sums each op's device time over the traced window
by the op's HLO instruction name (``ops_s``).  Those sums nest: a
``while`` op's time holds the ops of its body, a ``conditional``'s the
ops of its branches.  The program's compiled HLO text says which
computations each instruction calls and, in each instruction's
``op_name`` metadata, the named scopes it was traced under.  So an
instruction's self time is its own sum less the sums of the instructions
in the computations it calls, and a scope's self time is the sum of the
self times of its instructions; the self times of a program add up to
the time of its outermost ops.  (The device's op events carry no
``op_name`` of their own on a v5e: the map goes through the HLO text.)

An instruction's scope is the innermost ``dsfd.*``, ``fleet.*`` or
``fd.*`` component of its ``op_name``; an instruction with no scope in
its metadata (a copy the compiler added, say) takes the scope of the
instruction that calls its computation, and one outside every scope
counts as ``unscoped``.
"""

from __future__ import annotations

import re
from collections import defaultdict

UNSCOPED = "unscoped"
_SCOPE = re.compile(r"^(?:dsfd|fleet|fd)\.[a-z_]+$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope(op_name: str):
    for part in reversed(op_name.split("/")):
        if _SCOPE.match(part):
            return part
    return None


def parse(hlo_text: str) -> dict:
    """``{instruction: (scope, [instructions it calls])}`` of a compiled
    HLO module's text; names without the leading ``%``."""
    comps = defaultdict(list)          # computation -> its instructions
    own, calls = {}, {}
    comp = None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h and not line[:1].isspace():
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(1), m.group(2)
        comps[comp].append(name)
        op = _OP_NAME.search(rest)
        own[name] = _scope(op.group(1)) if op else None
        callees = _CALLS.findall(rest)
        for group in _CALL_LISTS.findall(rest):
            callees += [c.strip().lstrip("%") for c in group.split(",")
                        if c.strip()]
        calls[name] = callees
    caller = {}
    for name, callees in calls.items():
        for c in callees:
            for child in comps.get(c, ()):
                caller.setdefault(child, name)

    def scope(name, seen=()):
        if own.get(name) is not None or name not in caller or name in seen:
            return own.get(name)
        return scope(caller[name], seen + (name,))

    return {name: (scope(name),
                   [child for c in calls[name] for child in comps.get(c, ())])
            for name in own}


def self_times(ops_s: dict, hlo: dict) -> dict:
    """Self time per scope (and ``UNSCOPED``) of the program ``hlo``
    (from ``parse``) in a reduced trace's ``ops_s``; ops the program does
    not hold are left out."""
    times = {k.lstrip("%"): v for k, v in ops_s.items()}
    out = defaultdict(float)
    for name, (scope, children) in hlo.items():
        if name not in times:
            continue
        own = times[name] - sum(times.get(c, 0.0) for c in children)
        # an op already running when the profiler started has no event,
        # while the ops inside it that began later have theirs: its self
        # time is then lost (read as 0), and its children keep theirs
        out[scope or UNSCOPED] += max(own, 0.0)
    return dict(out)


def per_tick_ms(summary: dict, hlo_text: str, tick_s: float) -> dict:
    """Self time per scope in ms per tick: each scope's share of the
    program's op time in the traced window, times the program's device
    time per tick (``tick_s``).  The window holds a few whole executions
    and the end of the one in flight when it opened."""
    st = self_times(summary["ops_s"], parse(hlo_text))
    total = sum(st.values())
    if total <= 0:
        return {}
    return {k: 1e3 * tick_s * v / total for k, v in st.items()}
