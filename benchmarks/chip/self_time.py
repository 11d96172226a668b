"""Self time of device operations, and the engine scope each lies under.

A device's ``XLA Ops`` line nests: a ``while`` holds the operations of
its body, a ``call`` those it calls, down to a kernel's custom-call.  A
sum of durations counts a nested nanosecond once per level.  An
operation's self time is the time in which it is the innermost
operation running on its device, so self times add up to the busy
time.

The engine names its plan operations with ``jax.named_scope``
(``fivm.fused_chain``, ``fivm.scatter``, ``fivm.gather``,
``fivm.base_bump``, ``fivm.indicator``).  A TPU trace does not carry
the name stack: an ``XLA Ops`` event is named by its HLO instruction
(``%dynamic-update-slice.13 = f32[8,8,4194304]... ``) and its stats
hold only device times.  The compiled stream program's HLO text does:
each instruction's ``metadata={op_name="jit(run_stream)/.../fivm.
base_bump/..."}``.  So :func:`hlo_scopes` maps instruction names to
scopes, and an op's scope is that of its instruction
(:func:`scope_seconds`).  No metric reads these yet: the harness keeps
neither the stream program's HLO nor the raw trace for its readers
(PERF.md, Open questions).
"""
from __future__ import annotations

import heapq
import re

#: an engine scope in a name stack
SCOPE = re.compile(r"fivm\.[a-z_]+")
#: a computation's header in an HLO text, and an instruction's line
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+) = (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
REFERENCE = re.compile(r"%([\w.\-]+)")


def self_times(ops) -> dict:
    """``{id(op): self seconds}`` over the ``ops`` of one device: each
    instant it is busy goes to the innermost op running then, the one
    begun last (of two begun together, the shorter), so each nanosecond
    counts once and self times add up to the busy union, ops that
    overlap without nesting included."""
    out = {id(op): 0.0 for op in ops}
    ops = sorted(ops, key=lambda o: o.start)
    edges = sorted({t for op in ops for t in (op.start, op.end)})
    running: list = []  # heap of (-start, duration, index)
    i = 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(ops) and ops[i].start <= lo:
            op = ops[i]
            heapq.heappush(running, (-op.start, op.end - op.start, i))
            i += 1
        while running and ops[running[0][2]].end <= lo:
            heapq.heappop(running)
        if running:
            out[id(ops[running[0][2]])] += hi - lo
    return out


def hlo_scopes(text: str) -> dict:
    """``{instruction name: innermost engine scope}`` of a compiled HLO
    text.  An instruction the compiler made (a layout change, the loop
    that a large reshape becomes) has no metadata; it takes the scope of
    its users where they agree, else that of the instruction that calls
    its computation (a ``while`` its body's)."""
    found: dict = {}  # name -> (computation, op_name or None, references)
    comp = None
    for line in text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m and comp is not None:
            op_name = OP_NAME.search(m.group(2))
            found[m.group(1)] = (comp, op_name and op_name.group(1),
                                 REFERENCE.findall(m.group(2)))
    users: dict = {}
    caller: dict = {}  # computation -> the instruction that calls it
    for name, (_, _, refs) in found.items():
        for ref in refs:
            if ref in found:
                users.setdefault(ref, []).append(name)
            else:
                caller.setdefault(ref, name)
    scope = {name: (SCOPE.findall(op_name) or [None])[-1]
             for name, (_, op_name, _) in found.items() if op_name}
    changed = True
    while changed:
        changed = False
        for name, (comp, _, _) in found.items():
            if name in scope:
                continue
            around = users.get(name) or [caller.get(comp)]
            if all(u in scope for u in around) \
                    and len({scope[u] for u in around}) == 1:
                scope[name] = scope[around[0]]
                changed = True
    return {name: sc for name, sc in scope.items() if sc}


def instruction(op) -> str:
    """The HLO instruction an op event ran (``%name = ...`` on a TPU,
    ``name`` on the CPU)."""
    return op.name.split(" ", 1)[0].lstrip("%")


def scope_seconds(ops, scopes: dict) -> dict:
    """``{scope: self seconds}`` of one device's ``ops`` under the
    instruction → scope map ``scopes`` (``None``: under no engine
    scope)."""
    own = self_times(ops)
    out: dict = {}
    for op in ops:
        scope = scopes.get(instruction(op))
        out[scope] = out.get(scope, 0.0) + own[id(op)]
    return out
