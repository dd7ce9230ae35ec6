"""Collective operations in a device trace, for the collectives layer's
metrics (``collective_exposed_pct``, ``lloyd_reduce_share_pct``).

On the chip an event of the ``XLA Ops`` line is named by its HLO text
(``%all-reduce.4 = (f32[1024,256]{...}, ...) all-reduce(...)``), so a
collective is found by its instruction name or opcode — ``all-reduce``,
``all-gather``, ``collective-permute``, ``reduce-scatter``, ``all-to-all``,
with ``-start`` / ``-done`` for the asynchronous forms — never by a
``named_scope`` (PERF.md section 6, PR 25) and never by a substring of the
whole text, which also names the operands (a fusion that reads
``%all-reduce.4`` is no collective).

The same line holds operations that ENCLOSE others: a ``%while`` and its
``%body``, a ``%call``.  Held against them every collective inside a loop
would count as hidden, so the arithmetic here runs on the LEAVES of the
line: events that enclose no other event.  The exposure itself is
``trace_reduce.Trace.exposed_s``, unchanged, on a trace of leaves.
"""

import re

from lib import trace_reduce

COLLECTIVES = (
    "all-reduce", "all-gather", "collective-permute", "reduce-scatter",
    "all-to-all",
)
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def _is(word):
    return any(
        word == c or word.startswith((c + ".", c + "-start", c + "-done"))
        for c in COLLECTIVES
    )


def is_collective(name):
    """Whether an ``XLA Ops`` event name is a collective operation: by
    the instruction's own name (what stands before `` = ``), or by its
    opcode (the first lower-case word that opens a bracket after the
    result's shape; layouts spell their tiles ``T(8,128)`` in capitals)."""
    head, _, rest = name.partition(" = ")
    if _is(head.lstrip("%").strip()):
        return True
    m = _OPCODE.search(" " + rest) if rest else None
    return bool(m and _is(m.group(1)))


def leaves(ops):
    """The events of ``[(start, end, name)]`` that enclose no other one."""
    out, stack = [], []  # stack: [start, end, name, encloses another]
    for a, b, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            if not top[3]:
                out.append(tuple(top[:3]))
        if stack and b <= stack[-1][1]:
            stack[-1][3] = True
        stack.append([a, b, name, False])
    out.extend(tuple(t[:3]) for t in stack if not t[3])
    return out


def leaf_trace(tr):
    """``tr`` with every device's operations cut down to their leaves."""
    return trace_reduce.Trace(
        {dev: leaves(ops) for dev, ops in tr.device_ops.items()},
        tr.host_spans, tr.window,
    )


def collective_intervals(tr, dev):
    """Merged (start, end) of the collective leaves of one device."""
    return trace_reduce.merge(
        (a, b) for a, b, n in leaves(tr.device_ops[dev]) if is_collective(n)
    )


def compute_s(tr, dev):
    """Seconds of the window in which a leaf of one device that is NO
    collective ran: the device's own work, without what it spent waiting
    for the others inside a collective."""
    own = trace_reduce.merge(
        (a, b) for a, b, n in leaves(tr.device_ops[dev]) if not is_collective(n)
    )
    return trace_reduce.total(trace_reduce.clip(own, *tr.window))
