"""Exposed time of the program's collectives, all of them or those under
one of its name scopes (``repro.runtime.spans``): the 2D-Attention
exchange, read part by part, and everything the step moves between chips.

An operation is a collective where its name says so
(``bench.trace.COLLECTIVE``, or the TPU compiler's fused
``async-collective-start``/``-done``, which carry ZeRO's gathers) or, on a
TPU, where its instruction's opcode does: JAX names the instruction of its
``all_to_all`` primitive ``all_to_all.<n>``, which only the opcode
(``... all-to-all(%x), ...``) shows.  A collective counts where its
``op_name`` lies under the scope (``bench/scopes.py``); it is exposed where
it runs on a device and no operation that is not a collective does.
"""
from __future__ import annotations

import re

from bench import scopes
from bench.trace import COLLECTIVE, Op, Summary, clip, length, minus, union

#: a collective's opcode in an HLO instruction's text: after the shape,
#: before its operands (an operand is ``%<name>``, never a bare opcode)
OPCODE = re.compile(r"\s(?:all-to-all|all-gather|reduce-scatter|all-reduce|"
                    r"collective-permute|send|recv)(?:-start|-done)?\(")
#: the TPU compiler's async collective fusions: their opcode is ``fusion``
ASYNC = re.compile(r"^async-collective-(?:start|done)\b")


def is_collective(o: Op) -> bool:
    return bool(COLLECTIVE.search(o.name) or ASYNC.search(o.name)
                or OPCODE.search(o.detail))


def exposed_share(t: Summary, scope: str | None = None) -> float | None:
    """Per cent of the window in which a collective (under ``scope``, or
    any where ``scope`` is None) ran on a device and nothing else did, on
    the device where that is largest; None where no such collective ran in
    the window."""
    names = scopes.op_names(t) if scope is not None else {}
    found, worst = False, 0
    for d in t.devices:
        comm, comp = [], []
        for o in t.leaves[d]:
            if not is_collective(o):
                comp.append((o.start_ns, o.end_ns))
            elif scope is None or scopes.under(names.get(o.name, ""), scope):
                comm.append((o.start_ns, o.end_ns))
        comm = clip(union(comm), t.lo, t.hi)
        if not comm:
            continue
        found = True
        exposed = minus(comm, clip(union(comp), t.lo, t.hi))
        worst = max(worst, length(exposed))
    return 100.0 * worst / (t.hi - t.lo) if found else None
