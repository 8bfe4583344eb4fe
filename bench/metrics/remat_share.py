"""Share of the device's busy time that the backward spends recomputing
what the remat policy did not save.

Own device time of the operations (that hold no other) whose HLO
``op_name`` lies under JAX's ``rematted_computation`` name-stack entry,
over the device's busy time in the traced window, averaged over the
cell's devices (``bench/scopes.py``).  None where the trace names no
operation at all.
"""
from bench import scopes


def read(ctx):
    names = scopes.op_names(ctx.trace)
    if not names:
        return None
    return 100.0 * scopes.busy_share(
        ctx.trace, names, lambda op: scopes.under(op, scopes.REMATTED))
