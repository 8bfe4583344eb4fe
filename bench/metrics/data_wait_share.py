"""Share of the traced window in which the first device runs no
operation and the host is inside the trainer's ``train.data`` span: the
time the step waits for its batch.

None where the program names a data span (``repro.runtime.spans``) and
the trace holds none.  A program that names none reads 0: no time lies
under a span it does not open.
"""
from bench import scopes
from bench.trace import clip, length, minus, union


def read(ctx):
    t = ctx.trace
    spans = scopes.program_spans()
    if spans is None:
        return 0.0
    fetch = union((h.start_ns, h.end_ns) for h in t.host
                  if h.name == spans.DATA)
    if not fetch:
        return None
    idle = [(t.lo, t.hi)]
    if t.devices:
        idle = minus(idle, t.busy(t.devices[0]))
    waiting = minus(idle, minus(idle, clip(fetch, t.lo, t.hi)))
    return 100.0 * length(waiting) / (t.hi - t.lo)
