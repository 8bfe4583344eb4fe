"""Share of the traced window in which a collective under the program's
``ulysses_a2a`` scope (Ulysses' head <-> sequence all-to-alls, forward and
backward) ran on a device and no other operation did; the device where
that is largest (``bench/exchange.py``).  None where no such collective
ran."""
from bench.exchange import exposed_share

#: ``repro.runtime.spans.ULYSSES_A2A``
SCOPE = "ulysses_a2a"


def read(ctx):
    return exposed_share(ctx.trace, SCOPE)
