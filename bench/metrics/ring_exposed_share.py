"""Share of the traced window in which a collective under the program's
``ring`` scope (the Double-Ring's K/V and dK/dV passes) ran on a device
and no other operation did; the device where that is largest
(``bench/exchange.py``).  0 is the paper's claim that the ring's
point-to-point traffic hides behind the attention kernels.  None where no
such collective ran."""
from bench.exchange import exposed_share

#: ``repro.runtime.spans.RING``
SCOPE = "ring"


def read(ctx):
    return exposed_share(ctx.trace, SCOPE)
