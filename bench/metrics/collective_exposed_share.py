"""Share of the traced window in which any collective (the exchange's
all-to-alls and ring passes, ZeRO's gathers and reductions, the loss's
sums) ran on a device and no other operation did; the device where that
is largest (``bench/exchange.py``).  None where no collective ran."""
from bench.exchange import exposed_share


def read(ctx):
    return exposed_share(ctx.trace)
