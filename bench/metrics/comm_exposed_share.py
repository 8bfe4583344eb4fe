"""Share of the traced window in which a collective (all-to-all,
collective-permute, all-gather, reduce-scatter, all-reduce) ran on a
device and no other operation did; the device where that is largest.
None where no collective ran."""
from bench.trace import COLLECTIVE


def read(ctx):
    t = ctx.trace
    if not t.op_seconds(COLLECTIVE):
        return None
    return 100.0 * max(t.exposed_collective_s(d) for d in t.devices) \
        / t.window_s
