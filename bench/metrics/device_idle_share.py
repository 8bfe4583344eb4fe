"""Share of the traced window in which no operation ran on a device,
averaged over the cell's devices."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
