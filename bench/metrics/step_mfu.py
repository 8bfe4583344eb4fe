"""Share of the chips' bf16 peak that the step's required FLOPs reach.

Required FLOPs (``bench/flops.py``): 6 * N_matmul * tokens plus causal
attention's forward and twice that for its backward; recomputation is not
counted.  Over the traced window's wall time, all of its steps.
"""


def read(ctx):
    rate = ctx.work.flops * ctx.steps / ctx.trace.window_s
    return 100.0 * rate / (ctx.chips * ctx.peak.bf16_flops)
