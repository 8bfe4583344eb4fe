"""The flash backward kernels' (dq and dk/dv) share of their roofline.

The least time for the backward attention work the step requires (twice
the forward's FLOPs; the kernels' recompute of the scores is not counted),
over the summed device time of the dq and dk/dv kernel events, all devices
and steps of the traced window.
"""
import re

from bench.flops import least_time

KERNEL = re.compile(r"dq_kernel|dkv_kernel|flash_dq|flash_dkv", re.I)


def read(ctx):
    seconds = ctx.trace.op_seconds(KERNEL)
    if seconds <= 0:
        return None
    w = ctx.work
    return 100.0 * ctx.steps * least_time(w.attn_bwd_flops, w.attn_bwd_bytes,
                                          ctx.peak) / seconds
