"""The flash forward kernel's share of its roofline.

The least time for the forward attention work the step requires (the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth),
over the summed device time of the forward-kernel events, all devices and
steps of the traced window.  Recomputed forwards count in the time, not in
the work.
"""
import re

from bench.flops import least_time

KERNEL = re.compile(r"fwd_kernel|flash_fwd", re.I)


def read(ctx):
    seconds = ctx.trace.op_seconds(KERNEL)
    if seconds <= 0:
        return None
    w = ctx.work
    return 100.0 * ctx.steps * least_time(w.attn_fwd_flops, w.attn_fwd_bytes,
                                          ctx.peak) / seconds
