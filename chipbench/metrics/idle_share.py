"""Share of the traced window in which no operation ran on the device
(%)."""
from chipbench import trace_reduce


def read(ctx):
    ops = ctx.device_ops
    if not ops or not ctx.trace_window:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - trace_reduce.busy_seconds(ops, (lo, hi))
                    / (hi - lo))
