"""Device time the flash-attention kernel's roofline allows, over the
device time of its events in the trace (%). Every attention the window's
UNet evaluations ran counts, over unpadded rows, from shapes; where the
trace holds another number of kernel events than that, nothing is
read."""
from chipbench import flops, trace_reduce


def read(ctx):
    if not ctx.trace_window or ctx.peak is None:
        return None
    events = trace_reduce.matching(ctx.device_ops, ctx.trace_window,
                                   "flash_attention")
    calls = ctx.attention_calls()
    if not events or len(events) != sum(k for k, _c in calls):
        return None
    ideal = sum(k * flops.roofline_seconds(*flops.attention_cost(*c),
                                           ctx.peak) for k, c in calls)
    return 100.0 * ideal / sum(e.dur for e in events)
