"""Device milliseconds of the fused GroupNorm kernel per UNet evaluation:
the kernel's events in the traced stretch (the discriminator's
GroupNorms among them) over the UNet evaluations the traced sampler
calls ran. Where the trace holds another number of kernel events than
those calls ran, nothing is read.

A share of the HBM roofline would read above 100% here: XLA prefetches
many of the kernel's operands into VMEM with asynchronous copies that
overlap earlier operations, so the kernel's own events do not carry all
of its memory traffic."""
from chipbench import trace_reduce


def read(ctx):
    if not ctx.trace_window:
        return None
    events = trace_reduce.matching(ctx.device_ops, ctx.trace_window,
                                   "fused_groupnorm")
    calls = ctx.groupnorm_calls()
    if not events or len(events) != sum(k for k, _c in calls):
        return None
    evals = sum(ctx.steps(tier) for tier, _b, _sp in
                ctx.traced_stage_calls())
    return 1e3 * sum(e.dur for e in events) / evals
