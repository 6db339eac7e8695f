"""Device-idle milliseconds per batch: the idle time of the device inside
the program's ``diffserve.batch`` spans that lie wholly in the traced
stretch, over the number of those spans. The spans come from the
program's recorder, moved onto the trace's clock."""
import bisect

from chipbench import program_spans, trace_reduce


def read(ctx):
    ops, window = ctx.device_ops, ctx.trace_window
    spans = program_spans.trace_spans(ctx)
    if not ops or not window or spans is None:
        return None
    batches = trace_reduce.spans_in(spans, window, "diffserve.batch")
    if not batches:
        return None
    gaps = trace_reduce.idle_gaps(ops, window)
    starts = [g[0] for g in gaps]
    idle = 0.0
    for b in batches:
        # gaps are sorted and disjoint: those ending after the batch
        # starts, up to the first starting after it ends
        i = max(bisect.bisect_right(starts, b.start) - 1, 0)
        for lo, hi in gaps[i:bisect.bisect_left(starts, b.end)]:
            idle += max(0.0, min(hi, b.end) - max(lo, b.start))
    return 1e3 * idle / len(batches)
