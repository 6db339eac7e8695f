"""Median wall milliseconds a query waited at a tier: over every tier
visit of every completed query, from its ``submit`` stamp (or the
``defer`` that sent it on) to the start of the ``diffserve.batch`` span
that served it there."""
import statistics

from chipbench import program_spans


def read(ctx):
    rec = program_spans.recorder()
    if rec is None:
        return None
    waits = [w for tier in rec.queue_waits().values() for w in tier]
    return 1e3 * statistics.median(waits) if waits else None
