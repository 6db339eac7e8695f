"""The served program's own spans, counters and query stamps
(``repro.serving.spans``), for the per-layer metrics that read them.

``ClusterBackend.serve`` records them while a profiler trace is being
taken, so a traced run holds them for its whole window and an untraced
run never switches the recorder on. A program without the recorder, or
a run in which it recorded nothing, gives nothing to read: every
function here then returns None.

The recorder keeps ``time.perf_counter`` seconds. ``trace_spans`` moves
them onto the trace's clock by the offset between the benchmark's own
host spans in the trace (``chipbench.stage``, ``chipbench.disc``) and
the program spans that each of them opens first (``sample``, ``score``):
the k-th call of each kind in the trace is the k-th in the record, since
the profiler starts just before ``serve`` and the recorder with it.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

from chipbench.trace_reduce import Event

# the benchmark's host span around each call, and the program span that
# call opens first
PAIRS = (("chipbench.stage", "sample"), ("chipbench.disc", "score"))


def recorder():
    """The program's recorder, or None where the program has none or it
    recorded nothing."""
    try:
        from repro.serving.spans import RECORDER
    except ImportError:
        return None
    if not (RECORDER.spans or RECORDER.counters or RECORDER.stamps):
        return None
    return RECORDER


def clock_offset(host_spans, spans) -> Optional[float]:
    """Seconds to add to a recorder time to put it on the trace's clock:
    the median over the paired calls of (host span start - program span
    start). None where no call pairs up, or the trace holds more calls of
    a kind than the record."""
    diffs: List[float] = []
    for host_name, name in PAIRS:
        traced = [e for e in host_spans if e.name == host_name]
        recorded = sorted((s for s in spans if s.name == name),
                          key=lambda s: s.start)
        if len(traced) > len(recorded):
            return None
        diffs += [e.start - s.start for e, s in zip(traced, recorded)]
    return statistics.median(diffs) if diffs else None


def trace_spans(ctx) -> Optional[List[Event]]:
    """Every recorded program span as an ``Event`` on the trace's clock,
    named ``diffserve.<name>``, with its attributes and its id and parent
    id as stats, in order of start."""
    rec = recorder()
    if rec is None or ctx.trace is None:
        return None
    offset = clock_offset(ctx.trace.host_spans, rec.spans)
    if offset is None:
        return None
    return sorted(
        (Event("diffserve." + s.name, s.start + offset, s.end + offset,
               tuple((k, str(v)) for k, v in s.attrs)
               + (("id", str(s.id)), ("parent", str(s.parent))))
         for s in rec.spans), key=lambda e: e.start)
