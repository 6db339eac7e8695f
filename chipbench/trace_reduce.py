"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is read into plain intervals first (``read_xspace``), so every
reduction below works on lists of ``Event`` and can be checked on a
trace built by hand. Times are seconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def dur(self) -> float:
        return self.end - self.start

    def stat(self, key: str) -> str:
        return dict(self.stats).get(key, "")


@dataclasses.dataclass
class Trace:
    """Device operations and programs per device, and the benchmark's
    host spans."""
    device_ops: Dict[str, List[Event]]
    host_spans: List[Event]
    device_modules: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)

    def window(self, span: Optional[str] = None
               ) -> Optional[Tuple[float, float]]:
        """(start, end) of the first host span called ``span``; without a
        name, from the start of the first of the benchmark's host spans to
        the end of the last one (the traced stretch of the window)."""
        if span is None:
            if not self.host_spans:
                return None
            return (min(ev.start for ev in self.host_spans),
                    max(ev.end for ev in self.host_spans))
        for ev in self.host_spans:
            if ev.name == span:
                return ev.start, ev.end
        return None


def _events(line, with_stats: bool = True) -> List[Event]:
    """A line's events; an HLO operation's name is its instruction name
    (the text before `` = ``, without ``%``)."""
    out = []
    for e in line.events:
        name = e.name.split(" = ", 1)[0].lstrip("%")
        stats = tuple((str(k), str(v)) for k, v in e.stats) \
            if with_stats else ()
        out.append(Event(name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, stats))
    return out


def read_xspace(log_dir: str, op_line: str = "XLA Ops",
                module_line: str = "XLA Modules") -> Trace:
    """Read the ``.xplane.pb`` the JAX profiler wrote under ``log_dir``:
    the operations and the programs of every ``/device:`` plane, and
    every host event whose name starts with ``chipbench.``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == op_line:
                    device_ops[plane.name] = _events(line, False)
                elif line.name == module_line:
                    modules[plane.name] = _events(line, False)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                tuple((str(k), str(v)) for k, v in e.stats))
                          for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
    spans.sort(key=lambda ev: ev.start)
    return Trace(device_ops, spans, modules)


def _clip(events: Iterable[Event], window: Tuple[float, float]
          ) -> List[Tuple[float, float]]:
    lo, hi = window
    return sorted((max(e.start, lo), min(e.end, hi)) for e in events
                  if e.end > lo and e.start < hi)


def busy_intervals(events: Iterable[Event], window: Tuple[float, float]
                   ) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside the window, merged."""
    merged: List[List[float]] = []
    for s, e in _clip(events, window):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(events: Iterable[Event], window: Tuple[float, float]
                 ) -> float:
    return sum(e - s for s, e in busy_intervals(events, window))


def idle_gaps(events: Iterable[Event], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """The stretches of the window in which no event runs."""
    gaps, t = [], window[0]
    for s, e in busy_intervals(events, window):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def name_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The innermost host span that holds the gap's midpoint, or
    ``untracked`` where none does."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Event] = None
    for sp in spans:
        if sp.start <= mid <= sp.end and (best is None or sp.dur < best.dur):
            best = sp
    return best.name if best is not None else "untracked"


def longest_gaps(events: Sequence[Event], spans: Sequence[Event],
                 window: Tuple[float, float], k: int = 10
                 ) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps, each named by the host span it falls
    in."""
    gaps = sorted(idle_gaps(events, window), key=lambda g: g[0] - g[1])[:k]
    return [(name_gap(g, spans), g[1] - g[0]) for g in gaps]


def op_kind(e: Event) -> str:
    """An operation's name without its instance number (``fusion.12`` ->
    ``fusion``), so repeated executions of one kind add up."""
    head, _, tail = e.name.rpartition(".")
    return head if head and tail.isdigit() else e.name


def top_ops(events: Iterable[Event], window: Tuple[float, float],
            k: int = 10, key: Callable[[Event], str] = op_kind
            ) -> List[Tuple[str, float]]:
    """The ``k`` operation kinds with the most device seconds in the
    window."""
    total: Dict[str, float] = defaultdict(float)
    lo, hi = window
    for e in events:
        if e.end > lo and e.start < hi:
            total[key(e)] += min(e.end, hi) - max(e.start, lo)
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def spans_in(spans: Sequence[Event], window: Tuple[float, float],
             name: str) -> List[Event]:
    """Host spans called ``name`` that lie wholly inside the window."""
    return [s for s in spans if s.name == name
            and s.start >= window[0] and s.end <= window[1]]


def matching(events: Iterable[Event], window: Tuple[float, float],
             prefix: str) -> List[Event]:
    """Events inside the window whose name starts with ``prefix`` (an HLO
    instruction is named after its kernel, then a number)."""
    lo, hi = window
    return [e for e in events
            if e.start >= lo and e.end <= hi and e.name.startswith(prefix)]
