"""Spans and counters of the served path: one process-wide recorder,
off unless switched on.

Off, ``RECORDER.span(name, **attrs)`` returns one shared null context
manager and ``count``/``stamp`` return at once: the served path pays one
attribute test. On, each span does two things:

* opens ``jax.profiler.TraceAnnotation("diffserve.<name>", **attrs)``,
  so a profiler trace shows it on the device trace's clock and device
  idle gaps can be named by the host work that holds them;
* keeps ``Span(id, parent, name, start, end, attrs)`` in memory on
  ``time.perf_counter``, over the whole run and not only the stretch a
  profiler records.

Counters are named integers. Stamps are per-query wall-clock events
keyed by qid: ``submit`` (taken in), ``batch`` (the start of each batch
the query rides, with the batch span's id and tier), ``defer`` (sent to
a later tier) and ``done``.

Switch it on with ``enable()`` (``reset()`` first to drop an earlier
record), off with ``disable()``; the record stays readable. While a
profiler trace is being taken, ``ClusterBackend.serve`` switches it on
for its own duration if it is off (``follow_profiler``).

The served path is single-threaded; the recorder keeps one stack of open
spans.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

PREFIX = "diffserve."


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    parent: Optional[int]
    name: str                # without the ``diffserve.`` prefix
    start: float             # time.perf_counter seconds
    end: float
    attrs: Tuple[Tuple[str, object], ...] = ()

    @property
    def dur(self) -> float:
        return self.end - self.start

    def attr(self, key: str, default=None):
        return dict(self.attrs).get(key, default)


class _Null:
    """The context every span is while the recorder is off."""
    id = None
    start = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _Null()


class _Open:
    """A span while it is open."""
    __slots__ = ("rec", "name", "attrs", "id", "parent", "start", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        rec = self.rec
        self.id = rec._next_id
        rec._next_id += 1
        self.parent = rec._stack[-1] if rec._stack else None
        rec._stack.append(self.id)
        self._ann = TraceAnnotation(PREFIX + self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self.rec
        rec._stack.pop()
        rec.spans.append(Span(self.id, self.parent, self.name, self.start,
                              end, tuple(self.attrs.items())))
        return False


class Recorder:
    def __init__(self):
        self.on = False
        self.reset()

    def reset(self) -> None:
        """Drop every span, counter and stamp."""
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        # qid -> [(event, perf_counter seconds, tier, batch span id)]
        self.stamps: Dict[int, List[Tuple[str, float, Optional[int],
                                          Optional[int]]]] = {}
        self._stack: List[int] = []
        self._next_id = 0

    def enable(self) -> None:
        self.on = True

    def disable(self) -> None:
        self.on = False

    @contextlib.contextmanager
    def follow_profiler(self):
        """Record the block afresh if the recorder is off and a profiler
        trace is being taken, and switch it off again after; otherwise
        leave it as it is."""
        from jax.profiler import TraceAnnotation
        follow = not self.on and TraceAnnotation.is_enabled()
        if follow:
            self.reset()
            self.enable()
        try:
            yield
        finally:
            if follow:
                self.disable()

    def span(self, name: str, **attrs):
        """A context manager timing ``diffserve.<name>``; ``NULL`` while
        off. The open span's ``id`` and ``start`` are readable inside."""
        if not self.on:
            return NULL
        return _Open(self, name, attrs)

    def count(self, name: str, n: int = 1) -> None:
        if not self.on:
            return
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def stamp(self, qid: int, event: str, tier: Optional[int] = None,
              span: Optional[int] = None, t: Optional[float] = None) -> None:
        """Stamp query ``qid`` with ``event`` at ``t`` (now by default)."""
        if not self.on:
            return
        self.stamps.setdefault(qid, []).append(
            (event, time.perf_counter() if t is None else t, tier, span))

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per span name: its count, total seconds, and self seconds (its
        duration less what its direct children cover)."""
        child_s: Dict[int, float] = {}
        for sp in self.spans:
            if sp.parent is not None:
                child_s[sp.parent] = child_s.get(sp.parent, 0.0) + sp.dur
        out: Dict[str, Dict[str, float]] = {}
        for sp in self.spans:
            row = out.setdefault(PREFIX + sp.name,
                                 {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += sp.dur
            row["self_s"] += sp.dur - child_s.get(sp.id, 0.0)
        return out

    def queue_waits(self) -> Dict[int, List[float]]:
        """Per tier, the wall seconds each visit of a completed query
        waited: from ``submit`` (or the ``defer`` that sent it to the
        tier) to the start of the batch that served it there."""
        out: Dict[int, List[float]] = {}
        for events in self.stamps.values():
            if not events or events[-1][0] != "done":
                continue
            since = None
            for event, t, tier, _span in events:
                if event in ("submit", "defer"):
                    since = t
                elif event == "batch" and since is not None:
                    out.setdefault(tier, []).append(t - since)
                    since = None
        return out


RECORDER = Recorder()
