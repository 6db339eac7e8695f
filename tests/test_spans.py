"""The span-and-counter recorder of the served path (serving/spans.py):
off, it records nothing and costs one attribute test; on, every batch of
``ClusterBackend.serve`` has its span with the sampler, discriminator
and routing spans inside it, every completed query its wall-clock
stamps, and the batching counters match a count by hand."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.config.base import DiffusionConfig
from repro.core.milp import AllocationPlan
from repro.serving import spans
from repro.serving.baselines import make_profiles
from repro.serving.cluster import ClusterBackend, ClusterRuntime
from repro.serving.controlplane import build_control_plane
from repro.serving.profiles import default_serving
from repro.serving.spans import RECORDER


@pytest.fixture
def recorder():
    """The process-wide recorder, cleared and switched off again after
    the test."""
    RECORDER.disable()
    RECORDER.reset()
    yield RECORDER
    RECORDER.disable()
    RECORDER.reset()


@dataclasses.dataclass
class _Burst:
    """``n`` queries arriving together at t = 0."""
    n: int
    duration_s: float = 1.0

    def arrivals(self, rng):
        return np.zeros(self.n)


class _StubCascade:
    def __init__(self, n: int = 2):
        self.n = n

    def stage_fns(self):
        return [(None, None, None)] * self.n


def _stub_backend(batch_choices=(1, 8), batch=1, n_queries=5):
    """One tier-0 slice at a fixed plan of ``batch``, stubbed execution,
    every query kept at tier 0."""
    sv = default_serving("sdturbo", num_workers=2,
                         batch_choices=batch_choices)
    rt = ClusterRuntime(_StubCascade(), sv)
    profiles = make_profiles(sv, 0)
    plan = AllocationPlan(workers=(1, 1), batches=(batch, 1),
                          thresholds=(0.5,), expected_latency=1.0,
                          feasible=True)
    control = build_control_plane(sv.cascade, sv, profiles,
                                  fixed_plan=plan)
    backend = ClusterBackend(rt, sv, profiles, seed=0, model_load_s=0.0,
                             confidence_fn=lambda n, b: np.ones(n))
    backend._run_stage = lambda sl, tier, n: (0.01, np.zeros((n, 1, 1, 1)))
    return backend, control, _Burst(n_queries)


def test_off_records_nothing(recorder):
    assert recorder.span("batch", tier=0) is spans.NULL
    with recorder.span("batch") as sp:
        assert sp.id is None
    recorder.count("rows_batched", 3)
    recorder.stamp(0, "submit")
    backend, control, trace = _stub_backend()
    r = backend.serve(control, trace)
    assert r.completed == 5
    assert recorder.spans == [] and recorder.counters == {} \
        and recorder.stamps == {}


def test_counters_match_a_count_by_hand(recorder):
    """A plan at batch 1 over a ready queue of 5, batch choices (1, 8):
    5 rows batched over 5 + 4 + 3 + 2 + 1 fillable."""
    recorder.enable()
    backend, control, trace = _stub_backend()
    backend.serve(control, trace)
    assert recorder.counters == {"rows_batched": 5, "rows_fillable": 15}
    batches = [s for s in recorder.spans if s.name == "batch"]
    assert [s.attr("ready") for s in batches] == [5, 4, 3, 2, 1]
    assert all(s.attr("cap") == 1 and s.attr("rows") == 1 for s in batches)


def test_fillable_rows_stop_at_the_largest_batch_choice(recorder):
    recorder.enable()
    backend, control, trace = _stub_backend(batch_choices=(1, 2), batch=2,
                                            n_queries=5)
    backend.serve(control, trace)
    # ready 5, 3, 1 at batch 2: fillable min(5, 2) + min(3, 2) + 1
    assert recorder.counters == {"rows_batched": 5, "rows_fillable": 5}


def test_tick_spans_hold_the_solve_and_the_enactment(recorder):
    recorder.enable()
    backend, control, trace = _stub_backend()
    backend.serve(control, trace)
    ticks = {s.id: s for s in recorder.spans if s.name == "tick"}
    assert ticks
    for name in ("plan", "apply"):
        kids = [s for s in recorder.spans if s.name == name]
        assert len(kids) == len(ticks)
        for k in kids:
            parent = ticks[k.parent]
            assert parent.start <= k.start <= k.end <= parent.end


def test_summary_self_time_leaves_out_children(recorder):
    recorder.enable()
    with recorder.span("batch"):
        with recorder.span("sample"):
            pass
        with recorder.span("score"):
            pass
    with recorder.span("batch"):
        pass
    s = recorder.summary()
    assert s["diffserve.batch"]["count"] == 2
    assert s["diffserve.sample"]["count"] == 1
    outer = [sp for sp in recorder.spans if sp.name == "batch"]
    kids = sum(sp.dur for sp in recorder.spans if sp.parent is not None)
    assert s["diffserve.batch"]["total_s"] == pytest.approx(
        sum(sp.dur for sp in outer))
    assert s["diffserve.batch"]["self_s"] == pytest.approx(
        sum(sp.dur for sp in outer) - kids)


def test_queue_waits_start_at_submit_or_deferral(recorder):
    recorder.enable()
    for qid, events in {
            0: [("submit", 1.0, None), ("batch", 3.0, 0),
                ("defer", 4.0, 1), ("batch", 4.5, 1), ("done", 5.0, 1)],
            1: [("submit", 1.0, None), ("batch", 2.0, 0),
                ("done", 2.5, 0)],
            2: [("submit", 1.0, None), ("batch", 6.0, 0)]}.items():
        for event, t, tier in events:
            recorder.stamp(qid, event, tier, t=t)
    # query 2 has not completed: it is left out
    assert recorder.queue_waits() == {0: [2.0, 1.0], 1: [0.5]}


def test_serve_records_while_a_profiler_trace_is_taken(recorder, tmp_path):
    backend, control, trace = _stub_backend()
    jax.profiler.start_trace(str(tmp_path))
    try:
        backend.serve(control, trace)
    finally:
        jax.profiler.stop_trace()
    assert not recorder.on
    assert recorder.counters["rows_batched"] == 5
    # switched on by hand, it is left on and not cleared
    recorder.enable()
    recorder.count("marker")
    backend, control, trace = _stub_backend()
    backend.serve(control, trace)
    assert recorder.on and recorder.counters["marker"] == 1
    assert recorder.counters["rows_batched"] == 10


def _toy_cascade():
    from repro.core.cascade import DiffusionCascade
    from repro.models.efficientnet import (DiscriminatorConfig,
                                           init_discriminator)
    from repro.models.unet import init_unet
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    stages = []
    for i in range(2):
        cfg = DiffusionConfig(
            name=f"span-tier{i}", image_size=8, in_channels=4,
            base_channels=8, channel_mults=(1,), num_res_blocks=1,
            attn_resolutions=(8,), num_heads=2, num_steps=1 + i,
            text_dim=8)
        stages.append((cfg, init_unet(keys[i], cfg)))
    dcfg = DiscriminatorConfig(in_channels=4, stem_channels=8,
                               stages=((8, 1, 1, 1),), head_channels=8,
                               gn_groups=4)
    return DiffusionCascade(stages, dcfg, init_discriminator(keys[2], dcfg),
                            batch_buckets=(1, 4))


def test_real_batches_nest_their_device_work(recorder):
    """Each batch's sampler, discriminator and routing spans lie inside
    its interval with its id as their ancestor; every completed query's
    stamps run submit <= batch start <= done."""
    cascade = _toy_cascade()
    sv = default_serving("sdturbo", num_workers=2, batch_choices=(1, 4),
                         batch_buckets=(1, 4))
    rt = ClusterRuntime(cascade, sv)
    profiles = make_profiles(sv, 0)
    plan = AllocationPlan(workers=(1, 1), batches=(4, 1),
                          thresholds=(0.5,), expected_latency=1.0,
                          feasible=True)
    control = build_control_plane(sv.cascade, sv, profiles,
                                  fixed_plan=plan)
    backend = ClusterBackend(rt, sv, profiles, seed=0, model_load_s=0.0,
                             prompt_len=4)
    recorder.enable()
    r = backend.serve(control, _Burst(6))
    assert r.completed == 6
    by_id = {s.id: s for s in recorder.spans}

    def batch_of(sp):
        while sp.parent is not None:
            sp = by_id[sp.parent]
            if sp.name == "batch":
                return sp
        return None

    batches = [s for s in recorder.spans if s.name == "batch"]
    assert sum(s.attr("rows") for s in batches) == r.tier_processed[0] \
        + r.tier_processed[1]
    for name, parent in (("sample", "batch"), ("sample.wait", "batch"),
                         ("score", "batch"), ("route", "batch"),
                         ("sample.prep", "sample"),
                         ("sample.launch", "sample"),
                         ("score.prep", "score"), ("score.launch", "score"),
                         ("score.fetch", "score")):
        for sp in (s for s in recorder.spans if s.name == name):
            up = by_id[sp.parent]
            assert up.name in (parent, "warm"), (name, up.name)
            assert up.start <= sp.start <= sp.end <= up.end
    samples = [s for s in recorder.spans if s.name == "sample"
               and by_id[s.parent].name == "batch"]
    assert sorted(batch_of(s).id for s in samples) == \
        sorted(b.id for b in batches)
    scored = [batch_of(s) for s in recorder.spans if s.name == "score"]
    assert {b.id for b in scored} == {b.id for b in batches
                                      if b.attr("tier") == 0}
    assert len(recorder.stamps) == 6
    for events in recorder.stamps.values():
        kinds = [e for e, *_ in events]
        assert kinds[0] == "submit" and kinds[-1] == "done"
        ts = [t for _e, t, *_ in events]
        assert ts == sorted(ts)
        for event, t, tier, span in events:
            if event == "batch":
                assert by_id[span].name == "batch"
                assert by_id[span].start == t
                assert by_id[span].attr("tier") == tier
