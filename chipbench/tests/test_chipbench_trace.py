"""The reduction from a profiler trace to device numbers: on a trace
built by hand, and on one the profiler records here on the CPU."""
import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event


def _trace():
    ops = [Event("fusion.1", 0.0, 2.0), Event("fused_groupnorm.3", 2.0, 3.0),
           Event("fusion.2", 2.5, 4.0),                  # overlaps
           Event("flash_attention.1", 6.0, 7.0),
           Event("fusion.7", 9.0, 12.0)]                 # runs past the end
    spans = [Event("chipbench.serve", 0.0, 10.0),
             Event("chipbench.tick", 4.0, 5.5),
             Event("chipbench.stage", 5.5, 9.5)]
    return ops, spans, (0.0, 10.0)


def test_busy_union_and_idle_gaps():
    ops, _spans, win = _trace()
    assert tr.busy_intervals(ops, win) == [(0.0, 4.0), (6.0, 7.0),
                                           (9.0, 10.0)]
    assert tr.busy_seconds(ops, win) == pytest.approx(6.0)
    assert tr.idle_gaps(ops, win) == [(4.0, 6.0), (7.0, 9.0)]
    assert tr.idle_gaps([], win) == [win]


def test_gaps_are_named_by_the_innermost_host_span():
    ops, spans, win = _trace()
    assert tr.longest_gaps(ops, spans, win) == [("chipbench.tick", 2.0),
                                                ("chipbench.stage", 2.0)]
    assert tr.name_gap((10.5, 11.0), spans) == "untracked"


def test_kernel_time_by_name_and_top_ops():
    ops, _spans, win = _trace()
    gn = tr.matching(ops, win, "fused_groupnorm")
    assert [e.name for e in gn] == ["fused_groupnorm.3"]
    assert tr.matching(ops, win, "fusion") == ops[:1] + ops[2:3]
    top = dict(tr.top_ops(ops, win))
    assert top["fusion"] == pytest.approx(2.0 + 1.5 + 1.0)
    assert top["fused_groupnorm"] == pytest.approx(1.0)


def test_recorded_cpu_trace(tmp_path):
    """The profiler's own file: the benchmark's host spans come back with
    their nesting, and the window is the serve span."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("chipbench.serve"):
            for _ in range(3):
                with TraceAnnotation("chipbench.stage", tier=0, bucket=8):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = tr.read_xspace(str(tmp_path))
    names = [e.name for e in trace.host_spans]
    assert names.count("chipbench.stage") == 3
    lo, hi = trace.window("chipbench.serve")
    stages = [e for e in trace.host_spans if e.name == "chipbench.stage"]
    assert all(lo <= e.start and e.end <= hi for e in stages)
    assert dict(stages[0].stats).get("bucket") == "8"
