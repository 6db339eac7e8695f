"""The metrics that read the program's own spans and counters
(``repro.serving.spans``): moving recorded spans onto the trace's clock,
naming idle gaps by them, and a traced toy run with the recorder on and
off."""
import contextlib
import dataclasses

import pytest

from chipbench import program_spans, trace_reduce as tr
from chipbench.tests import toybench
from chipbench.trace_reduce import Event
from repro.serving.spans import RECORDER, Span


@pytest.fixture
def recorder():
    RECORDER.disable()
    RECORDER.reset()
    yield RECORDER
    RECORDER.disable()
    RECORDER.reset()


@dataclasses.dataclass
class _Ctx:
    trace: tr.Trace


def test_gaps_inside_program_spans_take_their_names(recorder):
    """A hand-built trace whose benchmark spans sit 100 s after the
    recorder's clock: the recorded spans land on the trace's clock and
    the gaps inside them are named by the innermost one."""
    ops = [Event("fusion.1", 100.0, 101.0), Event("fusion.2", 101.2, 103.0),
           Event("fusion.3", 103.5, 104.0)]
    host = [Event("chipbench.stage", 100.0, 101.3),
            Event("chipbench.disc", 101.3, 104.0)]
    recorder.spans = [
        Span(0, None, "batch", 0.0, 4.0),
        Span(1, 0, "sample", 0.0, 1.25),
        Span(2, 1, "sample.prep", 0.0, 0.1),
        Span(3, 0, "score", 1.3, 4.0),
        Span(4, 3, "score.launch", 1.3, 3.2),
        Span(5, 3, "score.fetch", 3.2, 4.0)]
    assert program_spans.clock_offset(host, recorder.spans) == \
        pytest.approx(100.0)
    trace = tr.Trace({"/device:0": ops}, host)
    prog = program_spans.trace_spans(_Ctx(trace))
    assert [e.name for e in prog][:3] == ["diffserve.batch",
                                          "diffserve.sample",
                                          "diffserve.sample.prep"]
    assert prog[0].start == pytest.approx(100.0)
    assert prog[4].stat("parent") == "3"
    win = trace.window()
    assert tr.longest_gaps(ops, host + prog, win) == [
        ("diffserve.score.fetch", pytest.approx(0.5)),
        ("diffserve.sample", pytest.approx(0.2))]
    # the benchmark's own names where no program span holds the gap
    assert tr.longest_gaps(ops, host, win)[0][0] == "chipbench.disc"


def test_nothing_to_read_without_a_record(recorder):
    trace = tr.Trace({}, [Event("chipbench.stage", 0.0, 1.0)])
    assert program_spans.recorder() is None
    assert program_spans.trace_spans(_Ctx(trace)) is None
    # more traced calls than recorded ones cannot be paired
    recorder.spans = [Span(0, None, "score", 0.0, 1.0)]
    assert program_spans.clock_offset(
        [Event("chipbench.disc", 0.0, 1.0)] * 2, recorder.spans) is None


def test_recorded_trace_keeps_the_benchmark_spans_and_aligns(recorder,
                                                             tmp_path):
    """On a trace the profiler records here: the program's spans leave
    ``host_spans`` and ``window()`` as the benchmark's spans alone make
    them, and the recorder's clock moves onto the trace's within each
    call."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    recorder.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with recorder.span("batch", tier=0):
                with TraceAnnotation("chipbench.stage", tier=0, bucket=8):
                    with recorder.span("sample"):
                        f(x).block_until_ready()
                with TraceAnnotation("chipbench.disc", bucket=8):
                    with recorder.span("score"):
                        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        recorder.disable()
    trace = tr.read_xspace(str(tmp_path))
    assert {e.name for e in trace.host_spans} == {"chipbench.stage",
                                                  "chipbench.disc"}
    stages = [e for e in trace.host_spans if e.name == "chipbench.stage"]
    discs = [e for e in trace.host_spans if e.name == "chipbench.disc"]
    assert trace.window() == (stages[0].start, discs[-1].end)
    prog = program_spans.trace_spans(_Ctx(trace))
    for host, name in ((stages, "diffserve.sample"),
                       (discs, "diffserve.score")):
        inner = [e for e in prog if e.name == name]
        for h, p in zip(host, inner):
            assert h.start - 1e-3 <= p.start and p.end <= h.end + 1e-3


def test_traced_run_reports_program_metrics():
    """The traced toy run of the deferring cell reads the program's
    batching counter and queue stamps; the CPU trace has no device plane,
    so the device-idle reading is left out."""
    line, _out, _err = toybench.run("c2-hard-backlog", trace=True,
                                    impl="ref")
    assert line["correct"]
    got = line["metrics"]
    assert {"queue_fill", "queue_wait_ms"} <= set(got)
    assert "batch_idle_ms" not in got
    assert 0 < got["queue_fill"]["value"] <= 100
    assert got["queue_wait_ms"]["value"] > 0
    assert not RECORDER.on


def test_existing_metrics_read_alike_with_the_recorder_on_and_off(
        monkeypatch):
    """The same traced toy run with the program's recorder following the
    profiler and with it kept off: every earlier per-layer metric is read
    in both, the counted one to the same number, from records of the same
    calls."""
    earlier = ("control_tick_ms", "batch_fill", "tier0_call_ms",
               "disc_call_ms", "serve_mfu", "groupnorm_ms",
               "attention_roofline", "idle_share")
    kept = {}

    def run(label):
        import chipbench.cell as cell
        real = cell.run_window

        def window(system, prep, *a, **kw):
            kept[label] = prep.records
            return real(system, prep, *a, **kw)
        with monkeypatch.context() as m:
            m.setattr(cell, "run_window", window)
            if label == "off":
                m.setattr(RECORDER, "follow_profiler",
                          contextlib.nullcontext)
            line, _o, _e = toybench.run("c2-easy-backlog", trace=True,
                                        impl="ref")
        assert line["correct"]
        return {k: v["value"] for k, v in line["metrics"].items()
                if k in earlier}

    on = run("on")
    assert RECORDER.spans and not RECORDER.on
    RECORDER.reset()
    off = run("off")
    assert RECORDER.spans == [] and RECORDER.counters == {}
    assert set(on) == set(off) and len(on) >= 4
    assert on["batch_fill"] == off["batch_fill"]
    a, b = kept["on"], kept["off"]
    assert [w[:3] for w in a.stage_walls] == [w[:3] for w in b.stage_walls]
    assert [w[:2] for w in a.disc_walls] == [w[:2] for w in b.disc_walls]
    assert len(a.tick_walls) == len(b.tick_walls)
