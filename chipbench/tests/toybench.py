"""The benchmark at toy size for tests on the CPU: the real metrics, over
toy cells of the real cells' shapes (one that defers a share of its
queries, one that defers none), each with the toy configuration and a
toy traffic mix beside this file. Every cell reports every metric whose
reader finds something to read."""
import copy
import pathlib

from chipbench import registry

HERE = pathlib.Path(__file__).resolve().parent / "toy"
CELLS = {"c2-hard-backlog": ("toy2", "toy_backlog"),
         "c2-easy-backlog": ("toy2", "toy_easy")}
SEED = 2**31 + 12345       # more than 32 signed bits


def bench():
    b = copy.deepcopy(registry.load_benchmark())
    b["configs"] = [{"name": n, "file": f"chipbench/tests/toy/{n}.json"}
                    for n in ("toy2",)]
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1}
                      for n, (c, t) in CELLS.items()]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b


def run(cell, *, seed=SEED, seconds=0.3, trace=False, impl=None, **kw):
    import io
    import time
    from chipbench import harness
    out, err = io.StringIO(), io.StringIO()
    line = harness.run(cell, seed, seconds, trace, time.perf_counter(),
                       bench=bench(), require_tpu=False, kernel_impl=impl,
                       traffic_dir=HERE, out=out, err=err, **kw)
    return line, out.getvalue(), err.getvalue()
