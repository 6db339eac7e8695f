"""The benchmark at toy size for tests on the CPU: the real metrics, over
toy cells of the real cells' shapes (one that defers a share of its
queries, one that defers none), each with a toy configuration (``toy2``
unless another is named) and a toy traffic mix beside this file. Model
modules in ``toy/models/`` are found beside the benchmark's own. Every
cell reports every metric whose reader finds something to read."""
import copy
import pathlib

from chipbench import registry

HERE = pathlib.Path(__file__).resolve().parent / "toy"
MODELS = HERE / "models"
CELLS = {"c2-hard-backlog": "toy_backlog", "c2-easy-backlog": "toy_easy"}
SEED = 2**31 + 12345       # more than 32 signed bits


def bench(config="toy2", file=None):
    """The benchmark over the toy cells, with the configuration ``config``
    read from ``file`` (``toy/<config>.json`` unless given)."""
    b = copy.deepcopy(registry.load_benchmark())
    b["configs"] = [{"name": config,
                     "file": str(file or HERE / f"{config}.json")}]
    b["workloads"] = [{"name": n, "config": config, "traffic": t,
                       "chips": 1} for n, t in CELLS.items()]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b


def run(cell, *, seed=SEED, seconds=0.3, trace=False, impl=None,
        config="toy2", file=None, **kw):
    import io
    import time
    from chipbench import harness
    out, err = io.StringIO(), io.StringIO()
    line = harness.run(cell, seed, seconds, trace, time.perf_counter(),
                       bench=bench(config, file), require_tpu=False,
                       kernel_impl=impl, traffic_dir=HERE,
                       model_folders=(MODELS, registry.MODELS),
                       out=out, err=err, **kw)
    return line, out.getvalue(), err.getvalue()
