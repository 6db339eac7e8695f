"""Find a cell's knee once: serve the cell's configuration and deferral
share at a series of offered rates and report, per rate, latency on the
backend's clock and the rate the backend drained.

    python3 chipbench/sweep.py --workload <cell> --rates 5,10,20,40 \\
        --queries 400 [--seed 1]

The knee is the highest rate whose 95th-percentile latency stays under
the configuration's SLO without growing from the first half of the
queries to the second. Not run by the benchmark itself.
"""
import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--queries", type=int, default=400)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from chipbench import cell, harness, registry, stats
    harness.enable_compile_cache()
    bench = registry.load_benchmark()
    wl = registry.workload(bench, args.workload)
    config = registry.config(bench, wl["config"])
    traffic = registry.traffic(wl["traffic"])
    harness.device_info(int(wl["chips"]))
    seeds = cell.Seeds.from_run_seed(args.seed)
    system = cell.System.build(config, registry.tier_models(config),
                               seeds.weights)
    for rate in [float(r) for r in args.rates.split(",")]:
        t = dict(traffic, rate_qps=rate, min_queries=args.queries)
        prep = cell.prepare(system, t, seeds, 0.0)
        arrivals = prep.trace.times
        t0 = time.perf_counter()
        w = cell.run_window(system, prep)
        wall = time.perf_counter() - t0
        lat = w.result.latencies
        done = sorted((q, lat_) for q, _t, lat_ in prep.records.completions)
        half = len(done) // 2
        first = [v for _q, v in done[:half]]
        second = [v for _q, v in done[half:]]
        end = max(arrivals[q] + v for q, v in done)
        print(json.dumps({
            "rate_qps": rate, "offered": w.offered,
            "completed": w.result.completed,
            "p50_s": stats.percentile(lat, 50, w.offered),
            "p95_s": stats.percentile(lat, 95, w.offered),
            "p95_first_half_s": stats.percentile(first, 95),
            "p95_second_half_s": stats.percentile(second, 95),
            "drained_qps": w.result.completed / end,
            "slo_s": config["slo_s"], "wall_s": wall,
            "images_per_wall_s": w.result.completed / wall,
            "batch_fill": float(np.mean([c.n / c.bucket
                                         for c in prep.records.calls])),
            "batch_rows": {t_: harness._histogram(
                c.n for c in prep.records.calls if c.tier == t_)
                for t_ in range(len(config["tiers"]))},
            "plans": harness._plans(prep.backend.plan_timeline),
            "shares": cell.realized_shares(prep.records,
                                           len(config["tiers"]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
