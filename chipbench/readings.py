"""Readings for the limits of ``correct``: the program's compared numbers
on many seeds, and the control's on the same rows.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5 [--control bfloat16]

One process builds the cell's programs once and serves each seed's
weights and traffic through them, a short window each. For every seed it
prints one JSON line: the numbers ``correct`` compares, and with
``--control`` the same numbers with the plain reference computed in that
precision put in the program's place. Not run by the benchmark itself.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def row_stats(pairs):
    """Per tier, the largest relative L2 distance, the largest share of
    saturated elements whose sign differs, and every row's relative L1
    distance; per boundary the mean and median confidence gap, and the
    largest gap were each served confidence altered to 1 - p."""
    import numpy as np
    from chipbench import correct
    out = {}
    for t, (got, want, ctl) in pairs[0].items():
        g = got.reshape(got.shape[0], -1)
        w = want.reshape(want.shape[0], -1)
        l2 = np.linalg.norm(g - w, axis=1) / np.maximum(
            np.linalg.norm(w, axis=1), 1e-30)
        flips = ((np.sign(g) != np.sign(w)) & (np.abs(w) > 0.5)).mean(1)
        out[f"tier{t}_l2"] = float(l2.max())
        out[f"tier{t}_rows"] = [round(float(v), 6)
                                for v in correct.rel_l1(got, want)]
        out[f"tier{t}_l1_mean"] = float(np.mean(correct.rel_l1(got, want)))
        out[f"tier{t}_ctl_l1_mean"] = float(
            np.mean(correct.rel_l1(ctl, want)))
        out[f"tier{t}_flips"] = float(flips.max())
    for b, (got, want) in pairs[1].items():
        gap = np.abs(got - want)
        # what conf_gap would read were each confidence altered to 1 - p
        out[f"conf{b}_altered_gap"] = float(np.max(np.abs(1.0 - got - want)))
        out[f"conf{b}_rows_mean"] = float(gap.mean())
        out[f"conf{b}_rows_median"] = float(np.median(gap))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import cell, correct, harness, registry
    harness.enable_compile_cache()
    bench = registry.load_benchmark()
    wl = registry.workload(bench, args.workload)
    config = registry.config(bench, wl["config"])
    traffic = registry.traffic(wl["traffic"])
    harness.device_info(int(wl["chips"]))
    system = prof = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        seeds = cell.Seeds.from_run_seed(seed)
        if system is None:
            system = cell.System.build(
                config, registry.tier_models(config), seeds.weights)
        else:
            # the last seed's weights go first: two sets need not fit
            system.release_weights()
            system.swap_weights(cell.make_weights(config, seeds.weights,
                                                  system.models))
        # e(b) depends on the shapes alone: measured for the first seed
        prep = cell.prepare(system, traffic, seeds, args.seconds, prof)
        prof = prep.profiles
        window = cell.run_window(system, prep)
        inputs = correct.sample_inputs(prep.records.calls, config,
                                       seeds.backend, seeds.sample,
                                       system.models)
        pairs = correct.compare_rows(inputs, system.weights, config,
                                     system.models)
        nums = correct.checks(window, prep, system.weights, config,
                              system.models, pairs)
        rec = {"seed": seed, "wall_s": window.wall_s,
               "offered": window.offered,
               "shares": cell.realized_shares(prep.records,
                                              len(config["tiers"])),
               "program": {k: v["value"] for k, v in nums.items()}}
        rec["program"].update(row_stats(pairs))
        if args.control:
            ctl = correct.compare_rows(inputs, system.weights, config,
                                       system.models, dtype=args.control,
                                       served=False)
            rec["control"] = dict(correct.numbers(ctl), **row_stats(ctl))
        rec["seconds"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
        prep = window = inputs = pairs = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
