"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = the figure's headline
number) and writes per-figure row CSVs to experiments/benchmarks/out/
(a gitignored artifact directory — benchmark outputs are never
committed). Figures run the comparison systems through the control-plane
policy registry (serving/baselines.py:CONTROLLERS); ``--only`` selects a
subset of figures by substring.
"""
import argparse
import csv
import json
import pathlib
import time

OUT = (pathlib.Path(__file__).resolve().parents[1]
       / "experiments" / "benchmarks" / "out")
ROOT = pathlib.Path(__file__).resolve().parents[1]


def _stage_latencies(kernel_impl: str, buckets: tuple, batches: tuple):
    """Really execute a tiny 2-tier attention cascade at each batch size:
    per-tier best-of-3 wall ms per batch, plus the cascade's compiled-
    program counts (stage samplers in order, then the discriminator).
    Warm-up happens before timing, so walls are steady-state e(b)."""
    import jax
    import jax.numpy as jnp

    from repro.config.base import DiffusionConfig
    from repro.core.cascade import DiffusionCascade
    from repro.models.efficientnet import (DiscriminatorConfig,
                                           init_discriminator)
    from repro.models.unet import init_unet

    stages = []
    for i in range(2):
        cfg = DiffusionConfig(
            name=f"bench-tier{i}", image_size=8, in_channels=3,
            base_channels=8, channel_mults=(1,), num_res_blocks=1,
            attn_resolutions=(8,), num_heads=2, num_steps=1 + i,
            text_dim=16)
        stages.append((cfg, init_unet(jax.random.PRNGKey(i), cfg)))
    dcfg = DiscriminatorConfig(stages=((16, 1, 1, 1), (24, 1, 2, 4)),
                               head_channels=32, in_channels=3)
    casc = DiffusionCascade(stages, dcfg,
                            init_discriminator(jax.random.PRNGKey(9), dcfg),
                            kernel_impl=kernel_impl, batch_buckets=buckets)
    per_tier = []
    for cfg, fn, params in casc.stage_fns():
        eb = {}
        for b in batches:
            toks = jnp.zeros((b, 8), jnp.int32)
            key = jax.random.PRNGKey(0)
            fn(params, key, toks).block_until_ready()     # compile warm
            walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn(params, key, toks).block_until_ready()
                walls.append(time.perf_counter() - t0)
            eb[str(b)] = round(min(walls) * 1e3, 3)
        per_tier.append(eb)
    return per_tier, casc.compile_counts(), casc.kernel_impl


def bench_serving(out_path: pathlib.Path) -> dict:
    """The serving perf fingerprint CI tracks (BENCH_serving.json at the
    repo root): control-tick wall time, simulator event throughput, and
    the end-to-end violation rate of the default controller on a pinned
    seed/trace — so 'makes a hot path measurably faster' is checkable
    against the previous run's JSON artifact."""
    import numpy as np

    from repro.serving.baselines import run_controller
    from repro.serving.profiles import default_serving
    from repro.serving.trace import azure_like_trace

    trace = azure_like_trace(360, seed=3).scale(4, 32)
    serving = default_serving("sdturbo", num_workers=16)
    t0 = time.perf_counter()
    r = run_controller("diffserve", trace, serving, seed=0)
    wall = time.perf_counter() - t0
    solve = np.asarray(r.solve_ms if r.solve_ms else [0.0])

    # overload datum: the same trace offered at 100x under queue-depth
    # admission — pins the vectorized arrival pump's event throughput at
    # high QPS and the door-shedding behavior of the guarded controller
    hot = azure_like_trace(120, seed=3).scale(4, 32).scaled(100.0)
    sv_g = default_serving("sdturbo", num_workers=16,
                           admission="queue-depth")
    t1 = time.perf_counter()
    rg = run_controller("diffserve", hot, sv_g, seed=0)
    wall_g = time.perf_counter() - t1

    # micro-serving datum: stage-granular serving vs whole-tier on the
    # same stage engine and worker budget at 16x offered load — the
    # acceptance bar is micro goodput strictly above whole-tier
    # (confidence-based preemption frees denoise slots early)
    from repro.serving.trace import static_trace
    deep = static_trace(30.0, 30).scaled(16.0)
    micro_res = {}
    for sg in ("whole-tier", "micro"):
        sv_m = default_serving("sdturbo", num_workers=8, stage_graph=sg)
        rm = run_controller("diffserve", deep, sv_m, seed=0)
        micro_res[sg] = rm
    # per-stage kernel hot-path datum: e(b) at every bucket under the
    # fused kernel plan ("auto" -> the fused jnp oracles on CPU CI) vs
    # the unfused, unbucketed xla baseline; compile counts pin the
    # bucketing invariant (<= one program per bucket per jitted fn)
    buckets = (1, 2, 4, 8)
    fused_eb, fused_counts, impl_name = _stage_latencies(
        "auto", buckets, buckets)
    xla_eb, xla_counts, _ = _stage_latencies("xla", (), buckets)
    top = str(buckets[-1])

    payload = {
        "pinned": {"trace": trace.name, "trace_seed": 3, "sim_seed": 0,
                   "cascade": "sdturbo", "workers": 16,
                   "controller": "diffserve"},
        "control_tick_ms_mean": round(float(solve.mean()), 4),
        "control_tick_ms_p99": round(float(np.percentile(solve, 99)), 4),
        "control_ticks": int(len(r.solve_ms)),
        "sim_events_processed": int(r.events_processed),
        "sim_events_per_s": round(r.events_processed / max(wall, 1e-9)),
        "sim_wall_s": round(wall, 3),
        "violation_ratio": round(r.violation_ratio, 6),
        "completed": r.completed,
        "total": r.total,
        "overload": {
            "trace": hot.name, "load_scale": 100.0,
            "admission": "queue-depth",
            "sim_events_processed": int(rg.events_processed),
            "sim_events_per_s": round(rg.events_processed
                                      / max(wall_g, 1e-9)),
            "sim_wall_s": round(wall_g, 3),
            "offered": rg.total,
            "shed_admission": rg.shed_admission,
            "violation_ratio": round(rg.violation_ratio, 6),
        },
        "microserve": {
            "trace": deep.name, "load_scale": 16.0, "workers": 8,
            **{sg.replace("-", "_"): {
                "offered": rm.total, "completed": rm.completed,
                "preempted_early": rm.preempted_early,
                "dropped_stage": rm.dropped_stage,
                "goodput": round(rm.goodput, 6),
            } for sg, rm in micro_res.items()},
            "micro_goodput_gain": round(
                micro_res["micro"].goodput
                - micro_res["whole-tier"].goodput, 6),
        },
        "stages": {
            "kernel_impl": impl_name,
            "buckets": list(buckets),
            # per-tier {batch: best-of-3 wall ms}, steady-state (warmed)
            "tiers_e_ms": fused_eb,
            # programs compiled per jitted fn (tiers..., discriminator):
            # the bucket ladder bounds each entry
            "compile_counts": fused_counts,
            "xla_unbucketed_e_ms": xla_eb,
            "xla_compile_counts": xla_counts,
            "fused_vs_xla_at_top_bucket": [
                round(f[top] / max(x[top], 1e-9), 4)
                for f, x in zip(fused_eb, xla_eb)],
            "control_tick_ms_mean": round(float(solve.mean()), 4),
            "sim_events_per_s": round(r.events_processed
                                      / max(wall, 1e-9)),
        },
    }
    out_path.write_text(json.dumps(payload, indent=1) + "\n")
    return payload


def main() -> None:
    from benchmarks.figures import ALL
    from repro.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only figures whose name contains this")
    ap.add_argument("--bench-serving", action="store_true",
                    help="write the serving perf fingerprint to "
                    "BENCH_serving.json at the repo root and exit")
    args = ap.parse_args()
    enable_compile_cache()
    if args.bench_serving:
        payload = bench_serving(ROOT / "BENCH_serving.json")
        print(json.dumps(payload, indent=1))
        return
    figures = {name: fn for name, fn in ALL.items()
               if args.only is None or args.only in name}
    if not figures:
        raise SystemExit(f"no figure matches {args.only!r}; "
                         f"known: {', '.join(ALL)}")
    OUT.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for name, fn in figures.items():
        t0 = time.perf_counter()
        rows, derived = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}", flush=True)
        if rows:
            with open(OUT / f"{name}.csv", "w", newline="") as f:
                w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
                w.writeheader()
                w.writerows(rows)


if __name__ == '__main__':
    main()
