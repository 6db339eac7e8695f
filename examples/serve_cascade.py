"""End-to-end serving driver: a real JAX-executed cascade, latencies
measured on THIS machine (replacing the paper's A100 profiling), then the
full DiffServe control loop replays a bursty trace against those profiles.

Builds one toy UNet per tier of the chosen cascade, so 3-tier registries
(`sdxs3`, `sdxl3`) run the full tier-recursive pipeline. Heterogeneous
clusters split the workers into speed classes; the allocator plans over
``x[tier][class]`` and the report shows the per-class split.

Two modes share one ControlPlane (serving/controlplane.py):

  --mode sim      measured profiles feed the discrete-event simulator
                  backend (default; the paper's own methodology)
  --mode cluster  the ClusterBackend really executes every batch on the
                  jitted stages: measured per-class profiles feed
                  solve_heterogeneous_cascade re-planning every control
                  tick, confidences come from the real discriminator

  PYTHONPATH=src python examples/serve_cascade.py
  PYTHONPATH=src python examples/serve_cascade.py --mode cluster \
      --cascade sdturbo --worker-classes a100:2:1.0,a10g:6:0.45
  PYTHONPATH=src python examples/serve_cascade.py \
      --cascade sdxs3 --controller diffserve --estimator sliding-window
  PYTHONPATH=src python examples/serve_cascade.py --mode cluster \
      --cascade sdxs3 --controller cascade-search
      # per-epoch cascade search over the measured spec's sub-chains:
      # the backend may switch cascades mid-run (staged slice reload)
"""
import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config.base import DiffusionConfig, as_cascade_spec
from repro.core.cascade import DiffusionCascade
from repro.models.unet import init_unet
from repro.core.quality import load_quality_models, save_quality_models
from repro.serving.baselines import CONTROLLERS, assemble_bundle
from repro.serving.cluster import (ClusterBackend, ClusterRuntime,
                                   measured_worker_classes)
from repro.kernels.impls import KERNEL_IMPLS
from repro.serving.controlplane import ESTIMATORS
from repro.serving.microserve import STAGES
from repro.serving.profiles import (CASCADES, class_costs_from_arg,
                                    default_serving, worker_classes_from_arg)
from repro.serving.simulator import SimConfig, Simulator
from repro.serving.trace import azure_like_trace

ap = argparse.ArgumentParser()
ap.add_argument("--cascade", default="sdturbo", choices=sorted(CASCADES))
ap.add_argument("--mode", default="sim", choices=("sim", "cluster"),
                help="sim: measured profiles drive the simulator backend; "
                "cluster: the ClusterBackend really executes every batch")
ap.add_argument("--controller", default="diffserve",
                choices=sorted(CONTROLLERS),
                help="control-plane policy bundle (serving/baselines.py)")
ap.add_argument("--estimator", default=None, choices=sorted(ESTIMATORS),
                help="demand estimator (default: the serving config's, "
                "i.e. ewma)")
ap.add_argument("--workers", type=int, default=8)
ap.add_argument("--worker-classes", default=None,
                help="name:count[:speed][@model=BASExMARG],... e.g. "
                "a100:2:1.0,a10g:6:0.45 (overrides --workers)")
ap.add_argument("--cost-per-class", default=None,
                help="$/hour per class as name[=cost],... — switches the "
                "allocator to the cost-weighted objective")
ap.add_argument("--stage-graph", default="off", choices=sorted(STAGES),
                help="stage-granular micro-serving: in cluster mode the "
                "discriminator decouples onto per-boundary disc queues "
                "drained by the cheapest class present")
ap.add_argument("--stage-denoise-steps", type=int, default=8,
                help="micro stage graph: denoise steps per tier")
ap.add_argument("--stage-preempt-frac", type=float, default=0.5,
                help="micro stage graph: earliest preemption fraction")
ap.add_argument("--kernel-impl", default="auto",
                choices=sorted(KERNEL_IMPLS),
                help="kernel hot path for the jitted stages: auto / "
                "pallas / interpret / ref / xla (unfused baseline)")
ap.add_argument("--batch-buckets", default="1,2,4,8",
                help="batch bucket ladder samplers pad to (empty string "
                "disables bucketing)")
ap.add_argument("--save-quality-models", default=None,
                help="cluster mode: persist per-boundary quality models "
                "fitted from this run's real discriminator confidences "
                "as JSON (core/quality.py round-trip)")
ap.add_argument("--quality-models", default=None,
                help="seed the control plane's deferral profiles from a "
                "saved quality-models JSON instead of the synthetic "
                "offline fit")
ap.add_argument("--duration", type=int, default=90)
ap.add_argument("--seed", type=int, default=1)
args = ap.parse_args()
enable_compile_cache()

wcs = (worker_classes_from_arg(args.worker_classes)
       if args.worker_classes else ())
if args.cost_per_class and not wcs:
    ap.error("--cost-per-class requires --worker-classes")
costs = (class_costs_from_arg(args.cost_per_class)
         if args.cost_per_class else ())
serving = default_serving(cascade=args.cascade, num_workers=args.workers,
                          worker_classes=wcs, class_costs=costs,
                          controller=args.controller,
                          estimator=args.estimator or "ewma",
                          stage_graph=args.stage_graph,
                          stage_denoise_steps=args.stage_denoise_steps,
                          stage_preempt_frac=args.stage_preempt_frac,
                          kernel_impl=args.kernel_impl,
                          batch_buckets=tuple(
                              int(b) for b in args.batch_buckets.split(",")
                              if b.strip()))
spec = as_cascade_spec(serving.cascade)
n_tiers = spec.num_tiers

key = jax.random.PRNGKey(args.seed)
keys = jax.random.split(key, n_tiers + 1)
stages = []
for i in range(n_tiers):
    # deeper tiers: wider UNet, more sampler steps (cheap -> heavy)
    cfg = DiffusionConfig(
        name=f"toy-tier{i}", image_size=16, in_channels=3,
        base_channels=16 + 8 * i, channel_mults=(1, 2),
        num_res_blocks=1 if i == 0 else 2, attn_resolutions=(),
        num_steps=max(1, round(1 + 7 * i / max(n_tiers - 1, 1))),
        text_dim=32)
    stages.append((cfg, init_unet(keys[i], cfg)))

from repro.training.discriminator import train_discriminator  # noqa: E402
disc_params, disc_cfg, _ = train_discriminator(keys[-1], steps=40,
                                               batch_size=16,
                                               image_size=16, lr=3e-3)
cascade = DiffusionCascade(stages, disc_cfg, disc_params)

runtime = ClusterRuntime(cascade, serving)
print("measuring on-device execution profiles ...")
prof = runtime.measure_profile(batches=(1, 2))
print([(round(p.base_s, 4), round(p.marginal_s, 4)) for p in prof])

# feed measured per-tier profiles into the controller and serve a trace
tiers = tuple(dataclasses.replace(t, profile=prof[i])
              for i, t in enumerate(spec.tiers))
spec = dataclasses.replace(spec, tiers=tiers,
                           slo_s=max(10 * prof[-1].base_s, 1.0))
serving = dataclasses.replace(serving, cascade=spec)
if args.mode == "cluster" and wcs:
    # measured per-class e(b) tables (once per class present in slices)
    # replace the static GPU latency-scale table in the solver
    class_profs = runtime.measure_class_profiles(batches=(1, 2))
    serving = dataclasses.replace(
        serving, worker_classes=measured_worker_classes(serving,
                                                        class_profs))
if args.mode == "cluster":
    # every plan batch size must already be warm (measure_profile jitted
    # b=1,2), so re-planning never stalls on a fresh XLA compile
    serving = dataclasses.replace(serving, batch_choices=(1, 2))
    runtime = ClusterRuntime(cascade, serving)

# capacity in speed-weighted worker-equivalents (a10g:0.45 is not an a100)
worker_eq = (sum(wc.count * wc.speed for wc in wcs) if wcs
             else serving.num_workers)
cap = worker_eq / prof[0].base_s * 0.25
trace = azure_like_trace(args.duration, seed=2).scale(max(cap / 8, 0.5),
                                                      max(cap, 1.0))

# one shared assembly path with run_controller: bundle fields (fixed
# plan, allocator ablation mode, random-confidence RNG) cannot drift
loaded_profiles = None
if args.quality_models:
    loaded_models = load_quality_models(args.quality_models)
    loaded_profiles = tuple(m.deferral_profile() for m in loaded_models)
bundle, profiles, fixed, control, bundle_conf = assemble_bundle(
    args.controller, trace, serving, seed=0, estimator=args.estimator,
    profiles=loaded_profiles)
# query-agnostic bundles (Proteus) route on the bundle's random
# confidences; the others score with the really-trained discriminator
probe_cfg = stages[0][0]
real_conf = lambda n: np.asarray(cascade.confidence(     # noqa: E731
    jnp.asarray(np.random.default_rng(0).normal(
        size=(n, probe_cfg.image_size, probe_cfg.image_size,
              probe_cfg.in_channels)).astype(np.float32))))

if args.mode == "cluster":
    backend = ClusterBackend(
        runtime, serving, profiles, seed=0, router=bundle.router,
        arrival_stage=bundle.arrival_stage, confidence_fn=bundle_conf)
    r = backend.serve(control, trace)
else:
    sim = Simulator(serving, profiles,
                    SimConfig(seed=0, router=bundle.router,
                              arrival_stage=bundle.arrival_stage,
                              fixed_plan=fixed),
                    control=control,
                    confidence_fn=bundle_conf or real_conf)
    r = sim.run(trace)

report = {
    "mode": args.mode,
    "cascade": args.cascade,
    "controller": args.controller,
    "estimator": args.estimator or serving.estimator,
    "tiers": [t.model for t in spec.tiers],
    "workers": serving.num_workers,
    "served": r.completed, "total": r.total,
    "slo_violation_ratio": round(r.violation_ratio, 3),
    "defer_fraction": round(r.defer_fraction, 2),
    "fid_star": round(r.mean_fid, 2),
}
if wcs:
    report["worker_classes"] = {wc.name: {"count": wc.count,
                                          "speed": wc.speed} for wc in wcs}
    report["workers_by_class"] = r.workers_by_class
    report["class_mean_batch_latency_s"] = r.class_latency_summary()
if args.mode == "cluster":
    if wcs:
        report["measured_class_scales"] = {
            wc.name: {m: [round(sc.base, 3), round(sc.marginal, 3)]
                      for m, sc in wc.profiles}
            for wc in serving.worker_classes}
    plans = backend.plan_timeline
    report["control_ticks"] = len(plans)
    report["distinct_plans"] = len({p[1:] for p in plans})
    report["plan_timeline_head"] = [
        {"t": round(t, 1), "workers": list(w), "batches": list(b)}
        for t, w, b in plans[:8]]
    if args.stage_graph != "off":
        report["stage_graph"] = args.stage_graph
        report["disc_class"] = backend.disc_class or "(homogeneous)"
    if args.save_quality_models:
        models = backend.fitted_quality_models()
        save_quality_models(args.save_quality_models, models)
        report["saved_quality_models"] = args.save_quality_models
        report["quality_model_samples"] = [
            len(s) for s in backend._conf_samples]
if args.quality_models:
    report["quality_models"] = args.quality_models
if costs and r.plan_cost_timeline:
    report["mean_cost_per_hour"] = round(r.mean_plan_cost_per_hour, 3)
if r.cascade_timeline:
    report["cascade_switches"] = r.cascade_switches
    report["cascade_timeline"] = [[round(t, 1), n]
                                  for t, n in r.cascade_timeline]
print(json.dumps(report, indent=1))
