"""Smoke run of the served DiffServe cascade on TPU.

Drives the path of ``examples/serve_cascade.py --mode cluster`` once, at
the default ``DiffusionConfig`` width (64x64x4 latent, base 128 channels,
mults (1, 2, 4), attention at 16x16, 64.6 M parameters per UNet): a
1-step tier 0 and a 50-step tier 1, and a discriminator that takes the
latent's channels. Weights are random from ``--seed``; no file is read.

    python3 chip_smoke.py             # one chip: phases A to D
    python3 chip_smoke.py --chips 4   # four chips: phases A and E only

  A  the first device is a TPU and the kernel plan resolves to Pallas;
     checked before any model is built
  B  the Pallas kernels and one tier-0 UNet evaluation through them
     against the repo's float32 references, at the served shapes
  C  each tier sampler and the discriminator at each batch bucket (output
     shape, finite values, compile and steady seconds), then
     ``DiffusionCascade.run_batch`` with every threshold at 1.0, so both
     tiers and the discriminator execute
  D  a short seeded trace served by ``ClusterBackend`` under the
     diffserve controller; every query is accounted for
  E  every worker slice, one per device, runs both tiers and the
     discriminator on the same (key, tokens); outputs stay on the slice's
     device and agree with device 0. Then the trace is served with the
     slices spread over the four devices, and each device's weights were
     placed once

Earlier lines of standard output are JSON records; their walls are a
smoke, not a benchmark. The last line is
``{"ok": true, "device": {...}}``. A failed check raises, and the script
exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.config.base import DiffusionConfig, as_cascade_spec  # noqa: E402
from repro.core.cascade import DiffusionCascade  # noqa: E402
from repro.kernels.impls import kernel_plan  # noqa: E402
from repro.models.efficientnet import (DiscriminatorConfig,  # noqa: E402
                                       init_discriminator)
from repro.models.unet import init_unet  # noqa: E402
from repro.serving.baselines import assemble_bundle  # noqa: E402
from repro.serving.cluster import ClusterBackend, ClusterRuntime  # noqa: E402
from repro.serving.profiles import default_serving  # noqa: E402
from repro.serving.trace import azure_like_trace  # noqa: E402

PROMPT_LEN = 8
NOTE = "smoke, not a benchmark"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def serving_config(num_workers: int, buckets, kernel_impl: str = "auto"):
    return default_serving("sdturbo", num_workers=num_workers,
                           controller="diffserve", kernel_impl=kernel_impl,
                           batch_buckets=tuple(buckets),
                           batch_choices=(1, max(buckets)))


def phase_device(serving, chips: int) -> dict:
    """Phase A: a TPU, enough of them, and the Pallas kernel plan."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == "tpu", f"no TPU: JAX reports {info}")
    check(len(devs) >= chips, f"{chips} chips asked for, JAX reports {info}")
    impl = kernel_plan(serving).impl
    check(impl == "pallas", f"kernel plan resolved to {impl!r}, not pallas")
    emit({"phase": "A", "device": info, "impl": impl})
    return info


def build_cascade(base: DiffusionConfig, seed: int,
                  disc: DiscriminatorConfig = DiscriminatorConfig()
                  ) -> DiffusionCascade:
    """Two tiers at ``base``'s width (1 step, then ``base.num_steps``) and
    a discriminator over the latent's channels, all seeded."""
    k0, k1, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    tiers = (dataclasses.replace(base, name="smoke-tier0", num_steps=1),
             dataclasses.replace(base, name="smoke-tier1"))
    stages = [(cfg, init_unet(k, cfg)) for cfg, k in zip(tiers, (k0, k1))]
    disc = dataclasses.replace(disc, in_channels=base.in_channels)
    return DiffusionCascade(stages, disc, init_discriminator(kd, disc))


def _tokens(n: int, seed: int):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, PROMPT_LEN), 0,
                              1024, dtype=jnp.int32)


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


def _check_latents(out, n: int, cfg: DiffusionConfig, what: str) -> None:
    want = (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    check(tuple(out.shape) == want, f"{what}: shape {out.shape} != {want}")
    check(bool(np.isfinite(np.asarray(out)).all()), f"{what}: non-finite")


def _check_confidences(conf, n: int, what: str) -> None:
    conf = np.asarray(conf)
    check(conf.shape == (n,), f"{what}: confidence shape {conf.shape}")
    check(bool(np.isfinite(conf).all() and (conf >= 0).all()
               and (conf <= 1).all()), f"{what}: confidences {conf}")


def _max_abs(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def phase_parity(cascade: DiffusionCascade, seed: int) -> dict:
    """Phase B: the kernel path against the repo's float32 references on
    one sample at the served shapes: fused GroupNorm+SiLU at the 64x64
    level's skip concat and the 16x16 level's, the UNet's pixel
    attention (padded K/V, ``kv_len`` mask) at the attention level, and
    one tier-0 UNet evaluation against the unfused ``xla`` baseline.
    References run at ``highest`` matmul precision, and so do the UNet's
    convolutions on both sides, so the UNet's error is the kernels'."""
    from repro.kernels import ops, ref
    from repro.models.unet import _fused_attn, apply_unet
    cfg, params = cascade.stages[0]
    impl = cascade.kernel_impl
    size, c0, deep = cfg.image_size, cfg.base_channels, cfg.channel_mults[-1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    err = {}
    for k, (hw, c) in zip(ks, ((size, 2 * c0), (size // 4, 8 * c0))):
        x = 2.0 * jax.random.normal(k, (1, hw, hw, c)) + 1.0
        s, b = jnp.linspace(0.5, 1.5, c), jnp.linspace(-0.2, 0.2, c)
        got = ops.fused_groupnorm(x, s, b, groups=8, impl=impl)
        want = ref.groupnorm_silu_ref(x, s, b, groups=8)
        err[f"groupnorm/{hw}x{hw}x{c}"] = _max_abs(got, want)
    res = size // 2 ** (len(cfg.channel_mults) - 1)
    heads, hd = cfg.num_heads, c0 * deep // cfg.num_heads
    q = jax.random.normal(ks[2], (1, res * res, heads, hd))
    kv = [jax.random.normal(k, (1, res * res + PROMPT_LEN, heads, hd))
          for k in ks[3:5]]
    got = jax.jit(functools.partial(_fused_attn, impl=impl))(q, *kv)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(q, *kv, causal=False)
    err[f"attention/{res * res}x{heads}x{hd}"] = _max_abs(got, want)
    x = jax.random.normal(ks[5], (1, size, size, cfg.in_channels))
    t = jnp.full((1,), 500, jnp.int32)
    toks = _tokens(1, seed)

    def unet(impl_):
        return jax.jit(lambda p, x, t, k: apply_unet(p, cfg, x, t, k,
                                                     impl=impl_))
    with jax.default_matmul_precision("highest"):
        got = unet(impl)(params, x, t, toks)
        want = unet("xla")(params, x, t, toks)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    err["unet_rel_l2"] = rel
    record = {"phase": "B", "impl": impl, "errors": err}
    emit(record)
    for name, e in err.items():
        bound = 1e-4 if name.startswith("groupnorm") else 2e-2
        check(e <= bound, f"{name}: error {e} above {bound}")
    return record


def phase_tiers(runtime: ClusterRuntime, seed: int) -> dict:
    """Phase C: every stage and the discriminator at every bucket, then
    the whole cascade with nothing kept at tier 0."""
    casc = runtime.cascade
    sl = runtime.slices[0]
    key = jax.random.PRNGKey(seed)
    compile_s, wall_s = {}, {}

    def timed_twice(name, call):
        first, out = _timed(call)
        steady = min(_timed(call)[0] for _ in range(2))
        compile_s[name], wall_s[name] = first - steady, steady
        return out

    stages = casc.stage_fns()
    cfg0 = stages[0][0]
    tier0_out = {}
    for i, stage in enumerate(stages):
        for b in casc.batch_buckets:
            name = f"tier{i}/b{b}"
            out = timed_twice(name, functools.partial(
                runtime.run_stage, sl, stage, key, _tokens(b, seed)))
            _check_latents(out, b, stage[0], name)
            tier0_out.setdefault(b, out)
    # the discriminator scores tier-0 outputs, as on the served path
    for b, imgs in tier0_out.items():
        conf = timed_twice(f"disc/b{b}",
                           functools.partial(runtime.score, sl, imgs))
        _check_confidences(conf, b, f"disc/b{b}")
    n = max(casc.batch_buckets)
    res = casc.run_batch(key, _tokens(n, seed + 1), 1.0)
    _check_latents(res.outputs, n, stages[-1][0], "run_batch outputs")
    _check_latents(res.light_outputs, n, cfg0, "run_batch tier-0 outputs")
    _check_confidences(res.confidences, n, "run_batch")
    check(bool((res.stage_index == len(stages) - 1).all()),
          f"thresholds at 1.0 must send every query to the last tier, got "
          f"stage_index {res.stage_index.tolist()}")
    record = {"phase": "C", "note": NOTE, "impl": casc.kernel_impl,
              "tiers": [dataclasses.asdict(cfg) for cfg, _, _ in stages],
              "compile_s": compile_s, "wall_s": wall_s,
              "compile_counts": casc.compile_counts(),
              "run_batch": {"n": n, "stage_index": res.stage_index.tolist(),
                            "confidences": res.confidences.tolist()}}
    emit(record)
    return record


def serve_trace(cascade: DiffusionCascade, serving, seed: int,
                duration_s: int) -> dict:
    """The served path of ``examples/serve_cascade.py --mode cluster``:
    measured per-tier profiles feed the controller, then
    ``ClusterBackend.serve`` replays a seeded trace through the jitted
    stages. Checks that every offered query is accounted for."""
    runtime = ClusterRuntime(cascade, serving)
    buckets = cascade.batch_buckets
    prof = runtime.measure_profile(batches=(1, max(buckets)), repeats=2)
    spec = as_cascade_spec(serving.cascade)
    spec = dataclasses.replace(
        spec, tiers=tuple(dataclasses.replace(t, profile=prof[i])
                          for i, t in enumerate(spec.tiers)),
        slo_s=max(10 * prof[-1].base_s, 1.0))
    serving = dataclasses.replace(serving, cascade=spec)
    runtime = ClusterRuntime(cascade, serving)
    trace = azure_like_trace(duration_s, seed=seed).scale(1.0, 3.0)
    bundle, profiles, _fixed, control, conf_fn = assemble_bundle(
        "diffserve", trace, serving, seed=seed)
    backend = ClusterBackend(runtime, serving, profiles, seed=seed,
                             router=bundle.router,
                             arrival_stage=bundle.arrival_stage,
                             confidence_fn=conf_fn)
    t0 = time.perf_counter()
    r = backend.serve(control, trace)
    wall = time.perf_counter() - t0
    accounted = (r.completed + r.shed_admission + r.dropped_predictive
                 + r.dropped_deadline)
    check(r.total > 0, "the trace offered no query")
    check(r.total == accounted,
          f"conservation: total {r.total} != completed {r.completed} + "
          f"shed {r.shed_admission} + predictive {r.dropped_predictive} + "
          f"deadline {r.dropped_deadline}")
    check(r.completed > 0, "no query completed")
    scored = sum(len(s) for s in backend._conf_samples)
    check(conf_fn is not None or scored > 0,
          "the discriminator scored no tier output")
    return {"note": NOTE, "total": r.total, "completed": r.completed,
            "completed_per_tier": list(r.completed_per_tier),
            "shed_admission": r.shed_admission,
            "dropped_predictive": r.dropped_predictive,
            "dropped_deadline": r.dropped_deadline,
            "discriminator_scored": scored,
            "slo_s": spec.slo_s,
            "profiles_s": [[p.base_s, p.marginal_s] for p in prof],
            "control_ticks": len(backend.plan_timeline),
            "placements_per_device": _placements_per_device(runtime),
            "serve_wall_s": wall,
            "compile_counts": cascade.compile_counts()}


def phase_serve(cascade, serving, seed: int, duration_s: int = 20) -> dict:
    """Phase D: the short seeded trace on one chip."""
    record = {"phase": "D",
              **serve_trace(cascade, serving, seed, duration_s)}
    emit(record)
    return record


def _placements_per_device(runtime: ClusterRuntime) -> dict:
    per_device: dict = {}
    for _pid, dev_id in runtime.placements:
        per_device[dev_id] = per_device.get(dev_id, 0) + 1
    return per_device


def _slice_outputs(runtime: ClusterRuntime, sl, key, toks):
    """Both tiers and the discriminator on one slice's device: (outputs
    as host arrays, first-call seconds per tier)."""
    dev = sl.devices[0]
    outs, first = {}, {}
    for i, stage in enumerate(runtime.cascade.stage_fns()):
        first[f"tier{i}"], out = _timed(functools.partial(
            runtime.run_stage, sl, stage, key, toks))
        check(out.devices() == {dev},
              f"slice {sl.wid}: tier {i} output on {out.devices()}, "
              f"not {dev}")
        if i == 0:
            outs["disc"] = runtime.score(sl, out)
        outs[f"tier{i}"] = np.asarray(out)
    return outs, first


def phase_devices(cascade, serving, seed: int, duration_s: int = 20
                  ) -> dict:
    """Phase E: one slice per device; the same (key, tokens) everywhere
    agrees with device 0, then the trace is served across the devices."""
    runtime = ClusterRuntime(cascade, serving)
    devs = jax.devices()
    slices = runtime.slices[:len(devs)]
    check([sl.devices[0] for sl in slices] == devs,
          f"slices are not one per device: "
          f"{[sl.devices for sl in slices]}")
    key = jax.random.PRNGKey(seed)
    toks = _tokens(max(cascade.batch_buckets), seed)
    # one thread per device: each compiles and runs its own programs
    with concurrent.futures.ThreadPoolExecutor(len(slices)) as pool:
        per_slice = list(pool.map(
            lambda sl: _slice_outputs(runtime, sl, key, toks), slices))
    dev0 = per_slice[0][0]
    max_diff = {}
    for sl, (outs, _first) in zip(slices, per_slice):
        for name, arr in outs.items():
            d = float(np.max(np.abs(arr - dev0[name])))
            max_diff[f"slice{sl.wid}/{name}"] = d
            check(d <= 1e-5, f"slice {sl.wid} {name} differs from device 0 "
                  f"by {d}")
    # every device got each weight tree (stages, discriminator) once
    trees = len(cascade.stages) + 1
    per_device = _placements_per_device(runtime)
    check(per_device == {d.id: trees for d in devs},
          f"weight placements per device: {per_device}")
    served = serve_trace(cascade, serving, seed, duration_s)
    check(all(n <= trees for n in served["placements_per_device"].values()),
          f"served run placed weights more than once per device: "
          f"{served['placements_per_device']}")
    record = {"phase": "E", "devices": [str(d) for d in devs],
              "max_abs_diff_vs_device0": max_diff,
              "first_call_s": {f"slice{sl.wid}/{k}": v
                               for sl, (_o, first) in zip(slices, per_slice)
                               for k, v in first.items()},
              "placements_per_device": per_device, "serve": served}
    emit(record)
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-device phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    buckets = (1, 4) if args.chips == 1 else (4,)
    serving = serving_config(2 if args.chips == 1 else 4, buckets)
    info = phase_device(serving, args.chips)
    emit({"compile_cache": cache})
    t0 = time.perf_counter()
    cascade = build_cascade(DiffusionConfig(name="default"), args.seed)
    if args.chips == 1:
        runtime = ClusterRuntime(cascade, serving)
        phase_parity(cascade, args.seed)
        phase_tiers(runtime, args.seed)
        phase_serve(cascade, serving, args.seed)
    else:
        phase_devices(cascade, serving, args.seed)
    emit({"total_s": time.perf_counter() - t0, "note": NOTE})
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
