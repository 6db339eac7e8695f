"""One cell of the benchmark: set-up, the measured window, the records.

Set-up builds the served cascade from a configuration file and each
tier's model module (weights from the seed, the ``pallas`` kernel plan),
measures each tier's e(b) with the
runtime's own ``measure_profile``, pins the deferral thresholds from the
cell's traffic, and warms every shape the window will use. The window is
one ``ClusterBackend.serve`` call over seeded Poisson arrivals. Thin
wrappers, installed on the objects of this run only, record each call
into the runtime, the discriminator and the control tick on the host
clock, name it for the profiler, and keep what the window produced for
the comparison that decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, registry
from chipbench.arrivals import ArrivalTrace, poisson_arrivals
from chipbench.policy import PinnedThresholds, threshold_for_share


@dataclasses.dataclass
class Seeds:
    """Independent 31-bit seeds for each use, derived from ``--seed``."""
    weights: int
    arrivals: int
    backend: int
    calibration: int
    sample: int

    @classmethod
    def from_run_seed(cls, seed: int) -> "Seeds":
        words = np.random.SeedSequence(int(seed)).generate_state(5)
        return cls(*(int(w) & 0x7FFFFFFF for w in words))


@dataclasses.dataclass
class StageCall:
    """One batch the backend ran: its place in the backend's key chain,
    tier, rows, bucket, output, and the queries it carried."""
    index: int
    tier: int
    n: int
    bucket: int
    out: object
    qids: List[int] = dataclasses.field(default_factory=list)
    confs: Optional[List[float]] = None


@dataclasses.dataclass
class Records:
    calls: List[StageCall] = dataclasses.field(default_factory=list)
    # (tier, bucket, real rows, seconds) of every sampler execution
    stage_walls: List[Tuple[int, int, int, float]] = \
        dataclasses.field(default_factory=list)
    # (bucket, real rows, seconds) of every discriminator call
    disc_walls: List[Tuple[int, int, float]] = \
        dataclasses.field(default_factory=list)
    tick_walls: List[float] = dataclasses.field(default_factory=list)
    # called after every runtime and discriminator call (the traced run
    # stops the profiler from here)
    after_call: Optional[object] = None
    # (qid, tier, latency on the backend's clock) per completion
    completions: List[Tuple[int, int, float]] = \
        dataclasses.field(default_factory=list)
    compiles: int = 0
    cache_hits: int = 0


def discriminator_config(config: Dict):
    from repro.models.efficientnet import DiscriminatorConfig
    d = config["discriminator"]
    return DiscriminatorConfig(
        in_channels=d["in_channels"], stem_channels=d["stem_channels"],
        stages=tuple(tuple(s) for s in d["stages"]),
        head_channels=d["head_channels"], num_classes=d["num_classes"],
        se_ratio=d["se_ratio"], gn_groups=d["gn_groups"])


@dataclasses.dataclass
class System:
    """The served cascade of one configuration, built once per process.
    ``models`` are the tiers' ``registry.Tier``s."""
    config: Dict
    models: List[registry.Tier]
    cascade: object
    serving: object
    weights: Tuple[List[Dict], Dict]

    @classmethod
    def build(cls, config: Dict, models: List[registry.Tier],
              weight_seed: int, kernel_impl: Optional[str] = None
              ) -> "System":
        from repro.core.cascade import DiffusionCascade
        from repro.serving.profiles import default_serving
        weights = make_weights(config, weight_seed, models)
        unets, disc = weights
        programs = [t.model.program_config(config, tier, t.m)
                    for t, tier in zip(models, config["tiers"])]
        cascade = DiffusionCascade(
            list(zip(programs, unets)),
            discriminator_config(config), disc,
            kernel_impl=kernel_impl or config["kernel_impl"],
            batch_buckets=tuple(config["batch_buckets"]))
        serving = default_serving(
            config["cascade"], num_workers=config["num_workers"],
            controller="diffserve",
            kernel_impl=kernel_impl or config["kernel_impl"],
            batch_buckets=tuple(config["batch_buckets"]),
            batch_choices=tuple(config["batch_choices"]),
            control_period_s=float(config["control_period_s"]))
        return cls(config, models, cascade, serving, weights)

    def release_weights(self) -> None:
        """Drop every reference to the weights, so their memory is free
        before another set is made."""
        self.cascade.stages = tuple((cfg, None)
                                    for cfg, _ in self.cascade.stages)
        self.cascade.disc_params = None
        self.weights = None

    def swap_weights(self, weights) -> None:
        """Serve other weights of the same shapes through the programs
        already compiled (used to read many seeds in one process)."""
        unets, disc = weights
        # committed like the weights the cascade was built with: an
        # uncommitted tree would compile every program again
        dev = jax.devices()[0]
        self.cascade.stages = tuple(
            (cfg, jax.device_put(w, dev))
            for (cfg, _), w in zip(self.cascade.stages, unets))
        self.cascade.disc_params = jax.device_put(disc, dev)
        self.weights = weights


def make_weights(config: Dict, seed: int, models: List[registry.Tier]):
    weights = reference.init_weights(jax.random.PRNGKey(seed), config,
                                     models)
    return jax.block_until_ready(weights)


@dataclasses.dataclass
class Prepared:
    """What set-up leaves for the window: the backend, its control plane,
    the pinned thresholds, the arrivals and the records."""
    backend: object
    control: object
    runtime: object
    thresholds: Tuple[float, ...]
    calibration_shares: Tuple[float, ...]
    profiles: List[object]
    profiles_s: List[Tuple[float, float]]
    disc_s: Dict[int, float]
    trace: ArrivalTrace
    records: Records
    seeds: Seeds


def _stage_of(cascade, tier: int):
    return cascade.stage_fns()[tier]


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0, out


def calibrate(runtime, config: Dict, traffic: Dict, seeds: Seeds
              ) -> Tuple[Tuple[float, ...], Tuple[float, ...],
                         Dict[int, float]]:
    """Pin each boundary's threshold at the quantile of the
    discriminator's confidences on seeded outputs of that boundary's tier
    that gives the traffic's deferral share (a share of 0 or 1 needs no
    confidences: 0.0 and infinity). Also warms every batch size
    the window can form (1 to the top bucket) through tier 0 and the
    discriminator, which covers the padding and slicing around every
    sampler, and times the discriminator at each bucket.

    Returns (thresholds, the share of calibration confidences each
    threshold defers, discriminator seconds per bucket)."""
    cascade = runtime.cascade
    sl = runtime.slices[0]
    top = max(config["batch_buckets"])
    toks = jnp.zeros((top, config["prompt_len"]), jnp.int32)
    key = jax.random.PRNGKey(seeds.calibration)
    share = float(traffic["defer_share_per_boundary"])
    thresholds, realized = [], []
    for b in range(len(config["tiers"]) - 1):
        if share in (0.0, 1.0):
            # every confidence lies above 0 and below infinity
            thresholds.append(threshold_for_share((), share))
            realized.append(share)
            continue
        confs = []
        for _ in range(int(traffic["calibration_batches"])):
            key, k = jax.random.split(key)
            imgs = runtime.run_stage(sl, _stage_of(cascade, b), k, toks)
            confs.append(runtime.score(sl, imgs))
        confs = np.concatenate(confs)
        t = threshold_for_share(confs, share)
        thresholds.append(t)
        realized.append(float(np.mean(confs < t)))
    disc_s: Dict[int, float] = {}
    stage0 = _stage_of(cascade, 0)
    for n in range(1, top + 1):
        key, k = jax.random.split(key)
        # the tokens as the backend makes them, so their program is warm
        out = runtime.run_stage(sl, stage0, k, jnp.zeros(
            (n, config["prompt_len"]), jnp.int32))
        runtime.score(sl, out)
        if n in config["batch_buckets"]:
            disc_s[n] = min(_timed(runtime.score, sl, out)[0]
                            for _ in range(2))
    return tuple(thresholds), tuple(realized), disc_s


def queries_for_window(seconds: float, traffic: Dict) -> int:
    """The fixed number of queries one window offers: the traffic's
    queries per window second, which fill about ``seconds`` of wall time
    on the program the cell was sized on; a faster program serves the
    same work sooner."""
    return max(int(math.ceil(seconds * float(traffic["queries_per_s"]))),
               int(traffic.get("min_queries", 1)))


def prepare(system: System, traffic: Dict, seeds: Seeds,
            seconds: float, prof=None) -> Prepared:
    """Measure e(b) (unless ``prof``, e(b) measured by an earlier
    ``prepare`` of the same programs, is given), pin thresholds, warm
    every shape, and build the backend and control plane that the window
    drives."""
    from repro.serving.baselines import assemble_bundle
    from repro.serving.cluster import ClusterBackend, ClusterRuntime
    from repro.config.base import as_cascade_spec
    config = system.config
    buckets = tuple(config["batch_buckets"])
    runtime = ClusterRuntime(system.cascade, system.serving)
    if prof is None:
        prof = runtime.measure_profile(
            batches=(min(buckets), max(buckets)),
            prompt_len=config["prompt_len"], repeats=1)
    spec = as_cascade_spec(system.serving.cascade)
    spec = dataclasses.replace(
        spec, slo_s=float(config["slo_s"]),
        tiers=tuple(dataclasses.replace(t, profile=prof[i])
                    for i, t in enumerate(spec.tiers)))
    serving = dataclasses.replace(system.serving, cascade=spec)
    runtime = ClusterRuntime(system.cascade, serving)
    thresholds, realized, disc_s = calibrate(runtime, config, traffic, seeds)
    profiles_s = [(p.base_s, p.marginal_s) for p in prof]
    n = queries_for_window(seconds, traffic)
    rate = float(traffic["rate_qps"])
    times = poisson_arrivals(rate, n, np.random.default_rng(seeds.arrivals))
    trace = ArrivalTrace(times, rate, name=traffic["name"])
    bundle, profiles, _fixed, control, conf_fn = assemble_bundle(
        "diffserve", trace, serving, seed=seeds.backend)
    control.thresholds = PinnedThresholds(thresholds)
    backend = ClusterBackend(
        runtime, serving, profiles, seed=seeds.backend,
        prompt_len=config["prompt_len"],
        model_load_s=float(config["model_load_s"]), router=bundle.router,
        arrival_stage=bundle.arrival_stage, confidence_fn=conf_fn)
    records = Records()
    _install(records, system.cascade, runtime, backend, control,
             seeds.sample)
    return Prepared(backend, control, runtime, thresholds, realized,
                    list(prof), profiles_s, disc_s, trace, records, seeds)


KEPT_CALLS = 16      # batches per tier whose outputs are kept for the check


def _install(rec: Records, cascade, runtime, backend, control,
             sample_seed: int) -> None:
    """Wrap this run's objects (never their classes) so each call into the
    runtime, the discriminator and the control tick is timed on the host
    clock, named for the profiler, and recorded. Every batch's queries
    and confidences are kept; the outputs of a uniform sample of
    ``KEPT_CALLS`` batches per tier, drawn from the seed, are kept for the
    comparison (a reservoir, so memory does not grow with the window)."""
    from jax.profiler import TraceAnnotation
    tier_of = {cfg.name: i for i, (cfg, _) in enumerate(cascade.stages)}
    last = len(cascade.stages) - 1
    state = {"current": None, "in_route": False, "calls": 0}
    rng = np.random.default_rng(sample_seed)
    kept: Dict[int, List[StageCall]] = {t: [] for t in tier_of.values()}
    seen = {t: 0 for t in tier_of.values()}

    def keep(call: StageCall) -> None:
        res = kept[call.tier]
        seen[call.tier] += 1
        if len(res) < KEPT_CALLS:
            res.append(call)
            return
        j = int(rng.integers(seen[call.tier]))
        if j < KEPT_CALLS:
            res[j].out = None
            res[j] = call
        else:
            call.out = None

    run_stage, score = runtime.run_stage, runtime.score
    backend_stage = backend._run_stage
    route, complete, tick = backend._route_scored, backend._complete, \
        control.tick

    def timed_stage(sl, stage, key, toks):
        tier, n = tier_of[stage[0].name], int(toks.shape[0])
        bucket = cascade.bucket_for(n)
        with TraceAnnotation("chipbench.stage", tier=tier, bucket=bucket,
                             rows=n):
            t0 = time.perf_counter()
            out = run_stage(sl, stage, key, toks)
            out.block_until_ready()
            rec.stage_walls.append((tier, bucket, n,
                                    time.perf_counter() - t0))
        if rec.after_call is not None:
            rec.after_call()
        return out

    def timed_score(sl, imgs):
        n = int(imgs.shape[0])
        with TraceAnnotation("chipbench.disc", bucket=cascade.bucket_for(n),
                             rows=n):
            t0 = time.perf_counter()
            out = score(sl, imgs)
            rec.disc_walls.append((cascade.bucket_for(n), n,
                                   time.perf_counter() - t0))
        if rec.after_call is not None:
            rec.after_call()
        return out

    def recorded_batch(sl, tier, batch_n):
        index = state["calls"]
        state["calls"] += 1
        wall, imgs = backend_stage(sl, tier, batch_n)
        call = StageCall(index, tier, batch_n, cascade.bucket_for(batch_n),
                         imgs)
        rec.calls.append(call)
        keep(call)
        state["current"] = call
        return wall, imgs

    def recorded_route(tier, batch, confs, done_t):
        call = state["current"]
        if call is None or call.tier != tier or call.n != len(batch):
            raise RuntimeError("routing does not follow its batch")
        call.qids = [q.qid for q in batch]
        call.confs = [float(c) for c in confs]
        state["in_route"] = True
        try:
            return route(tier, batch, confs, done_t)
        finally:
            state["in_route"] = False

    def recorded_complete(q, done_t):
        out = complete(q, done_t)
        rec.completions.append((q.qid, q.stage, q.done_at - q.arrival))
        if not state["in_route"]:
            call = state["current"]
            if call is None or call.tier != q.stage or q.stage != last:
                raise RuntimeError("completion outside a last-tier batch")
            call.qids.append(q.qid)
        return out

    def timed_tick(backend_, first=False):
        with TraceAnnotation("chipbench.tick"):
            t0 = time.perf_counter()
            out = tick(backend_, first=first)
            rec.tick_walls.append(time.perf_counter() - t0)
        return out

    runtime.run_stage, runtime.score = timed_stage, timed_score
    backend._run_stage = recorded_batch
    backend._route_scored = recorded_route
    backend._complete = recorded_complete
    control.tick = timed_tick


def count_compiles(rec: Records):
    """Count every XLA compilation from now on into ``rec.compiles``, and
    every program loaded from the persistent cache into
    ``rec.cache_hits``; returns the listeners so they can be removed."""
    from jax import monitoring

    def compiled(name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            rec.compiles += 1

    def hit(name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            rec.cache_hits += 1
    monitoring.register_event_duration_secs_listener(compiled)
    monitoring.register_event_listener(hit)
    return compiled, hit


@dataclasses.dataclass
class Window:
    result: object
    wall_s: float
    offered: int
    compile_counts: Tuple[List[int], List[int]]
    compiles: int


class _Profiler:
    """The profiler over the first ``seconds`` of the window: started
    before ``serve``, stopped after the first runtime or discriminator
    call that ends past ``seconds``, so every traced call is whole."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self.on = False

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.on = True
        self.t0 = time.perf_counter()

    def maybe_stop(self, force: bool = False):
        if self.on and (force or time.perf_counter() - self.t0
                        >= self.seconds):
            jax.profiler.stop_trace()
            self.on = False


def run_window(system: System, prep: Prepared,
               trace_dir: Optional[str] = None,
               trace_seconds: float = 4.0) -> Window:
    """The measured window: one ``serve`` over every arrival. With
    ``trace_dir`` the profiler records its first ``trace_seconds``."""
    from jax.profiler import TraceAnnotation
    cascade = system.cascade
    before = cascade.compile_counts()
    rec = prep.records
    compiles_before = rec.compiles
    prof = _Profiler(trace_dir, trace_seconds) if trace_dir else None
    if prof:
        prof.start()
        rec.after_call = prof.maybe_stop
    try:
        with TraceAnnotation("chipbench.serve"):
            t0 = time.perf_counter()
            result = prep.backend.serve(prep.control, prep.trace)
            wall = time.perf_counter() - t0
    finally:
        rec.after_call = None
        if prof:
            prof.maybe_stop(force=True)
    after = cascade.compile_counts()
    return Window(result, wall, len(prep.trace.times), (before, after),
                  rec.compiles - compiles_before)


def realized_shares(records: Records, n_tiers: int) -> List[float]:
    """The share of queries scored at each boundary that deferred."""
    reached = [0] * n_tiers
    for _qid, tier, _lat in records.completions:
        for t in range(tier + 1):
            reached[t] += 1
    return [reached[b + 1] / reached[b] if reached[b] else 0.0
            for b in range(n_tiers - 1)]
