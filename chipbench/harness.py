"""One run of one cell: set-up, window, metrics, and the comparison.

``run`` is what ``chipbench/run.py`` calls after it has found a TPU.
Tests call it on the CPU at toy size with ``require_tpu=False``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax

from chipbench import cell, correct, flops, peaks, registry, trace_reduce


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in the program's own directory
    inside the checkout (``repro.compile_cache.CHECKOUT_CACHE_DIR``),
    whatever cache the machine's environment names: a fixed path, with no
    cap on its size (one UNet program at published widths is some hundred
    MB) and every program kept however small or quick to compile, so that
    a run after the first in a checkout compiles nothing."""
    from repro.compile_cache import CHECKOUT_CACHE_DIR
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoAccelerator(f"the cell needs {chips} TPU chip(s); JAX "
                            f"reports {info}")
    return info


@dataclasses.dataclass
class Context:
    """Everything a metric reader may read."""
    cell: str
    config: Dict
    models: List[registry.Tier]
    traffic: Dict
    setup_s: float
    window: cell.Window
    prep: cell.Prepared
    device: Dict
    trace: Optional[trace_reduce.Trace] = None
    trace_window: Optional[tuple] = None

    @property
    def records(self) -> cell.Records:
        return self.prep.records

    @property
    def n_tiers(self) -> int:
        return len(self.config["tiers"])

    def steps(self, tier: int) -> int:
        return int(self.config["tiers"][tier]["num_steps"])

    @property
    def peak(self) -> Optional[Dict]:
        """The chip's published peaks; None off the TPU (a rehearsal)."""
        if self.device["platform"] != "tpu":
            return None
        return peaks.peak_for(self.device["kind"])

    @property
    def device_ops(self) -> List[trace_reduce.Event]:
        if self.trace is None or not self.trace.device_ops:
            return []
        return self.trace.device_ops[sorted(self.trace.device_ops)[0]]

    @property
    def device_modules(self) -> List[trace_reduce.Event]:
        if self.trace is None or not self.trace.device_modules:
            return []
        return self.trace.device_modules[
            sorted(self.trace.device_modules)[0]]

    def mean_stage_ms(self, tier: int, bucket: int) -> Optional[float]:
        walls = [s for t, b, _n, s in self.records.stage_walls
                 if t == tier and b == bucket]
        return 1e3 * sum(walls) / len(walls) if walls else None

    def traced_stage_calls(self):
        """(tier, bucket, span) of every sampler call wholly inside the
        traced stretch, from the benchmark's host spans in the trace."""
        if self.trace is None or not self.trace_window:
            return []
        return [(int(sp.stat("tier")), int(sp.stat("bucket")), sp)
                for sp in trace_reduce.spans_in(
                    self.trace.host_spans, self.trace_window,
                    "chipbench.stage")]

    def traced_disc_calls(self):
        """(bucket, span) of every discriminator call wholly inside the
        traced stretch."""
        if self.trace is None or not self.trace_window:
            return []
        return [(int(sp.stat("bucket")), sp)
                for sp in trace_reduce.spans_in(
                    self.trace.host_spans, self.trace_window,
                    "chipbench.disc")]

    @property
    def image_size(self) -> int:
        """The latents' side, which the discriminator scores."""
        tier = self.models[0]
        return int(tier.model.latent_shape(tier.m)[0])

    def groupnorm_calls(self):
        """(count, shape) of every GroupNorm kernel call the traced calls
        ran: each sampler call runs its tier's denoiser ``steps`` times at
        its bucket, each discriminator call once."""
        d = self.config["discriminator"]
        out = []
        for tier, bucket, _sp in self.traced_stage_calls():
            t = self.models[tier]
            out += [(self.steps(tier), c)
                    for c in t.model.groupnorm_calls(t.m, bucket)]
        for bucket, _sp in self.traced_disc_calls():
            out += [(1, c) for c in flops.discriminator_groupnorm_calls(
                d, bucket, self.image_size)]
        return out

    def attention_calls(self):
        out = []
        for tier, bucket, _sp in self.traced_stage_calls():
            t = self.models[tier]
            out += [(self.steps(tier), c) for c in t.model.attention_calls(
                t.m, bucket, self.config["prompt_len"])]
        return out


def _histogram(values) -> Dict[int, int]:
    out: Dict[int, int] = {}
    for v in values:
        out[v] = out.get(v, 0) + 1
    return dict(sorted(out.items()))


def _plans(timeline) -> List:
    """Each distinct (workers, batch caps) the control plane applied, with
    how many ticks applied it."""
    out: List = []
    for _t, workers, batches in timeline:
        if out and out[-1][0] == [list(workers), list(batches)]:
            out[-1][1] += 1
        else:
            out.append([[list(workers), list(batches)], 1])
    return out


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, bench: Optional[Dict] = None,
        require_tpu: bool = True, kernel_impl: Optional[str] = None,
        traffic_dir=None, trace_seconds: float = 4.0,
        model_folders=(registry.MODELS,),
        out=sys.stdout, err=sys.stderr) -> Dict:
    """Run the cell once and return the result line (also printed).
    ``model_folders`` are searched in turn for each tier's model
    module."""
    bench = bench or registry.load_benchmark()
    wl = registry.workload(bench, cell_name)
    config = registry.config(bench, wl["config"])
    traffic = registry.traffic(wl["traffic"], traffic_dir) \
        if traffic_dir else registry.traffic(wl["traffic"])
    kind = "per_layer" if trace else "end_to_end"
    readers = {m["name"]: (m, registry.reader(kind, m["name"]))
               for m in registry.metrics_for(bench, kind, cell_name)}
    info = device_info(int(wl["chips"]), require_tpu)
    if require_tpu:
        peaks.peak_for(info["kind"])        # an unknown chip is an error

    seeds = cell.Seeds.from_run_seed(seed)
    models = registry.tier_models(config, model_folders)
    counter = cell.Records()
    listener = cell.count_compiles(counter)
    try:
        t_build = time.perf_counter()
        system = cell.System.build(config, models, seeds.weights,
                                   kernel_impl)
        t_prep = time.perf_counter()
        prep = cell.prepare(system, traffic, seeds, seconds)
        setup_s = time.perf_counter() - t_start
        print(json.dumps({"setup": {
            "setup_s": setup_s, "before_build_s": t_build - t_start,
            "build_s": t_prep - t_build,
            "prepare_s": time.perf_counter() - t_prep,
            "compiles": counter.compiles,
            "cache_hits": counter.cache_hits, "queries": len(prep.trace.times),
            "rate_qps": traffic["rate_qps"],
            "thresholds": prep.thresholds,
            "calibration_defer_shares": prep.calibration_shares,
            "profiles_s": prep.profiles_s, "disc_s": prep.disc_s,
            "compile_counts": system.cascade.compile_counts()}}),
            file=out, flush=True)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else None
        compiles_before = counter.compiles
        window = cell.run_window(system, prep, trace_dir, trace_seconds)
        window.compiles = counter.compiles - compiles_before
    finally:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(listener[0])
        monitoring.unregister_event_listener(listener[1])
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    device = dict(info, memory_peak_bytes=mem)
    ctx = Context(cell_name, config, models, traffic, setup_s, window, prep,
                  device)
    breakdown = None
    if trace_dir:
        try:
            ctx.trace = trace_reduce.read_xspace(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace_window = ctx.trace.window()
        ops = ctx.device_ops
        if ctx.trace_window and ops:
            busy = trace_reduce.busy_seconds(ops, ctx.trace_window)
            span = ctx.trace_window[1] - ctx.trace_window[0]
            device.update(busy_s=busy, window_s=span)
            breakdown = {
                "device_ops": trace_reduce.top_ops(ops, ctx.trace_window),
                "idle_gaps": trace_reduce.longest_gaps(
                    ops, ctx.trace.host_spans, ctx.trace_window)}
    metrics = {}
    for name, (m, read) in readers.items():
        v = _finite(read(ctx))
        if v is not None:
            metrics[name] = {"value": v, "unit": m["unit"]}
    result = window.result
    shares = cell.realized_shares(prep.records, len(config["tiers"]))
    print(json.dumps({"window": {
        "wall_s": window.wall_s, "offered": window.offered,
        "completed": result.completed,
        "completed_per_tier": list(result.completed_per_tier),
        "realized_defer_shares": shares,
        "shed": result.shed_admission, "dropped": result.dropped_deadline,
        "batches": len(prep.records.calls),
        "batch_rows": {f"tier{t}": _histogram(
            c.n for c in prep.records.calls if c.tier == t)
            for t in range(len(config["tiers"]))},
        "plans": _plans(prep.backend.plan_timeline),
        "control_ticks": len(prep.records.tick_walls),
        "compiles_in_window": window.compiles,
        "compile_counts": window.compile_counts}}), file=out, flush=True)

    # the program's state goes before the reference runs
    weights = system.weights
    ctx.prep = None
    prep.backend = prep.runtime = prep.control = None
    system.cascade = None
    numbers = correct.checks(window, prep, weights, config, models)
    ok = correct.passed(numbers)
    served = {q for q, _t, _l in prep.records.completions}
    line = {"correct": ok, "attempted": window.offered,
            "failed": window.offered - len(served), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = numbers
    for name, v in numbers.items():
        print(f"check {name} = {v['value']!r} limit {v['limit']!r}",
              file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return line
