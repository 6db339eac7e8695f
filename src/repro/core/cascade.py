"""Model-cascade abstraction (the paper's core object), generalized to N
stages.

A cascade = an ordered list of (config, params) model stages plus a
discriminator. ``run_batch`` executes the real pipeline: stage-0
generation → discriminator confidence → threshold → next-stage generation
for deferred queries, repeated down the cascade. The same interface drives
diffusion cascades (the paper) and LM cascades (§5 extension, used for the
assigned LM architectures).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import DiffusionConfig
from repro.kernels import impls as kimpls
from repro.models import diffusion as diff
from repro.models.efficientnet import (DiscriminatorConfig,
                                       apply_discriminator)
from repro.serving.spans import RECORDER

Stage = Tuple[DiffusionConfig, object]        # (config, params)


def _stage_sample(params, noise, prompt_tokens, *, cfg, impl):
    """Inner jitted body of one cascade stage. Latents arrive pre-drawn so
    jit can donate their buffer (the DDIM loop rewrites x in place on
    accelerators); values match the key-derived draw exactly."""
    return diff.ddim_sample(params, cfg, None, prompt_tokens, impl=impl,
                            init_noise=noise)


def _disc_score(params, imgs, *, cfg, impl):
    logits, _ = apply_discriminator(params, cfg, imgs, impl=impl)
    return jax.nn.softmax(logits, -1)[:, 1]


def _device_of(params):
    """The device holding a weight tree (a single-device placement)."""
    return next(iter(jax.tree.leaves(params)[0].devices()))


@dataclasses.dataclass
class CascadeResult:
    outputs: np.ndarray            # final images / tokens per query
    confidences: np.ndarray        # stage-0 discriminator scores
    deferred: np.ndarray           # bool mask: sent past stage 0
    light_outputs: np.ndarray      # stage-0 generations
    stage_index: Optional[np.ndarray] = None   # final stage per query
    boundary_confidences: Optional[List[np.ndarray]] = None


def _normalize_thresholds(thresholds: Union[float, Sequence[float]],
                          num_boundaries: int) -> Tuple[float, ...]:
    if isinstance(thresholds, (int, float)):
        return (float(thresholds),) * num_boundaries
    ts = tuple(float(t) for t in thresholds)
    if len(ts) != num_boundaries:
        raise ValueError(f"need {num_boundaries} thresholds, got {len(ts)}")
    return ts


class DiffusionCascade:
    """Real-execution diffusion cascade (toy scale on CPU, full on TPU).

    ``stages`` is an ordered sequence of (DiffusionConfig, params) pairs,
    cheapest first; queries defer stage i -> i+1 when the discriminator
    scores stage i's output below ``thresholds[i]``.
    """

    def __init__(self, stages: Sequence[Stage],
                 disc_cfg: DiscriminatorConfig, disc_params,
                 latent_to_image: Optional[Callable] = None,
                 kernel_impl: str = "xla",
                 batch_buckets: Sequence[int] = ()):
        if isinstance(stages, DiffusionConfig):
            raise TypeError(
                "DiffusionCascade now takes an ordered list of "
                "(config, params) stages; wrap the light/heavy pair as "
                "[(light_cfg, light_params), (heavy_cfg, heavy_params)]")
        stages = tuple(stages)
        if len(stages) < 2:
            raise ValueError("a cascade needs >= 2 stages")
        if latent_to_image is None:
            # identity decode: the discriminator scores latents directly
            bad = [cfg.name for cfg, _ in stages
                   if cfg.in_channels != disc_cfg.in_channels]
            if bad:
                raise ValueError(
                    f"discriminator takes {disc_cfg.in_channels} channels "
                    f"but stages {bad} emit latents with other channel "
                    "counts; give DiscriminatorConfig(in_channels=...) the "
                    "latent channels or pass latent_to_image")
        # weights are committed to one device: the cluster runtime places
        # a committed copy per device, and a committed and an uncommitted
        # call of one sampler compile two separate programs
        dev = jax.devices()[0]
        self.stages: Tuple[Stage, ...] = tuple(
            (cfg, jax.device_put(params, dev)) for cfg, params in stages)
        self.disc_cfg = disc_cfg
        self.disc_params = jax.device_put(disc_params, dev)
        self.latent_to_image = latent_to_image or (lambda z: z)
        self.kernel_impl: Optional[str] = None
        self.batch_buckets: Tuple[int, ...] = ()
        self.configure_kernels(kernel_impl, batch_buckets)

    def configure_kernels(self, kernel_impl: str = "xla",
                          batch_buckets: Sequence[int] = ()) -> None:
        """(Re)build the jitted stage samplers + discriminator under a
        kernel plan: ``kernel_impl`` routes model math ("xla" = the
        baseline einsum path, "ref"/"interpret"/"pallas" the fused
        kernels; "auto" resolves per backend), ``batch_buckets`` pads
        batches up the bucket ladder so XLA compiles O(#buckets)
        programs per stage instead of one per batch size."""
        impl = kimpls.resolve_kernel_impl(kernel_impl)
        buckets = tuple(int(b) for b in batch_buckets)
        if (impl, buckets) == (self.kernel_impl, self.batch_buckets):
            return
        self.kernel_impl, self.batch_buckets = impl, buckets
        self._inner_samplers = [
            jax.jit(functools.partial(_stage_sample, cfg=cfg, impl=impl),
                    donate_argnums=(1,))
            for cfg, _ in self.stages]
        self._samplers = [
            self._make_sampler(cfg, fn)
            for (cfg, _), fn in zip(self.stages, self._inner_samplers)]
        self._score = jax.jit(
            functools.partial(_disc_score, cfg=self.disc_cfg, impl=impl))

    def bucket_for(self, n: int) -> int:
        return kimpls.bucket_for(n, self.batch_buckets)

    def _make_sampler(self, cfg: DiffusionConfig, inner) -> Callable:
        """Host-side stage fn keeping the (params, key, toks) signature:
        pads the batch to its bucket, draws the starting latent at bucket
        shape (outside jit — location does not change the values), and
        slices outputs back to the true batch. It runs on the device that
        holds ``params``: the tokens are committed there and the call is
        made under that default device, so every caller reaches the
        jitted sampler with one argument signature per device (the
        signature is part of its cache key)."""
        def sample(params, key, toks):
            dev = _device_of(params)
            n = toks.shape[0]
            m = self.bucket_for(n)
            with jax.default_device(dev), \
                    RECORDER.span("sample", rows=n, bucket=m):
                with RECORDER.span("sample.prep"):
                    toks = jax.device_put(toks, dev)
                    if m != n:
                        pad = jnp.zeros((m - n,) + tuple(toks.shape[1:]),
                                        toks.dtype)
                        toks = jnp.concatenate([toks, pad], axis=0)
                    noise = jax.random.normal(
                        key, (m, cfg.image_size, cfg.image_size,
                              cfg.in_channels), jnp.float32)
                with RECORDER.span("sample.launch"):
                    out = inner(params, noise, toks)
                return out[:n] if m != n else out
        return sample

    def compile_counts(self) -> List[int]:
        """Compiled-program count per jitted fn (stage samplers in order,
        then the discriminator scorer) — the bucketing invariant's
        observable: a batch sweep may add at most one entry per bucket."""
        fns = list(self._inner_samplers) + [self._score]
        return [int(f._cache_size()) for f in fns]

    # ------- structure / legacy accessors -------
    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def light_cfg(self) -> DiffusionConfig:
        return self.stages[0][0]

    @property
    def light_params(self):
        return self.stages[0][1]

    @property
    def heavy_cfg(self) -> DiffusionConfig:
        return self.stages[-1][0]

    @property
    def heavy_params(self):
        return self.stages[-1][1]

    def stage_fns(self):
        """(config, jitted_sampler, params) per stage (cluster mode uses
        this to measure per-stage execution profiles)."""
        return [(cfg, fn, params) for (cfg, params), fn in
                zip(self.stages, self._samplers)]

    def confidence(self, images, params=None) -> np.ndarray:
        """P('real') per image; ``params`` overrides the discriminator
        weights (a per-device copy of ``disc_params``)."""
        if params is None:
            params = self.disc_params
        dev = _device_of(params)
        n = images.shape[0]
        m = self.bucket_for(n)
        with jax.default_device(dev), \
                RECORDER.span("score", rows=n, bucket=m):
            with RECORDER.span("score.prep"):
                imgs = jax.device_put(images, dev)
                if m != n:
                    pad = jnp.zeros((m - n,) + tuple(imgs.shape[1:]),
                                    imgs.dtype)
                    imgs = jnp.concatenate([imgs, pad], axis=0)
            with RECORDER.span("score.launch"):
                scores = self._score(params, imgs)
            # GroupNorm stats are per-sample, so padded rows cannot leak
            # into real scores; their scores are dropped here.
            with RECORDER.span("score.fetch"):
                return np.asarray(scores[:n])

    def run_batch(self, key, prompt_tokens,
                  thresholds: Union[float, Sequence[float]]) -> CascadeResult:
        """Execute the full cascade: a scalar threshold broadcasts to all
        boundaries (legacy two-tier call sites pass one float)."""
        n = self.num_stages
        ths = _normalize_thresholds(thresholds, n - 1)
        keys = jax.random.split(key, n)
        first = self._samplers[0](self.stages[0][1], keys[0], prompt_tokens)
        imgs0 = self.latent_to_image(first)
        conf0 = self.confidence(imgs0)
        outputs = np.asarray(imgs0)
        light_outputs = np.asarray(imgs0)
        stage_idx = np.zeros(len(conf0), dtype=np.int64)
        boundary_confs: List[np.ndarray] = [conf0]
        active = conf0 < ths[0]
        for i in range(1, n):
            if not bool(active.any()):
                break
            gen = self._samplers[i](self.stages[i][1], keys[i], prompt_tokens)
            imgs = np.asarray(self.latent_to_image(gen))
            outputs = np.where(active[:, None, None, None], imgs, outputs)
            stage_idx = np.where(active, i, stage_idx)
            if i < n - 1:
                conf = self.confidence(jnp.asarray(imgs))
                boundary_confs.append(np.asarray(conf))
                active = active & (np.asarray(conf) < ths[i])
            else:
                active = np.zeros_like(active)
        return CascadeResult(outputs=outputs, confidences=conf0,
                             deferred=stage_idx > 0,
                             light_outputs=light_outputs,
                             stage_index=stage_idx,
                             boundary_confidences=boundary_confs)


class LMCascade:
    """LM cascade (paper §5): an ordered list of same-family LM step
    callables; confidence = mean top-token probability of each stage's
    generation."""

    def __init__(self, *steps: Callable):
        """Each step(prompt_tokens) -> (tokens, logprobs) host callable,
        cheapest first."""
        if len(steps) == 1 and isinstance(steps[0], (list, tuple)):
            steps = tuple(steps[0])
        if len(steps) < 2:
            raise ValueError("an LM cascade needs >= 2 stages")
        self.steps: Tuple[Callable, ...] = tuple(steps)

    @property
    def light_step(self) -> Callable:
        return self.steps[0]

    @property
    def heavy_step(self) -> Callable:
        return self.steps[-1]

    def run_batch(self, prompt_tokens,
                  thresholds: Union[float, Sequence[float]]) -> CascadeResult:
        n = len(self.steps)
        ths = _normalize_thresholds(thresholds, n - 1)
        tokens, logprobs = self.steps[0](prompt_tokens)
        conf0 = np.exp(np.asarray(logprobs)).mean(axis=-1)
        outputs = np.asarray(tokens)
        light_outputs = np.asarray(tokens)
        stage_idx = np.zeros(len(conf0), dtype=np.int64)
        active = conf0 < ths[0]
        for i in range(1, n):
            if not bool(active.any()):
                break
            toks_i, logp_i = self.steps[i](prompt_tokens)
            outputs = np.where(active[:, None], np.asarray(toks_i), outputs)
            stage_idx = np.where(active, i, stage_idx)
            if i < n - 1:
                conf = np.exp(np.asarray(logp_i)).mean(axis=-1)
                active = active & (conf < ths[i])
            else:
                active = np.zeros_like(active)
        return CascadeResult(outputs=outputs, confidences=conf0,
                             deferred=stage_idx > 0,
                             light_outputs=light_outputs,
                             stage_index=stage_idx)
