"""Pallas TPU kernel: fused GroupNorm (+ optional SiLU) for conv stages.

The UNet/discriminator hot path is GroupNorm -> SiLU everywhere; the XLA
path materializes the fp32 (B, H, W, g, C//g) intermediate, the rsqrt
normalization, and the separate silu HLO. This kernel does it in one
``pallas_call`` over grid = (B, phase, HW tile):

  * phase 0 reads each (T, C) tile of the sample and folds its
    per-channel mean and centred sum of squares into running (1, C)
    accumulators (Chan's parallel update, lane-dense over C). After the
    last tile the channel statistics are pooled into their groups with
    masked lane reductions on the (1, C) vectors — never through
    per-group lane slices, which pad each group to a full 128-lane tile
    and overflowed VMEM at 64x64 latents.
  * phase 1 re-reads the tiles and writes normalise * scale + bias
    (+ SiLU).

The output block index stays at tile 0 through phase 0, so nothing is
written back until phase 1 has filled it. When one sample fits a block
(``T == HW``) the input block index never changes and the sample is
read from HBM once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of one (T, C) f32 tile with C padded to whole 128-lane vregs. With
# the input and output double-buffered and a few tile-sized temporaries,
# the kernel needs about ten tiles of VMEM.
_TILE_BYTES = 1 << 20
_VMEM_LIMIT_BYTES = 32 << 20


def _tile_rows(hw: int, c: int) -> int:
    """Rows per HW tile: the whole sample when it fits ``_TILE_BYTES``,
    else the largest divisor of ``hw`` below the budget that is a
    multiple of 8 (the sublane tiling)."""
    lanes = -(-c // 128) * 128
    budget = max(_TILE_BYTES // (4 * lanes), 8)
    if hw <= budget:
        return hw
    for t in range(budget - budget % 8, 7, -8):
        if hw % t == 0:
            return t
    return hw


def _gn_kernel(x_ref, s_ref, b_ref, o_ref, mean_acc, m2_acc, *,
               groups: int, eps: float, act: bool, rows: int, hw: int):
    phase = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(phase == 0)
    def _stats():
        x = x_ref[0].astype(jnp.float32)                    # (T, C)
        tile_mean = jnp.sum(x, axis=0, keepdims=True) * (1.0 / rows)
        tile_m2 = jnp.sum(jnp.square(x - tile_mean), axis=0, keepdims=True)

        @pl.when(t == 0)
        def _first():
            mean_acc[...] = tile_mean
            m2_acc[...] = tile_m2

        @pl.when(t > 0)
        def _fold():
            # Chan et al.: merge n_a = t*rows seen rows with this tile
            n_a = t.astype(jnp.float32) * rows
            frac = rows / (n_a + rows)
            delta = tile_mean - mean_acc[...]
            mean_acc[...] += delta * frac
            m2_acc[...] += tile_m2 + jnp.square(delta) * (n_a * frac)

        @pl.when(t == nt - 1)
        def _pool_groups():
            mu = mean_acc[...]                              # (1, C)
            m2 = m2_acc[...]
            c = mu.shape[-1]
            cg = c // groups
            lane = jax.lax.broadcasted_iota(jnp.int32, mu.shape, 1)
            g_mean = jnp.zeros_like(mu)
            g_var = jnp.zeros_like(mu)
            for j in range(groups):                         # static unroll
                in_g = (lane >= j * cg) & (lane < (j + 1) * cg)
                m = jnp.sum(jnp.where(in_g, mu, 0.0), axis=-1,
                            keepdims=True) * (1.0 / cg)     # (1, 1)
                ss = jnp.sum(jnp.where(
                    in_g, m2 + hw * jnp.square(mu - m), 0.0), axis=-1,
                    keepdims=True)
                g_mean = jnp.where(in_g, m, g_mean)
                g_var = jnp.where(in_g, ss * (1.0 / (hw * cg)), g_var)
            mean_acc[...] = g_mean
            m2_acc[...] = jax.lax.rsqrt(g_var + eps)        # now rstd

    @pl.when(phase == 1)
    def _normalise():
        x = x_ref[0].astype(jnp.float32)
        y = (x - mean_acc[...]) * m2_acc[...] \
            * s_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
        if act:
            y = y * jax.nn.sigmoid(y)                       # silu
        o_ref[0] = y.astype(o_ref.dtype)


def fused_groupnorm(x, scale, bias, *, groups: int, act: bool = True,
                    eps: float = 1e-5, interpret: bool = False):
    """x: (B, ..., C) — spatial dims are flattened per sample. ``groups``
    shrinks to the largest divisor of C at or below the request (the
    same rule as ``models/efficientnet.groupnorm``). ``act`` fuses the
    trailing SiLU."""
    shape = x.shape
    B, C = shape[0], shape[-1]
    g = min(groups, C)
    while C % g:
        g -= 1
    xf = x.reshape(B, -1, C)
    hw = xf.shape[1]
    rows = _tile_rows(hw, C)
    out = pl.pallas_call(
        functools.partial(_gn_kernel, groups=g, eps=eps, act=act,
                          rows=rows, hw=hw),
        grid=(B, 2, hw // rows),
        in_specs=[pl.BlockSpec((1, rows, C), lambda i, p, t: (i, t, 0)),
                  pl.BlockSpec((1, C), lambda i, p, t: (0, 0)),
                  pl.BlockSpec((1, C), lambda i, p, t: (0, 0))],
        out_specs=pl.BlockSpec((1, rows, C), lambda i, p, t: (i, t * p, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hw, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xf, scale.reshape(1, C), bias.reshape(1, C))
    return out.reshape(shape)
