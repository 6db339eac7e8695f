"""Pallas TPU kernels: GQA attention, in two schedules that share no logic.

Online (causal prefill, and any call the whole-key schedule cannot hold):
grid = (batch*kv_heads*q_groups, Sq/BQ, Skv/BK); the KV axis is the
innermost (sequential on TPU) grid dim, carrying the online-softmax state
(m, l, acc) in VMEM scratch. Block sizes default to 128 (MXU-aligned); K/V
stream through VMEM in (BK, D) tiles so the working set is
O(BQ*D + BK*D + BQ*BK) regardless of sequence length.

Whole-key (non-causal, K/V small enough to sit in VMEM): grid =
(batch*kv_heads*q_groups, Sq/BQ); each step takes one query block against
its head's whole (Sk, D) key axis, whose block index ignores the query
block, so K/V are fetched once per head. One softmax pass per step: no
running max, no rescale, no padding or mask. ``attention_plan`` picks the
schedule from a call's shapes.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_len: int,
                  causal: bool, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: skip blocks fully above the diagonal; padded KV: skip
    # blocks entirely past the valid prefix
    run = (not causal) or (k_start <= q_start + block_q - 1)
    if kv_len is not None:
        run = jnp.logical_and(run, k_start < kv_len)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0].astype(jnp.float32)            # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len is not None:
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    kv_len=None, interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H = KH*G. Causal assumes
    q and k cover the same positions (prefill). ``kv_len`` marks k/v rows
    at or past that index as padding (masked out of the softmax) so
    callers can pad Sk up to a block multiple."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    if kv_len is not None and not 0 < kv_len <= Sk:
        raise ValueError(f"kv_len={kv_len} outside (0, {Sk}]")

    # layout: fold (B, KH, G) into the leading grid dim
    qr = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KH * G, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)

    grid = (B * KH * G, Sq // block_q, Sk // block_k)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, seq_len=Sk, causal=causal,
                          kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // G, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KH * G, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KH, G, Sq, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# Whole-key schedule and the plan that picks a schedule
# ---------------------------------------------------------------------------
ONLINE = "online"
WHOLE_KEY = "whole_key"
# VMEM the whole-key kernel may hold, and the scoped limit it compiles
# under (a v5e core has 128 MiB of VMEM; the compiler's default scope is
# 16 MiB).
WHOLE_KEY_VMEM_BUDGET = 32 << 20
WHOLE_KEY_BLOCKS_Q = (512, 256, 128)


class AttentionPlan(NamedTuple):
    """How one attention call runs: the schedule, its blocks, the padded
    row counts the kernel sees, and its grid steps per (batch, head)."""
    schedule: str
    block_q: int
    block_k: int
    sq: int
    sk: int
    steps: int


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def whole_key_vmem_bytes(block_q: int, sk: int, d: int, itemsize: int) -> int:
    """VMEM of one whole-key step: the q, k, v and output blocks, each
    double-buffered by the pipeline and padded to (8, 128) tiles, plus the
    float32 score and probability tiles (block_q, sk)."""
    lanes = _round_up(d, 128)
    blocks = 2 * (2 * block_q + 2 * _round_up(sk, 8)) * lanes * itemsize
    return blocks + 2 * block_q * _round_up(sk, 128) * 4


def attention_plan(causal: bool, sq: int, sk: int, d: int,
                   itemsize: int) -> AttentionPlan:
    """Pick the schedule for an attention call from its shapes.

    Non-causal calls whose whole K/V and score tile fit
    ``WHOLE_KEY_VMEM_BUDGET`` take the whole-key schedule at the query
    block that fits with the fewest padded query rows, the largest among
    equals; Sk is never padded. Every other call takes the online
    schedule with 128x128 blocks, Sq and Sk padded to block multiples
    (the caller masks padded keys with ``kv_len``)."""
    fits = [] if causal else [
        min(bq, sq) for bq in WHOLE_KEY_BLOCKS_Q
        if whole_key_vmem_bytes(min(bq, sq), sk, d, itemsize)
        <= WHOLE_KEY_VMEM_BUDGET]
    if fits:
        bq = min(fits, key=lambda b: (_round_up(sq, b), -b))
        sq_p = _round_up(sq, bq)
        return AttentionPlan(WHOLE_KEY, bq, sk, sq_p, sk, sq_p // bq)
    bq, bk = min(DEFAULT_BQ, sq), min(DEFAULT_BK, sk)
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)
    return AttentionPlan(ONLINE, bq, bk, sq_p, sk_p,
                         (sq_p // bq) * (sk_p // bk))


def _whole_key_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float):
    q = q_ref[0].astype(jnp.float32) * scale        # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                # (Sk, D)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.sum(p, axis=1, keepdims=True)).astype(o_ref.dtype)


def whole_key_attention(q, k, v, *, block_q: int, interpret: bool = False):
    """Non-causal attention, one softmax pass over the whole key axis per
    query block. q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H = KH*G;
    Sq a multiple of ``block_q`` (or equal to it)."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    assert Sq % block_q == 0, (Sq, block_q)
    qr = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KH * G, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
    out = pl.pallas_call(
        functools.partial(_whole_key_kernel, scale=1.0 / math.sqrt(D)),
        grid=(B * KH * G, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi: (b, qi, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, qi: (b // G, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, qi: (b // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KH * G, Sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=WHOLE_KEY_VMEM_BUDGET),
        name="flash_attention",
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KH, G, Sq, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, D)
