"""jit'd dispatch wrappers for every kernel.

On TPU: the Pallas kernel. On CPU: interpret mode (kernel body executed in
Python — correctness path used by the shape/dtype sweep tests) or the XLA
reference for speed. ``impl`` overrides: "pallas" | "interpret" | "xla".
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import whole_key_attention as _whole_key
from repro.kernels.fused_groupnorm import fused_groupnorm as _groupnorm
from repro.kernels.fused_rmsnorm import fused_rmsnorm as _rmsnorm
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.mlstm_chunk import mlstm_chunk as _mlstm
from repro.kernels.swiglu import swiglu as _swiglu


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if _on_tpu() else "xla"


@functools.partial(jax.jit, static_argnames=("causal", "impl", "block_q",
                                             "block_k", "kv_len"))
def flash_attention(q, k, v, *, causal=True, impl="auto",
                    block_q=128, block_k=128, kv_len=None):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, kv_len=kv_len)
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  kv_len=kv_len, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_q"))
def whole_key_attention(q, k, v, *, impl="auto", block_q=128):
    """Non-causal attention on the whole-key schedule (see
    ``flash_attention.attention_plan``)."""
    mode = _resolve(impl)
    if mode == "xla":
        return ref.flash_attention_ref(q, k, v, causal=False)
    return _whole_key(q, k, v, block_q=block_q,
                      interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_k"))
def decode_attention(q, k, v, valid_len, *, impl="auto", block_k=512):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.decode_attention_ref(q, k, v, valid_len)
    return _decode(q, k, v, valid_len, block_k=block_k,
                   interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("eps", "impl", "has_residual"))
def _fused_rmsnorm_impl(x, scale, residual, *, eps, impl, has_residual):
    mode = _resolve(impl)
    if mode == "xla":
        if has_residual:
            s = x.astype(jax.numpy.float32) + residual.astype(
                jax.numpy.float32)
            return (ref.rmsnorm_ref(x, scale, eps=eps, residual=residual),
                    s.astype(x.dtype))
        return ref.rmsnorm_ref(x, scale, eps=eps)
    return _rmsnorm(x, scale, residual=residual if has_residual else None,
                    eps=eps, interpret=(mode == "interpret"))


def fused_rmsnorm(x, scale, *, residual=None, eps=1e-5, impl="auto"):
    return _fused_rmsnorm_impl(x, scale,
                               residual if residual is not None else x,
                               eps=eps, impl=impl,
                               has_residual=residual is not None)


@functools.partial(jax.jit, static_argnames=("groups", "act", "eps", "impl"))
def fused_groupnorm(x, scale, bias, *, groups, act=True, eps=1e-5,
                    impl="auto"):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.groupnorm_silu_ref(x, scale, bias, groups=groups, eps=eps,
                                      act=act)
    return _groupnorm(x, scale, bias, groups=groups, act=act, eps=eps,
                      interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def swiglu(gate, up, *, impl="auto"):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.swiglu_ref(gate, up)
    return _swiglu(gate, up, interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def mamba_scan(u, dt, A, B, C, D, *, impl="auto", chunk=64):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.mamba_scan_ref(u, dt, A, B, C, D)
    return _mamba(u, dt, A, B, C, D, chunk=chunk,
                  interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def mlstm_chunk(q, k, v, i_pre, f_pre, *, impl="auto", chunk=64):
    mode = _resolve(impl)
    if mode == "xla":
        return ref.mlstm_chunk_ref(q, k, v, i_pre, f_pre)
    return _mlstm(q, k, v, i_pre, f_pre, chunk=chunk,
                  interpret=(mode == "interpret"))
