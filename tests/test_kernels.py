"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracles in kernels/ref.py (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (ONLINE, WHOLE_KEY,
                                           WHOLE_KEY_VMEM_BUDGET,
                                           attention_plan,
                                           whole_key_vmem_bytes)

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,D,bq,bk", [
    (1, 64, 4, 4, 32, 32, 32),     # MHA
    (2, 128, 4, 2, 32, 64, 64),    # GQA
    (1, 128, 8, 1, 16, 128, 32),   # MQA, uneven blocks
    # bk None: the whole-key schedule, non-causal, as the UNet calls it:
    # S pixels against S + 8 keys (pixels and prompt tokens, not a
    # multiple of 128), query blocks smaller than S so K/V stay resident
    # across them, the UNet's head sizes
    (2, 256, 2, 2, 40, 64, None),
    (1, 128, 3, 3, 80, 32, None),
    (2, 64, 4, 2, 160, 32, None),  # GQA
])
def test_flash_attention(dtype, B, S, H, KH, D, bq, bk):
    ks = jax.random.split(KEY, 3)
    sk = S if bk else S + 8
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, sk, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, sk, KH, D), dtype)
    if bk:
        out = ops.flash_attention(q, k, v, impl="interpret",
                                  block_q=bq, block_k=bk)
    else:
        out = ops.whole_key_attention(q, k, v, impl="interpret", block_q=bq)
    want = ref.flash_attention_ref(q, k, v, causal=bool(bk))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# The ten attentions of one cascade2-sd15 UNet evaluation (heads of
# 8): (query rows, key rows = pixels + 8 prompt tokens, head size, calls).
SD15_ATTENTIONS = [(4096, 4104, 40, 3), (1024, 1032, 80, 3),
                   (256, 264, 160, 3), (64, 72, 160, 1)]


def test_attention_plan():
    """Every cascade2-sd15 attention takes the whole-key schedule, which
    cuts a bucket-8 evaluation's grid steps from the online schedule's
    217,792 to 2,176; causal calls and key axes past the VMEM budget keep
    the online 128x128 schedule, padded to block multiples."""
    bh = 8 * 8
    new = old = 0
    for sq, sk, d, n in SD15_ATTENTIONS:
        plan = attention_plan(False, sq, sk, d, 4)
        assert plan.schedule == WHOLE_KEY
        assert (plan.sq, plan.sk, plan.block_k) == (sq, sk, sk)
        assert sq % plan.block_q == 0
        assert whole_key_vmem_bytes(plan.block_q, sk, d, 4) \
            <= WHOLE_KEY_VMEM_BUDGET
        new += n * bh * plan.steps
        old += n * bh * attention_plan(True, sq, sk, d, 4).steps
    assert (old, new) == (217_792, 2_176)

    causal = attention_plan(True, 4096, 4096, 40, 4)
    assert causal == (ONLINE, 128, 128, 4096, 4096, 32 * 32)
    # K and V alone, double-buffered, outgrow the budget
    sk = WHOLE_KEY_VMEM_BUDGET // (4 * 128 * 4) + 8
    long_keys = attention_plan(False, 256, sk, 40, 4)
    sk_p = -(-sk // 128) * 128
    assert long_keys == (ONLINE, 128, 128, 256, sk_p, 2 * sk_p // 128)
    # one block covers a short axis: no padding on either schedule
    assert attention_plan(True, 72, 72, 16, 4).steps == 1
    assert attention_plan(False, 64, 68, 4, 4)[:5] == (WHOLE_KEY, 64, 68,
                                                       64, 68)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,D,T,bk", [
    (2, 4, 2, 32, 256, 64),
    (1, 8, 8, 16, 128, 128),
    (3, 6, 1, 64, 192, 64),
])
def test_decode_attention(dtype, B, H, KH, D, T, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, T, KH, D), dtype)
    vl = jnp.asarray(np.random.default_rng(0).integers(1, T + 1, B),
                     jnp.int32)
    out = ops.decode_attention(q, k, v, vl, impl="interpret", block_k=bk)
    want = ref.decode_attention_ref(q, k, v, vl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 64), (4, 16, 96), (2, 3, 5, 128)])
def test_fused_rmsnorm(dtype, shape):
    x = jax.random.normal(KEY, shape, dtype)
    s = jnp.linspace(0.5, 1.5, shape[-1]).astype(jnp.float32)
    out = ops.fused_rmsnorm(x, s, impl="interpret")
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # with residual: returns (normed, sum)
    r = jax.random.normal(jax.random.PRNGKey(9), shape, dtype)
    o2, res = ops.fused_rmsnorm(x, s, residual=r, impl="interpret")
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(ref.rmsnorm_ref(x, s, residual=r),
                                          np.float32), **_tol(dtype))
    np.testing.assert_allclose(
        np.asarray(res, np.float32),
        np.asarray(x, np.float32) + np.asarray(r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(16, 128), (2, 8, 256), (64, 512)])
def test_swiglu(dtype, shape):
    g = jax.random.normal(KEY, shape, dtype)
    u = jax.random.normal(jax.random.PRNGKey(5), shape, dtype)
    out = ops.swiglu(g, u, impl="interpret")
    want = ref.swiglu_ref(g, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("Bt,T,E,N,chunk", [
    (1, 32, 16, 4, 8), (2, 64, 32, 8, 16), (1, 48, 8, 16, 12)])
def test_mamba_scan(Bt, T, E, N, chunk):
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (Bt, T, E)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, E))) * 0.1
    A = -jnp.abs(jax.random.normal(ks[2], (E, N)))
    B = jax.random.normal(ks[3], (Bt, T, N)) * 0.3
    C = jax.random.normal(ks[4], (Bt, T, N)) * 0.3
    D = jnp.ones((E,))
    out = ops.mamba_scan(u, dt, A, B, C, D, impl="interpret", chunk=chunk)
    want = ref.mamba_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,T,H,dh,chunk", [
    (1, 16, 2, 8, 4), (2, 32, 2, 16, 8), (1, 24, 4, 8, 6)])
def test_mlstm_chunk(B, T, H, dh, chunk):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, T, H, dh))
    k = jax.random.normal(ks[1], (B, T, H, dh)) * dh ** -0.5
    v = jax.random.normal(ks[2], (B, T, H, dh))
    ip = jax.random.normal(ks[3], (B, T, H))
    fp = jax.random.normal(ks[4], (B, T, H)) + 2.0
    out = ops.mlstm_chunk(q, k, v, ip, fp, impl="interpret", chunk=chunk)
    want = ref.mlstm_chunk_ref(q, k, v, ip, fp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


def test_xla_fallback_matches():
    """The ops-layer XLA path equals the oracle (the dry-run uses it)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 16))
    k = jax.random.normal(ks[1], (2, 32, 2, 16))
    v = jax.random.normal(ks[2], (2, 32, 2, 16))
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v, impl="xla")),
        np.asarray(ref.flash_attention_ref(q, k, v)), atol=1e-6)
