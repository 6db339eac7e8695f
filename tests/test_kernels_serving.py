"""The kernel-wired serving hot path: UNet/discriminator parity across
kernel impls (Pallas-interpret / fused jnp oracle / unfused xla
baseline), the flash kv_len padding mask, shape-bucketed batching
(compile counts bounded by the bucket ladder, padded rows masked out of
outputs and discriminator scores), and the ``_run_stage`` compile-time
leak regression pin."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import DiffusionConfig
from repro.core.cascade import DiffusionCascade
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (ONLINE, WHOLE_KEY,
                                           WHOLE_KEY_VMEM_BUDGET,
                                           attention_plan)
from repro.kernels.impls import bucket_for
from repro.models.efficientnet import (DiscriminatorConfig,
                                       apply_discriminator,
                                       init_discriminator)
from repro.models.unet import _fused_attn, apply_unet, init_unet
from repro.serving.baselines import make_profiles
from repro.serving.cluster import ClusterBackend, ClusterRuntime
from repro.serving.profiles import default_serving

KEY = jax.random.PRNGKey(0)
TOL = dict(atol=3e-5, rtol=3e-5)


def _unet_cfg(image_size=8, attn=(8,), steps=1, name="t0"):
    return DiffusionConfig(
        name=name, image_size=image_size, in_channels=3, base_channels=8,
        channel_mults=(1,), num_res_blocks=1, attn_resolutions=attn,
        num_heads=2, num_steps=steps, text_dim=16)


def _disc_cfg():
    return DiscriminatorConfig(stages=((16, 1, 1, 1), (24, 1, 2, 4)),
                               head_channels=32, in_channels=3)


# ---------------------------------------------------------------------------
# Kernel-level parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,groups,act", [
    ((3, 4, 4, 16), 8, True),     # conv feature map, fused silu
    ((3, 4, 4, 16), 8, False),    # attention pre-norm (no act)
    ((2, 6, 6, 10), 8, True),     # group shrink: 10 % 8 -> g=5
    ((5, 8, 24), 4, True),        # pre-flattened (B, HW, C)
    ((2, 64, 64, 12), 8, True),   # 64x64: two HW tiles, group shrink g=6
    ((1, 64, 64, 16), 8, False),  # 64x64: two HW tiles, no act
])
def test_fused_groupnorm_parity(shape, groups, act):
    x = jax.random.normal(KEY, shape, jnp.float32)
    s = jnp.linspace(0.5, 1.5, shape[-1]).astype(jnp.float32)
    b = jnp.linspace(-0.2, 0.2, shape[-1]).astype(jnp.float32)
    want = ref.groupnorm_silu_ref(x, s, b, groups=groups, act=act)
    for impl in ("interpret", "xla"):
        out = ops.fused_groupnorm(x, s, b, groups=groups, act=act, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


@pytest.mark.parametrize("Sq,Sk,kv", [
    (128, 256, 132),     # padded K/V: mask covers the whole tail block
    (128, 128, 72),      # padding inside a single block
])
def test_flash_attention_kv_len_mask(Sq, Sk, kv):
    """kv_len must reproduce attention over only the first kv rows — the
    contract the padded non-causal UNet attention path relies on."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, Sq, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, Sk, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, Sk, 2, 16), jnp.float32)
    want = ref.flash_attention_ref(q, k[:, :kv], v[:, :kv], causal=False)
    for impl in ("interpret", "xla"):
        out = ops.flash_attention(q, k, v, causal=False, kv_len=kv,
                                  impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


# ---------------------------------------------------------------------------
# Model-level parity (the wired hot path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("batch", [1, 3])   # odd batch exercises padding
def test_unet_impl_parity(impl, batch):
    cfg = _unet_cfg()
    params = init_unet(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 8, 8, 3))
    t = jnp.zeros((batch,), jnp.int32)
    toks = (jnp.arange(batch * 4).reshape(batch, 4) * 37) % 1024
    base = apply_unet(params, cfg, x, t, toks, impl="xla")
    out = apply_unet(params, cfg, x, t, toks, impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=5e-5, rtol=5e-5)


def test_unet_attention_padded_kv_path():
    """image 16 + ctx 4 gives Sk=260 — a multiple of neither 8 nor the
    online schedule's 128-row block: the whole-key schedule must run it
    unpadded and still match the einsum baseline."""
    cfg = _unet_cfg(image_size=16, attn=(16,), name="t16")
    params = init_unet(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 16, 3))
    t = jnp.zeros((1,), jnp.int32)
    toks = jnp.arange(4).reshape(1, 4) % 1024
    base = apply_unet(params, cfg, x, t, toks, impl="xla")
    out = apply_unet(params, cfg, x, t, toks, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,Sq,Sk,H,D,schedule", [
    (2, 256, 264, 2, 40, WHOLE_KEY),  # 64x64's kind: Sk = Sq + 8 prompt rows
    (1, 96, 100, 3, 16, WHOLE_KEY),   # Sk not a multiple of 8
    # K/V past the whole-key VMEM budget fall back to the online kernel
    # with Sk padded to 128 rows and the tail masked by kv_len
    (1, 8, WHOLE_KEY_VMEM_BUDGET // (4 * 128 * 4) + 8, 1, 8, ONLINE),
])
def test_fused_attn_schedules(B, Sq, Sk, H, D, schedule):
    """``_fused_attn`` takes the schedule the plan picks from the shapes
    and matches the oracle on either one."""
    assert attention_plan(False, Sq, Sk, D, 4).schedule == schedule
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, H, D), jnp.float32)
    out = _fused_attn(q, k, v, "interpret")
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_discriminator_impl_parity(impl):
    cfg = _disc_cfg()
    params = init_discriminator(KEY, cfg)
    imgs = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 16, 3))
    base, _ = apply_discriminator(params, cfg, imgs, impl="xla")
    out, _ = apply_discriminator(params, cfg, imgs, impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# Shape-bucketed batching
# ---------------------------------------------------------------------------
def test_bucket_for_ladder():
    buckets = (1, 2, 4, 8)
    assert [bucket_for(n, buckets) for n in range(1, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 8]
    assert bucket_for(9, buckets) == 16     # past the ladder: ceil to top
    assert bucket_for(3, ()) == 3           # () disables bucketing


@pytest.fixture(scope="module")
def bucketed_cascade():
    stages = []
    for i in range(2):
        cfg = _unet_cfg(name=f"b{i}", steps=1 + i)
        stages.append((cfg, init_unet(jax.random.PRNGKey(i), cfg)))
    dcfg = _disc_cfg()
    dparams = init_discriminator(jax.random.PRNGKey(9), dcfg)
    return DiffusionCascade(stages, dcfg, dparams, kernel_impl="xla",
                            batch_buckets=(1, 2, 4, 8))


def test_batch_sweep_compiles_at_most_one_program_per_bucket(
        bucketed_cascade):
    """Serving batches 1..8 must reuse O(#buckets) compiled programs per
    stage (and for the discriminator scorer), not one per batch size."""
    casc = bucketed_cascade
    for n in range(1, 9):
        toks = (jnp.arange(n * 4).reshape(n, 4) * 13) % 1024
        for cfg, fn, params in casc.stage_fns():
            out = fn(params, jax.random.PRNGKey(n), toks)
            assert out.shape[0] == n        # sliced back to the true batch
        casc.confidence(jnp.zeros((n, 8, 8, 3)))
    assert all(c <= 4 for c in casc.compile_counts()), casc.compile_counts()


def test_padded_rows_masked_out_of_scores(bucketed_cascade):
    """An odd batch pads to its bucket; the returned scores must be the
    real rows' scores only, matching an unbucketed evaluation."""
    casc = bucketed_cascade
    imgs = jax.random.normal(jax.random.PRNGKey(4), (3, 8, 8, 3))
    got = casc.confidence(imgs)
    plain = DiffusionCascade(casc.stages, casc.disc_cfg, casc.disc_params)
    want = plain.confidence(imgs)
    assert got.shape == (3,)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_configure_kernels_is_idempotent(bucketed_cascade):
    casc = bucketed_cascade
    fn = casc._inner_samplers[0]
    casc.configure_kernels("xla", (1, 2, 4, 8))
    assert casc._inner_samplers[0] is fn    # same plan: no jit rebuild


# ---------------------------------------------------------------------------
# Serving integration: plan threading + the compile-leak regression pin
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_runtime(bucketed_cascade):
    sv = default_serving("sdturbo", num_workers=2, batch_choices=(1, 2),
                         kernel_impl="xla", batch_buckets=(1, 2, 4, 8))
    return ClusterRuntime(bucketed_cascade, sv), sv


def test_runtime_applies_serving_kernel_plan(bucketed_cascade):
    sv = default_serving("sdturbo", num_workers=2, kernel_impl="ref",
                         batch_buckets=(1, 4))
    ClusterRuntime(bucketed_cascade, sv)
    assert bucketed_cascade.kernel_impl == "ref"
    assert bucketed_cascade.batch_buckets == (1, 4)
    # restore the module-scoped fixture's plan for later tests
    bucketed_cascade.configure_kernels("xla", (1, 2, 4, 8))


def test_measure_profile_excludes_compile(toy_runtime):
    """Timed repeats must run entirely on warm programs: compile counts
    may not move while measurement is in flight."""
    rt, _ = toy_runtime
    pre = rt.cascade.compile_counts()
    prof = rt.measure_profile(batches=(1, 2), repeats=2)
    assert len(prof) == 2 and all(p.base_s > 0 for p in prof)
    post = rt.cascade.compile_counts()
    # warms may add programs, but both sweeps fit inside the ladder
    assert all(c <= 4 for c in post), (pre, post)


def test_run_stage_compile_leak_pinned(toy_runtime):
    """Regression pin: the first ``_run_stage`` at a fresh (tier, bucket)
    used to time XLA compilation into the recorded wall (the planner then
    fit e(b) from walls 100x steady state). Now the backend warms the
    bucket untimed, so the first timed wall must be comparable to the
    second — and no compile may land between the two timed calls."""
    rt, sv = toy_runtime
    profiles = make_profiles(sv, 0)
    backend = ClusterBackend(rt, sv, profiles, seed=0, model_load_s=0.0)
    sl = rt.slices[0]
    # bucket 4 was never executed by measure_profile (batches (1, 2))
    w1, imgs1 = backend._run_stage(sl, 0, 3)
    counts = rt.cascade.compile_counts()
    w2, _ = backend._run_stage(sl, 0, 3)
    assert rt.cascade.compile_counts() == counts   # no compile mid-stream
    assert imgs1.shape[0] == 3
    # a leaked compile inflates w1 by ~hundreds of ms on this model size;
    # 5x + scheduling slack separates it cleanly from warm-run jitter
    assert w1 <= 5 * w2 + 0.1, (w1, w2)
