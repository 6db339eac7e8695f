"""The benchmark's operation counts against XLA's cost analysis of the
served models' unfused path, compiled on the CPU (nothing runs)."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, registry

JOINT = registry.model("joint_kv_unet")

UNET = dict(image_size=32, in_channels=4, base_channels=64,
            channel_mults=[1, 2, 4], num_res_blocks=2, attn_resolutions=[8],
            num_heads=4, text_dim=64)
DISC = dict(in_channels=4, stem_channels=24,
            stages=[[24, 1, 1, 1], [48, 2, 2, 4], [64, 2, 2, 4],
                    [96, 2, 2, 4]],
            head_channels=256, num_classes=2, se_ratio=0.25, gn_groups=8)


def _xla_flops(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().cost_analysis()["flops"]


def test_unet_count_matches_xla_within_two_percent():
    """XLA also counts element-wise work, which is under 1% here; the
    benchmark counts the matmuls and convolutions (in-bounds taps)."""
    from repro.config.base import DiffusionConfig
    from repro.models.unet import apply_unet, init_unet
    cfg = DiffusionConfig(name="t", image_size=32, base_channels=64,
                          channel_mults=(1, 2, 4), attn_resolutions=(8,),
                          num_heads=4, text_dim=64)
    params = jax.eval_shape(lambda k: init_unet(k, cfg),
                            jax.random.PRNGKey(0))
    b = 2
    xla = _xla_flops(
        lambda p, x, t, tok: apply_unet(p, cfg, x, t, tok), params,
        jax.ShapeDtypeStruct((b, 32, 32, 4), jnp.float32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b, 8), jnp.int32))
    assert JOINT.unet_flops(UNET, b, 8) == pytest.approx(xla, rel=0.02)


def test_discriminator_count_is_its_convolutions():
    """The discriminator's element-wise work (GroupNorm, squeeze-excite,
    SiLU) is about 13% of XLA's total at 64x64; the benchmark's count
    leaves it out and keeps every convolution and the classifier."""
    from repro.models.efficientnet import (DiscriminatorConfig,
                                           apply_discriminator,
                                           init_discriminator)
    cfg = DiscriminatorConfig(in_channels=4)
    params = jax.eval_shape(lambda k: init_discriminator(k, cfg),
                            jax.random.PRNGKey(0))
    xla = _xla_flops(lambda p, x: apply_discriminator(p, cfg, x), params,
                     jax.ShapeDtypeStruct((2, 64, 64, 4), jnp.float32))
    ratio = flops.discriminator_flops(DISC, 2, 64) / xla
    assert 0.8 < ratio < 1.0


def _pallas_calls(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(eqn.invars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_calls(sub)
    return out


def test_kernel_inventories_match_the_served_model():
    """Every GroupNorm and attention kernel call of one UNet evaluation and
    of one discriminator pass, as the benchmark lists them from shapes."""
    from repro.config.base import DiffusionConfig
    from repro.models.efficientnet import (DiscriminatorConfig,
                                           apply_discriminator,
                                           init_discriminator)
    from repro.models.unet import apply_unet, init_unet
    cfg = DiffusionConfig(name="t", image_size=32, base_channels=64,
                          channel_mults=(1, 2, 4), attn_resolutions=(8,),
                          num_heads=4, text_dim=64)
    params = jax.eval_shape(lambda k: init_unet(k, cfg),
                            jax.random.PRNGKey(0))
    b = 2
    jaxpr = jax.make_jaxpr(
        lambda p, x, t, tok: apply_unet(p, cfg, x, t, tok, impl="interpret")
    )(params, jax.ShapeDtypeStruct((b, 32, 32, 4), jnp.float32),
      jax.ShapeDtypeStruct((b,), jnp.int32),
      jax.ShapeDtypeStruct((b, 8), jnp.int32))
    calls = _pallas_calls(jaxpr.jaxpr)
    gn = [c for c in calls if len(c) == 3 and c[0] == b]
    attn = [c for c in calls if len(c) == 3 and c[0] == b * 4]
    want_gn = JOINT.groupnorm_calls(UNET, b)
    assert sorted(gn) == sorted((bb, p, c) for bb, p, c in want_gn)
    want_attn = JOINT.attention_calls(UNET, b, 8)
    assert len(attn) == len(want_attn)
    dcfg = DiscriminatorConfig(in_channels=4)
    dparams = jax.eval_shape(lambda k: init_discriminator(k, dcfg),
                             jax.random.PRNGKey(0))
    djaxpr = jax.make_jaxpr(
        lambda p, x: apply_discriminator(p, dcfg, x, impl="interpret"))(
        dparams, jax.ShapeDtypeStruct((b, 64, 64, 4), jnp.float32))
    assert sorted(_pallas_calls(djaxpr.jaxpr)) == sorted(
        flops.discriminator_groupnorm_calls(DISC, b, 64))


def test_roofline_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert flops.roofline_seconds(50.0, 1.0, peak) == 0.5
    assert flops.roofline_seconds(1.0, 50.0, peak) == 5.0
    fl, nb = flops.attention_cost(1, 1, 256, 264, 128)
    assert fl == 4 * 256 * 264 * 128
    assert nb == 4 * 128 * (2 * 256 + 2 * 264)
