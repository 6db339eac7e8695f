"""The served path's Pallas kernels compile for a TPU v5e at the default
``DiffusionConfig`` width (64x64x4 latent, base 128, attention at 16x16)
and the top batch bucket. Nothing runs: the TPU compiler builds each
kernel for a described v5e:2x2 chip that need not be attached, so VMEM
refusals and unaligned tiles fail here instead of on the chip.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import (WHOLE_KEY, attention_plan,
                                           flash_attention,
                                           whole_key_attention)
from repro.kernels.fused_groupnorm import fused_groupnorm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes its logs under /tmp unless told not to
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e device, with the persistent compilation
    cache off: compiles for a described chip are written to it but cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


# UNet GroupNorms at batch 8: the 64x64 level (128 channels, 256 where a
# skip is concatenated), the 32x32 level's 384-channel concat, and the
# 16x16 level's 1024-channel concat.
@pytest.mark.parametrize("shape", [
    (8, 64, 64, 128), (8, 64, 64, 256), (8, 32, 32, 384),
    (8, 16, 16, 1024)])
def test_fused_groupnorm_compiles_for_v5e(one_chip, shape):
    c = shape[-1]
    fn = functools.partial(fused_groupnorm, groups=8, act=True)
    compiled = _compile(fn, [shape, (c,), (c,)], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


# Pixel self+cross attention at the 16x16 level: Sq = 256 pixels,
# Sk = 256 + 8 prompt tokens padded to the 128-row block (384) with the
# padded K/V rows masked by kv_len; 4 heads of 128.
@pytest.mark.parametrize("sq", [256, 384])
def test_flash_attention_compiles_for_v5e(one_chip, sq):
    fn = functools.partial(flash_attention, causal=False, kv_len=264)
    compiled = _compile(fn, [(8, sq, 4, 128), (8, 384, 4, 128),
                             (8, 384, 4, 128)], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


# The whole-key schedule at cascade2-sd15's four attention shapes, batch
# 8 and 8 heads: (query rows, pixels + 8 prompt tokens, head size), at
# the query block the plan picks, K/V unpadded.
@pytest.mark.parametrize("sq,sk,d", [
    (4096, 4104, 40), (1024, 1032, 80), (256, 264, 160), (64, 72, 160)])
def test_whole_key_attention_compiles_for_v5e(one_chip, sq, sk, d):
    plan = attention_plan(False, sq, sk, d, 4)
    assert plan.schedule == WHOLE_KEY
    fn = functools.partial(whole_key_attention, block_q=plan.block_q)
    compiled = _compile(fn, [(8, sq, 8, d), (8, sk, 8, d), (8, sk, 8, d)],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_named_scopes_keep_the_kernel_names(one_chip):
    """The UNet's named scopes change op metadata only: the kernels'
    instructions keep the names a trace reduction matches them by."""
    from repro.models.unet import _attn, _groupnorm

    def fn(x, scale, bias, w):
        p = {"scale": scale, "bias": bias}
        h = _groupnorm(x, p, 8, impl="pallas")
        ap = {"gn": p, **{k: w for k in ("wq", "wk", "wv", "wo")},
              "ck": w[:8], "cv": w[:8]}
        ctx = jnp.zeros((x.shape[0], 8, 8), x.dtype)
        return _attn(ap, h, ctx, 1, impl="pallas")
    text = _compile(fn, [(1, 16, 16, 128), (128,), (128,), (128, 128)],
                    one_chip).as_text()
    names = [line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert {"fused_groupnorm", "flash_attention"} <= set(names)
    # attention_roofline reads one flash_attention event per attention
    assert names.count("flash_attention") == 1
    assert "/attn/" in text and "/groupnorm/" in text
