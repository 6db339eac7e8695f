"""EfficientNetV2-style discriminator (the paper's §3.2 design).

Binary classifier: 'real' (ground-truth images) vs 'fake' (diffusion
outputs). The softmax P(real) is the cascade confidence score. GroupNorm
replaces BatchNorm (stateless — TPU/serving friendly; noted in DESIGN.md).
``apply`` also returns penultimate features: they feed the FID* metric
(InceptionV3 is unavailable offline).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class DiscriminatorConfig:
    name: str = "efficientnet_s"
    in_channels: int = 3
    stem_channels: int = 24
    # (channels, depth, stride, expand) per stage — EfficientNetV2-S-ish,
    # scaled down for 32-64px inputs
    stages: Tuple[Tuple[int, int, int, int], ...] = (
        (24, 1, 1, 1), (48, 2, 2, 4), (64, 2, 2, 4), (96, 2, 2, 4))
    head_channels: int = 256
    num_classes: int = 2
    se_ratio: float = 0.25
    gn_groups: int = 8


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) \
        * math.sqrt(2.0 / fan_in)


def conv(x, w, stride=1, groups=1):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def groupnorm(x, scale, bias, groups):
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g).astype(jnp.float32)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * lax.rsqrt(var + 1e-5)
    return (xg.reshape(B, H, W, C) * scale + bias).astype(x.dtype)


def gn_act(x, p, groups, *, act=True, impl="xla"):
    """GroupNorm (+ optional SiLU) routed per ``impl``: "xla" keeps the
    original unfused ops (bit-identical baseline); anything else goes
    through ``kernels.ops.fused_groupnorm`` — "ref" selects its fused
    jnp oracle, "pallas"/"interpret" the Pallas kernel."""
    if impl == "xla":
        h = groupnorm(x, p["scale"], p["bias"], groups)
        return jax.nn.silu(h) if act else h
    from repro.kernels import ops
    return ops.fused_groupnorm(x, p["scale"], p["bias"], groups=groups,
                               act=act, impl="xla" if impl == "ref" else impl)


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _mbconv_init(key, cin, cout, expand, se_ratio):
    ks = jax.random.split(key, 5)
    mid = cin * expand
    p = {"gn0": _gn_init(cin)}
    if expand > 1:
        p["w_exp"] = _conv_init(ks[0], 1, 1, cin, mid)
        p["gn1"] = _gn_init(mid)
    p["w_dw"] = jax.random.normal(ks[1], (3, 3, 1, mid), jnp.float32) \
        * math.sqrt(2.0 / 9.0)
    p["gn2"] = _gn_init(mid)
    se = max(int(cin * se_ratio), 4)
    p["w_se1"] = _conv_init(ks[2], 1, 1, mid, se)
    p["w_se2"] = _conv_init(ks[3], 1, 1, se, mid)
    p["w_out"] = _conv_init(ks[4], 1, 1, mid, cout)
    p["gn3"] = _gn_init(cout)
    return p


def _mbconv_apply(p, x, stride, expand, gn_groups, impl="xla"):
    cin = x.shape[-1]
    h = gn_act(x, p["gn0"], gn_groups, act=False, impl=impl)
    if expand > 1:
        h = gn_act(conv(h, p["w_exp"]), p["gn1"], gn_groups, impl=impl)
    mid = h.shape[-1]
    h = conv(h, p["w_dw"], stride=stride, groups=mid)
    h = gn_act(h, p["gn2"], gn_groups, impl=impl)
    # squeeze-excite
    s = jnp.mean(h, axis=(1, 2), keepdims=True)
    s = jax.nn.silu(conv(s, p["w_se1"]))
    s = jax.nn.sigmoid(conv(s, p["w_se2"]))
    h = h * s
    h = conv(h, p["w_out"])
    if stride == 1 and h.shape[-1] == cin:
        h = h + x
    return h


def init_discriminator(key, cfg: DiscriminatorConfig):
    ks = jax.random.split(key, 3 + len(cfg.stages))
    p = {"stem": _conv_init(ks[0], 3, 3, cfg.in_channels, cfg.stem_channels),
         "stem_gn": _gn_init(cfg.stem_channels)}
    cin = cfg.stem_channels
    for i, (c, depth, stride, expand) in enumerate(cfg.stages):
        blocks = []
        bks = jax.random.split(ks[1 + i], depth)
        for d in range(depth):
            blocks.append(_mbconv_init(bks[d], cin if d == 0 else c, c,
                                       expand, cfg.se_ratio))
            cin = c
        p[f"stage{i}"] = blocks
    p["head"] = _conv_init(ks[-2], 1, 1, cin, cfg.head_channels)
    p["head_gn"] = _gn_init(cfg.head_channels)
    p["fc"] = jax.random.normal(ks[-1],
                                (cfg.head_channels, cfg.num_classes),
                                jnp.float32) / math.sqrt(cfg.head_channels)
    p["fc_b"] = jnp.zeros((cfg.num_classes,), jnp.float32)
    return p


@jax.named_scope("disc")
def apply_discriminator(params, cfg: DiscriminatorConfig, images,
                        impl="xla"):
    """images: (B, H, W, C) in [-1, 1]. Returns (logits (B,2),
    features (B, head_channels)). ``impl`` routes the GroupNorm+SiLU
    stacks (see ``gn_act``)."""
    x = gn_act(conv(images, params["stem"], stride=2), params["stem_gn"],
               cfg.gn_groups, impl=impl)
    for i, (c, depth, stride, expand) in enumerate(cfg.stages):
        for d, bp in enumerate(params[f"stage{i}"]):
            x = _mbconv_apply(bp, x, stride if d == 0 else 1, expand,
                              cfg.gn_groups, impl=impl)
    x = gn_act(conv(x, params["head"]), params["head_gn"], cfg.gn_groups,
               impl=impl)
    feats = jnp.mean(x, axis=(1, 2))
    logits = feats @ params["fc"] + params["fc_b"]
    return logits, feats


def confidence_score(params, cfg: DiscriminatorConfig, images, impl="xla"):
    """P('real') — the paper's confidence score (softmax over 2 classes)."""
    logits, _ = apply_discriminator(params, cfg, images, impl=impl)
    return jax.nn.softmax(logits, axis=-1)[:, 1]
