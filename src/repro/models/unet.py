"""Latent-diffusion UNet — the served model class of the paper.

ResBlocks (GroupNorm+SiLU) with timestep embedding, self+cross attention at
the configured resolutions, text conditioning via a toy prompt encoder.
Light variants = smaller width + 1-step sampling (SD-Turbo/SDXS analogues);
heavy variants = wider + 50-step DDIM (SDv1.5/SDXL analogues).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config.base import DiffusionConfig
from repro.models.efficientnet import _conv_init, _gn_init, conv, gn_act


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10_000) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _dense_init(key, cin, cout):
    return jax.random.normal(key, (cin, cout), jnp.float32) / math.sqrt(cin)


def _resblock_init(key, cin, cout, temb_dim):
    ks = jax.random.split(key, 4)
    p = {"gn1": _gn_init(cin), "w1": _conv_init(ks[0], 3, 3, cin, cout),
         "temb": _dense_init(ks[1], temb_dim, cout),
         "gn2": _gn_init(cout), "w2": _conv_init(ks[2], 3, 3, cout, cout)}
    if cin != cout:
        p["skip"] = _conv_init(ks[3], 1, 1, cin, cout)
    return p


# The named scopes below change op metadata only (the ``op_name`` a
# profiler trace shows), so device time can be split by layer.
@jax.named_scope("groupnorm")
def _groupnorm(x, p, groups, act=True, impl="xla"):
    return gn_act(x, p, groups, act=act, impl=impl)


@jax.named_scope("resblock")
def _resblock(p, x, temb, groups=8, impl="xla"):
    h = _groupnorm(x, p["gn1"], groups, impl=impl)
    h = conv(h, p["w1"])
    h = h + (jax.nn.silu(temb) @ p["temb"])[:, None, None, :]
    h = _groupnorm(h, p["gn2"], groups, impl=impl)
    h = conv(h, p["w2"])
    skip = conv(x, p["skip"]) if "skip" in p else x
    return h + skip


def _attn_init(key, c, text_dim):
    ks = jax.random.split(key, 6)
    return {"gn": _gn_init(c),
            "wq": _dense_init(ks[0], c, c), "wk": _dense_init(ks[1], c, c),
            "wv": _dense_init(ks[2], c, c), "wo": _dense_init(ks[3], c, c),
            "ck": _dense_init(ks[4], text_dim, c),
            "cv": _dense_init(ks[5], text_dim, c)}


def _pad_rows(a, rows):
    """Pad axis 1 of a (B,S,H,D) array with zero rows up to ``rows``."""
    s = a.shape[1]
    return a if rows == s else jnp.pad(a, ((0, 0), (0, rows - s),
                                           (0, 0), (0, 0)))


def _fused_attn(qh, kh, vh, impl):
    """Dispatch (B,S,H,D) attention through the kernels' schedule plan.
    "ref" uses the fused jnp oracle unpadded. "pallas"/"interpret" take
    the schedule ``attention_plan`` picks from the shapes: the whole-key
    schedule runs K/V unpadded; the online fallback pads Sk to a block
    multiple and masks the padded K/V rows via ``kv_len``. Padded q rows
    are sliced off (they never feed outputs)."""
    from repro.kernels import ops
    from repro.kernels.flash_attention import WHOLE_KEY, attention_plan
    if impl == "ref":
        return ops.flash_attention(qh, kh, vh, causal=False, impl="xla")
    sq, sk, d = qh.shape[1], kh.shape[1], qh.shape[3]
    plan = attention_plan(False, sq, sk, d, qh.dtype.itemsize)
    qh = _pad_rows(qh, plan.sq)
    if plan.schedule == WHOLE_KEY:
        out = ops.whole_key_attention(qh, kh, vh, impl=impl,
                                      block_q=plan.block_q)
    else:
        out = ops.flash_attention(
            qh, _pad_rows(kh, plan.sk), _pad_rows(vh, plan.sk),
            causal=False, impl=impl, block_q=plan.block_q,
            block_k=plan.block_k, kv_len=sk if plan.sk != sk else None)
    return out[:, :sq]


@jax.named_scope("attn")
def _attn(p, x, ctx, num_heads, groups=8, impl="xla"):
    """Self-attention over pixels + cross-attention to text ctx (B,L,T)."""
    B, H, W, C = x.shape
    h = _groupnorm(x, p["gn"], groups, act=False, impl=impl)
    seq = h.reshape(B, H * W, C)
    q = seq @ p["wq"]
    k = jnp.concatenate([seq @ p["wk"], ctx @ p["ck"]], axis=1)
    v = jnp.concatenate([seq @ p["wv"], ctx @ p["cv"]], axis=1)
    hd = C // num_heads

    if impl == "xla":
        def split(a):
            return a.reshape(B, -1, num_heads, hd).transpose(0, 2, 1, 3)
        qh, kh, vh = split(q), split(k), split(v)
        att = jax.nn.softmax(
            jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(hd), axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", att, vh)
        out = out.transpose(0, 2, 1, 3).reshape(B, H * W, C)
    else:
        out = _fused_attn(q.reshape(B, -1, num_heads, hd),
                          k.reshape(B, -1, num_heads, hd),
                          v.reshape(B, -1, num_heads, hd), impl)
        out = out.reshape(B, H * W, C)
    out = out @ p["wo"]
    return x + out.reshape(B, H, W, C)


def init_unet(key, cfg: DiffusionConfig):
    ks = jax.random.split(key, 64)
    ki = iter(range(64))
    c0 = cfg.base_channels
    temb_dim = 4 * c0
    p = {
        "temb1": _dense_init(ks[next(ki)], c0, temb_dim),
        "temb2": _dense_init(ks[next(ki)], temb_dim, temb_dim),
        "text_embed": jax.random.normal(
            ks[next(ki)], (1024, cfg.text_dim), jnp.float32) * 0.02,
        "in": _conv_init(ks[next(ki)], 3, 3, cfg.in_channels, c0),
    }
    res = cfg.image_size
    chans = [c0]
    cin = c0
    downs = []
    for lvl, mult in enumerate(cfg.channel_mults):
        cout = c0 * mult
        level = {"blocks": [], "attns": []}
        for _ in range(cfg.num_res_blocks):
            level["blocks"].append(
                _resblock_init(ks[next(ki)], cin, cout, temb_dim))
            level["attns"].append(
                _attn_init(ks[next(ki)], cout, cfg.text_dim)
                if res in cfg.attn_resolutions else None)
            cin = cout
            chans.append(cin)
        if lvl < len(cfg.channel_mults) - 1:
            level["down"] = _conv_init(ks[next(ki)], 3, 3, cin, cin)
            chans.append(cin)
            res //= 2
        downs.append(level)
    p["downs"] = downs
    p["mid1"] = _resblock_init(ks[next(ki)], cin, cin, temb_dim)
    p["mid_attn"] = _attn_init(ks[next(ki)], cin, cfg.text_dim)
    p["mid2"] = _resblock_init(ks[next(ki)], cin, cin, temb_dim)
    ups = []
    for lvl, mult in reversed(list(enumerate(cfg.channel_mults))):
        cout = c0 * mult
        level = {"blocks": [], "attns": []}
        for _ in range(cfg.num_res_blocks + 1):
            level["blocks"].append(
                _resblock_init(ks[next(ki)], cin + chans.pop(), cout,
                               temb_dim))
            level["attns"].append(
                _attn_init(ks[next(ki)], cout, cfg.text_dim)
                if res in cfg.attn_resolutions else None)
            cin = cout
        if lvl > 0:
            level["up"] = _conv_init(ks[next(ki)], 3, 3, cin, cin)
            res *= 2
        ups.append(level)
    p["ups"] = ups
    p["out_gn"] = _gn_init(cin)
    p["out"] = _conv_init(ks[next(ki)], 3, 3, cin, cfg.in_channels)
    return p


def apply_unet(params, cfg: DiffusionConfig, x, t, prompt_tokens,
               impl="xla"):
    """x: (B,H,W,Cin) noisy latent; t: (B,) timesteps in [0, 1000);
    prompt_tokens: (B, L) int32. Returns epsilon prediction. ``impl``
    routes GroupNorm+SiLU and attention through the kernel hot path
    ("pallas" | "interpret" | "ref") or the baseline ops ("xla")."""
    temb = timestep_embedding(t, cfg.base_channels)
    temb = jax.nn.silu(temb @ params["temb1"]) @ params["temb2"]
    ctx = jnp.take(params["text_embed"], prompt_tokens % 1024, axis=0)

    h = conv(x, params["in"])
    skips = [h]
    res = cfg.image_size
    for lvl, level in enumerate(params["downs"]):
        for bp, ap in zip(level["blocks"], level["attns"]):
            h = _resblock(bp, h, temb, impl=impl)
            if ap is not None:
                h = _attn(ap, h, ctx, cfg.num_heads, impl=impl)
            skips.append(h)
        if "down" in level:
            h = conv(h, level["down"], stride=2)
            skips.append(h)
            res //= 2
    h = _resblock(params["mid1"], h, temb, impl=impl)
    h = _attn(params["mid_attn"], h, ctx, cfg.num_heads, impl=impl)
    h = _resblock(params["mid2"], h, temb, impl=impl)
    for level in params["ups"]:
        for bp, ap in zip(level["blocks"], level["attns"]):
            h = _resblock(bp, jnp.concatenate([h, skips.pop()], axis=-1),
                          temb, impl=impl)
            if ap is not None:
                h = _attn(ap, h, ctx, cfg.num_heads, impl=impl)
        if "up" in level:
            B, H, W, C = h.shape
            h = jax.image.resize(h, (B, H * 2, W * 2, C), "nearest")
            h = conv(h, level["up"])
    h = _groupnorm(h, params["out_gn"], 8, impl=impl)
    return conv(h, params["out"])
