"""The repo's UNet (``repro.models.unet``), as a model module of the
benchmark: ResBlocks with GroupNorm, and an attention that puts the
pixels' and the prompt tokens' keys and values in one softmax.

A configuration's ``unet`` block is this module's model description
``m``. Everything but ``program_config`` is written from the equations,
in plain ``jax.numpy``, with nothing of the system under test imported.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import conv_flops
from chipbench.reference import Normal, _Math, conv_w, dense_w, gn_w

TEXT_VOCAB = 1024


def latent_shape(m: Dict) -> Tuple[int, int, int]:
    return (m["image_size"], m["image_size"], m["in_channels"])


def program_config(config: Dict, tier: Dict, m: Dict):
    """The served model's ``DiffusionConfig`` for ``tier``."""
    from repro.config.base import DiffusionConfig
    return DiffusionConfig(
        name=f"{config['name']}-{tier['name']}", image_size=m["image_size"],
        in_channels=m["in_channels"], base_channels=m["base_channels"],
        channel_mults=tuple(m["channel_mults"]),
        num_res_blocks=m["num_res_blocks"],
        attn_resolutions=tuple(m["attn_resolutions"]),
        num_heads=m["num_heads"], text_dim=m["text_dim"],
        num_steps=tier["num_steps"], dtype=config["dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def weight_specs(m: Dict) -> Dict:
    """One UNet's weights for the model description ``m``, as the tree the
    served model reads."""
    c0, res = m["base_channels"], m["image_size"]
    temb_dim = 4 * c0
    p = {"temb1": dense_w(c0, temb_dim),
         "temb2": dense_w(temb_dim, temb_dim),
         "text_embed": Normal((TEXT_VOCAB, m["text_dim"]), 0.02),
         "in": conv_w(3, 3, m["in_channels"], c0)}

    def resblock(cin, cout):
        b = {"gn1": gn_w(cin), "w1": conv_w(3, 3, cin, cout),
             "temb": dense_w(temb_dim, cout),
             "gn2": gn_w(cout), "w2": conv_w(3, 3, cout, cout)}
        if cin != cout:
            b["skip"] = conv_w(1, 1, cin, cout)
        return b

    def attn(c):
        return {"gn": gn_w(c),
                **{w: dense_w(c, c) for w in ("wq", "wk", "wv", "wo")},
                "ck": dense_w(m["text_dim"], c),
                "cv": dense_w(m["text_dim"], c)}

    chans, cin, downs = [c0], c0, []
    mults = m["channel_mults"]
    for lvl, mult in enumerate(mults):
        cout = c0 * mult
        level = {"blocks": [], "attns": []}
        for _ in range(m["num_res_blocks"]):
            level["blocks"].append(resblock(cin, cout))
            level["attns"].append(attn(cout) if res in m["attn_resolutions"]
                                  else None)
            cin = cout
            chans.append(cin)
        if lvl < len(mults) - 1:
            level["down"] = conv_w(3, 3, cin, cin)
            chans.append(cin)
            res //= 2
        downs.append(level)
    p["downs"] = downs
    p["mid1"] = resblock(cin, cin)
    p["mid_attn"] = attn(cin)
    p["mid2"] = resblock(cin, cin)
    ups = []
    for lvl, mult in reversed(list(enumerate(mults))):
        cout = c0 * mult
        level = {"blocks": [], "attns": []}
        for _ in range(m["num_res_blocks"] + 1):
            level["blocks"].append(resblock(cin + chans.pop(), cout))
            level["attns"].append(attn(cout) if res in m["attn_resolutions"]
                                  else None)
            cin = cout
        if lvl > 0:
            level["up"] = conv_w(3, 3, cin, cin)
            res *= 2
        ups.append(level)
    p["ups"] = ups
    p["out_gn"] = gn_w(cin)
    p["out"] = conv_w(3, 3, cin, m["in_channels"])
    return p


# ---------------------------------------------------------------------------
# the plain forward pass
# ---------------------------------------------------------------------------
def _temb(t, dim, f: _Math):
    half = dim // 2
    freqs = np.exp(-math.log(10_000) * np.arange(half) / half)
    args = f.c(t)[:, None] * f.c(freqs)[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _resblock(p, x, temb, f: _Math):
    h = f.conv(f.gn(x, p["gn1"], True), p["w1"])
    h = h + f.mm(jax.nn.silu(temb), p["temb"])[:, None, None, :]
    h = f.conv(f.gn(h, p["gn2"], True), p["w2"])
    return h + (f.conv(x, p["skip"]) if "skip" in p else f.c(x))


def _attention(p, x, ctx, heads, f: _Math):
    """Pixel self-attention with the prompt's tokens appended to the keys
    and values: one softmax over (pixels + tokens)."""
    b, h, w, c = x.shape
    seq = f.gn(x, p["gn"], False).reshape(b, h * w, c)
    q = f.mm(seq, p["wq"])
    k = jnp.concatenate([f.mm(seq, p["wk"]), f.mm(ctx, p["ck"])], axis=1)
    v = jnp.concatenate([f.mm(seq, p["wv"]), f.mm(ctx, p["cv"])], axis=1)
    hd = c // heads

    def split(a):
        return a.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", split(q), split(k),
                   precision=f.prec) / f.c(math.sqrt(hd))
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                     split(v), precision=f.prec)
    out = out.transpose(0, 2, 1, 3).reshape(b, h * w, c)
    return f.c(x) + f.mm(out, p["wo"]).reshape(b, h, w, c)


def eps(p, m: Dict, x, t, tokens, f: _Math):
    """Epsilon prediction of one UNet evaluation."""
    temb = _temb(t, m["base_channels"], f)
    temb = f.mm(jax.nn.silu(f.mm(temb, p["temb1"])), p["temb2"])
    ctx = f.c(jnp.take(p["text_embed"], tokens % TEXT_VOCAB, axis=0))
    heads = m["num_heads"]
    h = f.conv(x, p["in"])
    skips = [h]
    for level in p["downs"]:
        for bp, ap in zip(level["blocks"], level["attns"]):
            h = _resblock(bp, h, temb, f)
            if ap is not None:
                h = _attention(ap, h, ctx, heads, f)
            skips.append(h)
        if "down" in level:
            h = f.conv(h, level["down"], stride=2)
            skips.append(h)
    h = _resblock(p["mid1"], h, temb, f)
    h = _attention(p["mid_attn"], h, ctx, heads, f)
    h = _resblock(p["mid2"], h, temb, f)
    for level in p["ups"]:
        for bp, ap in zip(level["blocks"], level["attns"]):
            h = _resblock(bp, jnp.concatenate([h, skips.pop()], axis=-1),
                          temb, f)
            if ap is not None:
                h = _attention(ap, h, ctx, heads, f)
        if "up" in level:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = f.conv(h, level["up"])
    return f.conv(f.gn(h, p["out_gn"], True), p["out"])


# ---------------------------------------------------------------------------
# operation counts, from shapes
# ---------------------------------------------------------------------------
def groupnorm_calls(m: Dict, batch: int) -> List[Tuple[int, int, int]]:
    """(batch, pixels, channels) of every GroupNorm in one UNet
    evaluation, in order: two per ResBlock, one per attention block and
    the output norm."""
    calls: List[Tuple[int, int, int]] = []
    c0, res = m["base_channels"], m["image_size"]
    mults = m["channel_mults"]

    def resblock(hw, cin, cout):
        calls.append((batch, hw * hw, cin))
        calls.append((batch, hw * hw, cout))

    def attn(hw, c):
        calls.append((batch, hw * hw, c))

    chans, cin = [c0], c0
    for lvl, mult in enumerate(mults):
        cout = c0 * mult
        for _ in range(m["num_res_blocks"]):
            resblock(res, cin, cout)
            if res in m["attn_resolutions"]:
                attn(res, cout)
            cin = cout
            chans.append(cin)
        if lvl < len(mults) - 1:
            chans.append(cin)
            res //= 2
    resblock(res, cin, cin)
    attn(res, cin)
    resblock(res, cin, cin)
    for lvl, mult in reversed(list(enumerate(mults))):
        cout = c0 * mult
        for _ in range(m["num_res_blocks"] + 1):
            resblock(res, cin + chans.pop(), cout)
            if res in m["attn_resolutions"]:
                attn(res, cout)
            cin = cout
        if lvl > 0:
            res *= 2
    calls.append((batch, res * res, cin))
    return calls


def attention_calls(m: Dict, batch: int, prompt_len: int
                    ) -> List[Tuple[int, int, int, int, int]]:
    """(batch, heads, query rows, key rows, head size) of every attention
    in one UNet evaluation; key rows are the pixels plus the prompt's
    tokens, before any padding."""
    res, out = m["image_size"], []
    levels = []
    for lvl, mult in enumerate(m["channel_mults"]):
        levels.append((res, m["base_channels"] * mult))
        if lvl < len(m["channel_mults"]) - 1:
            res //= 2
    heads = m["num_heads"]
    n_down = m["num_res_blocks"]
    n_up = m["num_res_blocks"] + 1
    for hw, c in levels:                          # down path
        if hw in m["attn_resolutions"]:
            out += [(batch, heads, hw * hw, hw * hw + prompt_len,
                     c // heads)] * n_down
    hw, c = levels[-1]                            # middle
    out.append((batch, heads, hw * hw, hw * hw + prompt_len, c // heads))
    for hw, c in reversed(levels):                # up path
        if hw in m["attn_resolutions"]:
            out += [(batch, heads, hw * hw, hw * hw + prompt_len,
                     c // heads)] * n_up
    return out


def unet_flops(m: Dict, batch: int, prompt_len: int) -> int:
    """Matmul and convolution operations of one UNet evaluation."""
    c0, res = m["base_channels"], m["image_size"]
    temb_dim, text = 4 * c0, m["text_dim"]
    mults = m["channel_mults"]
    fl = 2 * batch * (c0 * temb_dim + temb_dim * temb_dim)
    fl += conv_flops(batch, res, 3, m["in_channels"], c0)

    def resblock(hw, cin, cout):
        f = conv_flops(batch, hw, 3, cin, cout) + \
            conv_flops(batch, hw, 3, cout, cout)
        f += 2 * batch * temb_dim * cout
        if cin != cout:
            f += conv_flops(batch, hw, 1, cin, cout)
        return f

    def attn(hw, c):
        s = hw * hw
        f = 2 * batch * s * c * c * 4                  # q, k, v, out
        f += 2 * batch * prompt_len * text * c * 2     # prompt k, v
        f += 4 * batch * s * (s + prompt_len) * c      # scores, weighted sum
        return f

    chans, cin = [c0], c0
    for lvl, mult in enumerate(mults):
        cout = c0 * mult
        for _ in range(m["num_res_blocks"]):
            fl += resblock(res, cin, cout)
            if res in m["attn_resolutions"]:
                fl += attn(res, cout)
            cin = cout
            chans.append(cin)
        if lvl < len(mults) - 1:
            fl += conv_flops(batch, res, 3, cin, cin, stride=2)
            chans.append(cin)
            res //= 2
    fl += 2 * resblock(res, cin, cin) + attn(res, cin)
    for lvl, mult in reversed(list(enumerate(mults))):
        cout = c0 * mult
        for _ in range(m["num_res_blocks"] + 1):
            fl += resblock(res, cin + chans.pop(), cout)
            if res in m["attn_resolutions"]:
                fl += attn(res, cout)
            cin = cout
        if lvl > 0:
            res *= 2
            fl += conv_flops(batch, res, 3, cin, cin)
    fl += conv_flops(batch, res, 3, cin, m["in_channels"])
    return fl
