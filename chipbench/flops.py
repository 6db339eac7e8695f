"""Operations and bytes of the served models and kernels, from shapes.

Everything here is counted from the configuration's sizes, never read
from the program. A multiply-add counts as two operations. Element-wise
work (activations, normalisation, residual adds) is left out: the totals
count the matmuls and convolutions that bound the step.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

F32 = 4


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


def _taps(n_in: int, k: int, stride: int) -> int:
    """Kernel taps that land inside the input, summed over the outputs of
    one spatial dimension under SAME padding (padded zeros need no
    multiply)."""
    n_out = -(-n_in // stride)
    lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for j in range(k)
               if 0 <= o * stride - lo + j < n_in)


def _conv(batch, hw_in, k, cin, cout, groups=1, stride=1):
    """Operations of a square SAME convolution over a ``hw_in`` input."""
    return 2 * batch * _taps(hw_in, k, stride) ** 2 * (cin // groups) * cout


def unet_flops(m: Dict, batch: int, prompt_len: int) -> int:
    """Matmul and convolution operations of one UNet evaluation."""
    c0, res = m["base_channels"], m["image_size"]
    temb_dim, text = 4 * c0, m["text_dim"]
    mults = m["channel_mults"]
    fl = 2 * batch * (c0 * temb_dim + temb_dim * temb_dim)
    fl += _conv(batch, res, 3, m["in_channels"], c0)

    def resblock(hw, cin, cout):
        f = _conv(batch, hw, 3, cin, cout) + _conv(batch, hw, 3, cout, cout)
        f += 2 * batch * temb_dim * cout
        if cin != cout:
            f += _conv(batch, hw, 1, cin, cout)
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
            fl += _conv(batch, res, 3, cin, cin, stride=2)
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
            fl += _conv(batch, res, 3, cin, cin)
    fl += _conv(batch, res, 3, cin, m["in_channels"])
    return fl


def discriminator_flops(d: Dict, batch: int, image_size: int) -> int:
    """Matmul and convolution operations of one discriminator pass."""
    fl = _conv(batch, image_size, 3, d["in_channels"], d["stem_channels"],
               stride=2)
    res = -(-image_size // 2)
    cin = d["stem_channels"]
    for c, depth, stride, expand in d["stages"]:
        for j in range(depth):
            s = stride if j == 0 else 1
            mid = cin * expand
            se = max(int(cin * d["se_ratio"]), 4)
            if expand > 1:
                fl += _conv(batch, res, 1, cin, mid)
            fl += _conv(batch, res, 3, mid, mid, groups=mid, stride=s)
            res = -(-res // s)
            fl += _conv(batch, 1, 1, mid, se) + _conv(batch, 1, 1, se, mid)
            fl += _conv(batch, res, 1, mid, c)
            cin = c
    fl += _conv(batch, res, 1, cin, d["head_channels"])
    fl += 2 * batch * d["head_channels"] * d["num_classes"]
    return fl


def discriminator_groupnorm_calls(d: Dict, batch: int, image_size: int
                                  ) -> List[Tuple[int, int, int]]:
    """(batch, pixels, channels) of every GroupNorm of one discriminator
    pass."""
    res = -(-image_size // 2)
    calls = [(batch, res * res, d["stem_channels"])]
    cin = d["stem_channels"]
    for c, depth, stride, expand in d["stages"]:
        for j in range(depth):
            s = stride if j == 0 else 1
            mid = cin * expand
            calls.append((batch, res * res, cin))
            if expand > 1:
                calls.append((batch, res * res, mid))
            res = -(-res // s)
            calls.append((batch, res * res, mid))
            cin = c
    calls.append((batch, res * res, d["head_channels"]))
    return calls


def attention_cost(batch: int, heads: int, sq: int, sk: int, d: int
                   ) -> Tuple[float, float]:
    """(operations, bytes) non-causal attention needs over the unpadded
    rows: the two matmuls, and q, k, v read and the output written once."""
    flops = 4.0 * batch * heads * sq * sk * d
    nbytes = float(F32 * batch * heads * d * (2 * sq + 2 * sk))
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
