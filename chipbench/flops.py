"""Operations and bytes of the discriminator and the kernels, from shapes
(a tier's denoiser counts its own, in its model module).

Everything here is counted from the configuration's sizes, never read
from the program. A multiply-add counts as two operations. Element-wise
work (activations, normalisation, residual adds) is left out: the totals
count the matmuls and convolutions that bound the step.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

F32 = 4


def _taps(n_in: int, k: int, stride: int) -> int:
    """Kernel taps that land inside the input, summed over the outputs of
    one spatial dimension under SAME padding (padded zeros need no
    multiply)."""
    n_out = -(-n_in // stride)
    lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for j in range(k)
               if 0 <= o * stride - lo + j < n_in)


def conv_flops(batch, hw_in, k, cin, cout, groups=1, stride=1):
    """Operations of a square SAME convolution over a ``hw_in`` input."""
    return 2 * batch * _taps(hw_in, k, stride) ** 2 * (cin // groups) * cout


def discriminator_flops(d: Dict, batch: int, image_size: int) -> int:
    """Matmul and convolution operations of one discriminator pass."""
    fl = conv_flops(batch, image_size, 3, d["in_channels"],
                    d["stem_channels"], stride=2)
    res = -(-image_size // 2)
    cin = d["stem_channels"]
    for c, depth, stride, expand in d["stages"]:
        for j in range(depth):
            s = stride if j == 0 else 1
            mid = cin * expand
            se = max(int(cin * d["se_ratio"]), 4)
            if expand > 1:
                fl += conv_flops(batch, res, 1, cin, mid)
            fl += conv_flops(batch, res, 3, mid, mid, groups=mid, stride=s)
            res = -(-res // s)
            fl += conv_flops(batch, 1, 1, mid, se) + \
                conv_flops(batch, 1, 1, se, mid)
            fl += conv_flops(batch, res, 1, mid, c)
            cin = c
    fl += conv_flops(batch, res, 1, cin, d["head_channels"])
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
