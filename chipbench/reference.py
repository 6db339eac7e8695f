"""Plain reference of the served cascade, and the seeded weights.

Written from the equations of the configuration, in straightforward
``jax.numpy``: no kernels, no batching tricks, nothing imported from the
system under test. Each tier's denoiser is a model module
(``chipbench/models/<name>.py``), which gives its weight specs and its
noise prediction; this file holds what every tier shares:

* ``init_weights`` makes every tier's denoiser and the discriminator from
  the run's seed in one jitted call, on the device, in float32 (the type
  they are served in). The trees have the layout the served models read.
* ``ddim_sample`` (over a tier's ``eps``) / ``confidence`` compute what
  the served path should produce. ``dtype=float32`` runs every matmul and
  convolution at ``highest`` precision (the reference);
  ``dtype=bfloat16`` runs the whole computation in bfloat16 (the control,
  the precision a later change would be tempted to drop to).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NUM_TRAIN_STEPS = 1000
GN_EPS = 1e-5
GN_GROUPS = 8


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
class Normal:
    """A weight to draw: ``mean + std * N(0, 1)`` of ``shape``."""

    def __init__(self, shape, std, mean=0.0):
        self.shape, self.std, self.mean = tuple(shape), float(std), mean

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))


def conv_w(kh, kw, cin, cout):
    return Normal((kh, kw, cin, cout), math.sqrt(2.0 / (kh * kw * cin)))


def dense_w(cin, cout):
    return Normal((cin, cout), 1.0 / math.sqrt(cin))


def gn_w(c):
    # scale and bias away from (1, 0), so a kernel that ignores them fails
    return {"scale": Normal((c,), 0.1, 1.0), "bias": Normal((c,), 0.1)}


def materialize(key, specs):
    """Draw every ``Normal`` of a tree inside the caller's jit: one
    standard-normal draw per distinct shape, from its own key folded from
    ``key``, sliced among the leaves of that shape. The program's working
    memory is that of the largest such group, not of the whole tree."""
    leaves, tree = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, Normal))
    shapes = sorted({w.shape for w in leaves})
    draws = {shape: jax.random.normal(
        jax.random.fold_in(key, g),
        (sum(w.shape == shape for w in leaves),) + shape, jnp.float32)
        for g, shape in enumerate(shapes)}
    taken = dict.fromkeys(shapes, 0)
    out = []
    for w in leaves:
        x = draws[w.shape][taken[w.shape]] * w.std
        taken[w.shape] += 1
        out.append(x + w.mean if w.mean else x)
    return jax.tree.unflatten(tree, out)


def discriminator_specs(d: Dict) -> Dict:
    """The discriminator's weights for ``d`` (a configuration file's
    ``discriminator`` block)."""
    p = {"stem": conv_w(3, 3, d["in_channels"], d["stem_channels"]),
         "stem_gn": gn_w(d["stem_channels"])}
    cin = d["stem_channels"]
    for i, (c, depth, _stride, expand) in enumerate(d["stages"]):
        blocks = []
        for j in range(depth):
            ci = cin if j == 0 else c
            mid = ci * expand
            se = max(int(ci * d["se_ratio"]), 4)
            b = {"gn0": gn_w(ci)}
            if expand > 1:
                b["w_exp"] = conv_w(1, 1, ci, mid)
                b["gn1"] = gn_w(mid)
            b["w_dw"] = Normal((3, 3, 1, mid), math.sqrt(2.0 / 9.0))
            b["gn2"] = gn_w(mid)
            b["w_se1"] = conv_w(1, 1, mid, se)
            b["w_se2"] = conv_w(1, 1, se, mid)
            b["w_out"] = conv_w(1, 1, mid, c)
            b["gn3"] = gn_w(c)
            blocks.append(b)
            cin = c
        p[f"stage{i}"] = blocks
    p["head"] = conv_w(1, 1, cin, d["head_channels"])
    p["head_gn"] = gn_w(d["head_channels"])
    p["fc"] = dense_w(d["head_channels"], d["num_classes"])
    p["fc_b"] = Normal((d["num_classes"],), 0.1)
    return p


def init_weights(seed_key, config: Dict, models) -> Tuple[List[Dict], Dict]:
    """(one denoiser per tier, the discriminator) from one key, in one
    jitted call on the default device. ``models`` are the tiers'
    ``registry.Tier``s."""
    specs = ([t.model.weight_specs(t.m) for t in models],
             discriminator_specs(config["discriminator"]))
    return jax.jit(lambda key: materialize(key, specs))(seed_key)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------
class _Math:
    """The arithmetic of one precision: float32 at ``highest`` for the
    reference, bfloat16 throughout for the control."""

    def __init__(self, dtype):
        self.dt = jnp.dtype(dtype)
        self.prec = (lax.Precision.HIGHEST if self.dt == jnp.float32
                     else lax.Precision.DEFAULT)

    def c(self, x):
        return jnp.asarray(x).astype(self.dt)

    def mm(self, a, b):
        return jnp.matmul(self.c(a), self.c(b), precision=self.prec)

    def conv(self, x, w, stride=1, groups=1):
        return lax.conv_general_dilated(
            self.c(x), self.c(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=self.prec)

    def gn(self, x, p, act):
        b, h, w, c = x.shape
        g = min(GN_GROUPS, c)
        while c % g:
            g -= 1
        xg = self.c(x).reshape(b, h * w, g, c // g)
        mu = jnp.mean(xg, axis=(1, 3), keepdims=True)
        var = jnp.mean(jnp.square(xg - mu), axis=(1, 3), keepdims=True)
        y = (xg - mu) / jnp.sqrt(var + GN_EPS)
        y = y.reshape(b, h, w, c) * self.c(p["scale"]) + self.c(p["bias"])
        return jax.nn.silu(y) if act else y


def alphas_bar() -> np.ndarray:
    """Cosine schedule (Nichol & Dhariwal), clipped away from 0."""
    s = np.arange(NUM_TRAIN_STEPS + 1) / NUM_TRAIN_STEPS
    fbar = np.cos((s + 0.008) / 1.008 * np.pi / 2) ** 2
    return np.clip(fbar / fbar[0], 1e-5, 1.0).astype(np.float32)


def ddim_timesteps(steps: int) -> np.ndarray:
    """The configured DDIM timesteps: a float32 ``linspace`` from 999 to 0,
    truncated to integers."""
    with jax.ensure_compile_time_eval():
        return np.asarray(jnp.linspace(NUM_TRAIN_STEPS - 1, 0, steps)
                          .astype(jnp.int32))


def ddim_sample(p, eps, noise, tokens, steps: int, dtype=jnp.float32):
    """Deterministic DDIM (eta = 0) from ``noise``; returns float32.
    ``eps(p, x, t, tokens, f)`` is the tier's noise prediction in the
    arithmetic ``f`` (a ``_Math``)."""
    f = _Math(dtype)
    ab = alphas_bar()
    ts = ddim_timesteps(steps)
    ab_n = np.append(ab[ts[1:]], 1.0)
    coef = jnp.asarray(np.stack([np.sqrt(1 - ab[ts]), np.sqrt(ab[ts]),
                                 np.sqrt(ab_n), np.sqrt(1 - ab_n)], axis=1),
                       jnp.float32)
    ts = jnp.asarray(ts)

    def step(i, x):
        c = f.c(coef[i])
        e = eps(p, x, jnp.full((x.shape[0],), ts[i]), tokens, f)
        x0 = jnp.clip((x - c[0] * e) / c[1], -3.0, 3.0)
        return c[2] * x0 + c[3] * e
    x = lax.fori_loop(0, steps, step, f.c(noise))
    return jnp.clip(x, -1.0, 1.0).astype(jnp.float32)


def confidence(p, d: Dict, images, dtype=jnp.float32):
    """P('real') of each image: the discriminator's softmax, class 1."""
    f = _Math(dtype)
    x = f.gn(f.conv(images, p["stem"], stride=2), p["stem_gn"], True)
    for i, (c, depth, stride, expand) in enumerate(d["stages"]):
        for j, bp in enumerate(p[f"stage{i}"]):
            cin = x.shape[-1]
            h = f.gn(x, bp["gn0"], False)
            if expand > 1:
                h = f.gn(f.conv(h, bp["w_exp"]), bp["gn1"], True)
            h = f.conv(h, bp["w_dw"], stride=stride if j == 0 else 1,
                       groups=h.shape[-1])
            h = f.gn(h, bp["gn2"], True)
            s = jnp.mean(h, axis=(1, 2), keepdims=True)
            s = jax.nn.sigmoid(f.conv(jax.nn.silu(f.conv(s, bp["w_se1"])),
                                      bp["w_se2"]))
            h = f.conv(h * s, bp["w_out"])
            x = h + x if (stride if j == 0 else 1) == 1 and h.shape[-1] == cin \
                else h
    x = f.gn(f.conv(x, p["head"]), p["head_gn"], True)
    logits = f.mm(jnp.mean(x, axis=(1, 2)), p["fc"]) + f.c(p["fc_b"])
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)[:, 1]


def cast_tree(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def stage_noise(keys: Sequence[np.ndarray], buckets: Sequence[int],
                rows: Sequence[int], shape: Tuple[int, int, int]):
    """The starting latents of the sampled rows: row ``rows[i]`` of a
    standard-normal draw of ``(buckets[i], *shape)`` from the raw key
    ``keys[i]``."""
    out = [jax.random.normal(jnp.asarray(k, jnp.uint32),
                             (int(m),) + tuple(shape), jnp.float32)[int(r)]
           for k, m, r in zip(keys, buckets, rows)]
    return jnp.stack(out)


def key_chain(seed: int, n: int) -> List[np.ndarray]:
    """The first ``n`` keys of a split chain from ``PRNGKey(seed)``: key
    ``i`` is the second half of the ``i``-th split, the first half is
    carried on."""
    carry, out = jax.random.PRNGKey(seed), []
    for _ in range(n):
        carry, k = jax.random.split(carry)
        out.append(np.asarray(k))
    return out
