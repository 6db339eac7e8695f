"""The comparison that decides ``correct``.

After the window has closed, what the timed path produced is held against
the plain reference (``chipbench/reference.py``):

* ``unserved``: offered queries not completed exactly once (limit 0);
* ``misrouted``: completed queries whose completion tier disagrees with
  their recorded confidences and the pinned thresholds (limit 0);
* ``compiles``: programs of the cascade compiled inside the window, from
  ``DiffusionCascade.compile_counts()`` (limit 0);
* ``tier<i>_err``: for a sample of rows of each tier's outputs, drawn
  from the seed, the mean relative L1 distance (mean absolute difference
  over the reference's mean absolute value) between the served latent
  and the reference's DDIM from the same starting noise, over the same
  distance of the reference computed in bfloat16. The latents saturate
  at the sampler's clip to [-1, 1]; an error in the UNet flips saturated
  elements in proportion to its size, and the L1 distance counts those
  flips linearly. Over many steps the sampler amplifies any error by a
  factor that depends on the weights (a seed reads 0.04, another 0.24 at
  50 steps), and dividing by the bfloat16 distance on the same rows
  takes that factor out: the bfloat16 control reads 1;
* ``conf_gap``: for a sample of the discriminator's scored rows, the
  largest gap between the served confidence and the reference
  discriminator's on the same served latent.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, registry

TIER_SAMPLE = 8      # rows per tier, one reference block
DISC_SAMPLE = 64     # scored rows per boundary, one reference block


def routing_faults(calls, completions, thresholds: Sequence[float],
                   offered: int, n_tiers: int) -> Tuple[int, int]:
    """(unserved, misrouted): queries not completed exactly once, and
    queries whose path disagrees with their confidences."""
    done: Dict[int, List[int]] = {}
    for qid, tier, _lat in completions:
        done.setdefault(qid, []).append(tier)
    unserved = sum(1 for q in range(offered) if len(done.get(q, [])) != 1)
    unserved += sum(1 for q in done if not 0 <= q < offered)
    conf: Dict[Tuple[int, int], float] = {}
    for c in calls:
        if c.confs is not None:
            for qid, v in zip(c.qids, c.confs):
                conf[(qid, c.tier)] = v
    misrouted = 0
    for qid, tiers in done.items():
        tier = tiers[0]
        for b in range(n_tiers - 1):
            if b > tier:
                break
            v = conf.get((qid, b))
            # deferred past b needs conf < t; stopping at b needs >= t
            ok = v is not None and ((v < thresholds[b]) == (b < tier))
            misrouted += not ok
    return unserved, misrouted


def _rows(calls, tier: int, k: int, rng) -> List[Tuple[object, int]]:
    """Up to ``k`` (call, row) pairs of ``tier`` drawn from the batches
    whose outputs were kept."""
    rows = [(c, r) for c in calls if c.tier == tier and c.out is not None
            for r in range(c.n)]
    if len(rows) <= k:
        return rows
    pick = rng.choice(len(rows), size=k, replace=False)
    return [rows[i] for i in sorted(pick)]


def _pad(x, k: int):
    return jnp.concatenate([x, jnp.repeat(x[-1:], k - x.shape[0], axis=0)]) \
        if x.shape[0] < k else x


@functools.lru_cache(maxsize=None)
def _sampler(model, m_json: str, steps: int, dtype: str):
    """The jitted reference DDIM of ``model`` for the description that
    ``m_json`` holds (a JSON string, so the cache can key on it)."""
    tier = registry.Tier(model, json.loads(m_json))
    return jax.jit(functools.partial(reference.ddim_sample, eps=tier.eps,
                                     steps=steps, dtype=jnp.dtype(dtype)))


@functools.lru_cache(maxsize=None)
def _scorer(d_json: str, dtype: str):
    return jax.jit(functools.partial(reference.confidence,
                                     d=json.loads(d_json),
                                     dtype=jnp.dtype(dtype)))


def _json(desc: Dict) -> str:
    return json.dumps(desc, sort_keys=True)


def rel_l1(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row mean |got - want| over mean |want|."""
    g = got.reshape(got.shape[0], -1).astype(np.float64)
    w = want.reshape(want.shape[0], -1).astype(np.float64)
    return np.abs(g - w).mean(axis=1) / np.maximum(np.abs(w).mean(axis=1),
                                                   1e-30)


def sample_inputs(calls, config: Dict, backend_seed: int, sample_seed: int,
                  models):
    """The rows to compare: per tier, the sampled (call, row) pairs with
    their starting noise; per boundary, sampled scored rows. ``models``
    are the tiers' ``registry.Tier``s, which give one latent shape."""
    rng = np.random.default_rng(sample_seed)
    n_tiers = len(config["tiers"])
    shape = tuple(models[0].model.latent_shape(models[0].m))
    picked = {t: _rows(calls, t, TIER_SAMPLE, rng) for t in range(n_tiers)}
    need = max([c.index for rows in picked.values() for c, _ in rows],
               default=-1) + 1
    keys = reference.key_chain(backend_seed, need)
    tiers = {}
    for t, rows in picked.items():
        if not rows:
            continue
        noise = reference.stage_noise([keys[c.index] for c, _ in rows],
                                      [c.bucket for c, _ in rows],
                                      [r for _, r in rows], shape)
        served = np.stack([np.asarray(c.out[r]) for c, r in rows])
        tiers[t] = (noise, served)
    scored = {}
    for b in range(n_tiers - 1):
        rows = _rows([c for c in calls if c.confs is not None], b,
                     DISC_SAMPLE, rng)
        if rows:
            scored[b] = (np.stack([np.asarray(c.out[r]) for c, r in rows]),
                         np.asarray([c.confs[r] for c, r in rows]))
    return tiers, scored


def compare_rows(inputs, weights, config: Dict, models,
                 dtype: str = "float32", served: bool = True,
                 control: str = "bfloat16"):
    """Per tier (got, want, control) latents and per boundary (got, want)
    confidences. ``want`` is the float32 reference of the tier's model
    module (``models[t]``) and ``control`` that reference computed in
    ``control`` precision from the same noise. With ``served``, ``got``
    is what the timed path produced; without, it is the reference at
    ``dtype`` put in the program's place."""
    tiers, scored = inputs
    unets, disc = weights
    d_json = _json(config["discriminator"])

    def cast(tree, dt):
        return tree if dt == "float32" else \
            reference.cast_tree(tree, jnp.dtype(dt))
    toks = jnp.zeros((TIER_SAMPLE, config["prompt_len"]), jnp.int32)
    out_t, out_b = {}, {}
    for t, (noise, got) in sorted(tiers.items()):
        steps = config["tiers"][t]["num_steps"]
        k, x = noise.shape[0], _pad(noise, TIER_SAMPLE)
        model, m_json = models[t].model, _json(models[t].m)

        def sample(dt):
            return np.asarray(_sampler(model, m_json, steps, dt)(
                cast(unets[t], dt), noise=x, tokens=toks))[:k]
        want, ctl = sample("float32"), sample(control)
        if not served:
            got = sample(dtype)
        out_t[t] = (got, want, ctl)
    for b, (imgs, got) in sorted(scored.items()):
        k, x = imgs.shape[0], _pad(jnp.asarray(imgs), DISC_SAMPLE)
        want = np.asarray(_scorer(d_json, "float32")(disc, images=x))[:k]
        if not served:
            got = np.asarray(_scorer(d_json, dtype)(cast(disc, dtype),
                                                   images=x))[:k]
        out_b[b] = (got, want)
    return out_t, out_b


def numbers(pairs) -> Dict[str, float]:
    """The compared numbers from ``compare_rows``' output."""
    out_t, out_b = pairs
    out = {f"tier{t}_err": float(np.mean(rel_l1(got, want))
                                 / max(np.mean(rel_l1(ctl, want)), 1e-30))
           for t, (got, want, ctl) in out_t.items()}
    if out_b:
        out["conf_gap"] = max(float(np.max(np.abs(got - want)))
                              for got, want in out_b.values())
    return out


def checks(window, prep, weights, config: Dict, models, pairs=None
           ) -> Dict[str, Dict]:
    """Every number compared, each with its limit. ``pairs`` are
    ``compare_rows``' output where the caller has them already."""
    n_tiers = len(config["tiers"])
    rec = prep.records
    unserved, misrouted = routing_faults(rec.calls, rec.completions,
                                         prep.thresholds, window.offered,
                                         n_tiers)
    before, after = window.compile_counts
    out = {"unserved": {"value": unserved, "limit": 0},
           "misrouted": {"value": misrouted, "limit": 0},
           "compiles": {"value": int(sum(after) - sum(before)), "limit": 0}}
    if pairs is None:
        pairs = compare_rows(sample_inputs(rec.calls, config,
                                           prep.seeds.backend,
                                           prep.seeds.sample, models),
                             weights, config, models)
    limits = config["limits"]
    for name, v in numbers(pairs).items():
        limit = (limits["tier_err"][int(name[4:-4])]
                 if name.startswith("tier") else limits[name])
        out[name] = {"value": v, "limit": limit}
    return out


def passed(numbers: Dict[str, Dict]) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in numbers.values())
