"""``correct`` comes out false when the timed path is broken underneath a
whole run (the look for a chip skipped, toy size on the CPU): the
control, the plain reference computed in bfloat16 in the program's place,
and each fault the served cascade can have. The cells run on one chip
with no exchange between chips, so that fault has no place here."""
import math

import jax.numpy as jnp
import pytest

from chipbench import reference, registry
from chipbench.tests import toybench


def _control_sample(params, noise, prompt_tokens, *, cfg, impl):
    m = dict(image_size=cfg.image_size, in_channels=cfg.in_channels,
             base_channels=cfg.base_channels,
             channel_mults=list(cfg.channel_mults),
             num_res_blocks=cfg.num_res_blocks,
             attn_resolutions=list(cfg.attn_resolutions),
             num_heads=cfg.num_heads, text_dim=cfg.text_dim)
    tier = registry.Tier(registry.model("joint_kv_unet"), m)
    return reference.ddim_sample(reference.cast_tree(params, jnp.bfloat16),
                                 tier.eps, noise, prompt_tokens,
                                 cfg.num_steps, dtype=jnp.bfloat16)


def _state_unchanged(params, cfg, key, prompt_tokens, num_steps=None,
                     eta=0.0, impl="xla", init_noise=None):
    """Every DDIM step returns its state as it came."""
    return jnp.clip(init_noise, -1.0, 1.0)


def _half_batch(orig):
    def sample(params, noise, prompt_tokens, **kw):
        out = orig(params, noise, prompt_tokens, **kw)
        half = (out.shape[0] + 1) // 2
        return out.at[half:].set(0.0)
    return sample


def _altered_confidence(orig):
    def score(params, imgs, **kw):
        return 1.0 - orig(params, imgs, **kw)
    return score


def _altered_route(orig):
    """Every scored query's routing is inverted: it defers where its
    confidence says stop, and stops where it says defer."""
    def route(self, tier, batch, confs, done_t):
        t = self.thresholds[tier]
        return orig(self, tier, batch,
                    [-math.inf if c >= t else math.inf for c in confs],
                    done_t)
    return route


def _patch(monkeypatch, fault):
    from repro.core import cascade
    from repro.models import diffusion
    from repro.serving.cluster import ClusterBackend
    if fault == "control":
        monkeypatch.setattr(cascade, "_stage_sample", _control_sample)
    elif fault == "state_unchanged":
        monkeypatch.setattr(diffusion, "ddim_sample", _state_unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(cascade, "_stage_sample",
                            _half_batch(cascade._stage_sample))
    elif fault == "confidence_altered":
        monkeypatch.setattr(cascade, "_disc_score",
                            _altered_confidence(cascade._disc_score))
    elif fault == "route_altered":
        monkeypatch.setattr(ClusterBackend, "_route_scored",
                            _altered_route(ClusterBackend._route_scored))


FAULTS = [
    ("control", ("tier0_err", "tier1_err")),
    ("state_unchanged", ("tier0_err", "tier1_err")),
    ("half_batch", ("tier0_err", "tier1_err")),
    ("confidence_altered", ("conf_gap",)),
    ("route_altered", ("misrouted",)),
]


@pytest.mark.parametrize("cell", sorted(toybench.CELLS))
@pytest.mark.parametrize("fault, numbers", FAULTS)
def test_fault_makes_run_incorrect(monkeypatch, cell, fault, numbers):
    _patch(monkeypatch, fault)
    line, _out, _err = toybench.run(cell, impl="ref")
    assert not line["correct"]
    assert any(line["checks"][n]["value"] > line["checks"][n]["limit"]
               for n in numbers if n in line["checks"])


def test_sound_run_is_correct():
    line, _out, _err = toybench.run("c2-hard-backlog", impl="ref",
                                    seed=toybench.SEED + 1)
    assert line["correct"], line["checks"]
