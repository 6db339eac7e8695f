"""The plain reference against the served models at toy size on the CPU,
where both compute in float32: the same weights give the same latents
and confidences, and the weight trees have the served models' layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell, reference, registry
from chipbench.tests import toybench


@pytest.fixture(scope="module")
def toy():
    import json
    config = json.loads((toybench.HERE / "toy3.json").read_text())
    return config, cell.make_weights(config, 9, registry.tier_models(config))


def _program_config(config, tier):
    t = registry.tier_models(config)[tier]
    return t.model.program_config(config, config["tiers"][tier], t.m)


def test_weight_trees_have_the_served_layout(toy):
    from repro.models.efficientnet import init_discriminator
    from repro.models.unet import init_unet
    config, (unets, disc) = toy
    cfg = _program_config(config, 0)
    served = jax.eval_shape(lambda k: init_unet(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(served) == jax.tree.structure(unets[0])
    assert jax.tree.map(lambda a: a.shape, served) == \
        jax.tree.map(lambda a: a.shape, unets[0])
    dserved = jax.eval_shape(
        lambda k: init_discriminator(k, cell.discriminator_config(config)),
        jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, dserved) == \
        jax.tree.map(lambda a: a.shape, disc)
    # one call, one seed: the same weights again, other weights elsewhere
    models = registry.tier_models(config)
    again = cell.make_weights(config, 9, models)
    other = cell.make_weights(config, 10, models)
    assert np.array_equal(again[1]["fc"], disc["fc"])
    assert not np.array_equal(other[1]["fc"], disc["fc"])


def test_timesteps_match_the_sampler():
    for steps in (1, 4, 50):
        want = jnp.linspace(999, 0, steps).astype(jnp.int32)
        assert np.array_equal(reference.ddim_timesteps(steps), want)


@pytest.mark.parametrize("tier", [0, 1, 2])
def test_sampler_matches_reference(toy, tier):
    from repro.models.diffusion import ddim_sample
    config, (unets, _disc) = toy
    cfg = _program_config(config, tier)
    t = registry.tier_models(config)[tier]
    noise = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 4))
    toks = jnp.zeros((2, config["prompt_len"]), jnp.int32)
    got = ddim_sample(unets[tier], cfg, None, toks, impl="xla",
                      init_noise=noise)
    want = reference.ddim_sample(unets[tier], t.eps, noise, toks,
                                 cfg.num_steps)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-4


def test_discriminator_matches_reference(toy):
    from repro.models.efficientnet import confidence_score
    config, (_unets, disc) = toy
    x = jax.random.uniform(jax.random.PRNGKey(4), (3, 8, 8, 4),
                           minval=-1, maxval=1)
    got = confidence_score(disc, cell.discriminator_config(config), x)
    want = reference.confidence(disc, config["discriminator"], x)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-5


def test_key_chain_and_noise_follow_the_backend():
    """Key i of the chain is what the backend's i-th batch splits off, and
    a row's noise is that row of the bucket-shaped draw."""
    carry = jax.random.PRNGKey(11)
    keys = reference.key_chain(11, 3)
    for k in keys:
        carry, want = jax.random.split(carry)
        assert np.array_equal(k, np.asarray(want))
    full = jax.random.normal(jnp.asarray(keys[2]), (4, 8, 8, 4))
    rows = reference.stage_noise([keys[2]], [4], [3], (8, 8, 4))
    assert np.array_equal(np.asarray(rows[0]), np.asarray(full[3]))
