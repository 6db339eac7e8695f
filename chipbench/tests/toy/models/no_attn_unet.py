"""``joint_kv_unet`` with a reference that departs from the served
model: its ``eps`` leaves every attention block's output out (the block
passes its input through), so the comparison has to fail."""
import jax.numpy as jnp

from chipbench import registry

_JOINT = registry.model("joint_kv_unet")

weight_specs = _JOINT.weight_specs
latent_shape = _JOINT.latent_shape
program_config = _JOINT.program_config
unet_flops = _JOINT.unet_flops
attention_calls = _JOINT.attention_calls
groupnorm_calls = _JOINT.groupnorm_calls


def _silent(a):
    return None if a is None else dict(a, wo=jnp.zeros_like(a["wo"]))


def eps(p, m, x, t, tokens, f):
    q = dict(p, mid_attn=_silent(p["mid_attn"]))
    for side in ("downs", "ups"):
        q[side] = [dict(lv, attns=[_silent(a) for a in lv["attns"]])
                   for lv in p[side]]
    return _JOINT.eps(q, m, x, t, tokens, f)
