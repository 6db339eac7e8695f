"""A model module that is ``joint_kv_unet`` under another name: what a
configuration that brings its own model adds, at its least."""
from chipbench import registry

_JOINT = registry.model("joint_kv_unet")

weight_specs = _JOINT.weight_specs
eps = _JOINT.eps
latent_shape = _JOINT.latent_shape
program_config = _JOINT.program_config
unet_flops = _JOINT.unet_flops
attention_calls = _JOINT.attention_calls
groupnorm_calls = _JOINT.groupnorm_calls
