"""Each tier's denoiser comes from a model module found by name
(``chipbench/models/<name>.py``).

* Parity: ``joint_kv_unet`` gives ``cascade2-sd15`` and ``toy2`` the
  weight specs, weights and operation counts the harness gave them before
  the UNet moved into its module (``parity/joint_kv_unet.json``, recorded
  from that harness).
* The hook, at toy size through a whole run on the CPU: a module under
  another name, a module whose reference departs, tiers of different
  widths, tiers of different latent shapes, and an unknown name.
* Independence: the plain reference imports nothing of the program.
"""
import ast
import copy
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from chipbench import cell, reference, registry
from chipbench.tests import toybench

HERE = pathlib.Path(__file__).resolve().parent
PARITY = json.loads((HERE / "parity" / "joint_kv_unet.json").read_text())
CONFIGS = {"toy2": toybench.HERE / "toy2.json",
           "cascade2-sd15": registry.HERE / "configs" / "cascade2-sd15.json"}


def _config(name):
    return json.loads(CONFIGS[name].read_text())


def _listing(config):
    specs = ([t.model.weight_specs(t.m)
              for t in registry.tier_models(config)],
             reference.discriminator_specs(config["discriminator"]))
    leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, reference.Normal))[0]
    return [[jax.tree_util.keystr(path), list(w.shape), w.std, w.mean]
            for path, w in leaves]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weight_specs_match_the_parent(name):
    assert _listing(_config(name)) == PARITY[name]["specs"]


def test_toy_weights_are_the_parents_bit_for_bit():
    config = _config("toy2")
    want = PARITY["toy2"]
    weights = cell.make_weights(config, want["weights_seed"],
                                registry.tier_models(config))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(weights):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == want["weights_sha256"]


@pytest.mark.parametrize("batch", [1, 8])
def test_counts_match_the_parent(batch):
    config = _config("cascade2-sd15")
    want = PARITY["cascade2-sd15"]["counts"][str(batch)]
    for t in registry.tier_models(config):
        assert t.model.unet_flops(t.m, batch, config["prompt_len"]) == \
            want["unet_flops"]
        assert [list(c) for c in t.model.attention_calls(
            t.m, batch, config["prompt_len"])] == want["attention_calls"]
        assert [list(c) for c in t.model.groupnorm_calls(t.m, batch)] == \
            want["groupnorm_calls"]
    if batch == 1:
        assert want["unet_flops"] == 380_634_624_000


# ---------------------------------------------------------------------------
# the hook, through whole runs at toy size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def default_run():
    return toybench.run("c2-hard-backlog", impl="ref")[0]


def test_module_under_another_name_reads_the_same(default_run):
    line = toybench.run("c2-hard-backlog", impl="ref",
                        config="toy2_delegate")[0]
    assert default_run["correct"] and line["correct"], line["checks"]
    for name in ("tier0_err", "tier1_err", "conf_gap"):
        assert line["checks"][name] == default_run["checks"][name]


def _variant(tmp_path, edit):
    config = _config("toy2")
    edit(config)
    path = tmp_path / f"{config['name']}.json"
    path.write_text(json.dumps(config))
    return config["name"], path


def _no_attn(c):
    c["name"], c["model"] = "toy2_no_attn", "no_attn_unet"


def _wide_latent(c):
    c["name"] = "toy2_latents"
    c["tiers"][1]["unet"] = dict(c["unet"], image_size=16)


def _unknown(c):
    c["name"], c["tiers"][1]["model"] = "toy2_unknown", "no_such_model"


def test_departing_reference_makes_run_incorrect(tmp_path):
    name, path = _variant(tmp_path, _no_attn)
    line = toybench.run("c2-hard-backlog", impl="ref", config=name,
                        file=path)[0]
    assert not line["correct"]
    assert line["checks"]["tier0_err"]["value"] > \
        line["checks"]["tier0_err"]["limit"]


def test_tiers_of_different_widths_run_correct():
    config = json.loads((toybench.HERE / "toy2_mixed.json").read_text())
    widths = [t.m["base_channels"] for t in registry.tier_models(config)]
    assert widths == [8, 16]
    line = toybench.run("c2-hard-backlog", impl="ref",
                        config="toy2_mixed")[0]
    assert line["correct"], line["checks"]
    assert {"tier0_err", "tier1_err"} <= set(line["checks"])


@pytest.mark.parametrize("edit, error, words", [
    (_wide_latent, ValueError, "latent shapes"),
    (_unknown, KeyError, "unknown model 'no_such_model'"),
])
def test_bad_tiers_fail_in_setup(tmp_path, edit, error, words):
    name, path = _variant(tmp_path, edit)
    with pytest.raises(error, match=words):
        toybench.run("c2-easy-backlog", impl="ref", config=name, file=path)


def test_unknown_model_lists_the_known():
    with pytest.raises(KeyError, match="joint_kv_unet"):
        registry.model("no_such_model")


def test_tier_takes_its_own_model_else_the_configs():
    config = copy.deepcopy(_config("toy2"))
    config["model"] = "delegate_unet"
    config["tiers"][0]["model"] = "joint_kv_unet"
    config["tiers"][1]["unet"] = dict(config["unet"], base_channels=16)
    tiers = registry.tier_models(config, (toybench.MODELS, registry.MODELS))
    assert [t.model.__name__ for t in tiers] == [
        "chipbench_models_joint_kv_unet", "chipbench_models_delegate_unet"]
    assert [t.m["base_channels"] for t in tiers] == [8, 16]
    del config["model"]
    assert registry.tier_models(config)[1].model is \
        registry.model("joint_kv_unet")


# ---------------------------------------------------------------------------
# independence of the reference
# ---------------------------------------------------------------------------
def _imports_repro(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Import) and any(
                a.name.split(".")[0] == "repro" for a in n.names):
            return True
        if isinstance(n, ast.ImportFrom) and n.level == 0 and \
                (n.module or "").split(".")[0] == "repro":
            return True
    return False


@pytest.mark.parametrize("path", sorted(
    (registry.MODELS).glob("*.py")) + [registry.HERE / "reference.py"],
    ids=lambda p: p.name)
def test_reference_imports_nothing_of_the_program(path):
    tree = ast.parse(path.read_text())
    allowed = [n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef) and n.name == "program_config"
               and path.name != "reference.py"]
    for node in tree.body:
        if node in allowed:
            continue
        assert not _imports_repro(node), (path.name, getattr(node, "name",
                                                             node.lineno))
