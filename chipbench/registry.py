"""Finds what ``BENCHMARK.json`` names: a cell's configuration file, its
traffic mix in ``traffic/<name>.json``, each metric's reader in
``end_to_end/<name>.py`` or ``metrics/<name>.py``, and each tier's model
module in ``models/<name>.py``. An unknown name is an error."""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import types
from typing import Dict, List, NamedTuple, Sequence

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
KINDS = {"end_to_end": "end_to_end", "per_layer": "metrics"}
MODELS = HERE / "models"
DEFAULT_MODEL = "joint_kv_unet"


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: Dict, name: str) -> Dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"unknown workload {name!r}; known "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict, name: str, root: pathlib.Path = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"unknown configuration {name!r}; known "
                   f"{[c['name'] for c in bench['configs']]}")


def traffic(name: str, folder: pathlib.Path = HERE / "traffic") -> Dict:
    path = pathlib.Path(folder) / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"unknown traffic mix {name!r}: no {path}")
    return json.loads(path.read_text())


def metrics_for(bench: Dict, kind: str, cell: str) -> List[Dict]:
    """The ``kind`` metrics (``end_to_end`` or ``per_layer``) a cell
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(kind: str, name: str, here: pathlib.Path = HERE):
    """The ``read(ctx)`` function of a metric, loaded from its own file."""
    path = here / KINDS[kind] / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"unknown {kind} metric {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{KINDS[kind]}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model(name: str, folders: Sequence[pathlib.Path] = (MODELS,)
          ) -> types.ModuleType:
    """The model module ``<name>.py`` from the first of ``folders`` that
    holds it. A model module gives, for its model description ``m`` (a
    configuration's ``unet`` block): ``weight_specs(m)``,
    ``eps(p, m, x, t, tokens, f)``, ``latent_shape(m)``,
    ``program_config(config, tier, m)``,
    ``unet_flops(m, batch, prompt_len)``,
    ``attention_calls(m, batch, prompt_len)`` and
    ``groupnorm_calls(m, batch)``."""
    for folder in folders:
        path = pathlib.Path(folder) / f"{name}.py"
        if path.is_file():
            return _load_model(path.resolve())
    known = sorted({p.stem for f in folders
                    for p in pathlib.Path(f).glob("*.py")})
    raise KeyError(f"unknown model {name!r}; known {known}")


@functools.lru_cache(maxsize=None)
def _load_model(path: pathlib.Path) -> types.ModuleType:
    """Each file once per process, so every tier that names it shares one
    module (and the jitted programs keyed by it)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_models_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Tier(NamedTuple):
    """One tier's denoiser: its model module and model description."""
    model: types.ModuleType
    m: Dict

    def eps(self, p, x, t, tokens, f):
        """The module's plain noise prediction for this description."""
        return self.model.eps(p, self.m, x, t, tokens, f)


def tier_models(config: Dict, folders: Sequence[pathlib.Path] = (MODELS,)
                ) -> List[Tier]:
    """Each tier's ``Tier``: its own ``model`` and ``unet`` where the tier
    gives them, else the configuration's (``model`` defaults to
    ``joint_kv_unet``). Every tier's output is scored by one
    discriminator, so all give one latent shape."""
    folders = tuple(folders)
    out = [Tier(model(t.get("model", config.get("model", DEFAULT_MODEL)),
                      folders), t.get("unet", config.get("unet")))
           for t in config["tiers"]]
    shapes = [tuple(t.model.latent_shape(t.m)) for t in out]
    if len(set(shapes)) > 1:
        raise ValueError(f"the tiers of {config['name']!r} give different "
                         f"latent shapes {shapes}; the discriminator scores "
                         "every tier's output, so all must give one")
    return out
