"""Finds what ``BENCHMARK.json`` names: a cell's configuration file, its
traffic mix in ``traffic/<name>.json``, and each metric's reader in
``end_to_end/<name>.py`` or ``metrics/<name>.py``. An unknown name is an
error."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
KINDS = {"end_to_end": "end_to_end", "per_layer": "metrics"}


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
