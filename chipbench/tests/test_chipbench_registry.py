"""BENCHMARK.json and the files it names: every configuration, traffic
mix and metric reader is found by its name, an unknown name fails, and
the file keeps the benchmark contract's shapes."""
import json
import re

import pytest

from chipbench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_every_cell_finds_its_files(bench):
    for wl in bench["workloads"]:
        config = registry.config(bench, wl["config"])
        assert config["name"] == wl["config"]
        traffic = registry.traffic(wl["traffic"])
        assert traffic["name"] == wl["traffic"]
        for kind in ("end_to_end", "per_layer"):
            for m in registry.metrics_for(bench, kind, wl["name"]):
                assert callable(registry.reader(kind, m["name"]))


@pytest.mark.parametrize("lookup", [
    lambda b: registry.workload(b, "no-such-cell"),
    lambda b: registry.config(b, "no-such-config"),
    lambda b: registry.traffic("no-such-traffic"),
    lambda b: registry.reader("per_layer", "no_such_metric"),
    lambda b: registry.reader("end_to_end", "no_such_metric"),
])
def test_unknown_name_fails(bench, lookup):
    with pytest.raises(KeyError):
        lookup(bench)


def test_cells_report_what_the_contract_asks(bench):
    names = [wl["name"] for wl in bench["workloads"]]
    assert names == ["c2-easy-backlog"]
    for wl in bench["workloads"]:
        e2e = [m["name"] for m in registry.metrics_for(bench, "end_to_end",
                                                       wl["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics_for(bench, "per_layer", wl["name"])
        assert wl["chips"] == 1


def test_names_units_and_bounds(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    seen = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[section]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_files_state_their_limits(bench):
    for c in bench["configs"]:
        config = registry.config(bench, c["name"])
        limits = config["limits"]
        assert len(limits["tier_err"]) == len(config["tiers"])
        assert limits["conf_gap"] > 0
