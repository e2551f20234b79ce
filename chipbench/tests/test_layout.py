"""BENCHMARK.json and the files the harness finds by name agree."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_every_workload_names_a_config_and_driver_that_exist(bench):
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = _load("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] in configs
        assert cell["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "configs", w["config"] + ".json"))


def test_every_config_has_its_file_and_reference(bench):
    for c in bench["configs"]:
        cfg = _load("configs", c["name"] + ".json")
        assert cfg["name"] == c["name"]
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert os.path.isfile(os.path.join(BENCH, "reference", c["name"] + ".py"))


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))


def test_names_and_units_use_only_the_allowed_characters(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in bench["end_to_end"] + bench["per_layer"])) \
        == len(bench["end_to_end"]) + len(bench["per_layer"])
    units = [m["unit"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    from chipbench.run import cell_metrics
    for w in bench["workloads"]:
        e2e = {m["name"] for m in cell_metrics(bench, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = cell_metrics(bench, w["name"], "per_layer")
        assert layers and all(m["moves"] in e2e for m in layers)
