"""BENCHMARK.json: every name resolves to its file under bench/, every
per-layer metric moves an end-to-end metric its cells report, and the
file keeps the shape the harness relies on."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_command_and_paths(spec):
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "run.py"))
    assert 1 <= spec["run_seconds"] <= 51


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_unique_and_well_formed(spec, section):
    names = [entry["name"] for entry in spec[section]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_configs_resolve(spec):
    for c in spec["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/configs/")
        with open(path) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert len(config["moduli"]) == config["n_limbs"]
        assert set(config["check_limits"]) == {"enc_c0_gap", "dec_slot_gap"}
        assert c["reduced"] == config["reduced"]
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200


def test_workloads_resolve(spec):
    configs = {c["name"] for c in spec["configs"]}
    used = set()
    for w in spec["workloads"]:
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
        used.add(w["config"])
    assert used == configs
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metrics_resolve_to_readers(spec, section):
    for m in spec[section]:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        with open(path) as f:
            assert "def read(r)" in f.read()
        assert UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_end_to_end_bounds(spec):
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def _reported(spec, cell):
    return {m["name"] for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_per_layer_moves_a_metric_of_each_cell(spec):
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["workloads"] and set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert m["moves"] in _reported(spec, cell), (m["name"], cell)


def test_each_cell_reports_enough(spec):
    for w in spec["workloads"]:
        e2e = _reported(spec, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in m["workloads"] for m in spec["per_layer"])


def test_layers_are_named_alike(spec):
    layers = {m["layer"] for m in spec["per_layer"]}
    assert all(layer and "\n" not in layer and len(layer) <= 200
               for layer in layers)
