"""A whole run of each traffic mix at the `tiny` ring on the CPU (the
harness's look for a chip skipped): the window, the metrics and the
check, correct on the program as it is."""

import copy
import json
import os

import pytest

import run
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "testdata", "tiny.json")) as f:
        return json.load(f)


def tiny_mix(name):
    mix = traffic.load(os.path.join(HERE, "traffic", name + ".json"))
    if mix["loop"] == "open":
        # fewer bucket shapes to compile in interpret mode
        mix["service"] = dict(mix["service"], buckets=[2, 16])
        mix["rate_per_s"] = 40.0
        mix["check"] = {"enc": 4, "dec": 2}
    return mix


def cell_of(spec, traffic_name):
    return [w for w in spec["workloads"] if w["traffic"] == traffic_name][0]


@pytest.mark.parametrize("mix_name", ["enc-closed", "dec-closed",
                                      "mix10-open"])
def test_run_is_correct_and_reports_its_metrics(spec, tiny, mix_name):
    if mix_name == "mix10-open":
        # the open-loop mix has no cell yet (PERF.md, section 7): a cell
        # is added by entries alone, a workload and its end-to-end metric
        spec = copy.deepcopy(spec)
        spec["workloads"].append({"name": "paper.mix10-open",
                                  "config": "paper", "traffic": mix_name,
                                  "chips": 1})
        spec["end_to_end"].append({"name": "p95_ms", "unit": "ms",
                                   "workloads": ["paper.mix10-open"]})
    cell = cell_of(spec, mix_name)
    result = run.run_cell(spec, cell, tiny, tiny_mix(mix_name),
                          seed=2**31 + 3, seconds=1.0, trace=False,
                          device=CPU)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    want = set(run.cell_metrics(spec, cell["name"], "end_to_end"))
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["checks"]["window_compiles"]["value"] == 0
