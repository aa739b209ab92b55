"""The span metrics read the service's own stamps. At the `tiny` ring on
the CPU, one untraced 1 s window of each closed mix, with every span
kept, gives each reader a finite positive number, and no reading says a
service thread was busy for longer than the window."""

import json
import math
import os

import pytest

import driver
import run
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = {"enc-closed": ("dispatch_ms.enc", "demux_ms.enc"),
           "dec-closed": ("dispatch_ms.dec", "demux_ms.dec")}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "testdata", "tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def windows(tiny):
    """One window per mix, built once for every reader."""
    out = {}
    for name in READERS:
        mix = traffic.load(os.path.join(HERE, "traffic", name + ".json"))
        runner = driver.Runner(tiny, mix, seed=2**31 + 11, seconds=1.0,
                               keep_spans=True)
        runner.warm_up()
        out[name] = runner.run()
    return out


@pytest.mark.parametrize("mix_name, metric", [
    (mix_name, metric) for mix_name, metrics in READERS.items()
    for metric in metrics])
def test_span_metric_reads_its_window(spec, windows, mix_name, metric):
    cell = [w["name"] for w in spec["workloads"]
            if w["traffic"] == mix_name][0]
    assert metric in run.cell_metrics(spec, cell, "per_layer")
    window = windows[mix_name]
    value = run.load_reader(metric)(run.Reading(window=window))
    assert value is not None and math.isfinite(value) and value > 0
    # one thread runs each stage of every job in turn
    kind = metric.rsplit(".", 1)[1]
    jobs = sum(d.kind == kind for d in window.dispatch)
    assert jobs > 0
    assert value / 1e3 * jobs <= window.t1 - window.t0
