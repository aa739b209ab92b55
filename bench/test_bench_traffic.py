"""The traffic generator and the load driver, on a stub service: the
open-loop schedule comes from the seed, due times are honoured, and a
request's latency runs from its due time, so a stall counts. Also the
knee sweep's reading of a rate."""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

import driver
import knee_sweep
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def _mix(name):
    return traffic.load(os.path.join(HERE, "traffic", name + ".json"))


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(HERE, "testdata", "tiny.json")) as f:
        return json.load(f)


def test_open_schedule_is_a_function_of_the_seed():
    mix = _mix("mix10-open")
    a = traffic.schedule(mix, 2**31 + 11, 20.0)
    b = traffic.schedule(mix, 2**31 + 11, 20.0)
    c = traffic.schedule(mix, 7, 20.0)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.kinds, b.kinds)
    assert not np.array_equal(a.due, c.due)
    # every seed gets the same gaps and the same share of each kind
    assert np.allclose(np.sort(np.diff(a.due, prepend=0.0)),
                       np.sort(np.diff(c.due, prepend=0.0)))
    assert len(a) == len(c)
    assert (a.kinds == "dec").sum() == (c.kinds == "dec").sum()
    assert np.all(np.diff(a.due) >= 0) and a.due[-1] < 20.0
    n = len(a)
    assert abs(n / 20.0 - mix["rate_per_s"]) < 0.2 * mix["rate_per_s"]
    assert abs((a.kinds == "dec").sum() - n / 11) <= 1


def test_closed_schedule_cycles_a_fixed_pattern():
    s = traffic.schedule(_mix("enc-closed"), 3, 20.0)
    assert s.due is None
    assert {s.kind(i) for i in range(100)} == {"enc"}
    assert s.entry(70) == 70 % 64


def test_reservoir_is_seeded_and_uniform():
    def sample(seed):
        r = traffic.Reservoir(8, seed, "check-enc")
        for i in range(1000):
            r.offer(i)
        return r.items
    assert sample(5) == sample(5)
    assert sample(5) != sample(6)
    assert len(set(sample(5))) == 8


def test_reservoir_spreads_its_sample_over_the_strata():
    r = traffic.Reservoir(32, 5, "check-dec", strata=16)
    for i in range(1000):
        r.offer(i, i)
    assert sorted(i % 16 for i in r.items) == sorted(list(range(16)) * 2)
    again = traffic.Reservoir(32, 5, "check-dec", strata=16)
    for i in range(1000):
        again.offer(i, i)
    assert again.items == r.items
    # more strata than items: as many strata as items
    few = traffic.Reservoir(3, 5, "check-enc", strata=16)
    for i in range(100):
        few.offer(i, i)
    assert len(few.items) == 3


class StubService:
    """Completes each request ``delay`` seconds after it is submitted;
    request ``stall_rid``'s submit blocks for ``stall`` seconds."""

    def __init__(self, n_slots, delay=0.01, stall_rid=None, stall=0.0):
        self.delay, self.stall_rid, self.stall = delay, stall_rid, stall
        self.n_slots = n_slots
        self.done_at = {}
        self.lock = threading.Lock()
        self.client = types.SimpleNamespace(nonce=0)
        self.telemetry = types.SimpleNamespace(
            tracer=types.SimpleNamespace(spans=lambda: []))
        self.dispatch_log = []

    def submit_encrypt(self, message):
        with self.lock:
            rid = len(self.done_at)
            self.done_at[rid] = time.monotonic() + self.delay
        if rid == self.stall_rid:
            time.sleep(self.stall)
        return rid

    def result(self, rid, timeout=None):
        time.sleep(max(self.done_at[rid] - time.monotonic(), 0.0))
        row = np.zeros((2, self.n_slots), np.uint32)
        return types.SimpleNamespace(c0=row, c1=row)

    def reset_telemetry(self):
        pass

    def lane_clients(self):
        return []

    def start(self):
        pass

    def stop(self, drain=True):
        pass

    def flush(self):
        pass


def _open_runner(tiny, svc, rate, seconds):
    mix = dict(_mix("mix10-open"), kinds={"enc": 1, "dec": 0},
               rate_per_s=rate, check={"enc": 2})
    return driver.Runner(tiny, mix, 5, seconds, service=svc)


def test_open_loop_honours_due_times(tiny):
    svc = StubService(32, delay=0.01)
    w = _open_runner(tiny, svc, 100.0, 1.0).run()
    assert len(w.requests) == len(traffic.schedule(
        dict(_mix("mix10-open"), rate_per_s=100.0), 5, 1.0))
    assert np.median(w.lateness_s) < 0.005
    for d in w.requests:
        assert d.t_done is not None
        assert d.t_done - d.t_due >= 0.01 - 1e-4
    assert len(w.samples["enc"]) == 2


def test_open_loop_latency_counts_a_stall(tiny):
    svc = StubService(32, delay=0.001, stall_rid=10, stall=0.3)
    w = _open_runner(tiny, svc, 200.0, 1.0).run()
    stalled = [d for d in w.requests if d.rid == 10][0]
    # requests due during the stall were sent late: their latency runs
    # from when they were due, so it holds the wait the stall imposed
    behind = [d for d in w.requests
              if stalled.t_due < d.t_due < stalled.t_due + 0.2]
    assert behind
    for d in behind:
        assert d.t_done - d.t_due >= stalled.t_due + 0.3 - d.t_due - 0.01
    assert max(w.lateness_s) >= 0.25


def test_closed_loop_keeps_its_requests_outstanding(tiny):
    svc = StubService(32, delay=0.005)
    mix = dict(_mix("enc-closed"), outstanding=4)
    w = driver.Runner(tiny, mix, 5, 0.5, service=svc).run()
    done = [d for d in w.requests if d.t_done is not None and d.t_done < w.t1]
    # four in flight, each 5 ms: about 800 a second
    assert 0.4 * 800 * 0.5 < len(done) <= 800 * 0.5 * 1.05
    assert all(d.t_done is not None for d in w.requests)


def _window(rows, t0=0.0, t1=10.0):
    return types.SimpleNamespace(
        t0=t0, t1=t1, requests=[driver.Done(i, "enc", i, due, done)
                                for i, (due, done) in enumerate(rows)])


def test_knee_sweep_reading_and_knee():
    fast = _window([(i * 0.1, i * 0.1 + 0.02) for i in range(100)])
    r = knee_sweep.reading(10.0, fast, 10.0, 16)
    assert r["keeps_up"] and r["backlog_end"] == 0
    # completions fall behind: the backlog at the close has grown
    slow = _window([(i * 0.05, i * 0.1 + 0.02) for i in range(200)])
    s = knee_sweep.reading(20.0, slow, 10.0, 16)
    assert not s["keeps_up"] and s["backlog_end"] > s["backlog_mid"] + 16
    assert knee_sweep.knee([r, s]) == 10.0
    assert knee_sweep.knee([s]) is None


class SerialStub(StubService):
    """Serves one request at a time, ``delay`` seconds each: a capacity
    of 1 / delay requests a second."""

    def submit_encrypt(self, message):
        with self.lock:
            rid = len(self.done_at)
            start = max([time.monotonic(), *self.done_at.values()])
            self.done_at[rid] = start + self.delay
        return rid


def test_knee_sweep_stops_at_the_first_rate_past_capacity(tiny):
    # capacity 100 req/s: 30 and 60 keep up, 200 does not, 400 is never
    # offered
    mix = dict(_mix("mix10-open"), kinds={"enc": 1, "dec": 0},
               check={"enc": 0})
    runner = driver.Runner(tiny, mix, 5, 1.0, service=SerialStub(32, 0.01))
    readings = knee_sweep.sweep(runner, mix, [400.0, 30.0, 200.0, 60.0],
                                1.0, 5)
    assert [r["rate_per_s"] for r in readings] == [30.0, 60.0, 200.0]
    assert [r["keeps_up"] for r in readings] == [True, True, False]
    assert knee_sweep.knee(readings) == 60.0
