"""The trace reduction: kernel device time, the union of busy intervals
and the idle gaps, on hand-made events and on a small trace recorded on
a TPU v5e chip (``testdata/``). Importing it loads no TPU library."""

import os
import subprocess
import sys
import types

import numpy as np

import pytest

import costs
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = costs.KERNELS
# 25 ms of a traced `n15.dec-closed` window on one TPU v5e chip: the
# device plane's ``XLA Ops`` line and the host's ``bench.*`` spans
RECORDED = os.path.join(HERE, "testdata", "n15_dec_closed.xplane.pb")


def ev(name, start_us, dur_us):
    return tr.Event(name, int(start_us * 1000), int(dur_us * 1000))


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.union([]) == []


def test_summary_of_hand_made_events():
    device = [ev("_encrypt_core_mega32_impl", 0, 100),
              ev("copy.1", 50, 100),            # overlaps the kernel
              ev("_encrypt_core_mega32_impl", 300, 100),
              ev("_decrypt_core_mega32_impl", 450, 10),
              ev("slice.3", 900, 100)]
    host = [ev("bench.result", 150, 100), ev("bench.submit", 160, 200),
            ev("bench.result", 470, 400)]
    s = tr.summarize([device], host, KINDS, window_s=1e-3)
    assert s.busy_s == pytest.approx((150 + 100 + 10 + 100) * 1e-6)
    assert s.kernels["_encrypt_core_mega32_impl"] == (2,
                                                      pytest.approx(2e-4))
    assert s.kernel("dec") == (1, pytest.approx(1e-5))
    assert s.ops["copy.1"] == pytest.approx(1e-4)
    # gaps: 150-300, 400-450, 460-900; each labelled by the host span
    # that overlaps it most
    assert [(g, round(t * 1e6)) for g, t in s.gaps] == [
        ("bench.result", 440), ("bench.submit", 150),
        ("unannotated", 50)]
    b = s.breakdown()
    assert b["device_ops"][0] == ["_encrypt_core_mega32_impl",
                                  pytest.approx(2e-4)]
    assert len(b["idle_gaps"]) == 3


def test_per_row_time_and_roofline():
    device = [ev("_encrypt_core_mega32_impl", i * 1000, 800)
              for i in range(4)]
    s = tr.summarize([device], [], KINDS, window_s=4e-3)
    launches = [types.SimpleNamespace(kind="enc", bucket=16)
                for _ in range(4)]
    assert s.us_per_row("enc", launches) == pytest.approx(800 / 16)
    n, limbs = 65536, 24
    want = 100 * 4 * costs.encrypt_bytes(16, n, limbs) / 819e9 / 3.2e-3
    assert s.roofline("enc", launches, n, limbs, 819e9) == pytest.approx(
        want)
    assert s.us_per_row("dec", launches) is None
    assert s.roofline("dec", launches, n, limbs, 819e9) is None


def test_window_defaults_to_the_device_events():
    s = tr.summarize([[ev("a", 10, 5), ev("b", 40, 10)]], [], KINDS)
    assert s.window_s == pytest.approx(40e-6)
    with pytest.raises(ValueError):
        tr.summarize([[]], [], KINDS)


def test_kernels_are_the_custom_calls_named_after_their_core():
    hlo = ('%_decrypt_core_mega32_impl.1 = (f32[16,128,128]) custom-call('
           'u32[2,243] %copy-done.1), custom_call_target="tpu_custom_call"')
    assert tr.op_name(hlo) == "_decrypt_core_mega32_impl.1"
    assert tr.kernel_of(hlo, KINDS) == "_decrypt_core_mega32_impl"
    # the same core's other ops, and custom calls of other targets, are not
    # the kernel
    assert tr.kernel_of("%_decrypt_core_mega32_impl.2 = f32[4] copy(%x)",
                        KINDS) is None
    assert tr.kernel_of('%custom-call.11 = f32[14] custom-call(), '
                        'custom_call_target="tpu_custom_call"', KINDS) is None
    assert tr.kernel_of('%_decrypt_core_mega32_impl_x.1 = f32[4] '
                        'custom-call(), custom_call_target="tpu_custom_call"',
                        KINDS) is None


def _recorded_events():
    from jax.profiler import ProfileData
    data = ProfileData.from_file(RECORDED)
    ops, host = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((e.name, int(e.start_ns), int(e.duration_ns)))
                elif plane.name == "/host:CPU":
                    host.append(e.name)
    return ops, host


def test_recorded_trace_kernel_time():
    s = tr.reduce(RECORDED, KINDS)
    ops, _host = _recorded_events()
    kernel = [d for name, _t, d in ops if "tpu_custom_call" in name
              and name.startswith("%_decrypt_core_mega32_impl")]
    # four decrypt jobs of 16 rows in the 25 ms, about 1.19 ms each
    assert len(kernel) == 4
    assert s.kernels == {"_decrypt_core_mega32_impl":
                         (4, pytest.approx(sum(kernel) / 1e9, abs=1e-12))}
    assert s.kernel("dec") == (4, pytest.approx(0.004750301, abs=1e-12))
    assert s.kernel("enc") == (0, 0.0)
    assert s.breakdown()["device_ops"][0] == [
        "_decrypt_core_mega32_impl", pytest.approx(0.004750301, abs=1e-12)]


def test_recorded_trace_busy_union_and_gaps():
    s = tr.reduce(RECORDED, KINDS)
    ops, _host = _recorded_events()
    # the union by another road: mark every nanosecond an op ran
    t0 = min(t for _n, t, _d in ops)
    t1 = max(t + d for _n, t, d in ops)
    busy = np.zeros(t1 - t0, bool)
    for _n, t, d in ops:
        busy[t - t0:t - t0 + d] = True
    assert s.busy_s == pytest.approx(busy.sum() / 1e9, abs=1e-12)
    assert s.busy_s == pytest.approx(0.005385441, abs=1e-12)
    # with no window given it is the span of the device events
    assert s.window_s == pytest.approx((t1 - t0) / 1e9, abs=1e-12)
    # the three long gaps lie between the decrypt jobs, while the host
    # waited in a result call; the idle time is the rest of the span
    gaps = [(label, round(sec * 1e9)) for label, sec in s.gaps[:3]]
    assert gaps == [("bench.result", 6214774), ("bench.result", 5830573),
                    ("bench.result", 5269130)]
    idle = np.flatnonzero(np.diff(busy.astype(np.int8)) == 1)
    assert len(s.gaps) == min(tr.TOP, len(idle))


def test_reading_a_trace_loads_no_tpu_library():
    code = ("import sys; sys.path.insert(0, sys.argv[1])\n"
            "import costs, trace_reduce\n"
            "trace_reduce.reduce(sys.argv[2], costs.KERNELS)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "assert not [m for m in sys.modules if 'libtpu' in m]\n")
    subprocess.run([sys.executable, "-c", code, HERE, RECORDED], check=True,
                   env=dict(os.environ, JAX_PLATFORMS=""), timeout=300)
