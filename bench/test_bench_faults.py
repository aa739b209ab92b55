"""The check must fail what it is there to catch. Each test drives a
whole run at the `tiny` ring on the CPU (the look for a chip skipped)
with the timed path broken underneath, and sees ``correct`` come out
false on the number that catches the fault; the float32 control fails
too."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import check
import control
import driver
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


def mix(name):
    return traffic.load(os.path.join(HERE, "traffic", name + ".json"))


def broken_run(monkeypatch, spec, tiny, mix_name, breaks):
    """One run whose service is built as usual and then broken by
    ``breaks(service)``."""
    build = driver.build_service

    def build_broken(config, m, keep_spans=False):
        svc = build(config, m, keep_spans)
        breaks(svc)
        return svc

    monkeypatch.setattr(driver, "build_service", build_broken)
    cell = [w for w in spec["workloads"] if w["traffic"] == mix_name][0]
    return run.run_cell(spec, cell, tiny, mix(mix_name), seed=2**31 + 9,
                        seconds=1.0, trace=False, device=CPU)


def failed_on(result, name):
    c = result["checks"][name]
    return not result["correct"] and c["value"] > c["limit"]


def wrap_encrypt_core(svc, change):
    client = svc.client
    core = client._encrypt_core_mega32

    def broken(*args):
        c0, c1 = core(*args)
        return change(c0, c1)

    client._encrypt_core_mega32 = broken


def test_an_altered_ciphertext_fails(monkeypatch, spec, tiny):
    # one residue of every ciphertext's first limb, where it is produced
    result = broken_run(monkeypatch, spec, tiny, "enc-closed",
                        lambda svc: wrap_encrypt_core(
                            svc, lambda c0, c1: (c0.at[:, 0, 3].add(1),
                                                 c1)))
    assert failed_on(result, "enc_c0_gap")


def test_half_the_batch_left_out_fails(monkeypatch, spec, tiny):
    # rows past the first half repeat row 0 instead of being computed
    def half(c0, c1):
        b = c0.shape[0]
        keep = jnp.arange(b)[:, None, None] < b // 2
        return (jnp.where(keep, c0, c0[:1]), jnp.where(keep, c1, c1[:1]))

    result = broken_run(monkeypatch, spec, tiny, "enc-closed",
                        lambda svc: wrap_encrypt_core(svc, half))
    assert failed_on(result, "enc_c1_mismatch")


def test_an_altered_decryption_fails(monkeypatch, spec, tiny):
    def breaks(svc):
        finish = svc.client.decrypt_results

        def altered(parts, scale):
            out = np.array(finish(parts, scale))
            out[:, 5] += 1e-6
            return out

        svc.client.decrypt_results = altered

    result = broken_run(monkeypatch, spec, tiny, "dec-closed", breaks)
    assert failed_on(result, "dec_slot_gap")


def test_a_nonce_counter_left_unchanged_fails(monkeypatch, spec, tiny):
    # the counter is the only state an encryption moves: leave it
    # where it is, and every job reuses one nonce range
    def breaks(svc):
        svc.client.take_nonces = lambda count: svc.client.nonce
        svc.registry.ledger.lease = lambda seed, base, count: None

    result = broken_run(monkeypatch, spec, tiny, "enc-closed", breaks)
    assert failed_on(result, "enc_c1_mismatch")


@pytest.mark.parametrize("mix_name", ["enc-closed", "dec-closed"])
def test_float32_control_fails(tiny, mix_name):
    checks = control.control_checks(tiny, mix(mix_name), seed=4)
    assert not check.passed(checks)
    name = "enc_c0_gap" if mix_name == "enc-closed" else "dec_slot_gap"
    value, limit = checks[name]
    assert value > limit
