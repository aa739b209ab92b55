"""FHE client service: batcher/bucketing invariants, wire round-trips,
scheduler policy/execution agreement, and the determinism contract —
anything encrypted or decrypted through the service (any bucket, padding,
stream or shard layout) is bit-identical to the direct batched client.

Multi-device coverage runs in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax initializes, so it cannot run in this process).
"""

import os
import subprocess
import sys
from collections import deque

import numpy as np
import pytest

import jax

from repro.core import scheduler as policy
from repro.core.context import get_context
from repro.core.encryptor import Ciphertext, keygen
from repro.distributed import sharding as shd
from repro.fhe_client.service import (ClientService, CoalescingBatcher,
                                      Request, wire)
from repro.kernels import ops as kops


def _msgs(client, b, seed=0):
    rng = np.random.default_rng(seed)
    n = client.ctx.params.n_slots
    return (rng.standard_normal((b, n))
            + 1j * rng.standard_normal((b, n))) * 0.5


def _backing_nbytes(a):
    """Bytes of the buffer a host array keeps alive: its own, or that of
    the array or buffer at the end of its ``base`` chain."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a.nbytes if a.base is None else memoryview(a.base).nbytes


@pytest.fixture(scope="module")
def svc_client():
    """Module-scoped client backing the service tests. NOT the session
    tiny_device_client: the service warms jit traces at bucket shapes, and
    the launch-count tests elsewhere count fresh lowerings on the session
    client via ``jax.make_jaxpr`` (which shares the pjit trace cache) —
    warming the session client here would make those count zero."""
    from repro.fhe_client.client import FHEClient
    return FHEClient(profile="tiny")


# ---------------------------------------------------------------------------
# pure policy + batcher units
# ---------------------------------------------------------------------------


def test_round_policy_matches_rsc_modes():
    # both queues pending -> cover both kinds first (ENC+DEC), decode
    # ahead of encode (latency-critical server returns)
    assert policy.assign_streams(10, 1) == ("dec", "enc")
    assert policy.assign_streams(1, 1) == ("dec", "enc")
    # single-kind queues fill both streams (2xENC / 2xDEC)
    assert policy.assign_streams(9, 0) == ("enc", "enc")
    assert policy.assign_streams(0, 3) == ("dec", "dec")
    assert policy.round_mode(("enc", "enc")) is policy.Mode.ENC2
    assert policy.round_mode(("dec", "dec")) is policy.Mode.DEC2
    assert policy.round_mode(("dec", "enc")) is policy.Mode.MIX
    assert policy.round_mode(("enc",)) is policy.Mode.MIX

    plan = policy.plan_rounds(5, 1, 2)
    assert plan[0] == (policy.Mode.MIX, ("dec", "enc"))
    assert [m for m, _k in plan] == [policy.Mode.MIX, policy.Mode.ENC2,
                                     policy.Mode.ENC2]
    kinds = [k for _m, ks in plan for k in ks]
    assert kinds.count("enc") == 5 and kinds.count("dec") == 1
    # the plan drains any queue snapshot completely
    for e, d, s in ((0, 4, 2), (7, 0, 1), (3, 3, 4)):
        kinds = [k for _m, ks in policy.plan_rounds(e, d, s) for k in ks]
        assert kinds.count("enc") == e and kinds.count("dec") == d


def test_single_stream_never_starves_decrypts():
    """On one stream the 10:1 encrypt backlog must not delay the
    latency-critical decode jobs: decodes dispatch first."""
    plan = policy.plan_rounds(10, 2, 1)
    kinds = [k for _m, ks in plan for k in ks]
    assert kinds[:2] == ["dec", "dec"]
    assert kinds[2:] == ["enc"] * 10


def test_batcher_buckets_nonces_and_fifo():
    b = CoalescingBatcher(buckets=(2, 4))
    assert b.bucket_for(1) == 2 and b.bucket_for(3) == 4
    with pytest.raises(ValueError):
        b.bucket_for(5)

    q = deque(Request(rid=i, kind="enc", payload=np.full(4, i + 0j),
                      t_submit=float(i)) for i in range(6))
    jobs, used = b.coalesce_enc(q, nonce0=100, n_slots=4)
    assert not q and used == 6
    assert [j.bucket for j in jobs] == [4, 2]
    assert [j.n_real for j in jobs] == [4, 2]
    # FIFO order, nonce bases account for padded rows of earlier jobs
    assert jobs[0].rids == (0, 1, 2, 3) and jobs[1].rids == (4, 5)
    assert jobs[0].nonce0 == 100 and jobs[1].nonce0 == 104

    # padding rows are zero and appended at the tail only
    q2 = deque([Request(rid=9, kind="enc", payload=np.full(4, 7 + 0j),
                        t_submit=0.0)])
    (job,), used2 = b.coalesce_enc(q2, nonce0=0, n_slots=4)
    assert used2 == 2 and job.bucket == 2 and job.n_real == 1
    np.testing.assert_array_equal(job.messages[1], np.zeros(4, complex))

    # shard-count padding: buckets round up to pad_multiple
    assert CoalescingBatcher(buckets=(1, 2, 3), pad_multiple=2).buckets \
        == (2, 4)


# ---------------------------------------------------------------------------
# wire layer
# ---------------------------------------------------------------------------


def test_wire_roundtrips_and_determinism(svc_client):
    cl = svc_client
    cts = cl.encode_encrypt_batch(_msgs(cl, 2, seed=11))

    buf = wire.serialize_ciphertext_batch(cts)
    assert buf == wire.serialize_ciphertext_batch(cts)   # deterministic
    assert wire.payload_kind(buf) == wire.KIND_CT_BATCH
    rt = wire.deserialize_ciphertext_batch(buf)
    np.testing.assert_array_equal(np.asarray(rt.c0), np.asarray(cts.c0))
    np.testing.assert_array_equal(np.asarray(rt.c1), np.asarray(cts.c1))
    assert rt.n_limbs == cts.n_limbs and rt.scale == cts.scale

    # seeded (compressed) ciphertext: c0 + a-regeneration stream id
    row = cts[0]
    seeded = Ciphertext(c0=row.c0, c1=None, n_limbs=row.n_limbs,
                        scale=row.scale, a_stream=0x10017)
    sbuf = wire.serialize_ciphertext_seeded(seeded)
    assert wire.payload_kind(sbuf) == wire.KIND_CT_SEEDED
    srt = wire.deserialize_ciphertext_seeded(sbuf)
    np.testing.assert_array_equal(np.asarray(srt.c0), np.asarray(row.c0))
    assert srt.c1 is None and srt.a_stream == 0x10017
    # compression: the seeded payload is about half the full pair
    full_row = wire.serialize_ciphertext_batch(cts.truncated(cts.n_limbs))
    assert len(sbuf) < len(full_row) / 2 + 64
    with pytest.raises(ValueError):
        wire.serialize_ciphertext_seeded(row)            # c1 present

    z = _msgs(cl, 3, seed=12)
    np.testing.assert_array_equal(
        wire.deserialize_result(wire.serialize_result(z)), z)
    with pytest.raises(ValueError):
        wire.deserialize_ciphertext_batch(b"XXXX" + buf[4:])
    with pytest.raises(ValueError):
        wire.deserialize_result(buf)                     # wrong kind


# ---------------------------------------------------------------------------
# service <-> direct bit-identity (single device, bucketed + padded)
# ---------------------------------------------------------------------------


def test_service_encrypt_bit_identical_any_bucket(svc_client):
    """3 messages through bucket-2 jobs (one padded) == one direct B=3
    call from the same nonce base, bit for bit."""
    cl = svc_client
    msgs = _msgs(cl, 3, seed=1)
    base = cl.nonce
    direct = cl.encode_encrypt_batch(msgs)
    cl.nonce = base                       # replay the same nonce range
    svc = ClientService(client=cl, buckets=(2,))
    cts = svc.encrypt_many(msgs)
    np.testing.assert_array_equal(np.asarray(cts.c0), np.asarray(direct.c0))
    np.testing.assert_array_equal(np.asarray(cts.c1), np.asarray(direct.c1))
    assert [r.bucket for r in svc.dispatch_log] == [2, 2]
    assert [r.kind for r in svc.dispatch_log] == ["enc", "enc"]


@pytest.mark.parametrize("streams", ["single", "oversubscribed"])
@pytest.mark.parametrize("mode", ["flush", "always_on"])
def test_service_encrypt_rows_own_host_memory(svc_client, mode, streams):
    """Service encrypt rows are host ``np.ndarray``s, each a contiguous
    (L, N) uint32 array over a buffer of its own (no row shares memory
    with another or pins a whole bucket), bit-identical to the direct
    batch from the same nonce base, the row of a partial bucket
    included."""
    cl = svc_client
    msgs = _msgs(cl, 3, seed=6)
    base = cl.nonce
    direct = cl.encode_encrypt_batch(msgs)
    cl.nonce = base
    group = dict(n_streams=2, oversubscribe=True) \
        if streams == "oversubscribed" else dict(n_streams=1)
    svc = ClientService(client=cl, buckets=(2,), **group)
    if mode == "always_on":
        svc.start()
    try:
        rids = [svc.submit_encrypt(m) for m in msgs]    # jobs: 2 + 1(pad)
        assert svc.flush() == 3
        rows = [svc.result(r) for r in rids]
    finally:
        svc.stop()
    assert [r.bucket for r in svc.dispatch_log] == [2, 2]
    assert {r.stream for r in svc.dispatch_log} \
        == ({0, 1} if streams == "oversubscribed" else {0})

    p = cl.ctx.params
    arrays = []
    for i, row in enumerate(rows):
        assert (row.n_limbs, row.scale) == (p.n_limbs, p.delta)
        for got, ref in ((row.c0, direct.c0[i]), (row.c1, direct.c1[i])):
            assert isinstance(got, np.ndarray)
            assert not isinstance(got, jax.Array)
            assert got.shape == (p.n_limbs, p.n) and got.dtype == np.uint32
            assert got.flags.c_contiguous
            assert _backing_nbytes(got) == got.nbytes
            np.testing.assert_array_equal(got, np.asarray(ref))
            arrays.append(got)
    for i, a in enumerate(arrays):
        for b in arrays[i + 1:]:
            assert not np.shares_memory(a, b)


def test_readback_bytes_counter(svc_client, monkeypatch):
    """``fhe_readback_bytes_total{kind}`` counts the bytes each job's
    demux read: the real rows' c0 and c1 for encrypt (a padding row never
    comes to the host), the parts ``decrypt_results`` reads for decrypt;
    nothing when telemetry is off."""
    cl = svc_client
    read = []
    decrypt_results = cl.decrypt_results

    def counting(parts, scale):
        read.append(sum(np.asarray(x).nbytes for x in parts))
        return decrypt_results(parts, scale)

    monkeypatch.setattr(cl, "decrypt_results", counting)
    p = cl.ctx.params
    svc = ClientService(client=cl, buckets=(2,))
    cts = svc.encrypt_many(_msgs(cl, 3, seed=7))      # 2 + 1(pad) rows
    launched = sum(r.bucket for r in svc.dispatch_log)
    assert launched == 4
    counter = svc.telemetry.readback_bytes
    assert counter.value(kind="enc") == 3 * 2 * p.n_limbs * p.n * 4
    assert counter.value(kind="dec") == 0

    svc.decrypt_many(cts.truncated(2))                 # 2 + 1(pad) rows
    assert len(read) == 2 and counter.value(kind="dec") == sum(read)
    # the df32 decrypt core returns four f32 slot planes per row
    assert sum(read) == launched * 4 * p.n_slots * 4

    off = ClientService(client=cl, buckets=(2,), telemetry=False)
    off.decrypt_many(off.encrypt_many(_msgs(cl, 3, seed=8)).truncated(2))
    assert off.telemetry.readback_bytes.n_series() == 0
    assert off.telemetry_snapshot()["metrics"] \
        ["fhe_readback_bytes_total"]["series"] == []


def test_service_decrypt_bit_identical(svc_client):
    cl = svc_client
    direct = cl.encode_encrypt_batch(_msgs(cl, 5, seed=2))
    ref = cl.decrypt_decode_batch(direct.truncated(2))
    svc = ClientService(client=cl, buckets=(2, 4))
    got = svc.decrypt_many(direct.truncated(2))   # jobs: bucket 4 + 2(pad)
    np.testing.assert_array_equal(got, ref)
    assert [r.bucket for r in svc.dispatch_log] == [4, 2]
    # malformed payloads are rejected at submit, not mid-flush (where they
    # would take the whole coalesced batch down with them)
    n = cl.ctx.params.n
    with pytest.raises(ValueError, match="limb stack"):
        svc.submit_decrypt((np.zeros((1, n), np.uint32),
                            np.zeros((1, n), np.uint32), 1.0))


def test_e2e_mixed_requests_and_policy_agreement(svc_client):
    """Acceptance path: mixed enc/dec requests through the queue return
    bit-identical results, and the dispatch log replays exactly the mode
    schedule ``core.scheduler.plan_rounds`` predicts (single-stream
    fallback on this 1-device container)."""
    cl = svc_client
    msgs = _msgs(cl, 5, seed=3)
    base = cl.nonce
    direct = cl.encode_encrypt_batch(msgs)
    ref_dec = cl.decrypt_decode_batch(direct.truncated(2))
    cl.nonce = base

    svc = ClientService(client=cl, buckets=(2,))
    enc_rids = [svc.submit_encrypt(m) for m in msgs]              # 3 jobs
    dec_rids = [svc.submit_decrypt(row)
                for row in direct.truncated(2)]                   # 3 jobs
    assert svc.pending() == {"enc": 5, "dec": 5}
    done = svc.flush()
    assert done == 10 and svc.pending() == {"enc": 0, "dec": 0}

    for i, rid in enumerate(enc_rids):
        row = svc.result(rid)
        np.testing.assert_array_equal(np.asarray(row.c0),
                                      np.asarray(direct.c0)[i])
        np.testing.assert_array_equal(np.asarray(row.c1),
                                      np.asarray(direct.c1)[i])
    got_dec = np.stack([svc.result(r) for r in dec_rids])
    np.testing.assert_array_equal(got_dec, ref_dec)

    # policy/execution agreement through the recorded dispatch log
    executed = svc.scheduler.modes_executed()
    assert executed == policy.plan_rounds(3, 3, svc.scheduler.n_streams)
    if len(jax.devices()) == 1:           # clean single-stream fallback
        assert svc.scheduler.n_streams == 1
        assert {r.stream for r in svc.dispatch_log} == {0}
    assert all(svc.latency(r) > 0 for r in enc_rids + dec_rids)
    stats = svc.stats()
    assert stats["jobs_dispatched"] == 6 and stats["rounds"] == 6

    # results are consumed on retrieval; a re-ask neither re-flushes nor
    # crashes opaquely, and telemetry windows can be reset
    with pytest.raises(KeyError, match="already retrieved"):
        svc.result(enc_rids[0])
    with pytest.raises(KeyError, match="unknown"):
        svc.result(10 ** 6)
    svc.reset_telemetry()
    assert svc.stats()["jobs_dispatched"] == 0


def test_no_retrace_across_same_bucket_jobs(pallas_call_counter):
    """Bucketed coalescing means a warm service never re-lowers: jobs of
    the same bucket (any real/padded composition) hit the jit cache —
    under the new default (megakernel + datapath='df32'), whose warm
    lowering set is exactly the two megakernel bodies (per-kernel-name
    counts from the conftest LaunchLog)."""
    from repro.fhe_client.client import FHEClient
    cl = FHEClient(profile="tiny")        # fresh traces land in the counter
    assert (cl.pipeline, cl.datapath) == ("megakernel", "df32")
    svc = ClientService(client=cl, buckets=(2,))
    cts = svc.encrypt_many(_msgs(cl, 2, seed=4))      # warms enc bucket 2
    svc.decrypt_many(cts.truncated(2))                # warms dec bucket 2
    warm = len(pallas_call_counter)
    warm_names = pallas_call_counter.by_name()
    # one megakernel body per direction: the whole warm service lowered
    # exactly one encode+encrypt and one decrypt+decode pallas_call
    assert warm_names == {"_encode_encrypt_kernel": 1,
                          "_decrypt_decode_kernel": 1}
    cts2 = svc.encrypt_many(_msgs(cl, 3, seed=5))     # 2 jobs, one padded
    svc.decrypt_many(cts2.truncated(2))               # 2 jobs, one padded
    assert len(pallas_call_counter) == warm           # zero new lowerings
    assert pallas_call_counter.by_name() == warm_names


# ---------------------------------------------------------------------------
# sharded kernel entry points (1-device mesh in-process; >=2 in subprocess)
# ---------------------------------------------------------------------------


def test_sharded_ops_bit_identical_single_device_mesh():
    ctx = get_context("tiny")
    sk, pk = keygen(ctx)
    mesh = shd.stream_mesh(jax.devices()[:1])
    rng = np.random.default_rng(0)
    pt = rng.integers(0, ctx.q_list[0],
                      (3, ctx.params.n_limbs, ctx.params.n)).astype(np.uint32)
    c0s, c1s = kops.encrypt_fused_sharded(pt, pk.b_mont, pk.a_mont, ctx,
                                          mesh, nonce0=7)
    c0r, c1r = kops.encrypt_fused(pt, pk.b_mont, pk.a_mont, ctx, nonce0=7)
    np.testing.assert_array_equal(np.asarray(c0s), np.asarray(c0r))
    np.testing.assert_array_equal(np.asarray(c1s), np.asarray(c1r))
    ms = kops.decrypt_fused_sharded(c0s[:, :2], c1s[:, :2], sk.s_mont, ctx,
                                    mesh)
    mr = kops.decrypt_fused(c0r[:, :2], c1r[:, :2], sk.s_mont, ctx)
    np.testing.assert_array_equal(np.asarray(ms), np.asarray(mr))
    with pytest.raises(ValueError):      # batch must divide the mesh
        shd.stream_groups(jax.devices(), n_streams=len(jax.devices()) + 1)


def _run_multidevice(script: str, n_devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


_DUAL_STREAM_SCRIPT = r"""
import numpy as np, jax
from repro.fhe_client.client import FHEClient
from repro.fhe_client.service import ClientService

assert len(jax.devices()) == 2
cl = FHEClient(profile="tiny")
rng = np.random.default_rng(0)
n = cl.ctx.params.n_slots
msgs = (rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))) * .5
base = cl.nonce
direct = cl.encode_encrypt_batch(msgs)
ref_dec = cl.decrypt_decode_batch(direct.truncated(2))
cl.nonce = base

svc = ClientService(client=cl, buckets=(2,), n_streams=2)
assert svc.scheduler.n_streams == 2
cts = svc.encrypt_many(msgs)                       # 2 enc jobs -> 2xENC
assert (np.asarray(cts.c0) == np.asarray(direct.c0)).all()
assert (np.asarray(cts.c1) == np.asarray(direct.c1)).all()
got = svc.decrypt_many(direct.truncated(2))        # 2 dec jobs -> 2xDEC
assert (got == ref_dec).all()

rounds = {}
for rec in svc.dispatch_log:
    rounds.setdefault(rec.round, set()).add(rec.stream)
concurrent = [streams for streams in rounds.values() if len(streams) >= 2]
assert concurrent, f"no round used both streams: {svc.dispatch_log}"
modes = svc.stats()["modes"]
assert "2xENC" in modes and "2xDEC" in modes, modes

# encrypt results from both stream devices come back as host rows of their
# own; fed straight back for decryption they upload to either stream
rids = [svc.submit_encrypt(m) for m in msgs]
svc.flush()
rows = [svc.result(r) for r in rids]
assert all(type(a) is np.ndarray and memoryview(a.base).nbytes == a.nbytes
           for row in rows for a in (row.c0, row.c1))
drids = [svc.submit_decrypt(row) for row in rows]
svc.flush()
out = np.stack([svc.result(r) for r in drids])
assert np.max(np.abs(out - msgs)) < 1e-3      # round-trip through both devices
print("DUAL-STREAM-OK", modes)
"""


def test_dual_stream_two_devices_subprocess():
    """On a 2-device mesh the service runs two concurrent streams (2xENC /
    2xDEC rounds land on both devices) and stays bit-identical."""
    out = _run_multidevice(_DUAL_STREAM_SCRIPT, 2)
    assert "DUAL-STREAM-OK" in out


_SHARDED_STREAM_SCRIPT = r"""
import numpy as np, jax
from repro.fhe_client.client import FHEClient
from repro.fhe_client.service import ClientService

assert len(jax.devices()) == 2
cl = FHEClient(profile="tiny")
rng = np.random.default_rng(0)
n = cl.ctx.params.n_slots
msgs = (rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))) * .5
base = cl.nonce
direct = cl.encode_encrypt_batch(msgs)
ref_dec = cl.decrypt_decode_batch(direct.truncated(2))
cl.nonce = base

# one stream spanning both devices: the batch axis shard_maps across them
svc = ClientService(client=cl, buckets=(4,), n_streams=1,
                    devices=jax.devices())
assert svc.scheduler.pad_multiple == 2
cts = svc.encrypt_many(msgs)
assert (np.asarray(cts.c0) == np.asarray(direct.c0)).all()
assert (np.asarray(cts.c1) == np.asarray(direct.c1)).all()
got = svc.decrypt_many(direct.truncated(2))
assert (got == ref_dec).all()

# each row of the sharded bucket comes to the host over a buffer of its own
base = cl.nonce
rids = [svc.submit_encrypt(m) for m in msgs[:3]]          # partial bucket
svc.flush()
rows = [svc.result(r) for r in rids]
cl.nonce = base
direct = cl.encode_encrypt_batch(msgs[:3])
arrays = [a for row in rows for a in (row.c0, row.c1)]
assert all(type(a) is np.ndarray and memoryview(a.base).nbytes == a.nbytes
           for a in arrays)
assert not any(np.shares_memory(a, b)
               for i, a in enumerate(arrays) for b in arrays[i + 1:])
for i, row in enumerate(rows):
    assert (row.c0 == np.asarray(direct.c0)[i]).all()
    assert (row.c1 == np.asarray(direct.c1)[i]).all()
print("SHARDED-STREAM-OK")
"""


@pytest.mark.slow
def test_sharded_stream_two_devices_subprocess():
    """A 2-device stream group shard_maps the batch axis of the limb-folded
    grid and still reproduces the direct path bit for bit."""
    out = _run_multidevice(_SHARDED_STREAM_SCRIPT, 2)
    assert "SHARDED-STREAM-OK" in out


# ---------------------------------------------------------------------------
# nightly sweeps
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multi_bucket_identity_sweep(svc_client):
    """Every (request count, bucket composition) reproduces the direct
    batched ciphertexts bit for bit from the same nonce base."""
    cl = svc_client
    svc = ClientService(client=cl, buckets=(1, 2, 4))
    for k in (1, 2, 3, 5, 8):
        msgs = _msgs(cl, k, seed=100 + k)
        base = cl.nonce
        direct = cl.encode_encrypt_batch(msgs)
        ref_dec = cl.decrypt_decode_batch(direct.truncated(2))
        cl.nonce = base
        cts = svc.encrypt_many(msgs)
        np.testing.assert_array_equal(np.asarray(cts.c0),
                                      np.asarray(direct.c0))
        np.testing.assert_array_equal(np.asarray(cts.c1),
                                      np.asarray(direct.c1))
        np.testing.assert_array_equal(svc.decrypt_many(direct.truncated(2)),
                                      ref_dec)
