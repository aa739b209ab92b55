"""Multi-host service mesh (ISSUE 10 tentpole): worker subprocesses
behind the tenant-routing ``MeshRouter`` front-end.

The correctness half of the mesh acceptance, in the fast tier:

* **bit-transparency across the process boundary** — mesh encrypts are
  bit-identical to a single-process ``ClientService`` from the same base
  nonce (central ledger lease == solo batcher accounting), per lane;
* **tenant routing over kind-5 envelopes** — co-resident tenants through
  the mesh match their SOLO single-process runs bit for bit, and a
  default-lane envelope under a mismatched parameter fingerprint is
  rejected at the worker boundary (an error reply, never a silent
  re-key);
* **mid-round worker death** — a worker dying after reading a chunk off
  the socket loses nothing: the router re-sends the same bytes under the
  same nonce grant to a survivor, and the results stay bit-identical;
* **key distribution** — evaluation keys broadcast to every worker must
  come back byte-identical (cross-process key-derivation determinism),
  and match the local client's derivation.

Ordering note: the module-scoped router and solo service share per-lane
nonce accounting ONLY when each lane's first encrypt goes through both
in the same test — the bit-identity tests therefore run first for their
lane (pytest executes in definition order).

The multi-worker scaling soak is ``@slow`` (nightly lane): 3 workers,
three lanes, a mid-round hard kill, and a full encrypt->decrypt loop
through the surviving fleet.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import encode, encrypt_symmetric_seeded, expand_seeded
from repro.core.context import PROFILES
from repro.fhe_client.client import FHEClient
from repro.fhe_client.service import (ClientService, MeshRequestError,
                                      MeshRouter, wire)
from repro.fhe_client.service import mesh as mesh_mod
from repro.fhe_client.service.mesh import (DEFAULT_LANE_ID, ANON_LANE_ID,
                                           MeshError, _Chunk,
                                           lane_wire_identity)

TINY = PROFILES["tiny"]
BUCKETS = (1, 2, 4)


def _msgs(b, seed=0):
    rng = np.random.default_rng(seed)
    n = TINY.n_slots
    return (rng.standard_normal((b, n))
            + 1j * rng.standard_normal((b, n))) * 0.5


def _ct_equal(a, b) -> bool:
    return (np.array_equal(np.asarray(a.c0), np.asarray(b.c0))
            and np.array_equal(np.asarray(a.c1), np.asarray(b.c1))
            and a.n_limbs == b.n_limbs and a.scale == b.scale)


@pytest.fixture(scope="module")
def mesh():
    """2-worker mesh, module-scoped: the worker client builds dominate
    the cost, so every routing/identity test shares one fleet."""
    with MeshRouter(n_workers=2, profile="tiny", buckets=BUCKETS) as m:
        yield m


@pytest.fixture(scope="module")
def local():
    """In-process client under the SAME params the workers run — the
    solo side of every bit-identity comparison."""
    return FHEClient(profile="tiny")


@pytest.fixture(scope="module")
def solo_svc(local):
    """Single-process service sharing the mesh's bucket config; its
    per-lane nonce accounting starts at 0 exactly like the router's
    central ledger."""
    return ClientService(client=local, buckets=BUCKETS, n_streams=1)


# ---------------------------------------------------------------------------
# bit-transparency across the process boundary
# ---------------------------------------------------------------------------


def test_mesh_encrypt_bit_identical_to_solo(mesh, local, solo_svc):
    """5 messages -> FIFO groups of (4, 1) -> central leases (0..3, 4):
    the mesh ciphertexts must equal the single-process service's bit for
    bit, whichever worker encrypted each chunk."""
    msgs = _msgs(5, seed=1)
    rids = [mesh.submit_encrypt(m) for m in msgs]
    assert mesh.flush() == 5
    got = [mesh.result(r) for r in rids]

    solo = solo_svc.encrypt_many(msgs)
    for i, ct in enumerate(got):
        assert np.array_equal(np.asarray(ct.c0), np.asarray(solo.c0[i])), i
        assert np.array_equal(np.asarray(ct.c1), np.asarray(solo.c1[i])), i
        assert ct.n_limbs == solo.n_limbs and ct.scale == solo.scale
    st = mesh.stats()
    assert st["failed_requests"] == 0 and st["leases_granted"] >= 2


def test_mesh_decrypt_full_and_seeded_bit_identical(mesh, local):
    """The seeded kind-2 path (c1 regenerated worker-side from the lane
    stream) must decode identically to the same ciphertext shipped full
    as kind-1 — and at measurably fewer wire bytes."""
    z = _msgs(1, seed=2)[0]
    pt = encode(z, local.ctx)
    sct = encrypt_symmetric_seeded(pt, local.keys.sk, local.ctx, nonce=123)
    fct = expand_seeded(sct, local.ctx)

    rid_s = mesh.submit_decrypt(sct)
    rid_f = mesh.submit_decrypt((fct.c0, fct.c1, fct.scale))
    mesh.flush()
    zs, zf = mesh.result(rid_s), mesh.result(rid_f)
    np.testing.assert_array_equal(zs, zf)      # bit-identical decode
    np.testing.assert_allclose(zs, z, atol=1e-6)

    # the compression is visible on the measured transport: kind-2
    # submit bytes < kind-1 submit bytes for the same ciphertext
    wb = mesh.telemetry.wire_bytes
    seeded = sum(wb.value(worker=w, kind=wire.KIND_CT_SEEDED, dir="send")
                 for w in mesh.workers)
    full = sum(wb.value(worker=w, kind=wire.KIND_CT_BATCH, dir="send")
               for w in mesh.workers)
    assert 0 < seeded < full


def test_mesh_seeded_rejects_missing_stream(mesh, local):
    from repro.core.encryptor import Ciphertext
    bare = Ciphertext(c0=np.zeros((3, TINY.n), np.uint32), c1=None,
                      n_limbs=3, scale=2.0 ** 40, a_stream=None)
    with pytest.raises(ValueError, match="a_stream"):
        mesh.submit_decrypt(bare)


# ---------------------------------------------------------------------------
# tenant routing over kind-5 envelopes
# ---------------------------------------------------------------------------


def test_mesh_tenant_coresident_matches_solo(mesh, solo_svc):
    """Interleaved tenants through the mesh == each tenant alone through
    a single-process service: the kind-5 lane identity reaches the right
    worker-side key context and the per-lane leases stay independent of
    the cross-lane interleave."""
    alice, bob = _msgs(3, seed=3), _msgs(2, seed=4)
    rids_a = [mesh.submit_encrypt(m, tenant="alice") for m in alice]
    rids_b = [mesh.submit_encrypt(m, tenant="bob") for m in bob]
    mesh.flush()
    got_a = [mesh.result(r) for r in rids_a]
    got_b = [mesh.result(r) for r in rids_b]

    solo_a = [solo_svc.submit_encrypt(m, tenant="alice") for m in alice]
    solo_b = [solo_svc.submit_encrypt(m, tenant="bob") for m in bob]
    solo_svc.flush()
    for got, solo in ((got_a, solo_a), (got_b, solo_b)):
        for ct, rid in zip(got, solo):
            assert _ct_equal(ct, solo_svc.result(rid))
    # distinct lanes, distinct key streams: alice's first ct != bob's
    assert not np.array_equal(np.asarray(got_a[0].c0),
                              np.asarray(got_b[0].c0))


def test_mesh_reserved_lane_ids_rejected(mesh):
    for tid in (DEFAULT_LANE_ID, ANON_LANE_ID):
        with pytest.raises(ValueError, match="reserved"):
            mesh.submit_encrypt(_msgs(1)[0], tenant=tid)


def test_mesh_submit_validation_matches_service(mesh):
    with pytest.raises(ValueError, match="1-D"):
        mesh.submit_encrypt(_msgs(2, seed=5))            # 2-D batch
    with pytest.raises(ValueError, match="slots"):
        mesh.submit_encrypt(np.zeros(TINY.n_slots + 1, complex))
    with pytest.raises(ValueError, match="non-finite"):
        bad = np.zeros(TINY.n_slots, complex)
        bad[0] = np.nan
        mesh.submit_encrypt(bad)
    with pytest.raises(ValueError, match="not numeric"):
        mesh.submit_encrypt(np.array(["x"] * TINY.n_slots))
    with pytest.raises(ValueError, match="Ciphertext"):
        mesh.submit_decrypt("not a ciphertext")
    with pytest.raises(KeyError):
        mesh.result(10_000_000)


def test_mesh_result_consumed_once(mesh):
    rid = mesh.submit_encrypt(_msgs(1, seed=6)[0])
    mesh.result(rid)                           # flushes + retrieves
    with pytest.raises(KeyError, match="already retrieved"):
        mesh.result(rid)


def test_mesh_fingerprint_mismatch_rejected_at_worker_boundary(mesh):
    """A kind-5 envelope claiming the DEFAULT lane under a different
    parameter fingerprint must come back as an error reply from the
    worker (never silently served under the worker's own keys). The
    router never emits such an envelope, so this dispatches a crafted
    chunk through its transport seam."""
    bad_p = dataclasses.replace(mesh.params, seed=mesh.params.seed + 1)
    inner = wire.serialize_result(_msgs(1, seed=7))
    rid = mesh._next_rid
    mesh._next_rid += 1
    mesh._send_chunk(_Chunk(
        tag=next(mesh._tags), lane=None, kind="enc",
        wire_kind=wire.KIND_RESULT, rids=(rid,),
        payload=wire.serialize_tenant_envelope(DEFAULT_LANE_ID, bad_p,
                                               inner),
        aux=0, count=1))
    mesh.flush()
    with pytest.raises(MeshRequestError, match="parameter"):
        mesh.result(rid)
    # the worker survives the rejection and keeps serving
    rid2 = mesh.submit_encrypt(_msgs(1, seed=8)[0])
    mesh.flush()
    mesh.result(rid2)


def test_lane_wire_identity_mapping(mesh):
    p = mesh.params
    assert lane_wire_identity(None, p) == (DEFAULT_LANE_ID, p)
    assert lane_wire_identity((None, p), p) == (ANON_LANE_ID, p)
    assert lane_wire_identity(("alice", p), p) == ("alice", p)


# ---------------------------------------------------------------------------
# key distribution
# ---------------------------------------------------------------------------


def test_mesh_eval_keys_consensus_and_local_match(mesh, local):
    """The broadcast requires byte-identical kind-4 replies from every
    worker, and the consensus keys equal the local client's derivation —
    same lane => same derived material on every process."""
    keys = mesh.evaluation_keys(rotations=(1, 2), include_relin=True)
    assert keys.relin is not None and keys.rotations == (1, 2)
    ours = local.make_evaluation_keys((1, 2), include_relin=True,
                                      seed=local.seed)
    assert wire.serialize_evaluation_keys(keys) == \
        wire.serialize_evaluation_keys(ours)


# ---------------------------------------------------------------------------
# mid-round worker death
# ---------------------------------------------------------------------------


def test_mesh_worker_kill_recovery_bit_identical(local):
    """Worker 0 exits after READING its first submit frame (before
    handling): the router must detect the EOF, re-send the orphaned
    chunks verbatim to the survivor, and the results must still be
    bit-identical to a single-process service — the same nonce grant
    travels with the re-sent chunk."""
    with MeshRouter(n_workers=2, profile="tiny", buckets=BUCKETS,
                    worker_faults={0: 0}) as m:
        msgs = _msgs(5, seed=9)
        rids = [m.submit_encrypt(x) for x in msgs]
        assert m.flush() == 5
        got = [m.result(r) for r in rids]

        assert m.alive_workers == [1]
        st = m.stats()
        assert st["requeues"] >= 1 and st["failed_requests"] == 0
        assert [e.kind for e in m.events.replay(kind="worker_failed")] \
            == ["worker_failed"]
        assert len(m.events.replay(kind="requeue")) == st["requeues"]

        base = local.nonce
        local.nonce = 0                    # replay the mesh's lease range
        try:
            solo = ClientService(client=local, buckets=BUCKETS,
                                 n_streams=1).encrypt_many(msgs)
        finally:
            local.nonce = base
        for i, ct in enumerate(got):
            assert np.array_equal(np.asarray(ct.c0),
                                  np.asarray(solo.c0[i])), i
            assert np.array_equal(np.asarray(ct.c1),
                                  np.asarray(solo.c1[i])), i

        # the surviving single-worker mesh still serves decrypts
        rid = m.submit_decrypt((got[0].c0, got[0].c1, got[0].scale))
        np.testing.assert_allclose(m.result(rid), msgs[0], atol=1e-6)


def test_mesh_all_workers_dead_fails_loudly(local):
    from repro.fhe_client.service import AllWorkersFailed
    with MeshRouter(n_workers=1, profile="tiny", buckets=BUCKETS,
                    worker_faults={0: 0}) as m:
        rid = m.submit_encrypt(_msgs(1, seed=10)[0])
        with pytest.raises(AllWorkersFailed):
            m.flush()
        with pytest.raises(MeshRequestError):
            m.result(rid)
        assert m.stats()["alive_workers"] == []


# ---------------------------------------------------------------------------
# nightly scaling soak
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mesh_multi_worker_soak_with_midround_kill():
    """3 workers, three lanes, a hard kill while chunks are in flight,
    then the full loop: every ciphertext encrypted by the (degraded)
    mesh decrypts back through the mesh to its message."""
    with MeshRouter(n_workers=3, profile="tiny", buckets=BUCKETS) as m:
        lanes = {None: _msgs(6, seed=20), "alice": _msgs(6, seed=21),
                 "bob": _msgs(6, seed=22)}
        rids = {lane: [m.submit_encrypt(x, tenant=lane) for x in zs]
                for lane, zs in lanes.items()}
        m._pump()                          # dispatch: chunks now in flight
        victim = next(w.id for w in m.workers.values()
                      if w.alive and w.outstanding)
        m.kill_worker(victim)
        m.flush()
        assert victim not in m.alive_workers
        assert len(m.alive_workers) == 2
        cts = {lane: [m.result(r) for r in rs]
               for lane, rs in rids.items()}

        drids = {lane: [m.submit_decrypt((ct.c0, ct.c1, ct.scale),
                                         tenant=lane) for ct in row]
                 for lane, row in cts.items()}
        m.flush()
        for lane, zs in lanes.items():
            for i, dr in enumerate(drids[lane]):
                np.testing.assert_allclose(m.result(dr), zs[i], atol=1e-6)

        st = m.stats()
        assert st["failed_requests"] == 0
        assert st["wire"]["requests"] == 36
        assert st["wire"]["send_bytes"] > 0 and st["wire"]["recv_bytes"] > 0


def _fake_host(root, pci, vfio=(), accel=()):
    for i, (vendor, device) in enumerate(pci):
        d = root / "pci" / f"0000:00:{i:02x}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    (root / "dev" / "vfio").mkdir(parents=True)
    (root / "dev" / "vfio" / "vfio").write_text("")
    for n in vfio:
        (root / "dev" / "vfio" / str(n)).write_text("")
    for n in accel:
        (root / "dev" / f"accel{n}").write_text("")
    return str(root / "pci"), str(root / "dev")


V5E = ("0x1ae0", "0x0063")
GVNIC = ("0x1ae0", "0x0042")


@pytest.mark.parametrize("pci,vfio,accel,chips", [
    ([V5E] * 4, range(4), (), 4),
    ([V5E] * 4, (0,), (), 1),          # a host that passes one chip of 4
    ([V5E] * 4, (), range(4), 4),
    ([GVNIC, ("0x10de", "0x0063")], range(2), (), 0),
    ([], (), (), 0),
], ids=["vfio4", "vfio1_of_4", "accel4", "vfio_not_tpu", "none"])
def test_host_tpu_chips(tmp_path, pci, vfio, accel, chips):
    """Chips are the TPU device files the host passes, counted only where
    the PCI bus shows TPU chips: a Google device that is not a TPU (a
    gVNIC) or another vendor's device behind VFIO is no chip."""
    assert mesh_mod.host_tpu_chips(
        *_fake_host(tmp_path, pci, vfio, accel)) == chips


@pytest.mark.parametrize("platforms,pinned", [
    (None, True), ("", True), ("tpu", True), ("tpu,cpu", True),
    ("cpu", False),
], ids=["unset", "empty", "tpu", "tpu_cpu", "cpu"])
def test_worker_envs_follow_the_callers_platform(platforms, pinned):
    """On a host with TPU chips the workers are pinned one chip each only
    where the inherited JAX_PLATFORMS lets JAX take the TPU; a CPU mesh
    (JAX_PLATFORMS=cpu) keeps the inherited environment unchanged."""
    env = {"PATH": "/bin"}
    if platforms is not None:
        env["JAX_PLATFORMS"] = platforms
    envs = mesh_mod.worker_envs(env, 2, chips=4)
    if pinned:
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
        assert {e["JAX_PLATFORMS"] for e in envs} == {"tpu,cpu"}
        assert envs[0]["TPU_PROCESS_PORT"] != envs[1]["TPU_PROCESS_PORT"]
    else:
        assert envs == [env, env]
    assert mesh_mod.worker_envs(env, 2, chips=0) == [env, env]


def test_worker_envs_refuse_more_workers_than_chips():
    with pytest.raises(MeshError, match="need one TPU chip each"):
        mesh_mod.worker_envs({}, 5, chips=4)
    assert len(mesh_mod.worker_envs({"JAX_PLATFORMS": "cpu"}, 5,
                                    chips=4)) == 5
