"""Service-mesh throughput + wire-compression harness (nightly lane).

Runs the multi-process mesh end-to-end for N in {1, 2} workers against a
single-process ``ClientService`` baseline and reports, per fleet size:

* requests/s for the closed-loop encrypt/decrypt mix,
* p50/p99 single-request round-trip latency (submit -> flush -> result
  through a worker subprocess),
* measured wire bytes/request from the router's transport telemetry,
* a hard ``bit_identical`` column: every mesh ciphertext is compared
  bit-for-bit against the single-process service from the same base
  nonce — the run FAILS (assert) if bit-transparency breaks, it never
  just reports a worse number.

Two more row families:

* ``mesh_wire`` — the seeded-upload claim, measured: the same
  ciphertexts submitted for decrypt as kind-2 (c0 + stream id, worker
  regenerates ``a``) vs kind-1 (full pair), as send-bytes/request off
  the router's frame counters. At the default ``test`` profile the
  payload is plane-dominated and the ratio lands near the paper's 2x;
  tiny profiles are header-dominated and measurably below it — which is
  exactly why this is a measured column and not a constant.
* ``mesh_recovery`` — a worker killed mid-round (after reading its
  first chunk off the socket, before handling it): the run asserts the
  re-sent chunks produce bit-identical ciphertexts under the same nonce
  lease and reports the requeue count.

Standalone entry point (also the CI artifact producer):

    PYTHONPATH=src python -m benchmarks.bench_mesh --profile test

merges its rows into benchmarks/results/benchmarks.json like the other
standalone benches.
"""

import argparse
import json
import os
import time

import numpy as np

from benchmarks.bench_client_service import merge_rows


def _percentiles(lats_s):
    return (float(np.percentile(lats_s, 50)) * 1e6,
            float(np.percentile(lats_s, 99)) * 1e6)


def _assert_bit_identical(cts, solo, what):
    for i, ct in enumerate(cts):
        assert np.array_equal(np.asarray(ct.c0), np.asarray(solo.c0[i])) \
            and np.array_equal(np.asarray(ct.c1), np.asarray(solo.c1[i])), \
            f"{what}: mesh ciphertext {i} is not bit-identical to the " \
            f"single-process service"


def run(profile: str = "test", workers=(1, 2), n_enc: int = 16,
        n_dec: int = 4, buckets=(1, 4, 8), reps: int = 2,
        n_probe: int = 6):
    from repro.core import encode, encrypt_symmetric_seeded, expand_seeded
    from repro.fhe_client.client import FHEClient
    from repro.fhe_client.service import ClientService, MeshRouter

    client = FHEClient(profile=profile)
    ctx = client.ctx
    rng = np.random.default_rng(7)
    n_req = n_enc + n_dec
    enc_msgs = (rng.standard_normal((n_enc, ctx.params.n_slots))
                + 1j * rng.standard_normal((n_enc, ctx.params.n_slots))) * 0.5

    # single-process baseline: same buckets, same FIFO grouping, nonce
    # base 0 — the bit-identity reference for every mesh fleet size
    base = client.nonce
    client.nonce = 0
    solo_svc = ClientService(client=client, buckets=buckets, n_streams=1)
    solo_cts = solo_svc.encrypt_many(enc_msgs)
    client.nonce = base

    dec_src = [ct for ct in solo_cts.truncated(2)]
    dec_rows = [(np.asarray(ct.c0), np.asarray(ct.c1), ct.scale)
                for ct in dec_src[:n_dec]]
    # seeded-vs-full wire probes: the SAME ciphertexts in both encodings
    # (kind-2 = c0 + stream id; kind-1 = the expanded full pair). The
    # nonce range is private to this probe — far above any service lease
    # but small enough that the derived stream id stays within u32.
    seeded = [encrypt_symmetric_seeded(
        encode(enc_msgs[i], ctx), client.keys.sk, ctx,
        nonce=(1 << 20) + i) for i in range(n_dec)]
    seeded_full = [expand_seeded(ct, ctx) for ct in seeded]

    rows = []
    for n_workers in workers:
        with MeshRouter(n_workers=n_workers, profile=profile,
                        buckets=buckets) as mesh:
            # --- bit-identity batch (doubles as the enc-bucket warm) ---
            rids = [mesh.submit_encrypt(m) for m in enc_msgs]
            mesh.flush()
            _assert_bit_identical([mesh.result(r) for r in rids], solo_cts,
                                  f"w{n_workers}")
            for tr in dec_rows[:1]:                 # dec-path warm
                mesh.result(mesh.submit_decrypt(tr))

            # --- closed-loop throughput ---
            mesh.telemetry.reset()
            t0 = time.perf_counter()
            for _ in range(reps):
                rids = [mesh.submit_encrypt(m) for m in enc_msgs]
                rids += [mesh.submit_decrypt(tr) for tr in dec_rows]
                mesh.flush()
                for r in rids:
                    mesh.result(r)
            t_mix = (time.perf_counter() - t0) / reps
            wire_rep = mesh.telemetry.wire_report()

            # --- single-request round-trip latency ---
            lats = []
            for i in range(n_probe):
                t0 = time.perf_counter()
                mesh.result(mesh.submit_encrypt(enc_msgs[i % n_enc]))
                lats.append(time.perf_counter() - t0)
            p50, p99 = _percentiles(lats)

            st = mesh.stats()
            assert st["failed_requests"] == 0
            rows.append({
                "bench": "mesh",
                "name": f"{profile}_w{n_workers}_mix{n_enc}to{n_dec}",
                "us_per_call": round(t_mix / n_req * 1e6, 1),
                "derived": f"req_per_s={n_req / t_mix:.1f};"
                           f"p50_us={p50:.1f};p99_us={p99:.1f};"
                           f"workers={n_workers};bit_identical=1;"
                           f"send_bytes_per_req="
                           f"{wire_rep['send_bytes_per_request']:.0f};"
                           f"recv_bytes_per_req="
                           f"{wire_rep['recv_bytes_per_request']:.0f};"
                           f"leases={st['leases_granted']};"
                           f"buckets={'/'.join(map(str, buckets))}",
            })

            if n_workers == max(workers):
                rows.append(_wire_row(mesh, profile, seeded, seeded_full))

    rows.append(_recovery_row(profile, buckets, enc_msgs, solo_cts))
    return rows


def _wire_row(mesh, profile, seeded, seeded_full):
    """The SAME ciphertexts submitted for decrypt as kind-2 (seeded,
    c0 + stream id) vs kind-1 (expanded full pair), as measured send
    bytes/request off the router's frame counters. The two runs must
    also DECODE identically — the compression is free, not lossy."""
    from repro.fhe_client.service import wire

    n = len(seeded)
    mesh.telemetry.reset()
    zs = [mesh.result(mesh.submit_decrypt(ct)) for ct in seeded]
    seeded_bytes = mesh.telemetry.wire_report()["send_bytes"] / n

    mesh.telemetry.reset()
    zf = [mesh.result(mesh.submit_decrypt((ct.c0, ct.c1, ct.scale)))
          for ct in seeded_full]
    full_bytes = mesh.telemetry.wire_report()["send_bytes"] / n

    for a, b in zip(zs, zf):
        assert np.array_equal(a, b), "seeded decode != full decode"
    # sanity: the measured split must match the serialized payload kinds
    wb = mesh.telemetry.wire_bytes
    assert sum(wb.value(worker=w, kind=wire.KIND_CT_BATCH, dir="send")
               for w in mesh.workers) > 0
    ratio = full_bytes / seeded_bytes
    return {
        "bench": "mesh_wire",
        "name": f"{profile}_seeded_vs_full_upload",
        "us_per_call": 0.0,
        "derived": f"seeded_send_bytes_per_req={seeded_bytes:.0f};"
                   f"full_send_bytes_per_req={full_bytes:.0f};"
                   f"full_over_seeded={ratio:.2f}x;"
                   f"n={n};kind2_vs_kind1_measured_on_router",
    }


def _recovery_row(profile, buckets, enc_msgs, solo_cts):
    """Worker 0 dies after READING its first submit frame: the router
    requeues its in-flight chunks verbatim onto the survivor and the
    results must stay bit-identical (same nonce grant)."""
    from repro.fhe_client.service import MeshRouter

    t0 = time.perf_counter()
    with MeshRouter(n_workers=2, profile=profile, buckets=buckets,
                    worker_faults={0: 0}) as mesh:
        rids = [mesh.submit_encrypt(m) for m in enc_msgs]
        mesh.flush()
        cts = [mesh.result(r) for r in rids]
        st = mesh.stats()
    t_total = time.perf_counter() - t0
    _assert_bit_identical(cts, solo_cts, "kill-recovery")
    assert st["requeues"] >= 1 and st["failed_requests"] == 0
    assert st["alive_workers"] == [1]
    return {
        "bench": "mesh_recovery",
        "name": f"{profile}_w2_midround_kill",
        "us_per_call": round(t_total / len(enc_msgs) * 1e6, 1),
        "derived": f"requeues={st['requeues']};bit_identical=1;"
                   f"alive_workers=1/2;failed_requests=0;"
                   f"includes_worker_startup=1",
    }


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="test",
                    help="CKKS profile; 'test' keeps the wire ratio "
                         "plane-dominated (the ~2x regime)")
    ap.add_argument("--workers", default="1,2",
                    help="comma-separated fleet sizes to run")
    ap.add_argument("--n-enc", type=int, default=16)
    ap.add_argument("--n-dec", type=int, default=4)
    ap.add_argument("--buckets", default="1,4,8")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args()
    rows = run(profile=args.profile,
               workers=tuple(int(w) for w in args.workers.split(",")),
               n_enc=args.n_enc, n_dec=args.n_dec,
               buckets=tuple(int(b) for b in args.buckets.split(",")),
               reps=args.reps)
    print("bench,name,us_per_call,derived")
    for r in rows:
        print(f"{r['bench']},{r['name']},{r['us_per_call']},"
              f"\"{r['derived']}\"", flush=True)
    path = merge_rows(rows)
    print(f"# merged {len(rows)} rows into {os.path.relpath(path)}")


if __name__ == "__main__":
    main()
