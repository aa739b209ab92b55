"""Workload matrix: mixed-tenant service throughput across the shipped
parameter presets.

One row per (preset, tenant count): a ``ClientService`` on the preset's
default client plus named tenants resolved through the
``KeyContextRegistry`` (derived seeds, per-tenant nonce leases), driven
with the paper's ~10:1 encrypt-heavy mix interleaved round-robin across
tenants — the co-residency pattern the multi-tenant layer exists for.

Each preset runs a warm-up pass over every (tenant, bucket) shape first,
then pins the WARM-PATH invariant the matrix exists to guard: during the
timed pass no jit core re-lowers (``warm_relowerings=0`` in the derived
column — computed from the jit cache sizes of every lane client's cores)
and the context cache stays within its bound. A regression that silently
retraces per tenant or per bucket shows up here as a nonzero count, not
just as a latency blip.

Fast lane (CI): the small presets (``tiny``, ``tinyboot``) — seconds.
Nightly: ``--presets n14,boot`` adds the paper-scale geometries.

Standalone entry point (also the CI artifact producer):

    PYTHONPATH=src python -m benchmarks.bench_workload_matrix \
        --presets tiny,tinyboot

merges its rows into benchmarks/results/benchmarks.json (replacing prior
``workload_matrix`` rows), composing with the full ``benchmarks.run``
driver exactly like ``bench_client_service``.
"""

import argparse
import os
import time

import numpy as np

from benchmarks.bench_client_service import (merge_rows, _mix_requests,
                                             telemetry_block)
from repro.telemetry import jit_cache_entries

FAST_PRESETS = ("tiny", "tinyboot")


def run_preset(preset: str, tenants=("alice", "bob"), n_enc: int = 20,
               n_dec: int = 2, buckets=(1, 2, 4), reps: int = 2,
               max_wait_ms: float = 5.0):
    """One matrix cell: mixed-tenant closed-loop run on one preset."""
    from repro.core.context import context_cache_len
    from repro.fhe_client.service import ClientService

    service = ClientService(profile=preset, buckets=buckets,
                            max_wait_s=max_wait_ms / 1e3)
    ctx = service.client.ctx
    n_slots = ctx.params.n_slots
    lanes = [None] + list(tenants)
    rng = np.random.default_rng(7)
    enc_msgs = (rng.standard_normal((n_enc, n_slots))
                + 1j * rng.standard_normal((n_enc, n_slots))) * 0.5
    kinds = _mix_requests(n_enc, n_dec)

    # decrypt sources PER LANE (a tenant can only decrypt its own rows)
    dec_rows = {}
    for i, lane in enumerate(lanes):
        rids = [service.submit_encrypt(enc_msgs[j % n_enc], tenant=lane)
                for j in range(n_dec)]
        service.flush()
        dec_rows[lane] = [
            (np.asarray(ct.c0[:2]), np.asarray(ct.c1[:2]), ct.scale)
            for ct in (service.result(r) for r in rids)]

    def one_pass():
        rids, e, d = [], 0, 0
        for i, kind in enumerate(kinds):
            lane = lanes[i % len(lanes)]      # round-robin across tenants
            if kind == "enc":
                rids.append(service.submit_encrypt(enc_msgs[e % n_enc],
                                                   tenant=lane))
                e += 1
            else:
                rids.append(service.submit_decrypt(
                    dec_rows[lane][d % n_dec], tenant=lane))
                d += 1
        service.flush()
        lats = [service.latency(r) for r in rids]
        for r in rids:
            service.result(r)
        return lats

    one_pass()                                # warm every (lane, bucket)
    # the shared re-lowering probe (telemetry.probe — same odometer the
    # service's telemetry_snapshot exports as fhe_jit_cache_entries).
    # Warm-up made every tenant resident, so lane_clients() is complete.
    clients = service.lane_clients()
    warm_jit = jit_cache_entries(clients)
    service.reset_telemetry()                 # timed window only

    t0 = time.perf_counter()
    lats = []
    for _ in range(reps):
        lats += one_pass()
    t_total = (time.perf_counter() - t0) / reps

    relowered = jit_cache_entries(clients) - warm_jit
    n_req = len(kinds)
    p50, p99 = np.percentile(np.asarray(lats) * 1e6, [50, 99])
    reg = service.registry.stats()
    n_ctx = context_cache_len()
    return {
        "bench": "workload_matrix",
        "name": f"{preset}_tenants{len(lanes)}_mix{n_enc}to{n_dec}",
        "us_per_call": round(t_total / n_req * 1e6, 1),
        "derived": f"req_per_s={n_req / t_total:.1f};"
                   f"p50_us={p50:.1f};p99_us={p99:.1f};"
                   f"tenants={len(lanes)};"
                   f"warm_relowerings={relowered};"
                   f"contexts={n_ctx};"
                   f"registry_resident={reg['resident']};"
                   f"registry_evictions={reg['evictions']};"
                   f"nonce_leases={reg['leases_granted']};"
                   f"buckets={'/'.join(map(str, buckets))}",
        "telemetry": telemetry_block(service),
    }, relowered


def run(presets=FAST_PRESETS, tenants=("alice", "bob"), n_enc: int = 20,
        n_dec: int = 2, buckets=(1, 2, 4), reps: int = 2,
        strict: bool = True):
    """Matrix over presets; raises if the warm path re-lowered anywhere
    (strict=True) — CI treats a retrace regression as a failure, not a
    number that drifts."""
    rows, violations = [], []
    for preset in presets:
        row, relowered = run_preset(preset, tenants=tenants, n_enc=n_enc,
                                    n_dec=n_dec, buckets=buckets, reps=reps)
        rows.append(row)
        if relowered:
            violations.append(f"{preset}: {relowered} warm re-lowerings")
    if strict and violations:
        raise RuntimeError(
            "workload matrix warm-path pin violated — the timed pass "
            "retraced jit cores that the warm-up pass should have "
            "compiled: " + "; ".join(violations))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--presets", default=",".join(FAST_PRESETS),
                    help="comma-separated preset names (nightly adds "
                         "n14,boot)")
    ap.add_argument("--tenants", default="alice,bob",
                    help="comma-separated named tenants co-resident with "
                         "the default lane")
    ap.add_argument("--n-enc", type=int, default=20)
    ap.add_argument("--n-dec", type=int, default=2)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--buckets", default="1,2,4")
    ap.add_argument("--no-strict", action="store_true",
                    help="report warm re-lowerings instead of failing")
    args = ap.parse_args()
    rows = run(presets=tuple(args.presets.split(",")),
               tenants=tuple(t for t in args.tenants.split(",") if t),
               n_enc=args.n_enc, n_dec=args.n_dec,
               buckets=tuple(int(b) for b in args.buckets.split(",")),
               reps=args.reps, strict=not args.no_strict)
    print("bench,name,us_per_call,derived")
    for r in rows:
        print(f"{r['bench']},{r['name']},{r['us_per_call']},"
              f"\"{r['derived']}\"", flush=True)
    path = merge_rows(rows)
    print(f"# merged {len(rows)} rows into {os.path.relpath(path)}")


if __name__ == "__main__":
    main()
