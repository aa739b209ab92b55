"""Client-service throughput harness: requests/s and p50/p99 latency
under the paper's ~10:1 encrypt-heavy mix (Fig. 2b), service vs direct,
closed-loop and open-loop.

The direct baseline calls ``encode_encrypt_batch``/``decrypt_decode_batch``
once with perfectly pre-formed batches — the best case the service can
approach while it additionally pays for queueing, coalescing/padding into
buckets, per-job dispatch and per-request demux. Rows report the service's
absolute requests/s, its submit->materialize latency percentiles, and the
ratio to the direct baseline; the dispatch summary (streams, rounds, mode
sequence) is embedded in the derived column so TPU-mesh runs can be
compared against the single-device fallback.

The OPEN-LOOP section (``client_service_openloop`` rows) drives the
always-on engine (``start()``/background dispatch) with Poisson request
arrivals at several offered loads, expressed as fractions of the measured
closed-loop capacity so the sweep is machine-independent. Each load runs
fault-free and fault-injected (a ``FaultInjector`` kills one of two
oversubscribed streams mid-run; every request must still complete through
bounded retry on the survivor) and reports p50/p99 submit->result latency
against achieved throughput — the latency-vs-load curve a serving client
actually lives on, which the closed-loop rows structurally cannot show.

Standalone entry point (also the CI artifact producer):

    PYTHONPATH=src python -m benchmarks.bench_client_service --profile tiny

merges its rows into benchmarks/results/benchmarks.json (replacing prior
``client_service``/``client_service_openloop`` rows) instead of rewriting
the whole file the way the full ``benchmarks.run`` driver does.
"""

import argparse
import json
import os
import time

import numpy as np


def _mix_requests(n_enc: int, n_dec: int):
    """Interleaved ~10:1 request kinds, deterministic order."""
    kinds = []
    ratio = max(1, n_enc // max(1, n_dec))
    e = d = 0
    while e < n_enc or d < n_dec:
        for _ in range(ratio):
            if e < n_enc:
                kinds.append("enc")
                e += 1
        if d < n_dec:
            kinds.append("dec")
            d += 1
    return kinds


def telemetry_block(service) -> dict:
    """The ``telemetry`` block attached to benchmarks.json rows: per-stage
    histogram summaries (count/p50/p99 seconds, bucket-interpolated) plus
    windowed job/event counters — enough to see WHERE a row's time went
    (queue_wait vs dispatch vs execute) without shipping the full trace."""
    st = service.stats()
    return {
        "stages": st["stages"],
        "jobs_by_stream": {str(k): v for k, v in
                           st["jobs_by_stream"].items()},
        "rounds": st["rounds"],
        "events": st["events"],
        "spans": st["telemetry"]["spans"],
        "spans_dropped": st["telemetry"]["spans_dropped"],
    }


def _export_telemetry(service, telemetry_dir, prefix="service"):
    """Write (validated) Chrome trace + metrics snapshot artifacts for the
    service's current telemetry window; returns the two paths."""
    os.makedirs(telemetry_dir, exist_ok=True)
    trace_path = os.path.join(telemetry_dir, f"{prefix}_trace.json")
    metrics_path = os.path.join(telemetry_dir, f"{prefix}_metrics.json")
    service.export_trace(trace_path)           # validates before writing
    with open(metrics_path, "w") as f:
        json.dump(service.telemetry_snapshot(), f, indent=1)
    return trace_path, metrics_path


def run(profile: str = "test", n_enc: int = 40, n_dec: int = 4,
        buckets=(1, 4, 16), reps: int = 2, open_loop: bool = True,
        load_fracs=(0.5, 0.8, 1.2), max_wait_ms: float = 5.0,
        telemetry_dir=None):
    import jax

    from repro.fhe_client.client import FHEClient
    from repro.fhe_client.service import ClientService

    client = FHEClient(profile=profile)
    ctx = client.ctx
    n_req = n_enc + n_dec

    def msgs(b, seed):
        r = np.random.default_rng(seed)
        return (r.standard_normal((b, ctx.params.n_slots))
                + 1j * r.standard_normal((b, ctx.params.n_slots))) * 0.5

    enc_msgs = msgs(n_enc, 1)
    dec_src = client.encode_encrypt_batch(msgs(n_dec, 2)).truncated(2)
    dec_rows = list(dec_src)

    # --- direct baseline: pre-formed batches, one call per direction -------
    def direct_once():
        ct = client.encode_encrypt_batch(enc_msgs)
        jax.block_until_ready((ct.c0, ct.c1))
        client.decrypt_decode_batch(dec_src)     # returns numpy: synchronous

    direct_once()                                # warm (B=n_enc/n_dec traces)
    t0 = time.perf_counter()
    for _ in range(reps):
        direct_once()
    t_direct = (time.perf_counter() - t0) / reps

    # --- service: per-message requests through queue+batcher+streams -------
    service = ClientService(client=client, buckets=buckets)
    kinds = _mix_requests(n_enc, n_dec)

    def service_once():
        e = d = 0
        rids = []
        for kind in kinds:
            if kind == "enc":
                rids.append(service.submit_encrypt(enc_msgs[e]))
                e += 1
            else:
                rids.append(service.submit_decrypt(dec_rows[d]))
                d += 1
        service.flush()
        lats = [service.latency(r) for r in rids]
        for r in rids:
            service.result(r)
        return lats

    service_once()                               # warm (bucket traces)
    service.reset_telemetry()                    # timed window only: the
    t0 = time.perf_counter()                     # dispatch log, metrics and
    for _ in range(reps):                        # trace ring all restart here
        service_once()
    t_service = (time.perf_counter() - t0) / reps

    stats = service.stats()
    # latency percentiles come from the fhe_stage_seconds histogram (the
    # "total" stage = submit->demux), bucket-interpolated — the same
    # numbers stats()/the metrics snapshot report, one source of truth
    total = stats["stages"]["total"]
    p50, p99 = total["p50_s"] * 1e6, total["p99_s"] * 1e6
    timed_modes = [m.value for m, _k in
                   service.scheduler.modes_executed()]
    per_run = len(timed_modes) // reps           # one rep's round schedule
    modes = ",".join(timed_modes[:per_run][:8])
    rows = [{
        "bench": "client_service",
        "name": f"{profile}_mix{n_enc}to{n_dec}_direct",
        "us_per_call": round(t_direct / n_req * 1e6, 1),
        "derived": f"req_per_s={n_req / t_direct:.1f};"
                   f"preformed_batch_baseline",
    }, {
        "bench": "client_service",
        "name": f"{profile}_mix{n_enc}to{n_dec}_service",
        "us_per_call": round(t_service / n_req * 1e6, 1),
        "derived": f"req_per_s={n_req / t_service:.1f};"
                   f"p50_us={p50:.1f};p99_us={p99:.1f};"
                   f"vs_direct={t_direct / t_service:.2f}x;"
                   f"streams={stats['n_streams']};"
                   f"shards_per_stream={stats['shards_per_stream']};"
                   f"buckets={'/'.join(map(str, stats['buckets']))};"
                   f"modes={modes}",
        "telemetry": telemetry_block(service),
    }]
    if telemetry_dir is not None:
        tp, mp = _export_telemetry(service, telemetry_dir)
        print(f"# telemetry artifacts: {os.path.relpath(tp)} "
              f"{os.path.relpath(mp)}")
    if open_loop:
        rows += run_open_loop(profile=profile, n_req=n_req,
                              load_fracs=load_fracs, buckets=buckets,
                              max_wait_ms=max_wait_ms, client=client,
                              capacity=n_req / t_service)
    return rows


def _warm_buckets(service, enc_msgs, dec_rows):
    """Trace every (kind, bucket) shape once so open-loop latency
    percentiles measure the warm steady state, not jit compiles. Traces
    live on the shared client cores, so warming ONE service warms every
    service built over the same client."""
    for b in service.batcher.buckets:
        rids = [service.submit_encrypt(enc_msgs[i % len(enc_msgs)])
                for i in range(b)]
        rids += [service.submit_decrypt(dec_rows[i % len(dec_rows)])
                 for i in range(b)]
        service.flush()
        for r in rids:
            service.result(r)
    service.reset_telemetry()


def run_open_loop(profile: str = "test", n_req: int = 44,
                  load_fracs=(0.5, 0.8, 1.2), buckets=(1, 4, 16),
                  max_wait_ms: float = 5.0, seed: int = 0,
                  capacity: float | None = None, client=None):
    """Open-loop Poisson sweep over the always-on engine. Offered loads
    are fractions of the measured closed-loop capacity (machine-
    independent); each load runs fault-free and with a ``FaultInjector``
    killing stream 1 of 2 oversubscribed streams mid-run (recovery =
    bounded retry on the survivor; the run fails loudly if any request
    is lost). Two rows per load: p50/p99 latency vs achieved throughput."""
    import time as _time

    from repro.fhe_client.client import FHEClient
    from repro.fhe_client.service import ClientService, FaultInjector

    if client is None:
        client = FHEClient(profile=profile)
    ctx = client.ctx
    rng = np.random.default_rng(seed)
    n_dec = max(1, n_req // 11)
    n_enc = n_req - n_dec
    enc_msgs = (rng.standard_normal((n_enc, ctx.params.n_slots))
                + 1j * rng.standard_normal((n_enc, ctx.params.n_slots))) * 0.5
    dec_rows = [(np.asarray(ct.c0[:2]), np.asarray(ct.c1[:2]), ct.scale)
                for ct in client.encode_encrypt_batch(enc_msgs[:n_dec])
                .truncated(2)]
    kinds = _mix_requests(n_enc, n_dec)

    warm_svc = ClientService(client=client, buckets=buckets)
    _warm_buckets(warm_svc, enc_msgs, dec_rows)
    if capacity is None:                         # closed-loop capacity probe
        e = d = 0
        t0 = _time.perf_counter()
        for kind in kinds:
            if kind == "enc":
                warm_svc.submit_encrypt(enc_msgs[e])
                e += 1
            else:
                warm_svc.submit_decrypt(dec_rows[d])
                d += 1
        warm_svc.flush()
        capacity = n_req / (_time.perf_counter() - t0)

    rows = []
    for frac in load_fracs:
        rate = frac * capacity
        for fault in (False, True):
            # stream 0 takes every single-job round, so a fault pinned to
            # it is guaranteed to fire a few launches in, whatever the load
            faults = FaultInjector.kill_stream(0, after=2) if fault else None
            svc = ClientService(
                client=client, buckets=buckets,
                n_streams=2 if fault else None, oversubscribe=fault,
                faults=faults, max_wait_s=max_wait_ms / 1e3)
            run_rng = np.random.default_rng([seed, int(frac * 1000),
                                             int(fault)])
            schedule = np.cumsum(run_rng.exponential(1.0 / rate,
                                                     size=n_req))
            with svc:
                rids, e, d = [], 0, 0
                t0 = _time.perf_counter()
                for kind, t_at in zip(kinds, schedule):
                    dt = t_at - (_time.perf_counter() - t0)
                    if dt > 0:
                        _time.sleep(dt)
                    if kind == "enc":
                        rids.append(svc.submit_encrypt(enc_msgs[e]))
                        e += 1
                    else:
                        rids.append(svc.submit_decrypt(dec_rows[d]))
                        d += 1
                svc.flush()
                t_total = _time.perf_counter() - t0
                for r in rids:
                    svc.latency(r)              # raises if any request lost
                    svc.result(r)
                stats = svc.stats()
                tele = telemetry_block(svc)
                requeues = len(svc.events.replay("requeue"))
            # submit->demux percentiles from the stage histogram (bucket-
            # interpolated; same source as the telemetry block)
            total = stats["stages"]["total"]
            p50, p99 = total["p50_s"] * 1e3, total["p99_s"] * 1e3
            rows.append({
                "bench": "client_service_openloop",
                "name": f"{profile}_poisson_load{frac:g}"
                        + ("_fault" if fault else ""),
                "us_per_call": round(t_total / n_req * 1e6, 1),
                "derived": f"offered_req_s={rate:.1f};"
                           f"achieved_req_s={n_req / t_total:.1f};"
                           f"p50_ms={p50:.2f};p99_ms={p99:.2f};"
                           f"load_frac={frac:g};"
                           f"capacity_req_s={capacity:.1f};"
                           f"faults={int(fault)};"
                           f"requeues={requeues};"
                           f"retries={stats['retries']};"
                           f"alive_streams={len(stats['alive_streams'])}"
                           f"/{stats['n_streams']};"
                           f"completed={stats['completed']}",
                "telemetry": tele,
            })
    return rows


def merge_rows(rows, path=None):
    """Merge rows into results/benchmarks.json, replacing same-bench rows
    (so the standalone entry point composes with the full driver)."""
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "results",
                            "benchmarks.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    old = []
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
    benches = {r["bench"] for r in rows}
    merged = [r for r in old if r.get("bench") not in benches] + rows
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    return path


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="test")
    ap.add_argument("--n-enc", type=int, default=40)
    ap.add_argument("--n-dec", type=int, default=4)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--buckets", default="1,4,16",
                    help="comma-separated bucket sizes")
    ap.add_argument("--loads", default="0.5,0.8,1.2",
                    help="open-loop offered loads as fractions of the "
                         "measured closed-loop capacity")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="always-on partial-bucket deadline (ms)")
    ap.add_argument("--no-open-loop", action="store_true",
                    help="skip the open-loop Poisson sweep")
    ap.add_argument("--telemetry-dir", default=None,
                    help="export service_trace.json (validated Chrome "
                         "trace) + service_metrics.json (metrics snapshot) "
                         "for the timed closed-loop window into this dir")
    args = ap.parse_args()
    buckets = tuple(int(b) for b in args.buckets.split(","))
    load_fracs = tuple(float(x) for x in args.loads.split(","))
    rows = run(profile=args.profile, n_enc=args.n_enc, n_dec=args.n_dec,
               buckets=buckets, reps=args.reps,
               open_loop=not args.no_open_loop, load_fracs=load_fracs,
               max_wait_ms=args.max_wait_ms,
               telemetry_dir=args.telemetry_dir)
    print("bench,name,us_per_call,derived")
    for r in rows:
        print(f"{r['bench']},{r['name']},{r['us_per_call']},"
              f"\"{r['derived']}\"", flush=True)
    path = merge_rows(rows)
    print(f"# merged {len(rows)} rows into {os.path.relpath(path)}")


if __name__ == "__main__":
    main()
