"""Server-side CKKS op latency: ct x pt, ct x ct (+relin+rescale) and
slot rotation (key switching), warm per-call wall time plus the analytic
transform inventory at the bootstrappable preset.

Two kinds of rows:

  * ``server_ops`` — MEASURED warm per-call latency at a small preset
    (default ``tiny``: N=2^6, 3 limbs — the fast-lane geometry; pass
    ``--profile server`` standalone for the N=2^10 preset, which pays
    ~1-2 min of kernel compiles first).  ``derived`` carries the op's
    level/limb trajectory and the NTT-transform count the megakernel
    executes, so the row is machine-comparable.
  * ``server_ops_inventory`` — ANALYTIC per-op transform counts at the
    bootstrappable preset (N=2^16, 24 limbs, the paper's BTS geometry):
    no compile, no device time; pins the 3l+2-transform key-switch
    structure (DESIGN.md §6) the measured rows exercise at small l.

Standalone entry point (the CI artifact producer):

    PYTHONPATH=src python -m benchmarks.bench_server_ops --profile tiny

merges its rows into benchmarks/results/benchmarks.json (replacing prior
``server_ops``/``server_ops_inventory`` rows) like bench_client_service.
"""

import argparse
import time

import numpy as np

from benchmarks.bench_client_service import merge_rows


def _transforms(l: int) -> dict:
    """NTT-transform instances per op at level l (vectorized megakernel
    counts: a batched (rows, N) stacked transform is ONE instance)."""
    return {
        # decompose: l per-digit iNTTs batch to l instances; base-extend
        # re-NTTs all digits per target row as l+1 stacked instances
        "ks_decompose": 2 * l + 1,
        # + mod-down iNTT on the special row and the final per-poly NTT
        "keyswitch": 3 * l + 2,
        "rescale": 1,                    # one iNTT of the dropped limb
        "mul_pt_rescale": 1,
        "mul_ct": 3 * l + 3,             # keyswitch + rescale
        "rotate": 3 * l + 2,
    }


def _measured_rows(profile: str, reps: int) -> list:
    import jax

    from repro.fhe_client.client import FHEClient
    from repro.fhe_server import (ServerCiphertext, ServerEvaluator,
                                  encode_plaintext)

    client = FHEClient(profile=profile, pipeline="staged", datapath="f64")
    ctx = client.ctx
    lvl = min(ctx.params.n_limbs, 4)     # bound compile cost at deep L
    rng = np.random.default_rng(5)
    z = rng.uniform(-1, 1, ctx.params.n_slots)
    keys = client.make_evaluation_keys(rotations=(1,))
    ev = ServerEvaluator(ctx, keys)
    x = ServerCiphertext.from_batch(
        client.encode_encrypt_batch(z[None])).drop_to(lvl)
    pt = encode_plaintext(z.astype(np.complex128), ctx, x.level,
                          float(ctx.q_list[x.level - 1]))

    tf = _transforms(lvl)
    ops = {
        "mul_pt": (lambda: ev.mul_pt(x, pt), tf["mul_pt_rescale"]),
        "mul_ct": (lambda: ev.mul_ct(x, x), tf["mul_ct"]),
        "rotate": (lambda: ev.rotate(x, 1), tf["rotate"]),
    }
    rows = []
    for name, (fn, n_tf) in ops.items():
        out = fn()                       # compile + warm jit caches
        jax.block_until_ready((out.c0, out.c1))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready((out.c0, out.c1))
        dt = (time.perf_counter() - t0) / reps
        rows.append({
            "bench": "server_ops",
            "name": f"{profile}_{name}",
            "us_per_call": round(dt * 1e6, 1),
            "derived": f"n=2^{ctx.params.logn};level={lvl};"
                       f"out_level={out.level};transforms={n_tf};"
                       f"datapath=f64",
        })
    return rows


def _inventory_rows(profile: str = "boot") -> list:
    from repro.core import get_context

    ctx = get_context(profile)
    l = ctx.params.n_limbs
    tf = _transforms(l)
    rows = []
    for op in ("mul_pt_rescale", "mul_ct", "rotate", "ks_decompose"):
        rows.append({
            "bench": "server_ops_inventory",
            "name": f"{profile}_{op}",
            "us_per_call": 0.0,
            "derived": f"n=2^{ctx.params.logn};limbs={l};"
                       f"transforms={tf[op]};"
                       f"butterflies={tf[op] * l * ctx.n // 2 * ctx.params.logn:.3e}",
        })
    return rows


def run(profile: str = "tiny", reps: int = 20):
    return _measured_rows(profile, reps) + _inventory_rows()


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="tiny",
                    help="measured preset (tiny | server)")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    rows = run(profile=args.profile, reps=args.reps)
    print("bench,name,us_per_call,derived")
    for r in rows:
        print(f"{r['bench']},{r['name']},{r['us_per_call']},"
              f"\"{r['derived']}\"", flush=True)
    import os
    path = merge_rows(rows)
    print(f"# merged {len(rows)} rows into {os.path.relpath(path)}")


if __name__ == "__main__":
    main()
