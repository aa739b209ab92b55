"""Benchmark driver (deliverable d): one bench per paper table/figure,
plus the system benches that grew past the paper (client service, ...).

Bench modules are DISCOVERED, not hard-coded: every ``bench_*.py`` in this
directory exposing a ``run() -> rows`` hook is included (paper-figure
order first for stable CSV diffs, newly added modules after). Prints
``bench,name,us_per_call,derived`` CSV and writes
benchmarks/results/benchmarks.json.

  PYTHONPATH=src python -m benchmarks.run [--only fig5b_lanes]
"""

import argparse
import json
import os

# canonical ordering for the paper-figure reproductions; discovered
# modules not listed here run afterwards in name order
PREFERRED_ORDER = (
    "bench_modmul",          # Table I
    "bench_radix",           # Fig. 4
    "bench_precision",       # Fig. 3c
    "bench_workload",        # Fig. 2b
    "bench_lanes",           # Fig. 5b
    "bench_memory",          # Fig. 6b + §IV-B
    "bench_client_latency",  # Fig. 5a
    "bench_client_service",  # §III scheduling, executed (requests/s)
    "bench_server_ops",      # server-side CKKS ops + BTS inventory
)


def discover_modules():
    here = os.path.dirname(os.path.abspath(__file__))
    found = sorted(f[:-3] for f in os.listdir(here)
                   if f.startswith("bench_") and f.endswith(".py"))
    ordered = [m for m in PREFERRED_ORDER if m in found]
    return ordered + [m for m in found if m not in ordered]


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module name")
    args = ap.parse_args()

    import importlib
    all_rows = []
    print("bench,name,us_per_call,derived")
    for mod_name in discover_modules():
        if args.only and args.only not in mod_name:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        if not hasattr(mod, "run"):
            print(f"# skipping {mod_name}: no run() hook", flush=True)
            continue
        rows = mod.run()
        for r in rows:
            print(f"{r['bench']},{r['name']},{r['us_per_call']},"
                  f"\"{r['derived']}\"", flush=True)
        all_rows += rows
    out = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "benchmarks.json"), "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"# wrote {len(all_rows)} rows to results/benchmarks.json")


if __name__ == "__main__":
    main()
