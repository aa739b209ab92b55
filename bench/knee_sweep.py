#!/usr/bin/env python3
"""Find the knee of an open-loop mix: the highest offered rate at which
the completed rate keeps up and the backlog does not grow.

    python3 bench/knee_sweep.py --config paper --traffic mix10-open \\
        --rates 60 90 120 150 180 --seconds 10 --seed 1

One process on one chip builds and warms the configuration's service
once, then runs the open-loop mix at each offered rate in turn, lowest
first, each
for ``--seconds``, up to the first rate that does not keep up, and
prints one JSON line per rate and the knee last. A rate keeps up
when the requests completed inside the window are at least
``KEEP_UP`` of those due, and the backlog (due, not yet in host memory)
at the window's close is no more than one full bucket above the backlog
at its middle. The knee is written into the mix's traffic file by hand,
as a number; the benchmark never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

KEEP_UP = 0.95


def backlog(requests, t: float) -> int:
    """Requests due before t whose result was not in host memory at t."""
    return sum(d.t_due <= t and (d.t_done is None or d.t_done > t)
               for d in requests)


def reading(rate: float, window, seconds: float, bucket: int) -> dict:
    import numpy as np
    due = len(window.requests)
    done = sum(d.t_done is not None and d.t_done < window.t1
               for d in window.requests)
    mid = backlog(window.requests, window.t0 + seconds / 2)
    end = backlog(window.requests, window.t1)
    lat = [d.t_done - d.t_due for d in window.requests
           if d.t_done is not None]
    return {"rate_per_s": rate, "offered_per_s": due / seconds,
            "completed_per_s": done / seconds, "backlog_mid": mid,
            "backlog_end": end,
            "p95_ms": float(np.percentile(lat, 95) * 1e3) if lat else None,
            "keeps_up": bool(due and done >= KEEP_UP * due
                             and end <= mid + bucket)}


def knee(readings) -> float | None:
    """The highest rate that keeps up, below the first that does not."""
    best = None
    for r in sorted(readings, key=lambda r: r["rate_per_s"]):
        if not r["keeps_up"]:
            break
        best = r["rate_per_s"]
    return best


def sweep(runner, mix: dict, rates, seconds: float, seed: int) -> list:
    """One reading per offered rate, lowest first, up to the first rate
    that does not keep up: past the knee the backlog only grows, and its
    results pile up in device memory until an allocation fails."""
    import traffic
    bucket = max(mix["service"]["buckets"])
    readings = []
    for rate in sorted(rates):
        runner.schedule = traffic.schedule(dict(mix, rate_per_s=rate), seed,
                                           seconds)
        readings.append(reading(rate, runner.run(), seconds, bucket))
        print(json.dumps(readings[-1]), flush=True)
        if not readings[-1]["keeps_up"]:
            break
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import run
    config = run.load_config(run.load_spec(), args.config)
    mix = run.load_mix(args.traffic)
    if mix["loop"] != "open":
        raise SystemExit(f"{args.traffic} is not an open-loop mix")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    sys.path.insert(0, run.SRC)
    run.check_device(1)
    import driver
    run.enable_cache()
    runner = driver.Runner(config, mix, args.seed, args.seconds)
    runner.warm_up()
    readings = sweep(runner, mix, args.rates, args.seconds, args.seed)
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "knee_per_s": knee(readings)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
