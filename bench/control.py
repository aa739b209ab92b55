#!/usr/bin/env python3
"""The check's control: the reference, computed in float32 where the
configuration states float64, put in the program's place.

    python3 bench/control.py --workload paper.enc-closed --seeds 1 2 3

For each seed it makes the run's own inputs (``driver.Pools``), lets the
float32 reference produce the results a run would sample (encryptions
under their nonces, decryptions of the run's ciphertexts) and hands
them to the same comparison a run makes (``check.compare``). Each
seed's numbers are printed as one JSON line; every one of them has to
come out not correct, and the smallest reading of each number is the
upper end its limit is set below. It needs no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import check                          # noqa: E402
import driver                         # noqa: E402
from reference import Reference       # noqa: E402


def control_checks(config: dict, mix: dict, seed: int) -> dict:
    """The comparison's numbers with the float32 reference as the
    program, on the inputs of a run with this seed."""
    pools = driver.Pools(config, mix, seed)
    low = Reference(config, precision="float32")
    want = {k: int(v) for k, v in mix.get("check", {}).items() if v}
    samples, nonces, requests = {}, {}, []
    for kind, count in want.items():
        samples[kind] = []
        for i in range(count):
            done = driver.Done(index=i, kind=kind, rid=len(requests),
                               t_due=0.0, t_done=0.0)
            requests.append(done)
            if kind == "enc":
                nonces[done.rid] = i
                z = pools.messages[i % len(pools.messages)]
                c0, c1 = low.encrypt(z, i)
                samples[kind].append((done, (c0, c1)))
            else:
                c0, c1, _ = pools.ciphertexts[i % len(pools.ciphertexts)]
                samples[kind].append((done, low.decrypt(c0, c1)))
    window = types.SimpleNamespace(requests=requests, samples=samples,
                                   nonces=nonces, compiles=0,
                                   jit_entries_added=0)
    return check.compare(config, mix, window, pools.messages,
                         pools.ciphertexts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    _spec, _cell, config, mix = run.load_cell(args.workload)
    for seed in args.seeds:
        checks = control_checks(config, mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": check.passed(checks),
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
