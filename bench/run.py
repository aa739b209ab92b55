#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload paper.enc-closed --seed 7 \\
        --seconds 20 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything
that belongs to it is found by name: its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and its metrics
(``bench/metrics/<metric>.py``, one reader each). With ``--trace 0`` the
run prints the cell's end-to-end metrics; with ``--trace 1`` it records
a profiler trace of the window and prints the cell's per-layer metrics.

Set-up (``setup_s``) runs from process start to the window's first
request: building the service (keygen included), loading or compiling
every shape the mix uses, warming them, and making the run's messages
from ``--seed``. The reference's own time is left out of it: the
two-limb ciphertexts a decrypt mix sends are made by the reference, as
a server would return them. After the window the plain reference
checks a seeded sample of the window's own results (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks`` (each compared number with its limit, also printed
as the last lines of standard error). Without a TPU the run exits
non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
# JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
sys.path.insert(0, HERE)

import traffic           # noqa: E402


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell needs."""


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(spec: dict, name: str) -> dict:
    """A configuration named in BENCHMARK.json, from its file."""
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(ROOT, configs[name]["file"])) as f:
        return json.load(f)


def load_mix(name: str) -> dict:
    return traffic.load(os.path.join(HERE, "traffic", name + ".json"))


def load_cell(name: str):
    """(spec, cell, config, mix) for a workload named in BENCHMARK.json."""
    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    return spec, cell, load_config(spec, cell["config"]), \
        load_mix(cell["traffic"])


def cell_metrics(spec: dict, cell: str, section: str) -> list[str]:
    """Names of the metrics of ``section`` that the cell reports."""
    return [m["name"] for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int) -> dict:
    import jax
    from repro.kernels import ops as kops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} chips, the cell needs {chips}")
    if kops.default_interpret():
        raise NoChip("the kernels would run in interpret mode")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class Reading:
    """What a metric's reader reads: the cell, the window the driver
    recorded, the trace's reduction (traced runs) and the chip's
    peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def log(msg: str) -> None:
    print(msg, flush=True)


def run_cell(spec, cell, config, mix, seed: int, seconds: float,
             trace: bool, device: dict, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object."""
    import jax
    import numpy as np
    import check
    import costs
    import driver

    t = time.monotonic()
    runner = driver.Runner(config, mix, seed, seconds, keep_spans=trace)
    log(f"bench: service built and pools made in "
        f"{time.monotonic() - t:.3f} s, of which the reference "
        f"{runner.pools.reference_s:.3f} s")
    t = time.monotonic()
    runner.warm_up()
    log(f"bench: warm-up of buckets {list(runner.svc.batcher.buckets)} for "
        f"{traffic.kinds_used(mix)} in {time.monotonic() - t:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        cpu0, wall0 = os.times(), time.monotonic()
        window = runner.run(trace_dir)
        cpu1, wall1 = os.times(), time.monotonic()
        setup_s = window.t0 - t_start - runner.pools.reference_s
        summary = None
        if trace:
            import trace_reduce
            summary = trace_reduce.reduce_dir(trace_dir, costs.KERNELS,
                                                window.trace_s)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    messages = runner.pools.messages
    ciphertexts = runner.pools.ciphertexts
    limbs = runner.svc.client.ctx.params.n_limbs
    del runner
    gc.collect()

    failed = [d for d in window.requests if d.t_done is None]
    if failed:
        log(f"bench: {len(failed)} requests failed; the first: "
            f"{failed[0].error}")
    log(f"bench: {len(window.gc_pauses_s)} full collections in the window, "
        f"longest {max(window.gc_pauses_s, default=0.0) * 1e3:.3f} ms")
    if window.lateness_s:
        late = np.asarray(window.lateness_s)
        log(f"bench: generator lateness over {len(late)} sends: median "
            f"{np.median(late) * 1e3:.3f} ms, p99 "
            f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms")
    # how far the host held the process back: its CPU seconds in the
    # window against the wall, and the host's load
    log(f"bench: window: process CPU {cpu1.user - cpu0.user:.3f} s user, "
        f"{cpu1.system - cpu0.system:.3f} s system over "
        f"{wall1 - wall0:.3f} s; host load average "
        f"{' '.join(f'{x:.2f}' for x in os.getloadavg())}")
    sampled = {done.rid: done.kind for kind_samples in window.samples.values()
               for done, _ in kind_samples}
    rows = sorted({(sampled[rid], r) for rec in window.dispatch
                   for r, rid in enumerate(rec.rids) if rid in sampled})
    log(f"bench: sampled (kind, row) positions: {rows}")
    t = time.monotonic()
    checks = check.compare(config, mix, window, messages, ciphertexts)
    log(f"bench: reference check of {sum(len(v) for v in window.samples.values())}"
        f" results in {time.monotonic() - t:.3f} s")

    reading = Reading(cell=cell, config=config, mix=mix, seconds=seconds,
                      setup_s=setup_s, window=window, trace=summary,
                      n=1 << int(config["logn"]), limbs=limbs,
                      peaks=costs.peaks(device["kind"]) if trace else None)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec[section]}
    for name in cell_metrics(spec, cell["name"], section):
        value = load_reader(name)(reading)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    dev = dict(device, count=int(cell["chips"]),
               memory_peak_bytes=window.memory_peak_bytes)
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    result = {
        "correct": check.passed(checks),
        "attempted": len(window.requests),
        "failed": sum(d.t_done is None for d in window.requests),
        "metrics": metrics,
        "device": dev,
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def enable_cache() -> None:
    """JAX's persistent compilation cache in ``CACHE_DIR`` (the process
    set ``JAX_COMPILATION_CACHE_DIR`` to it before importing JAX), every
    program kept."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction, whatever the environment says: an evicting cache reads
    # an access-time file beside every entry, fails to write when one is
    # missing, and may drop a program the next run needs
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, config, mix = load_cell(args.workload)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program next to the benchmark ({SRC} is missing)",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, SRC)
    try:
        device = check_device(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    log(f"bench: {args.workload} on {device['count']} x {device['kind']}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    enable_cache()
    result = run_cell(spec, cell, config, mix, args.seed, args.seconds,
                      bool(args.trace), device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
