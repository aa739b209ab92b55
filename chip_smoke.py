#!/usr/bin/env python3
"""Bring-up smoke run of the CKKS client service on a TPU.

    python chip_smoke.py              # one chip: ClientService at `paper`
    python chip_smoke.py --chips 4    # four one-chip MeshRouter workers

One chip: builds ``ClientService(profile="paper")`` (N=2^16, 24 limbs,
2-limb decrypt, Delta=2^55) with the default ``FHEClient`` (streaming
megakernel, df32 datapath), warms every bucket shape the run uses, then
serves 20 encrypt and 2 decrypt requests (the paper's ~10:1 mix) through
``submit_encrypt``/``submit_decrypt``/``flush``/``result``. A child
process pinned to the CPU (``JAX_PLATFORMS=cpu``) regenerates the keys
from the same seed and computes the reference: ``encoder.encode(...,
fourier="device")`` + ``encryptor.encrypt`` for four of the messages, and
``encryptor.decrypt`` + ``encoder.decode(..., fourier="device")`` for the
two decrypted ciphertexts. The run fails unless the chip ciphertexts
equal the reference bit for bit, decode keeps >= 19.29 bits and agrees
with the reference within the df32 pair window, the service logged no
requeue or stream death, a second warm round compiled nothing, and each
compiled client core holds exactly one ``tpu_custom_call``.

Four chips: the router process stays on the CPU; each of four mesh
workers is given one chip. The mesh ciphertexts must equal the CPU
reference from the same base nonce.

Earlier lines print bring-up readings (compile seconds, warm per-batch
wall times, peak device memory); they are not a benchmark. The last line
is one JSON object: ``{"ok": true, "device": {...}}``. Without a TPU, or
outside a checkout of the repository, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

PROFILE = "paper"
N_ENC = 20                # encrypt requests per round
N_DEC = 2                 # decrypt requests per round (~10:1, Fig. 2b)
N_REF = 4                 # encrypt requests checked against the reference
BOOT_PREC_BITS = 19.29    # the paper's bootstrapping precision bar
PAIR_WINDOW = 2.0 ** -48  # df32 /Delta pair window (DESIGN.md §4)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def messages(n_slots: int, count: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    shape = (count, n_slots)
    return rng.uniform(-1, 1, shape) + 1j * rng.uniform(-1, 1, shape)


# ---------------------------------------------------------------------------
# CPU reference (runs in a child process with JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def reference(out: str, profile: str, seed: int, enc_nonces,
              dec_nonces) -> None:
    """Keys from the client seed, then the core reference path: encode
    with the device Fourier engine (interpret mode on the CPU) + encrypt
    for message i under enc_nonces[i]; encrypt + decrypt + decode for
    message i under dec_nonces[i]."""
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    from repro.core import encoder, encryptor
    from repro.core.context import get_context

    enable_compile_cache()
    ctx = get_context(profile)
    p = ctx.params
    sk, pk = encryptor.keygen(ctx, seed=p.seed)
    msgs = messages(p.n_slots, N_ENC, seed)
    c0, c1, dec = [], [], []
    for i, nonce in enumerate(enc_nonces):
        ct = encryptor.encrypt(encoder.encode(msgs[i], ctx, fourier="device"),
                               pk, ctx, nonce=nonce)
        c0.append(np.asarray(ct.c0))
        c1.append(np.asarray(ct.c1))
    for i, nonce in enumerate(dec_nonces):
        ct = encryptor.encrypt(encoder.encode(msgs[i], ctx, fourier="device"),
                               pk, ctx, nonce=nonce)
        pt = encryptor.decrypt(ct, sk, ctx)
        dec.append(encoder.decode(pt, ctx, scale=ct.scale, fourier="device"))
    np.savez(out, c0=np.stack(c0), c1=np.stack(c1), dec=np.stack(dec))


def start_reference(tmp: str, seed: int, enc_nonces, dec_nonces):
    out = os.path.join(tmp, "reference.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.abspath(__file__), "--reference-out", out,
           "--profile", PROFILE, "--seed", str(seed),
           "--enc-nonces", ",".join(map(str, enc_nonces)),
           "--dec-nonces", ",".join(map(str, dec_nonces))]
    return subprocess.Popen(cmd, env=env), out


def finish_reference(proc, out: str, timeout_s: float = 900.0):
    import numpy as np
    t0 = time.perf_counter()
    rc = proc.wait(timeout=timeout_s)
    check(rc == 0, f"CPU reference process exited with {rc}")
    log(f"reference: waited {time.perf_counter() - t0:.1f} s for the CPU "
        f"reference")
    with np.load(out) as z:
        return z["c0"], z["c1"], z["dec"]


def compare(cts, dec, ref, msgs) -> None:
    """Chip outputs vs the CPU reference: ciphertexts bit for bit, decode
    within the pair window and above the bootstrapping precision."""
    import numpy as np
    from repro.core import boot_precision_bits
    ref_c0, ref_c1, ref_dec = ref
    for i in range(len(ref_c0)):
        check(np.array_equal(np.asarray(cts[i].c0), ref_c0[i])
              and np.array_equal(np.asarray(cts[i].c1), ref_c1[i]),
              f"ciphertext {i} differs from the CPU reference")
    log(f"ciphertexts: {len(ref_c0)} of {len(cts)} bit-identical to the CPU "
        f"reference (all {len(ref_c0)} checked)")
    n_slots = ref_dec.shape[-1]
    for i, got in enumerate(dec):
        bits = boot_precision_bits(msgs[i], got)
        diff = float(np.max(np.abs(got - ref_dec[i])))
        # the pair window bounds each coefficient's relative error; the
        # n-point transform sums n of them into one slot
        bound = PAIR_WINDOW * n_slots * float(np.max(np.abs(ref_dec[i])))
        log(f"decode {i}: {bits:.2f} bits; max |chip - reference| = "
            f"{diff:.3e} (bound {bound:.3e}); "
            f"{int(np.sum(got == ref_dec[i]))}/{n_slots} slots identical")
        check(bits >= BOOT_PREC_BITS,
              f"decode {i} keeps {bits:.2f} < {BOOT_PREC_BITS} bits")
        check(diff <= bound, f"decode {i} leaves the pair window")


# ---------------------------------------------------------------------------
# one chip: ClientService
# ---------------------------------------------------------------------------


def custom_calls(core, *args) -> int:
    return core.lower(*args).compile().as_text().count("tpu_custom_call")


def run_service(seed: int) -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    from repro.core.encryptor import Ciphertext
    from repro.fhe_client.service.service import ClientService
    from repro.kernels import ops as kops
    from repro.telemetry import jit_cache_entries

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r})")
    check(not kops.default_interpret(),
          "kernels would run in interpret mode on this backend")
    log(f"device: {dev.platform} {dev.device_kind}, count "
        f"{len(jax.devices())}")
    cache = enable_compile_cache()
    log(f"compile cache: {cache}")

    # round A leases nonces [0, 20), round B [20, 40): the reference
    # encrypts round B's first messages and decrypts round A's first two
    # (the ciphertexts round B's decrypt requests carry)
    enc_nonces = list(range(N_ENC, N_ENC + N_REF))
    dec_nonces = list(range(N_DEC))
    with tempfile.TemporaryDirectory() as tmp:
        proc, out = start_reference(tmp, seed, enc_nonces, dec_nonces)
        try:
            result = serve_and_check(seed, jax, jnp, np, ClientService,
                                     Ciphertext, jit_cache_entries, proc,
                                     out)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    return result


def serve_and_check(seed, jax, jnp, np, ClientService, Ciphertext,
                    jit_cache_entries, proc, out) -> dict:
    t0 = time.perf_counter()
    svc = ClientService(profile=PROFILE)
    client = svc.client
    p = client.ctx.params
    log(f"service: profile {PROFILE} (N={p.n}, L={p.n_limbs}), client "
        f"pipeline={client.pipeline} datapath={client.datapath}; built in "
        f"{time.perf_counter() - t0:.1f} s")
    msgs = messages(p.n_slots, N_ENC, seed)

    def round_trip(dec_cts):
        rids = [svc.submit_encrypt(m) for m in msgs]
        drids = [svc.submit_decrypt(ct) for ct in dec_cts]
        svc.flush()
        cts = [svc.result(r) for r in rids]
        dec = [np.asarray(svc.result(r)) for r in drids]
        jax.block_until_ready([ct.c0 for ct in cts])
        return cts, dec

    # round A: compiles every bucket shape (encrypt 16 + 4, decrypt 2)
    t = time.perf_counter()
    rids = [svc.submit_encrypt(m) for m in msgs]
    svc.flush()
    cts_a = [svc.result(r) for r in rids]
    two = [Ciphertext(c0=ct.c0[:2], c1=ct.c1[:2], n_limbs=2, scale=ct.scale)
           for ct in cts_a[:N_DEC]]
    drids = [svc.submit_decrypt(ct) for ct in two]
    svc.flush()
    for r in drids:
        svc.result(r)
    log(f"round A (compile + run, encrypt buckets 16+4, decrypt 2): "
        f"{time.perf_counter() - t:.1f} s")
    check(client.nonce == N_ENC, f"round A leased {client.nonce} nonces")

    # round B: the checked run; a warm round compiles nothing
    warm = jit_cache_entries(svc.lane_clients())
    t = time.perf_counter()
    cts_b, dec_b = round_trip(two)
    log(f"round B (warm, {N_ENC} encrypt + {N_DEC} decrypt): "
        f"{time.perf_counter() - t:.3f} s")
    recompiles = jit_cache_entries(svc.lane_clients()) - warm
    check(recompiles == 0, f"the warm round compiled {recompiles} programs")
    kinds = svc.events.kinds()
    bad = [k for k in kinds if k in ("requeue", "stream_failed")]
    check(not bad, f"service events hold {bad}")

    # warm per-batch wall times of the cores, operands already on device
    dev = jax.devices()[0]           # placed as the service places them
    ops = [jax.device_put(o, dev) for o in client.encrypt_operands(msgs[:16])]
    times = []
    for _ in range(3):
        n0 = jnp.uint32(client.take_nonces(16))
        t = time.perf_counter()
        jax.block_until_ready(client.encrypt_core(*ops, n0))
        times.append(time.perf_counter() - t)
    log("encrypt core, batch 16, warm wall s: "
        + ", ".join(f"{x:.4f}" for x in times))
    # (c0, c1, per-row f64 scales): the operands the service's decrypt
    # jobs carry, so the warm program is reused
    dops = [jax.device_put(o, dev) for o in (
        jnp.stack([ct.c0 for ct in two]), jnp.stack([ct.c1 for ct in two]),
        jnp.asarray(np.full((N_DEC, 1), p.delta)))]
    times = []
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(client.decrypt_core(*dops))
        times.append(time.perf_counter() - t)
    log("decrypt core, batch 2, warm wall s: "
        + ", ".join(f"{x:.4f}" for x in times))
    n_enc = custom_calls(client.encrypt_core, *ops, jnp.uint32(0))
    n_dec = custom_calls(client.decrypt_core, *dops)
    log(f"tpu_custom_call per compiled core: encrypt {n_enc}, "
        f"decrypt {n_dec}")
    check(n_enc == 1 and n_dec == 1,
          "a compiled client core is not exactly one kernel")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")

    compare(cts_b, dec_b, finish_reference(proc, out), msgs)
    return {"ok": True}


# ---------------------------------------------------------------------------
# four chips: MeshRouter with one chip per worker
# ---------------------------------------------------------------------------


def probe_devices() -> dict:
    """Ask a short-lived child what JAX reports for the chips, so the
    router process itself never initialises a TPU backend."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    code = ("import json, jax; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    check(res.returncode == 0, f"device probe failed: {res.stderr[-2000:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def run_mesh(seed: int, n_chips: int) -> dict:
    device = probe_devices()
    check(device["platform"] == "tpu",
          f"JAX found no TPU (platform {device['platform']!r})")
    check(device["count"] >= n_chips,
          f"{device['count']} chips, {n_chips} needed")
    log(f"device: {device['platform']} {device['kind']}, count "
        f"{device['count']}")
    import jax
    import numpy as np
    # the router stays off the chips; its environment, which the workers
    # inherit, still lets them take one chip each
    jax.config.update("jax_platforms", "cpu")
    from repro.compile_cache import enable_compile_cache
    from repro.core.context import PROFILES
    from repro.core.encryptor import Ciphertext
    from repro.fhe_client.service.mesh import MeshRouter, host_tpu_chips

    # the router pins one chip per worker only where it sees the chips
    # on the PCI bus; without them the workers would run on the CPU
    check(host_tpu_chips() >= n_chips,
          f"the router sees {host_tpu_chips()} TPU chips on the PCI bus, "
          f"{n_chips} needed")
    enable_compile_cache()
    p = PROFILES[PROFILE]
    msgs = messages(p.n_slots, N_ENC, seed)
    enc_nonces = list(range(N_REF))
    dec_nonces = list(range(N_DEC))
    with tempfile.TemporaryDirectory() as tmp:
        proc, out = start_reference(tmp, seed, enc_nonces, dec_nonces)
        try:
            t = time.perf_counter()
            with MeshRouter(n_workers=n_chips, profile=PROFILE) as mesh:
                log(f"mesh: {n_chips} workers up in "
                    f"{time.perf_counter() - t:.1f} s")
                t = time.perf_counter()
                rids = [mesh.submit_encrypt(m) for m in msgs]
                mesh.flush()
                cts = [mesh.result(r) for r in rids]
                two = [Ciphertext(c0=np.asarray(ct.c0)[:2],
                                  c1=np.asarray(ct.c1)[:2], n_limbs=2,
                                  scale=ct.scale) for ct in cts[:N_DEC]]
                drids = [mesh.submit_decrypt(ct) for ct in two]
                mesh.flush()
                dec = [np.asarray(mesh.result(r)) for r in drids]
                log(f"mesh: {N_ENC} encrypt + {N_DEC} decrypt (compile + "
                    f"run) {time.perf_counter() - t:.1f} s")
                kinds = [e.kind for e in mesh.events.replay()]
                bad = [k for k in kinds if k in ("worker_died", "requeue")]
                check(not bad, f"mesh events hold {bad}")
            compare(cts, dec, finish_reference(proc, out), msgs)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    device["count"] = n_chips
    return {"ok": True, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request messages")
    ap.add_argument("--reference-out", help=argparse.SUPPRESS)
    ap.add_argument("--profile", default=PROFILE, help=argparse.SUPPRESS)
    ap.add_argument("--enc-nonces", help=argparse.SUPPRESS)
    ap.add_argument("--dec-nonces", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository checkout around {HERE} "
              f"(src/repro missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    if args.reference_out:
        reference(args.reference_out, args.profile, args.seed,
                  [int(x) for x in args.enc_nonces.split(",") if x],
                  [int(x) for x in args.dec_nonces.split(",") if x])
        return 0
    try:
        if args.chips == 1:
            result = run_service(args.seed)
        else:
            result = run_mesh(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
