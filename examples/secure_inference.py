"""Private inference: FHE client wrapping an LM server (paper Fig. 1).

    PYTHONPATH=src python examples/secure_inference.py [--direct]
    PYTHONPATH=src python examples/secure_inference.py --encrypted \
        [--profile server|boot] [--dim 8]

The client boundary runs through the client SERVICE by default: prompt
embeddings are submitted as per-message requests, the coalescing batcher
forms bucketed jobs, the dual-stream scheduler executes them on the
device streams, and ciphertexts/results cross the trust boundary as
deterministic wire payloads. ``--direct`` keeps the original path that
calls ``FHEClient`` batched entry points directly (the pre-service
protocol, retained as the reference).

In those two modes the server boundary is simulated (decrypt, run the LM,
re-encrypt) — the focus is the client data path. ``--encrypted`` removes
the simulation: the server sees ONLY wire payloads (ciphertexts + the
one-time evaluation-key broadcast) and evaluates a real linear layer plus
a degree-3 activation polynomial homomorphically (``repro.fhe_server``:
hoisted rotations, ct x pt, ct x ct with relinearization, rescales), and
the client decrypts a result that must match the plaintext model within
the documented noise budget (~2^-16 at the ``server`` preset; budget
asserted at 2^-12). ``--profile boot`` runs the same flow at the
bootstrappable parameter set (N=2^16, 24 limbs) — correct but slow on
CPU; the default ``server`` preset (N=2^10, 8 limbs) keeps the
off-accelerator demo interactive.
"""

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.fhe_client.client import FHEClient, simulate_private_inference
from repro.fhe_client.service import ClientService, wire
from repro.models import model as M
from repro.models.archs import get_arch, reduced_config


def simulate_private_inference_service(service: ClientService, serve_fn,
                                       x: np.ndarray, out_features: int):
    """The ``simulate_private_inference`` loop routed through the service:
    per-message submit -> coalesced/bucketed jobs -> wire payloads across
    the trust boundary -> decrypt requests for the returned results."""
    client = service.client
    msgs = client.pack(x)
    cts = service.encrypt_many(msgs)
    payload = wire.serialize_ciphertext_batch(cts)     # client -> server

    # --- server boundary (simulated; see module docstring) -----------------
    server_cts = wire.deserialize_ciphertext_batch(payload).truncated(2)
    served_inputs = service.decrypt_many(server_cts)
    x_rec = client.unpack(served_inputs, x.shape[1])
    y = serve_fn(x_rec.astype(np.float32))
    y_cts = service.encrypt_many(client.pack(y.astype(np.float64)))
    returned = wire.serialize_ciphertext_batch(y_cts.truncated(2))
    # ------------------------------------------------------------------------

    y_dec = service.decrypt_many(wire.deserialize_ciphertext_batch(returned))
    return client.unpack(y_dec, out_features), {
        "roundtrip_err": float(np.max(np.abs(x_rec - x))),
        "upload_bytes": len(payload),
        "download_bytes": len(returned),
    }


NOISE_BUDGET_E2E = 2.0 ** -12     # measured ~8e-6 (~2^-16) at `server`


def run_encrypted(args) -> None:
    """End-to-end ENCRYPTED inference: poly3(W @ x + b) evaluated on
    ciphertexts server-side; the server never decrypts anything."""
    from repro.fhe_server import (ServerCiphertext, ServerEvaluator,
                                  inference as inf)

    d = args.dim
    # non-power-of-two scales appear after ct x ct rescales, so the client
    # decrypt runs the f64 datapath (the df32 scale chain is pow2-only)
    client = FHEClient(profile=args.profile, pipeline="staged",
                       datapath="f64")
    ctx = client.ctx
    print(f"CKKS: N=2^{ctx.params.logn}, {ctx.params.n_limbs} limbs, "
          f"delta=2^{ctx.params.delta_bits}  (profile={args.profile})")

    rng = np.random.default_rng(7)
    xv = rng.standard_normal(d) * 0.5
    w = rng.standard_normal((d, d)) * 0.4
    bias = rng.standard_normal(d) * 0.3
    poly = (0.1, 0.5, -0.2, 0.05)          # c0 + c1 y + c2 y^2 + c3 y^3

    # client -> server: ciphertext + one-time evaluation-key broadcast
    z = inf.replicate_slots(xv, ctx.params.n_slots)
    ct_up = wire.serialize_ciphertext_batch(client.encode_encrypt_batch(
        z[None]))
    ek_up = wire.serialize_evaluation_keys(client.make_evaluation_keys(
        rotations=inf.matvec_rotations(d)))
    print(f"upload: ciphertext {len(ct_up) / 1e3:.1f} KB, evaluation keys "
          f"{len(ek_up) / 1e6:.2f} MB (one-time)")

    # --- server: wire payloads in, wire payloads out, zero decryptions -----
    t0 = time.time()
    ev = ServerEvaluator(ctx, wire.deserialize_evaluation_keys(ek_up))
    x_ct = ServerCiphertext.from_batch(
        wire.deserialize_ciphertext_batch(ct_up))
    x_ct = x_ct.drop_to(min(x_ct.level, args.level))    # 4 levels needed
    y_ct = inf.encrypted_linear_poly3(ev, x_ct, w, bias, poly)
    ct_down = wire.serialize_ciphertext_batch(y_ct.to_batch())
    print(f"server: {x_ct.level} -> {y_ct.level} levels "
          f"({time.time() - t0:.1f}s cold, includes kernel compiles); "
          f"download {len(ct_down) / 1e3:.1f} KB")
    # ------------------------------------------------------------------------

    got = np.asarray(client.decrypt_batch(
        list(wire.deserialize_ciphertext_batch(ct_down))))[0].real[:d]
    ref = inf.reference_linear_poly3(xv, w, bias, poly)
    err = float(np.max(np.abs(got - ref)))
    print(f"poly3(W @ x + b): encrypted vs plaintext max err {err:.2e} "
          f"(budget {NOISE_BUDGET_E2E:.2e})")
    assert err < NOISE_BUDGET_E2E
    print("OK — encrypted-inference loop verified")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--direct", action="store_true",
                    help="call the FHEClient batched path directly instead "
                         "of going through the client service")
    ap.add_argument("--encrypted", action="store_true",
                    help="evaluate the model homomorphically server-side "
                         "(no simulated decrypt at the server)")
    ap.add_argument("--profile", default="server",
                    help="CKKS profile for --encrypted (server | boot)")
    ap.add_argument("--dim", type=int, default=8,
                    help="linear-layer dimension for --encrypted")
    ap.add_argument("--level", type=int, default=6,
                    help="working level for --encrypted (>= 6)")
    args = ap.parse_args()
    if args.encrypted:
        run_encrypted(args)
        return

    cfg = reduced_config(get_arch("qwen2-vl-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    client = FHEClient(profile="test")
    print(f"model: {cfg.name}  d_model={cfg.d_model}")
    print(f"CKKS: N=2^{client.ctx.params.logn}, "
          f"{client.ctx.params.n_limbs} limbs")

    batch, seq = 2, 16

    def serve_fn(x_rows: np.ndarray) -> np.ndarray:
        """Stand-in server: embeds -> one LM forward -> last hidden state."""
        embeds = jnp.asarray(
            x_rows.reshape(batch, seq, cfg.d_model), jnp.float32)
        mrope = jnp.broadcast_to(jnp.arange(seq)[None, :, None],
                                 (batch, seq, 3)).astype(jnp.int32)
        lg, _ = M.prefill(params, {"embeds": embeds, "mrope_pos": mrope},
                          cfg, cache_len=seq, q_chunk=16, kv_chunk=16)
        out = np.asarray(lg.astype(jnp.float32))[:, 0, : cfg.d_model]
        return out.reshape(batch, cfg.d_model) / 10.0

    x = np.random.default_rng(1).standard_normal(
        (batch, seq * cfg.d_model)) * 0.1
    if args.direct:
        print("client boundary: direct FHEClient batched path")
        y, stats = simulate_private_inference(client, serve_fn, x,
                                              out_features=cfg.d_model)
    else:
        service = ClientService(client=client, buckets=(1, 2, 4, 8))
        st = service.stats()
        print(f"client boundary: service ({st['n_streams']} stream(s), "
              f"{st['shards_per_stream']} shard(s)/stream, "
              f"buckets {st['buckets']})")
        y, stats = simulate_private_inference_service(
            service, serve_fn, x, out_features=cfg.d_model)
        st = service.stats()
        print(f"service dispatched {st['jobs_dispatched']} jobs over "
              f"{st['rounds']} rounds; modes: {','.join(st['modes'][:8])}"
              f"{'...' if len(st['modes']) > 8 else ''}")
        print(f"wire payloads: {stats['upload_bytes'] / 1e3:.1f} KB up, "
              f"{stats['download_bytes'] / 1e3:.1f} KB down")
    rep = client.upload_report(batch)
    print(f"client->server ciphertext: {rep['ct_bytes'] / 1e3:.1f} KB "
          f"({rep['ct_bytes_seeded'] / 1e3:.1f} KB seeded, "
          f"{rep['compression']:.2f}x compression)")
    print(f"input round-trip error through FHE: {stats['roundtrip_err']:.2e}")
    print(f"served output shape: {y.shape}")
    assert stats["roundtrip_err"] < 1e-4
    print("OK — private-inference loop verified")


if __name__ == "__main__":
    main()
