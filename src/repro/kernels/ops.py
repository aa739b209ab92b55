"""Public jit'd wrappers over the Pallas kernels.

All wrappers auto-select interpret mode on CPU (the kernels are written for
TPU; interpret=True executes the same kernel body in Python for validation,
per the repo's CPU-container / TPU-target split).

Domains: the butterfly path produces bit-reversed evaluation order (matching
``repro.core.ntt``); the four-step MXU path produces natural order. Pointwise
ciphertext algebra is order-agnostic as long as both operands share a domain;
the client pipeline uses the butterfly domain as canonical.

Batched, limb-folded launches
-----------------------------
The client hot path is batched struct-of-arrays: residue stacks travel as
``(L, ..., N)`` (NTT) or ``(B, L, N)`` (ciphertexts) arrays and the limb loop
lives in the Pallas grid (``grid = (L, B)``), with per-limb constants
streamed from a stacked (L, K) table. ``encrypt_fused``, ``decrypt_fused``,
``ntt_limbs`` and ``intt_limbs`` therefore each issue exactly ONE
pallas_call per invocation regardless of limb count or batch size (the
four-step ``path='matmul'`` NTT keeps its per-limb launches: its precomputed
F matrices are per-prime MXU operands, not scalar seeds).

``encode_encrypt_stream`` / ``decrypt_decode_stream`` go one step further:
the WHOLE client op — Fourier transform included — is one pallas_call (the
streaming megakernel, ``kernels.client_stream`` / DESIGN.md §4).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import fft as fftmod
from repro.core.context import CKKSContext
from repro.kernels import client_pointwise, client_stream, common, fft_df, \
    ntt_butterfly, ntt_matmul, server_eval


def default_interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Unified Fourier engine dispatch (the paper's NTT/FFT mode switch)
# ---------------------------------------------------------------------------


def fourier(x, ctx: CKKSContext, cfg: common.FourierConfig | None = None,
            *, inverse: bool = False, n_limbs: int | None = None):
    """Single entry point for the reconfigurable Fourier engine.

    Dispatches on ``cfg.mode`` (see ``common.FourierConfig``):

      * ``'ntt'``:  x is a (L, ..., N) uint32 RNS residue stack ->
        limb-folded modular NTT/INTT (one pallas_call for the stack);
      * ``'fft'``:  x is a four-plane df32 tuple of (rows, n) f32 ->
        SpecialFFT/IFFT stage-pipeline kernel (jit-traceable; the
        device-resident client path);
      * ``'host'``: x is (rows, n) complex128 -> numpy oracle (reference).

    The two kernel modes launch through the same rows-streaming grid
    surface (``common.row_grid``/``row_block_spec``) — the TPU analogue of
    the ASIC multiplexing one datapath between both transforms.
    """
    cfg = common.FourierConfig() if cfg is None else cfg
    if cfg.mode == "ntt":
        f = intt_limbs if inverse else ntt_limbs
        return f(x, ctx, n_limbs=n_limbs, block_rows=cfg.block_rows,
                 interpret=cfg.interpret)
    if cfg.mode == "fft":
        f = special_ifft_planes if inverse else special_fft_planes
        return f(x, ctx.params.m, block_rows=cfg.block_rows,
                 interpret=cfg.interpret)
    if cfg.mode == "host":
        # attribute access (not a from-import) so tests can monkeypatch the
        # oracle to count host FFT invocations
        f = fftmod.special_ifft if inverse else fftmod.special_fft
        return f(np.asarray(x), ctx.params.m)
    raise ValueError(
        f"unknown Fourier mode {cfg.mode!r}; expected one of "
        f"{common.FOURIER_MODES}")


# ---------------------------------------------------------------------------
# NTT / INTT over RNS limb stacks
# ---------------------------------------------------------------------------


def ntt_limbs(x, ctx: CKKSContext, n_limbs: int | None = None,
              path: str = "butterfly", block_rows: int = 1,
              interpret: bool | None = None):
    """x: (L, ..., N) uint32 residues -> forward negacyclic NTT per limb.

    path: 'butterfly' (VPU streaming kernel, bit-reversed out; limb-folded,
          one pallas_call for the whole stack) or
          'matmul' (four-step MXU kernel, natural out; per-limb launches).
    """
    interpret = default_interpret() if interpret is None else interpret
    n_limbs = x.shape[0] if n_limbs is None else n_limbs
    if path == "butterfly":
        x2 = x[:n_limbs].reshape(n_limbs, -1, x.shape[-1])
        out = ntt_butterfly.ntt_limb_rows(
            x2, ctx.plans[:n_limbs], block_rows=block_rows,
            interpret=interpret)
        return out.reshape(x[:n_limbs].shape)
    rows = []
    for i in range(n_limbs):
        xi = x[i].reshape(-1, x.shape[-1])
        out = ntt_matmul.ntt_rows_mm(xi, ctx.plans[i], block_rows=block_rows,
                                     interpret=interpret)
        rows.append(out.reshape(x.shape[1:]))
    return jnp.stack(rows)


def intt_limbs(x, ctx: CKKSContext, n_limbs: int | None = None,
               path: str = "butterfly", block_rows: int = 1,
               interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    n_limbs = x.shape[0] if n_limbs is None else n_limbs
    if path == "butterfly":
        x2 = x[:n_limbs].reshape(n_limbs, -1, x.shape[-1])
        out = ntt_butterfly.intt_limb_rows(
            x2, ctx.plans[:n_limbs], block_rows=block_rows,
            interpret=interpret)
        return out.reshape(x[:n_limbs].shape)
    rows = []
    for i in range(n_limbs):
        xi = x[i].reshape(-1, x.shape[-1])
        out = ntt_matmul.intt_rows_mm(xi, ctx.plans[i], block_rows=block_rows,
                                      interpret=interpret)
        rows.append(out.reshape(x.shape[1:]))
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# Fused streaming client ops
# ---------------------------------------------------------------------------


def encrypt_fused(pt_data, pk_b_mont, pk_a_mont, ctx: CKKSContext,
                  seed: int | None = None, nonce0=0,
                  interpret: bool | None = None):
    """Streaming encrypt. pt_data: (L, N) or (batch, L, N) uint32 NTT-domain
    plaintext; returns (c0, c1) of the same shape. PRNG + NTT run in-kernel,
    all limbs and batch rows in ONE limb-folded pallas_call.

    Matches ``repro.core.encrypt`` bit-for-bit for nonce = nonce0 + batch_idx
    (nonce0 may be a traced uint32 scalar for jit-stable entry points).
    """
    interpret = default_interpret() if interpret is None else interpret
    seed = ctx.params.seed if seed is None else seed
    squeeze = pt_data.ndim == 2
    pt = pt_data[None] if squeeze else pt_data           # (B, L, N)
    c0, c1 = client_pointwise.encrypt_limbs(
        pt, pk_b_mont, pk_a_mont, ctx, seed=seed, nonce0=nonce0,
        interpret=interpret)
    if squeeze:
        return c0[0], c1[0]
    return c0, c1


def decrypt_fused(c0, c1, s_mont, ctx: CKKSContext, n_limbs: int = 2,
                  interpret: bool | None = None):
    """Streaming decrypt -> coefficient-domain residues (…, n_limbs, N).
    One limb-folded pallas_call for the whole batch."""
    interpret = default_interpret() if interpret is None else interpret
    squeeze = c0.ndim == 2
    c0b = c0[None] if squeeze else c0
    c1b = c1[None] if squeeze else c1
    out = client_pointwise.decrypt_limbs(
        c0b[:, :n_limbs], c1b[:, :n_limbs], s_mont, ctx,
        interpret=interpret)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Mesh-sharded entry points: batch axis of the limb-folded grid over devices
# ---------------------------------------------------------------------------
#
# Each shard runs the SAME limb-folded kernel on its slice of the batch
# axis (one pallas_call per device — each device is an RSC-equivalent
# stream), so a b-device mesh issues b concurrent launches for one batch.
# ``check_vma=False``: shard_map has no replication rule for pallas_call;
# every output is batch-sharded anyway. Nonce bases are offset per shard so
# row r of the batch always encrypts under ``nonce0 + r`` — bit-identical
# to the single-device launch.


def _shard_b(batch: int, mesh) -> int:
    n_shards = mesh.shape["batch"]
    if batch % n_shards:
        raise ValueError(
            f"batch axis {batch} does not divide the {n_shards}-device "
            f"'batch' mesh axis; pad to a multiple (the service batcher's "
            f"buckets are forced to multiples of the shard count)")
    return batch // n_shards


def shard_nonce_base(nonce0, shard_rows: int):
    """Per-shard nonce base inside a shard_map'ed encrypt body: global row
    r of the batch must keep ``nonce0 + r``, so shard s (holding rows
    [s*shard_rows, (s+1)*shard_rows)) starts at ``nonce0 + s*shard_rows``.
    The ONE place the sharded row<->nonce convention lives — both the raw
    sharded kernel entries below and the service stream executors use it
    (nonce reuse across shards would break RLWE security)."""
    return nonce0 + jax.lax.axis_index("batch").astype(jnp.uint32) \
        * jnp.uint32(shard_rows)


def encrypt_fused_sharded(pt_data, pk_b_mont, pk_a_mont, ctx: CKKSContext,
                          mesh, seed: int | None = None, nonce0=0,
                          interpret: bool | None = None):
    """``encrypt_fused`` with the (B, L, N) batch axis shard_map'ed over
    the mesh's 'batch' axis. Keys replicate; per-shard nonce bases keep the
    row<->nonce mapping of the unsharded launch."""
    shard_b = _shard_b(pt_data.shape[0], mesh)

    def local(pt, b, a, n0):
        return encrypt_fused(pt, b, a, ctx, seed=seed,
                             nonce0=shard_nonce_base(n0, shard_b),
                             interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("batch", None, None), P(None, None), P(None, None), P()),
        out_specs=P("batch", None, None), check_vma=False,
    )(pt_data, pk_b_mont, pk_a_mont, jnp.uint32(nonce0))


def decrypt_fused_sharded(c0, c1, s_mont, ctx: CKKSContext, mesh,
                          n_limbs: int = 2, interpret: bool | None = None):
    """``decrypt_fused`` with the (B, L, N) batch axis shard_map'ed over
    the mesh's 'batch' axis (secret key replicated)."""
    _shard_b(c0.shape[0], mesh)

    def local(c0_l, c1_l, s):
        return decrypt_fused(c0_l, c1_l, s, ctx, n_limbs=n_limbs,
                             interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("batch", None, None), P("batch", None, None),
                  P(None, None)),
        out_specs=P("batch", None, None), check_vma=False,
    )(c0, c1, s_mont)


# ---------------------------------------------------------------------------
# Streaming megakernels: the WHOLE client op in one pallas_call
# ---------------------------------------------------------------------------


def encode_encrypt_stream(planes, pk_b_mont, pk_a_mont, ctx: CKKSContext,
                          seed: int | None = None, nonce0=0,
                          interpret: bool | None = None,
                          datapath: str = "f64"):
    """df32 slot planes -> (c0, c1) ciphertext stacks, ONE pallas_call:
    SpecialIFFT + Delta-scale + RNS + NTT + fused encrypt fused into a
    single kernel body (``kernels.client_stream``). Bit-identical to the
    staged ``fourier='device'`` pipeline for fixed seeds, under either
    ``datapath`` ('df32' = the compile-ready f32/u32 interior)."""
    interpret = default_interpret() if interpret is None else interpret
    seed = ctx.params.seed if seed is None else seed
    return client_stream.encode_encrypt_stream(
        planes, pk_b_mont, pk_a_mont, ctx, seed=seed, nonce0=nonce0,
        interpret=interpret, datapath=datapath)


def decrypt_decode_stream(c0, c1, s_mont, ctx: CKKSContext, scale,
                          interpret: bool | None = None,
                          datapath: str = "f64"):
    """(B, 2, N) ciphertext stacks -> four (B, n_slots) f32 df slot planes,
    ONE pallas_call: decrypt pointwise + INTT + CRT + /Delta + SpecialFFT
    in a single kernel body."""
    interpret = default_interpret() if interpret is None else interpret
    return client_stream.decrypt_decode_stream(
        c0, c1, s_mont, ctx, scale, interpret=interpret, datapath=datapath)


# ---------------------------------------------------------------------------
# df32 Fourier transforms
# ---------------------------------------------------------------------------


def special_fft_planes(planes, m: int, block_rows: int = 1,
                       interpret: bool | None = None):
    """Jit-traceable df32 SpecialFFT on a four-plane (rows, n) f32 tuple.
    Nests inside the client's jitted decode core (no host round-trip)."""
    interpret = default_interpret() if interpret is None else interpret
    return fft_df.special_fft_planes(planes, m, block_rows=block_rows,
                                     interpret=interpret)


def special_ifft_planes(planes, m: int, block_rows: int = 1,
                        interpret: bool | None = None):
    """Jit-traceable df32 SpecialIFFT on df planes (encode direction)."""
    interpret = default_interpret() if interpret is None else interpret
    return fft_df.special_ifft_planes(planes, m, block_rows=block_rows,
                                      interpret=interpret)


def special_fft(z, m: int, block_rows: int = 1, interpret: bool | None = None):
    """(rows, n) complex -> slots, df32 Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    z = np.asarray(z)
    squeeze = z.ndim == 1
    z2 = z[None] if squeeze else z
    out = fft_df.special_fft_rows(z2, m, block_rows=block_rows,
                                  interpret=interpret)
    return out[0] if squeeze else out


def special_ifft(z, m: int, block_rows: int = 1,
                 interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    z = np.asarray(z)
    squeeze = z.ndim == 1
    z2 = z[None] if squeeze else z
    out = fft_df.special_ifft_rows(z2, m, block_rows=block_rows,
                                   interpret=interpret)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# server-side eval ops (fhe_server; kernels in kernels/server_eval.py)
# ---------------------------------------------------------------------------
#
# Same wiring contract as the client cores: each wrapper resolves the
# interpret default and forwards to exactly one pallas_call.  Pointwise ops
# run the (L, B) limb-folded grid; cross-limb ops (rescale / relinearize /
# key switch) run the megakernel (B,) grid with the limb loop unrolled in
# the body.  `datapath` selects the pointwise REDC engine ('df32' pure
# uint32 / 'f64' traced u64), bit-identical results.


def server_add_ct(c0a, c1a, c0b, c1b, ctx: CKKSContext,
                  interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.add_ct(c0a, c1a, c0b, c1b, ctx, interpret=interpret)


def server_add_pt(c0, c1, pt, ctx: CKKSContext,
                  interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.add_pt(c0, c1, pt, ctx, interpret=interpret)


def server_mul_pt(c0, c1, pt_mont, ctx: CKKSContext, datapath: str = "f64",
                  rescale: bool = False, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    fn = server_eval.mul_pt_rescale if rescale else server_eval.mul_pt
    return fn(c0, c1, pt_mont, ctx, datapath=datapath, interpret=interpret)


def server_rescale(c0, c1, ctx: CKKSContext, datapath: str = "f64",
                   interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.rescale(c0, c1, ctx, datapath=datapath,
                               interpret=interpret)


def server_mul_ct(a0, a1, b0, b1, ksk_b, ksk_a, ctx: CKKSContext,
                  datapath: str = "f64", interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.mul_ct_relin(a0, a1, b0, b1, ksk_b, ksk_a, ctx,
                                    datapath=datapath, interpret=interpret)


def server_rotate(c0, c1, perm, ksk_b, ksk_a, ctx: CKKSContext,
                  datapath: str = "f64", interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.rotate(c0, c1, perm, ksk_b, ksk_a, ctx,
                              datapath=datapath, interpret=interpret)


def server_ks_decompose(c1, ctx: CKKSContext, interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.ks_decompose(c1, ctx, interpret=interpret)


def server_ks_apply_rot(c0, h, perm, ksk_b, ksk_a, ctx: CKKSContext,
                        datapath: str = "f64",
                        interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return server_eval.ks_apply_rot(c0, h, perm, ksk_b, ksk_a, ctx,
                                    datapath=datapath, interpret=interpret)
