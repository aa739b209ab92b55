"""Streaming client megakernel: ONE pallas_call per batched client op.

This is the end of the ROADMAP's "fold the df32 FFT rows grid together with
the Delta-scale/RNS stage" item — the TPU analogue of ABC-FHE's full MDC
streaming pipeline, where encode/encrypt flow through the Reconfigurable
Streaming Core as one dataflow and the Fourier engine mode-switches between
FFT and NTT *inside* the pipeline (paper Fig. 3a). The staged PR 2 cores
launch the df32 SpecialFFT kernel and the limb-folded NTT/pointwise kernel
as separate pallas_calls inside one jit; here the whole chain is one kernel
body:

  encode+encrypt (one launch):
      df32 SpecialIFFT stages -> Delta-scale + exact round -> Philox PRNG
      -> per-limb RNS reduction -> per-limb NTT -> fused encrypt pointwise
  decrypt+decode (one launch):
      per-limb decrypt pointwise -> INTT -> two-limb CRT -> /Delta
      -> df32 SpecialFFT stages

The stage bodies are the SAME functions the staged kernels run
(``fft_df.fft_stage_pipeline``, ``client_pointwise.encrypt_limb_stage`` /
``decrypt_limb_stage``, ``common.ntt_stages_t`` family), so megakernel
ciphertexts are bit-identical to the staged path for fixed seeds — asserted
by tests/test_client_stream.py.

Launch geometry. Every polynomial travels tiled as (R, C) (``common.
tile_layout``), so each block's last two dims are whole arrays and every
butterfly partner is a roll away. The encode+encrypt grid is
(batch blocks, limbs): at limb 0 a block runs the FFT, the Delta-scale and
the PRNG once and parks the exact digits and the (v, e0, e1) draws in VMEM
scratch; every limb step then reads its row of the whole (L, K) SMEM
constant table by ``program_id``, reduces, runs the NTT and writes its
ciphertext limbs. That is the ASIC's Fourier reconfiguration: the FFT runs
once per ciphertext, then the same datapath replays the NTT stage schedule
per limb — with one limb of public key and ciphertext resident at a time.
The decrypt+decode grid is batch blocks only; its two limbs feed the CRT
together. The FFT stages run relabeled by the bit reversal
(``fft_df.fft_stage_pipeline``), so the permutation is an XLA gather on
the kernel's input (encode) or output (decode), never inside it. Rows per
grid step come from N, L and the batch (``stream_batch_block``).

Datapath note: the Delta-scale / RNS / CRT interior comes in two dtype
paths selected by ``datapath=``:

  * ``'f64'``  — df64/fmod/uint64 arithmetic (exact; the interpret-mode
    oracle, and what the staged jitted cores do between their launches);
  * ``'df32'`` — df32^2 split-limb chains + uint32 modular arithmetic
    (``dfloat.df_round_rne``/``expansion3_digits``,
    ``rns.digits_to_residue``/``crt2_centered_u32``): the same exact
    integers with no float64/uint64 op anywhere in the body, so the
    megakernel lowers on TPU VPUs (and traces with JAX_ENABLE_X64=0).
    Bit-identical ciphertexts to the f64 oracle by construction; the
    device default (DESIGN.md §4, tests/test_datapath_oracle.py).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dfloat as dfl
from repro.core import encoder, modmul, rns
from repro.core.context import CKKSContext
from repro.kernels import client_pointwise, common, fft_df

# Bytes of VMEM each kernel may use. The estimates below size the batch
# block against half of it, leaving the other half to Mosaic's temporaries.
VMEM_LIMIT = 96 << 20


def _encrypt_vmem_bytes(rows: int, n: int, n_slots: int) -> int:
    """VMEM estimate of one encode+encrypt grid step over `rows`
    ciphertexts: double-buffered slot planes, pk rows and ciphertext
    limbs, the digit/draw scratch, the twiddle table and the live
    values of a 4-polynomial NTT stage (about ten u32 planes per row)."""
    tw = 2 * 16 * n_slots * max(n_slots.bit_length() - 1, 1)
    per_row = 4 * (2 * 4 * n_slots + 2 * 2 * n + 6 * n + 10 * 4 * n)
    return tw + 2 * 2 * 4 * n + rows * per_row


def _decrypt_vmem_bytes(rows: int, n: int, n_slots: int) -> int:
    """VMEM estimate of one decrypt+decode grid step (two limbs in, four
    slot planes out, the FFT's df32 temporaries)."""
    tw = 2 * 16 * n_slots * max(n_slots.bit_length() - 1, 1)
    per_row = 4 * (2 * 2 * 2 * n + 2 * 4 * n_slots + 10 * 2 * n + 24 * n_slots)
    return tw + 2 * 2 * 4 * n + rows * per_row


def stream_batch_block(batch: int, n: int, vmem_bytes) -> int:
    """Rows per grid step: the largest divisor of the batch whose VMEM
    estimate fits half of ``VMEM_LIMIT`` (one row at least)."""
    best = 1
    for rows in range(1, batch + 1):
        if batch % rows == 0 and vmem_bytes(rows, n, n // 2) <= VMEM_LIMIT // 2:
            best = rows
    return best


def _spec(block_shape, index_map, memory_space=None):
    """BlockSpec whose index map yields int32 block indices: under x64 a
    literal 0 would trace as int64, which Mosaic cannot return."""
    def int32_map(*grid_idx):
        return tuple(jnp.asarray(i, jnp.int32) for i in index_map(*grid_idx))
    if memory_space is None:
        return pl.BlockSpec(block_shape, int32_map)
    return pl.BlockSpec(block_shape, int32_map, memory_space=memory_space)


def _whole(shape, memory_space=None):
    """The whole array as one block, the same at every grid step."""
    return _spec(shape, lambda *_: (0,) * len(shape), memory_space)


def _compiler_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def stream_consts(ctx: CKKSContext, n_limbs: int, inverse: bool):
    """The megakernel's constant bundle for one direction: the (L, K)
    stacked NTT seed table (SMEM) and the expanded df32 FFT stage
    twiddles (VMEM) — the in-kernel mode switch reads NTT state from the
    first and FFT state from the second."""
    p = ctx.params
    kc = common.stacked_kernel_consts(ctx.plans[:n_limbs])
    tw = fft_df.stage_twiddles(p.n_slots, p.m, inverse=inverse)
    return kc, tw


def _split_rows(x, n_rows: int):
    """(rows, 2R', C) tiled coefficients -> (re, im) halves (R' rows)."""
    return x[:, :n_rows], x[:, n_rows:]


# ---------------------------------------------------------------------------
# encode+encrypt megakernel
# ---------------------------------------------------------------------------


def _encode_encrypt_kernel(c_ref, dc_ref, nz_ref, rh_ref, rl_ref, ih_ref,
                           il_ref, tw_ref, b_ref, a_ref, c0_ref, c1_ref,
                           pt_scr, vee_scr, *,
                           kc: common.StackedKernelConsts, seed: int,
                           delta: float, n_slots: int,
                           datapath: str = "f64"):
    rows = rh_ref.shape[0]
    # grid indices read at the top of the body: the interpreter resolves
    # program_id only outside control flow
    b, l = pl.program_id(0), pl.program_id(1)

    @pl.when(l == 0)
    def _():
        # --- Fourier engine, FFT mode: df32 SpecialIFFT (natural out) ----
        z = dfl.dfc_from_planes(
            (rh_ref[...], rl_ref[...], ih_ref[...], il_ref[...]))
        w = fft_df.fft_stage_pipeline(z, tw_ref, n=n_slots, inverse=True)
        # --- Delta-scale + exact round (dtype-path switch) ----------------
        if datapath == "df32":
            # stay on the df32 pair: exact RNE + balanced digit split
            digits = encoder.delta_scale_digits(dfl.DF(
                jnp.concatenate([w.re.hi, w.im.hi], axis=1),
                jnp.concatenate([w.re.lo, w.im.lo], axis=1)), delta)
        else:
            coeffs = jnp.concatenate(
                [dfl.df_to_float(w.re), dfl.df_to_float(w.im)], axis=1)
            digits = encoder.delta_scale_round(coeffs, delta)
        for i, d in enumerate(digits):
            pt_scr[i] = d
        # --- PRNG once per ciphertext (limb-independent streams) ----------
        nonce = (nz_ref[0, 0]
                 + b.astype(jnp.uint32) * np.uint32(rows)
                 + jax.lax.broadcasted_iota(jnp.uint32, (rows, 1, 1), 0))
        for i, x in enumerate(client_pointwise.sample_vee_k(
                seed, nonce, pt_scr.shape[1:])):
            vee_scr[i] = x

    # --- Fourier engine, NTT mode: this limb's RNS -> NTT -> pointwise ----
    q = c_ref[l, common.OFF_Q]
    qinv = c_ref[l, common.OFF_QINV]
    if datapath == "df32":
        pt = rns.digits_to_residue(pt_scr[0], pt_scr[1], pt_scr[2], q, qinv,
                                   dc_ref[l, 0], dc_ref[l, 1])
    else:
        pt = rns.to_rns_limb_t(dfl.DF(pt_scr[0], pt_scr[1]),
                               q.astype(jnp.float64))
    v, e0, e1 = (client_pointwise._to_residue_k(vee_scr[i], q)
                 for i in range(3))
    # one stacked stage loop for all four polynomials: the NTT is
    # row-independent, so this is bit-identical to four transforms while
    # generating each stage's twiddles once
    h = common.ntt_tiled_t(jnp.concatenate([pt, v, e0, e1], axis=0),
                           c_ref, kc, q, qinv, row=l)
    pt_h, v_h, e0_h, e1_h = (h[i * rows:(i + 1) * rows] for i in range(4))
    vb = modmul.mulmod_montgomery_limb_t(v_h, b_ref[0], q, qinv)
    va = modmul.mulmod_montgomery_limb_t(v_h, a_ref[0], q, qinv)
    c0_ref[:, 0] = modmul.addmod(modmul.addmod(vb, e0_h, q), pt_h, q)
    c1_ref[:, 0] = modmul.addmod(va, e1_h, q)


def encode_encrypt_stream(planes, pk_b_mont, pk_a_mont, ctx: CKKSContext,
                          seed: int, nonce0=0, interpret: bool = True,
                          datapath: str = "f64"):
    """The whole encode+encrypt chain in ONE pallas_call.

    planes: four (B, n_slots) f32 df planes of the slot values (the same
    ``dfloat.dfc_to_planes`` layout the staged device core feeds its FFT
    kernel); pk rows (L, N) Montgomery form; nonce0 a Python int or traced
    uint32 scalar. Returns (c0, c1), each (B, L, N) uint32, bit-identical
    to the staged pipeline for the nonce layout nonce0 + batch_idx —
    under EITHER datapath ('df32' carries the same exact integers through
    f32/u32 chains; see the module docstring).
    """
    common.check_datapath(datapath)
    p = ctx.params
    batch = planes[0].shape[0]
    n_limbs, n, n_slots = p.n_limbs, p.n, p.n_slots
    tile = common.tile_layout(n)
    stile = (n_slots // tile[1], tile[1])
    bb = stream_batch_block(batch, n, _encrypt_vmem_bytes)
    kc, tw = stream_consts(ctx, n_limbs, inverse=True)
    dc = np.asarray(common.stacked_digit_consts(ctx.q_list[:n_limbs]),
                    np.uint32)
    nz = jnp.asarray(nonce0, jnp.uint32).reshape(1, 1)
    if datapath == "df32":
        scratch = pltpu.VMEM((3, bb) + tile, jnp.int32)
    else:
        scratch = pltpu.VMEM((2, bb) + tile, jnp.float64)

    sspec = _spec((bb,) + stile, lambda b, l: (b, 0, 0))
    pkspec = _spec((1,) + tile, lambda b, l: (l, 0, 0))
    ctspec = _spec((bb, 1) + tile, lambda b, l: (b, l, 0, 0))
    shape = jax.ShapeDtypeStruct((batch, n_limbs) + tile, jnp.uint32)
    call = pl.pallas_call(
        functools.partial(_encode_encrypt_kernel, kc=kc, seed=seed,
                          delta=p.delta, n_slots=n_slots, datapath=datapath),
        grid=(batch // bb, n_limbs),
        in_specs=[_whole(kc.table.shape, pltpu.SMEM),
                  _whole(dc.shape, pltpu.SMEM), _whole((1, 1), pltpu.SMEM)]
        + [sspec] * 4 + [_whole(tw.shape), pkspec, pkspec],
        out_specs=(ctspec, ctspec),
        out_shape=(shape, shape),
        scratch_shapes=[scratch, pltpu.VMEM((3, bb) + tile, jnp.int32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )
    c0, c1 = call(
        jnp.asarray(kc.table), jnp.asarray(dc), nz,
        *(x.reshape((batch,) + stile) for x in fft_df.bitrev_gather(planes)),
        jnp.asarray(tw), pk_b_mont[:n_limbs].reshape((n_limbs,) + tile),
        pk_a_mont[:n_limbs].reshape((n_limbs,) + tile))
    return (c0.reshape(batch, n_limbs, n), c1.reshape(batch, n_limbs, n))


# ---------------------------------------------------------------------------
# decrypt+decode megakernel
# ---------------------------------------------------------------------------


def _decrypt_decode_kernel(c_ref, c0_ref, c1_ref, s_ref, sc_ref, tw_ref,
                           orh, orl, oih, oil, *,
                           kc: common.StackedKernelConsts,
                           q0: int, q1: int, n_slots: int,
                           datapath: str = "f64"):
    # --- per-limb decrypt pointwise + INTT (Fourier engine, NTT mode) -----
    m = [client_pointwise.decrypt_limb_stage(
            c0_ref[:, l], c1_ref[:, l], s_ref[l], c_ref, kc, limb=l)
         for l in range(2)]
    half = orh.shape[1]

    if datapath == "df32":
        # --- uint32 CRT -> centered word pair -> exact /Delta pair --------
        sign, vh, vl = rns.crt2_centered_u32(m[0], m[1], q0, q1)
        x = rns.centered_to_df(sign, vh, vl, sc_ref[...])  # sc: 1/scale
        (rh, ih), (rl, il) = _split_rows(x.hi, half), _split_rows(x.lo, half)
        z = dfl.dfc_from_planes((rh, rl, ih, il))
    else:
        # --- two-limb CRT -> centered df64 -> /Delta ----------------------
        v = rns.crt2_to_df(m[0].astype(jnp.uint64), m[1].astype(jnp.uint64),
                           q0, q1)
        scale = sc_ref[...]                              # (rows, 1, 1) f64
        re, im = _split_rows(v.hi / scale + v.lo / scale, half)
        z = dfl.dfc_from_parts(re, im)

    # --- Fourier engine, FFT mode: df32 SpecialFFT (bit-reversed out) -----
    z = fft_df.fft_stage_pipeline(z, tw_ref, n=n_slots, inverse=False)
    orh[...], orl[...], oih[...], oil[...] = dfl.dfc_to_planes(z)


def _pow2_reciprocal(scale):
    """Exact 1/scale of positive normal power-of-two f32 scales, by
    exponent arithmetic (the f32 bit pattern of 2^-e is 0x7F000000 minus
    that of 2^e) — no division on the device."""
    bits = jax.lax.bitcast_convert_type(scale, jnp.int32)
    return jax.lax.bitcast_convert_type(jnp.int32(0x7F000000) - bits,
                                        jnp.float32)


def decrypt_decode_stream(c0, c1, s_mont, ctx: CKKSContext, scale,
                          interpret: bool = True, datapath: str = "f64"):
    """The whole decrypt+decode chain in ONE pallas_call.

    c0/c1: (B, 2, N) uint32 server-returned limb stacks; s_mont (L, N);
    scale a traced scalar or (B, 1) array (per-ciphertext scales; carried
    as f32 on the df32 datapath — exact for the power-of-two Deltas).
    Returns four (B, n_slots) f32 df planes of the decoded slots (collapse
    with ``dfloat.df_to_float`` outside), matching the staged device decode
    bit-for-bit (same stage functions, same op order).
    """
    common.check_datapath(datapath)
    p = ctx.params
    batch, _, n = c0.shape
    n_slots = p.n_slots
    tile = common.tile_layout(n)
    stile = (n_slots // tile[1], tile[1])
    bb = stream_batch_block(batch, n, _decrypt_vmem_bytes)
    kc, tw = stream_consts(ctx, 2, inverse=False)
    if datapath == "df32":
        sc = _pow2_reciprocal(jnp.asarray(scale, jnp.float32))
    else:
        sc = jnp.asarray(scale, jnp.float64)
    sc = jnp.broadcast_to(sc.reshape(-1, 1, 1), (batch, 1, 1))

    ctspec = _spec((bb, 2) + tile, lambda b: (b, 0, 0, 0))
    scspec = _spec((bb, 1, 1), lambda b: (b, 0, 0))
    ospec = _spec((bb,) + stile, lambda b: (b, 0, 0))
    oshape = jax.ShapeDtypeStruct((batch,) + stile, jnp.float32)
    call = pl.pallas_call(
        functools.partial(_decrypt_decode_kernel, kc=kc, q0=ctx.q_list[0],
                          q1=ctx.q_list[1], n_slots=n_slots,
                          datapath=datapath),
        grid=(batch // bb,),
        in_specs=[_whole(kc.table.shape, pltpu.SMEM), ctspec, ctspec,
                  _whole((2,) + tile), scspec, _whole(tw.shape)],
        out_specs=(ospec,) * 4,
        out_shape=(oshape,) * 4,
        compiler_params=_compiler_params(("parallel",)),
        interpret=interpret,
    )
    out = call(jnp.asarray(kc.table),
               c0[:, :2].reshape((batch, 2) + tile),
               c1[:, :2].reshape((batch, 2) + tile),
               s_mont[:2].reshape((2,) + tile), sc, jnp.asarray(tw))
    return fft_df.bitrev_gather(tuple(o.reshape(batch, n_slots)
                                      for o in out))
