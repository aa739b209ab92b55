"""FHE client pipeline: private-inference I/O for the model substrate.

The paper's deployment (Fig. 1): the *client* encodes+encrypts inputs and
decodes+decrypts outputs; the *server* computes on ciphertexts (server-side
acceleration is other papers' territory — Trinity/SHARP et al.; out of scope
here, so examples simulate the server boundary).

This module glues the CKKS core to the LM substrate:

  * messages are model activations (e.g. prompt embeddings of width d_model)
    packed into CKKS slot vectors (n_slots = N/2 complex = N real values);
  * a batch of messages travels as struct-of-arrays (B, L, N) residue stacks
    (``CiphertextBatch``) and is encrypted with the FUSED limb-folded
    streaming kernels — PRNG + NTT + pointwise in ONE pallas_call for the
    whole batch (the RSC datapath with the limb loop in the Pallas grid);
  * with the default ``fourier='device'`` engine the WHOLE pipeline —
    df32 SpecialIFFT/FFT Pallas kernels, Delta-scale, RNS, stacked-limb
    NTT, fused kernels, CRT — runs inside a single jit per direction: no
    complex128 array and no host FFT between entry and exit (the paper's
    no-off-chip-round-trip property). ``fourier='host'`` keeps the
    complex128 CPU oracle Fourier path as a bit-stable reference;
  * on a mesh, ciphertext batches shard over the flattened device axis
    (each device runs its own RSC-equivalent stream; the dual-RSC scheduler
    generalises to device groups).

Seeded (compressed) symmetric ciphertexts halve upload traffic, matching
the paper's on-chip `a`-regeneration trick.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import dfloat as dfl
from repro.core import encoder, encryptor, rns
from repro.core.context import CKKSContext, get_context
from repro.core.encryptor import CiphertextBatch
from repro.kernels import ops as kops


@dataclasses.dataclass
class ClientKeys:
    sk: encryptor.SecretKey
    pk: encryptor.PublicKey


def _host_keygen(ctx: CKKSContext, seed: int) -> ClientKeys:
    """Keygen on the host CPU device, keys then placed on the default one.

    Keygen's reference arithmetic is 64-bit (uint64 products, int64
    residues) where x64 is on; run on the CPU it gives the same bits on
    every machine, with no 64-bit program sent to the chip (at `paper` a
    TPU v5e gave the same bits in 119 s, the host CPU in about 21 s). The
    keys are exact integers, so where they are computed changes nothing
    else.
    """
    with jax.default_device(jax.devices("cpu")[0]):
        sk, pk = encryptor.keygen(ctx, seed=seed)

    def place(x):
        return jnp.asarray(np.asarray(x))

    return ClientKeys(
        encryptor.SecretKey(s_mont=place(sk.s_mont),
                            s_coeffs=place(sk.s_coeffs)),
        encryptor.PublicKey(b_mont=place(pk.b_mont), a_mont=place(pk.a_mont),
                            a_stream=pk.a_stream))


class FHEClient:
    """Client-side encode/encrypt + decode/decrypt over model activations.

    ``fourier`` selects the Fourier engine for the slot<->coefficient
    transforms (the paper's NTT/FFT mode switch, DESIGN.md):

      * ``'device'`` (default) — df32 SpecialFFT Pallas kernels traced into
        the jitted cores: encode+encrypt and decrypt+decode are each ONE
        jitted program, fully device-resident;
      * ``'host'`` — complex128 numpy oracle FFTs outside the jit
        (bit-equivalent to the pre-device-Fourier pipeline; the reference
        path equivalence tests compare against).

    ``pipeline`` selects how the device-resident chain is launched:

      * ``'staged'`` — one jitted program per direction, with the df32 FFT
        kernel and the limb-folded NTT/pointwise kernel as separate
        pallas_calls inside it;
      * ``'megakernel'`` (default for ``fourier='device'``) — the streaming
        megakernel (``kernels.client_stream``): the ENTIRE encode+encrypt
        and decrypt+decode chains are each ONE pallas_call, the Fourier
        engine mode-switching FFT->NTT inside the kernel body (the ASIC's
        MDC streaming pipeline). Ciphertexts are bit-identical to 'staged'
        for fixed seeds. Requires ``fourier='device'`` (the megakernel IS
        the device Fourier path).

    ``datapath`` selects the dtype path of the Delta-scale/RNS/CRT
    interior (DESIGN.md §4):

      * ``'df32'`` (default for ``fourier='device'``) — df32^2 split-limb
        chains + uint32 modular arithmetic: the same exact integers with
        zero float64/uint64 ops in the jitted cores, so the client traces
        with ``JAX_ENABLE_X64=0`` and lowers on TPU VPUs. Bit-identical
        ciphertexts AND decode planes to the f64 oracle
        (tests/test_datapath_oracle.py). Requires the standard
        power-of-two Delta.
      * ``'f64'`` — the exact df64/fmod/uint64 interior: the interpret-mode
        oracle the df32 path is differenced against (and the only path for
        ``fourier='host'``).
    """

    def __init__(self, profile="test", seed: int | None = None,
                 fourier: str = "device", pipeline: str | None = None,
                 datapath: str | None = None):
        # `profile` is a named profile string or a CKKSParams value (the
        # property-test parameter grids construct clients off-profile).
        if fourier not in ("device", "host"):
            raise ValueError(f"fourier must be 'device' or 'host', "
                             f"got {fourier!r}")
        if pipeline is None:
            pipeline = "megakernel" if fourier == "device" else "staged"
        if pipeline not in ("staged", "megakernel"):
            raise ValueError(f"pipeline must be 'staged' or 'megakernel', "
                             f"got {pipeline!r}")
        if pipeline == "megakernel" and fourier != "device":
            raise ValueError("pipeline='megakernel' fuses the df32 Fourier "
                             "kernels into the streaming kernel body and "
                             "therefore requires fourier='device'")
        if datapath is None:
            datapath = "df32" if fourier == "device" else "f64"
        if datapath not in ("f64", "df32"):
            raise ValueError(f"datapath must be 'f64' or 'df32', "
                             f"got {datapath!r}")
        if datapath == "df32" and fourier != "device":
            raise ValueError("datapath='df32' is the device-kernel dtype "
                             "path and requires fourier='device' (the host "
                             "oracle pipeline is f64 by construction)")
        self.ctx: CKKSContext = get_context(profile)
        self.fourier = fourier
        self.pipeline = pipeline
        self.datapath = datapath
        if datapath == "df32":
            encoder._check_pow2_delta(self.ctx.params.delta)
        # The client's PRNG seed keys BOTH keygen and every encryption's
        # (v, e0, e1) Philox streams. Distinct co-resident tenants MUST get
        # distinct seeds (tenancy.tenant_seed) or they'd draw mask/error
        # polynomials from the same streams — see fhe_client.tenancy.
        self.seed = int(seed) if seed is not None else self.ctx.params.seed
        self.keys = _host_keygen(self.ctx, self.seed)
        self._nonce = 0
        # jit-compiled device cores (shape-polymorphic via retrace-per-B;
        # the nonce base is a traced operand so fresh nonces never retrace).
        self._encrypt_core = jax.jit(self._encrypt_core_impl)
        self._decrypt_core = jax.jit(self._decrypt_core_impl)
        self._encrypt_core_dev = jax.jit(self._encrypt_core_dev_impl)
        self._decrypt_core_dev = jax.jit(self._decrypt_core_dev_impl)
        self._encrypt_core_mega = jax.jit(self._encrypt_core_mega_impl)
        self._decrypt_core_mega = jax.jit(self._decrypt_core_mega_impl)
        self._encrypt_core_dev32 = jax.jit(self._encrypt_core_dev32_impl)
        self._decrypt_core_dev32 = jax.jit(self._decrypt_core_dev32_impl)
        self._encrypt_core_mega32 = jax.jit(self._encrypt_core_mega32_impl)
        self._decrypt_core_mega32 = jax.jit(self._decrypt_core_mega32_impl)

    # --- evaluation-key generation (server-side eval material) --------------

    def make_evaluation_keys(self, rotations=(), include_relin: bool = True,
                             seed: int | None = None):
        """Evaluation material for a ``fhe_server.ServerEvaluator``:
        relinearization + rotation keys (hybrid key switching, one special
        prime).  The secret key never leaves this method's frame — only
        RLWE-encrypted key pairs are returned, and only those cross the
        wire (``service.wire.serialize_evaluation_keys``).

        ``rotations``: the slot left-rotation amounts the server may apply
        (e.g. ``fhe_server.inference.matvec_rotations(d)``)."""
        from repro.fhe_server import keys as server_keys
        return server_keys.make_evaluation_keys(
            self.ctx, self.keys.sk, rotations=rotations,
            include_relin=include_relin, seed=seed)

    # --- message packing ----------------------------------------------------

    def slot_capacity(self) -> int:
        """Real values per ciphertext (real/imag interleaving)."""
        return 2 * self.ctx.params.n_slots

    def pack(self, x: np.ndarray) -> np.ndarray:
        """Activation rows (B, F) -> complex slot rows (B*k, n_slots).
        Rows wider than one ciphertext split across k = ceil(F/capacity)
        ciphertexts (standard multi-ct packing)."""
        b, f = x.shape
        cap = self.slot_capacity()
        k = -(-f // cap)
        buf = np.zeros((b, k * cap), np.float64)
        buf[:, :f] = x
        buf = buf.reshape(b * k, cap)
        n_slots = self.ctx.params.n_slots
        return buf[:, :n_slots] + 1j * buf[:, n_slots:]

    def unpack(self, z: np.ndarray, f: int) -> np.ndarray:
        cap = self.slot_capacity()
        k = -(-f // cap)
        b = z.shape[0] // k
        buf = np.concatenate([z.real, z.imag], axis=-1)  # (B*k, cap)
        return buf.reshape(b, k * cap)[:, :f]

    # --- batched encode+encrypt / decrypt+decode (fused streaming kernels) --

    def _encrypt_core_impl(self, coeffs, nonce0):
        """(B, N) float64 slot-IFFT coefficients -> (c0, c1) (B, L, N).
        Jit-traced: Delta-scale + RNS + stacked-limb NTT + ONE folded
        encrypt pallas_call."""
        ctx = self.ctx
        L = ctx.params.n_limbs
        residues = encoder.coeffs_to_plaintext_data(coeffs, ctx, L)
        pt = jnp.swapaxes(residues, 0, 1)                 # (B, L, N)
        return kops.encrypt_fused(pt, self.keys.pk.b_mont,
                                  self.keys.pk.a_mont, ctx, seed=self.seed,
                                  nonce0=nonce0)

    def _decrypt_core_impl(self, c0, c1):
        """(B, 2, N) ciphertext stacks -> exact df64 CRT coefficients.
        Jit-traced: ONE folded decrypt pallas_call + two-limb CRT."""
        ctx = self.ctx
        m = kops.decrypt_fused(c0, c1, self.keys.sk.s_mont, ctx)
        v = rns.crt2_to_df(m[:, 0].astype(jnp.uint64),
                           m[:, 1].astype(jnp.uint64),
                           ctx.q_list[0], ctx.q_list[1])
        return v.hi, v.lo

    # --- fully device-resident cores (fourier='device') ---------------------

    def _encrypt_core_dev_impl(self, re, im, nonce0):
        """(B, n_slots) f64 slot parts -> (c0, c1) (B, L, N): the ENTIRE
        encode+encrypt — df32 SpecialIFFT Pallas kernel, Delta-scale + RNS
        rounding, stacked-limb NTT, ONE folded encrypt pallas_call — in a
        single traced region. No complex128 array, no host FFT."""
        ctx = self.ctx
        L = ctx.params.n_limbs
        coeffs = encoder.slots_to_coeffs_device(re, im, ctx)  # (B, N) f64
        residues = encoder.coeffs_to_plaintext_data(coeffs, ctx, L)
        pt = jnp.swapaxes(residues, 0, 1)                 # (B, L, N)
        return kops.encrypt_fused(pt, self.keys.pk.b_mont,
                                  self.keys.pk.a_mont, ctx, seed=self.seed,
                                  nonce0=nonce0)

    def _decrypt_core_dev_impl(self, c0, c1, scale):
        """(B, 2, N) ciphertext stacks -> (B, n_slots) f64 (re, im) slot
        parts: ONE folded decrypt pallas_call + two-limb CRT + /scale +
        df32 SpecialFFT Pallas kernel, all in one traced region. `scale` is
        a traced f64 scalar or (B, 1) array (per-ciphertext scales)."""
        ctx = self.ctx
        m = kops.decrypt_fused(c0, c1, self.keys.sk.s_mont, ctx)
        v = rns.crt2_to_df(m[:, 0].astype(jnp.uint64),
                           m[:, 1].astype(jnp.uint64),
                           ctx.q_list[0], ctx.q_list[1])
        return encoder.coeffs_to_slots_device(v.hi, v.lo, ctx, scale)

    # --- compile-ready df32-datapath cores (datapath='df32') ----------------
    # The f64/u64 glue between kernels is replaced by the exact df32^2 /
    # uint32 chains (encoder.delta_scale_digits, rns.digits_to_residues_
    # stacked / crt2_centered_u32), and the stacked-limb NTT by the u32
    # kernel path, so the whole traced region holds no float64/uint64 op —
    # pinned by the jaxpr scan in tests/test_datapath_oracle.py.

    def _encrypt_core_dev32_impl(self, rh, rl, ih, il, nonce0):
        """Four (B, n_slots) f32 slot planes -> (c0, c1) (B, L, N): staged
        df32 pipeline — SpecialIFFT kernel, df32^2 Delta-scale digits, u32
        RNS reduction, limb-folded u32 NTT kernel, fused encrypt kernel."""
        ctx = self.ctx
        L = ctx.params.n_limbs
        w = dfl.dfc_from_planes(
            kops.special_ifft_planes((rh, rl, ih, il), ctx.params.m))
        digits = encoder.delta_scale_digits(
            encoder.planes_to_coeff_df(w), ctx.params.delta)
        residues = rns.digits_to_residues_stacked(*digits,
                                                 ctx.q_list[:L])  # (L, B, N)
        pt = jnp.swapaxes(kops.ntt_limbs(residues, ctx), 0, 1)    # (B, L, N)
        return kops.encrypt_fused(pt, self.keys.pk.b_mont,
                                  self.keys.pk.a_mont, ctx, seed=self.seed,
                                  nonce0=nonce0)

    def _decrypt_core_dev32_impl(self, c0, c1, scale):
        """(B, 2, N) ciphertext stacks -> four (B, n_slots) f32 decoded
        slot planes: fused decrypt kernel, uint32 CRT + exact /Delta pair,
        SpecialFFT kernel. `scale` is a traced f32 scalar or (B, 1) array
        (power-of-two per-ciphertext scales)."""
        ctx = self.ctx
        ns = ctx.params.n_slots
        m = kops.decrypt_fused(c0, c1, self.keys.sk.s_mont, ctx)
        sign, vh, vl = rns.crt2_centered_u32(m[:, 0], m[:, 1],
                                             ctx.q_list[0], ctx.q_list[1])
        inv = jnp.float32(1.0) / jnp.asarray(scale, jnp.float32)
        x = rns.centered_to_df(sign, vh, vl, inv)
        planes = dfl.dfc_to_planes(dfl.DFComplex(
            dfl.DF(x.hi[..., :ns], x.lo[..., :ns]),
            dfl.DF(x.hi[..., ns:], x.lo[..., ns:])))
        return kops.special_fft_planes(planes, ctx.params.m)

    def _encrypt_core_mega32_impl(self, rh, rl, ih, il, nonce0):
        """Megakernel + df32 datapath (the device default): ONE pallas_call
        with the f32/u32 interior — nothing but the kernel in the trace."""
        return kops.encode_encrypt_stream(
            (rh, rl, ih, il), self.keys.pk.b_mont, self.keys.pk.a_mont,
            self.ctx, seed=self.seed, nonce0=nonce0, datapath="df32")

    def _decrypt_core_mega32_impl(self, c0, c1, scale):
        """Megakernel decrypt+decode, df32 interior: ONE pallas_call in,
        four f32 slot planes out (host collapses to complex)."""
        return kops.decrypt_decode_stream(
            c0, c1, self.keys.sk.s_mont, self.ctx, scale, datapath="df32")

    # --- streaming megakernel cores (pipeline='megakernel') -----------------

    def _encrypt_core_mega_impl(self, re, im, nonce0):
        """(B, n_slots) f64 slot parts -> (c0, c1) (B, L, N): the ENTIRE
        encode+encrypt chain as ONE pallas_call (SpecialIFFT, Delta-scale,
        RNS, NTT, PRNG, pointwise all inside one kernel body). The only
        jnp work outside the kernel is the f64 -> df32 plane split."""
        z = dfl.dfc_from_parts(re, im)
        return kops.encode_encrypt_stream(
            dfl.dfc_to_planes(z), self.keys.pk.b_mont, self.keys.pk.a_mont,
            self.ctx, seed=self.seed, nonce0=nonce0)

    def _decrypt_core_mega_impl(self, c0, c1, scale):
        """(B, 2, N) ciphertext stacks -> (B, n_slots) f64 (re, im) slot
        parts: decrypt pointwise, INTT, CRT, /Delta and SpecialFFT as ONE
        pallas_call; outside the kernel only the df32 -> f64 collapse."""
        planes = kops.decrypt_decode_stream(
            c0, c1, self.keys.sk.s_mont, self.ctx, scale)
        w = dfl.dfc_from_planes(planes)
        return dfl.df_to_float(w.re), dfl.df_to_float(w.im)

    # --- core selection seams (shared with the client service) --------------
    #
    # The serving layer (``repro.fhe_client.service``) executes the SAME
    # pipelines on its device streams: it preps operands with
    # ``encrypt_operands``/``decrypt_operands``, then either calls the
    # jitted ``encrypt_core``/``decrypt_core`` (single-device streams) or
    # shard_maps the untraced ``encrypt_impl``/``decrypt_impl`` over a
    # device-group mesh. Every impl is row-independent along the leading
    # batch axis, which is what makes batch-axis sharding (and tail
    # padding in the batcher) bit-transparent per row.

    @property
    def n_encrypt_operands(self) -> int:
        """Arity of ``encrypt_operands`` output (the service shard_maps
        each operand over the batch axis, so it needs the count)."""
        if self.fourier != "device":
            return 1
        return 4 if self.datapath == "df32" else 2

    def encrypt_operands(self, messages) -> tuple:
        """Host-side prep for one encrypt batch: (B, n_slots) complex ->
        the operand arrays ``encrypt_impl``/``encrypt_core`` consume
        (four f32 df planes for datapath='df32', (re, im) f64 parts for
        the f64 device path, (coeffs,) for the host oracle path)."""
        msgs = np.asarray(messages, np.complex128)
        if self.fourier == "device":
            if self.datapath == "df32":
                # host-side df split (numpy): identical values to the f64
                # path's in-jit dfc_from_parts, but the traced region then
                # starts f32-pure
                rh = msgs.real.astype(np.float32)
                ih = msgs.imag.astype(np.float32)
                rl = (msgs.real - rh).astype(np.float32)
                il = (msgs.imag - ih).astype(np.float32)
                return tuple(jnp.asarray(p) for p in (rh, rl, ih, il))
            return (jnp.asarray(msgs.real), jnp.asarray(msgs.imag))
        return (jnp.asarray(encoder.slots_to_coeffs(msgs, self.ctx)),)

    @property
    def encrypt_impl(self):
        """Untraced encrypt core ``f(*operands, nonce0) -> (c0, c1)`` for
        the configured fourier/pipeline/datapath (row-independent over
        batch)."""
        if self.fourier != "device":
            return self._encrypt_core_impl
        if self.pipeline == "megakernel":
            return (self._encrypt_core_mega32_impl if self.datapath == "df32"
                    else self._encrypt_core_mega_impl)
        return (self._encrypt_core_dev32_impl if self.datapath == "df32"
                else self._encrypt_core_dev_impl)

    @property
    def encrypt_core(self):
        """Jit-compiled counterpart of ``encrypt_impl``."""
        if self.fourier != "device":
            return self._encrypt_core
        if self.pipeline == "megakernel":
            return (self._encrypt_core_mega32 if self.datapath == "df32"
                    else self._encrypt_core_mega)
        return (self._encrypt_core_dev32 if self.datapath == "df32"
                else self._encrypt_core_dev)

    def _scale_operand(self, scale):
        """Traced scale operand: f32 on the df32 datapath (power-of-two
        scales are exact in f32; checked on the host), f64 otherwise."""
        if self.fourier == "device" and self.datapath == "df32":
            for s in np.atleast_1d(np.asarray(scale, np.float64)).ravel():
                encoder._check_pow2_delta(s)
            return jnp.asarray(scale, jnp.float32)
        return jnp.asarray(scale, jnp.float64)

    def decrypt_operands(self, cts: CiphertextBatch) -> tuple:
        """(c0, c1, scale) operands for ``decrypt_impl``/``decrypt_core``.
        ``scale`` may be a scalar or a (B, 1) per-row array."""
        return (cts.c0[:, :2], cts.c1[:, :2], self._scale_operand(cts.scale))

    @property
    def decrypt_impl(self):
        """Untraced decrypt core ``f(c0, c1, scale) -> parts`` (the host
        oracle applies its scale on the host, so its core ignores the
        traced operand)."""
        if self.fourier != "device":
            return lambda c0, c1, scale: self._decrypt_core_impl(c0, c1)
        if self.pipeline == "megakernel":
            return (self._decrypt_core_mega32_impl if self.datapath == "df32"
                    else self._decrypt_core_mega_impl)
        return (self._decrypt_core_dev32_impl if self.datapath == "df32"
                else self._decrypt_core_dev_impl)

    @property
    def decrypt_core(self):
        if self.fourier != "device":
            return lambda c0, c1, scale: self._decrypt_core(c0, c1)
        if self.pipeline == "megakernel":
            return (self._decrypt_core_mega32 if self.datapath == "df32"
                    else self._decrypt_core_mega)
        return (self._decrypt_core_dev32 if self.datapath == "df32"
                else self._decrypt_core_dev)

    def decrypt_results(self, parts, scale) -> np.ndarray:
        """Core output parts -> (B, n_slots) complex messages (the host
        path finishes its decode — FFT + /scale — here; the df32 path
        collapses its four f32 planes in f64 numpy, which is exactly the
        ``df_to_float`` the f64 path traces)."""
        if self.fourier == "device":
            if self.datapath == "df32":
                rh, rl, ih, il = (np.asarray(p, np.float64) for p in parts)
                return (rh + rl) + 1j * (ih + il)
            re, im = parts
            return np.asarray(re) + 1j * np.asarray(im)
        hi, lo = parts
        return encoder.coeffs_to_slots(np.asarray(hi) + np.asarray(lo),
                                       self.ctx, scale)

    # --- nonce discipline ----------------------------------------------------

    @property
    def nonce(self) -> int:
        """Next unused PRNG nonce. Settable so replay/equivalence tests can
        pin the base; never rewind in production — (seed, nonce) reuse
        breaks RLWE security."""
        return self._nonce

    @nonce.setter
    def nonce(self, value: int):
        self._nonce = int(value)

    def take_nonces(self, count: int) -> int:
        """Reserve ``count`` consecutive nonces, returning the base. The
        service batcher draws from the client counter through this, so
        direct calls and service batches never collide on a PRNG stream
        (padding rows consume nonces too — row r of any batch always uses
        ``base + r``, which is what keeps bucketing bit-transparent)."""
        base = self._nonce
        self._nonce += int(count)
        return base

    def encode_encrypt_batch(self, messages: np.ndarray) -> CiphertextBatch:
        """(B, n_slots) complex messages -> CiphertextBatch (B, L, N).

        fourier='device': one jitted program does everything (df32 Pallas
        SpecialIFFT included) — the only host work is splitting the message
        into real/imag operand planes at entry. With pipeline='megakernel'
        that jitted program is ONE pallas_call.
        fourier='host': host batched complex128 SpecialIFFT, then the
        jitted device core (the PR 1 pipeline, kept as oracle).
        """
        p = self.ctx.params
        if np.shape(messages)[0] == 0:
            raise ValueError("encode_encrypt_batch needs a non-empty batch")
        nonce0 = self.take_nonces(np.shape(messages)[0])
        c0, c1 = self.encrypt_core(*self.encrypt_operands(messages),
                                   jnp.uint32(nonce0))
        return CiphertextBatch(c0=c0, c1=c1, n_limbs=p.n_limbs,
                               scale=p.delta)

    def decrypt_decode_batch(self, cts: CiphertextBatch) -> np.ndarray:
        """CiphertextBatch (server-returned view; first 2 limbs are used)
        -> (B, n_slots) complex messages."""
        parts = self.decrypt_core(*self.decrypt_operands(cts))
        return self.decrypt_results(parts, cts.scale)

    # --- list[Ciphertext] interop (legacy per-ciphertext protocol) ----------

    def encrypt_batch(self, messages: np.ndarray) -> list:
        """(B, n_slots) complex -> list of ciphertexts (fused kernel path).
        Thin wrapper over ``encode_encrypt_batch``; rows are views into the
        batch arrays."""
        return list(self.encode_encrypt_batch(messages))

    def decrypt_batch(self, cts) -> np.ndarray:
        """Server-returned (2-limb) ciphertexts -> (B, n_slots) complex.
        Accepts a CiphertextBatch or a list of Ciphertexts; list rows may
        carry per-ciphertext scales (e.g. different rescale depths)."""
        if isinstance(cts, CiphertextBatch):
            return self.decrypt_decode_batch(cts)
        cts = list(cts)
        c0 = jnp.stack([ct.c0[:2] for ct in cts])
        c1 = jnp.stack([ct.c1[:2] for ct in cts])
        scale = np.array([ct.scale for ct in cts])[:, None]
        parts = self.decrypt_core(c0, c1, self._scale_operand(scale))
        return self.decrypt_results(parts, scale)

    # --- traffic accounting (paper Table/figs analogues) ---------------------

    def ciphertext_bytes(self, seeded: bool = False) -> int:
        p = self.ctx.params
        polys = 1 if seeded else 2
        return polys * p.n_limbs * p.n * 4 + (16 if seeded else 0)

    def upload_report(self, batch: int) -> dict:
        return {
            "batch": batch,
            "ct_bytes": self.ciphertext_bytes(),
            "ct_bytes_seeded": self.ciphertext_bytes(seeded=True),
            "compression": self.ciphertext_bytes()
            / self.ciphertext_bytes(seeded=True),
        }


def simulate_private_inference(client: FHEClient, serve_fn, x: np.ndarray,
                               out_features: int):
    """End-to-end loop: encrypt -> (trust boundary) -> serve -> encrypt
    result -> decrypt. `serve_fn`: (B, F) -> (B, out_features) plaintext
    model function standing in for the FHE server."""
    msgs = client.pack(x)
    cts = client.encode_encrypt_batch(msgs)

    # --- server boundary (simulated; see module docstring) -----------------
    served_inputs = client.decrypt_decode_batch(cts.truncated(2))
    x_rec = client.unpack(served_inputs, x.shape[1])
    y = serve_fn(x_rec.astype(np.float32))
    y_msgs = client.pack(y.astype(np.float64))
    y_cts = client.encode_encrypt_batch(y_msgs)
    # ------------------------------------------------------------------------

    y_dec = client.decrypt_decode_batch(y_cts.truncated(2))
    return client.unpack(y_dec, out_features), {
        "roundtrip_err": float(np.max(np.abs(x_rec - x))),
    }
