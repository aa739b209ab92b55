"""Streaming client megakernel: launch-count invariants, bit-identity
against the staged pipeline, decode precision, and PRNG determinism.

The tentpole contract (ISSUE 3):

  * ``FHEClient(pipeline='megakernel')`` lowers encode+encrypt and
    decrypt+decode to exactly ONE ``pallas_call`` each (the staged device
    cores lower one FFT kernel + one folded NTT/pointwise kernel);
  * megakernel ciphertexts are BIT-identical to the staged path for fixed
    seeds (the integer datapath is shared stage functions);
  * megakernel decode differs from the staged device decode only by
    jit-vs-trace f64 rounding (~1e-15) and stays inside the paper's
    bootstrapping precision budget;
  * the traced-nonce contract: the same seed/nonce base produces
    bit-identical ciphertexts whether a batch is encrypted as B=1 rows in
    a loop or as one B=16 launch, in either pipeline mode.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import boot_precision_bits, encoder, encryptor
from repro.fhe_client.client import FHEClient
from repro.kernels import ops as kops

BOOT_PREC_BITS = 19.29


def _messages(ctx, batch, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((batch, ctx.params.n_slots))
            + 1j * rng.standard_normal((batch, ctx.params.n_slots))) * 0.5


# ---------------------------------------------------------------------------
# launch-count invariants (the shared conftest counter)
# ---------------------------------------------------------------------------
# jax.make_jaxpr re-traces the core impls outside the jit cache, so every
# pallas_call lowering fires the counter without paying an XLA compile —
# the launch-count guard stays cheap enough for the tier-1 lane.


def _fresh(impl):
    """A new function object around a core impl: make_jaxpr caches traces
    by function, and another test may already have traced this one (the
    counter sees only pallas_calls that trace)."""
    return lambda *args: impl(*args)


def test_megakernel_cores_lower_single_pallas_call(pallas_call_counter,
                                                   tiny_mega_client):
    """pipeline='megakernel' traces encode+encrypt and decrypt+decode as
    exactly ONE pallas_call each — on BOTH datapaths: the f64 oracle
    interior and the df32 default (ISSUE 3 + ISSUE 5). Per-kernel-name
    counts pin WHICH kernel lowers, not just how many."""
    client = tiny_mega_client
    ctx = client.ctx
    msgs = _messages(ctx, 3)
    re, im = jnp.asarray(msgs.real), jnp.asarray(msgs.imag)

    # grid (batch blocks, limbs): the whole tiny batch per step, one limb
    # of public key and ciphertext per step
    limb_grid = [(1, ctx.params.n_limbs)]
    pallas_call_counter.clear()
    jax.make_jaxpr(_fresh(client._encrypt_core_mega_impl))(
        re, im, jnp.uint32(0))
    assert pallas_call_counter == limb_grid

    c0 = jnp.zeros((3, 2, ctx.params.n), jnp.uint32)
    pallas_call_counter.clear()
    jax.make_jaxpr(_fresh(client._decrypt_core_mega_impl))(
        c0, c0, jnp.float64(ctx.params.delta))
    assert pallas_call_counter == [(1,)]

    # df32 datapath (the device default): still one launch per direction,
    # and it is the megakernel body that lowers
    ops = client.encrypt_operands(msgs)
    pallas_call_counter.clear()
    jax.make_jaxpr(_fresh(client._encrypt_core_mega32_impl))(
        *ops, jnp.uint32(0))
    assert pallas_call_counter == limb_grid
    assert pallas_call_counter.by_name() == {"_encode_encrypt_kernel": 1}

    pallas_call_counter.clear()
    jax.make_jaxpr(_fresh(client._decrypt_core_mega32_impl))(
        c0, c0, jnp.float32(ctx.params.delta))
    assert pallas_call_counter == [(1,)]
    assert pallas_call_counter.by_name() == {"_decrypt_decode_kernel": 1}


def test_staged_device_cores_lower_two_pallas_calls(pallas_call_counter,
                                                    tiny_device_client):
    """The staged device pipeline remains two launches per direction (FFT
    kernel + folded NTT/pointwise kernel) — pins the difference the
    megakernel eliminates, and guards against silent launch growth."""
    client = tiny_device_client
    ctx = client.ctx
    msgs = _messages(ctx, 2)
    re, im = jnp.asarray(msgs.real), jnp.asarray(msgs.imag)

    pallas_call_counter.clear()
    jax.make_jaxpr(client._encrypt_core_dev_impl)(re, im, jnp.uint32(0))
    assert len(pallas_call_counter) == 2

    c0 = jnp.zeros((2, 2, ctx.params.n), jnp.uint32)
    pallas_call_counter.clear()
    jax.make_jaxpr(client._decrypt_core_dev_impl)(
        c0, c0, jnp.float64(ctx.params.delta))
    assert len(pallas_call_counter) == 2


# (the staged encrypt_limbs / decrypt_limbs one-launch guard lives in
# tests/test_batched_client.py::test_fused_ops_issue_single_pallas_call)


def test_eager_stream_entry_points_single_launch(pallas_call_counter,
                                                 tiny_mega_client):
    """The ops-layer stream wrappers issue one launch per call outside any
    jit as well (eager regression guard, mirrors the encrypt_limbs /
    decrypt_limbs staged guard)."""
    client = tiny_mega_client
    ctx = client.ctx
    from repro.core import dfloat as dfl
    msgs = _messages(ctx, 2, seed=3)
    z = dfl.dfc_from_parts(jnp.asarray(msgs.real), jnp.asarray(msgs.imag))

    def enc(planes):
        return kops.encode_encrypt_stream(
            planes, client.keys.pk.b_mont, client.keys.pk.a_mont, ctx,
            nonce0=0)

    pallas_call_counter.clear()
    jax.make_jaxpr(enc)(dfl.dfc_to_planes(z))
    assert len(pallas_call_counter) == 1

    c0 = jnp.zeros((2, 2, ctx.params.n), jnp.uint32)

    def dec(c0, c1):
        return kops.decrypt_decode_stream(
            c0, c1, client.keys.sk.s_mont, ctx, jnp.float64(ctx.params.delta))

    pallas_call_counter.clear()
    jax.make_jaxpr(dec)(c0, c0)
    assert len(pallas_call_counter) == 1


# ---------------------------------------------------------------------------
# bit-identity and precision vs the staged pipeline
# ---------------------------------------------------------------------------
# Session clients share one jit compile per (direction, B) shape; the
# B=16 / B=1 shapes below are the session's standard batches. Cross-client
# bit-identity comparisons synchronize the nonce base explicitly (the
# session clients' nonce counters advance independently).


def test_megakernel_bit_identical_ciphertexts(tiny_device_client,
                                              tiny_mega_client):
    """Fixed seed + synchronized nonce base: the megakernel's integer
    ciphertexts equal the staged device path's word for word (shared
    stage bodies)."""
    staged, mega = tiny_device_client, tiny_mega_client
    msgs = _messages(staged.ctx, 16, seed=1)
    staged._nonce = mega._nonce = 100
    bs = staged.encode_encrypt_batch(msgs)
    bm = mega.encode_encrypt_batch(msgs)
    np.testing.assert_array_equal(np.asarray(bs.c0), np.asarray(bm.c0))
    np.testing.assert_array_equal(np.asarray(bs.c1), np.asarray(bm.c1))

    got_staged = staged.decrypt_decode_batch(bs.truncated(2))
    got_mega = mega.decrypt_decode_batch(bm.truncated(2))
    # decode runs the same stage functions; only jit scheduling of the f64
    # tail differs (the staged path shows the same jit-vs-eager delta)
    np.testing.assert_allclose(got_mega, got_staged, atol=1e-12)
    assert boot_precision_bits(msgs, got_mega) >= BOOT_PREC_BITS


@pytest.mark.slow
def test_megakernel_bit_identical_ciphertexts_test_profile():
    """Nightly: same bit-identity + budget contract on the 'test' profile
    (N=2^10, 6 limbs) with fresh end-to-end jitted clients."""
    staged = FHEClient(profile="test", pipeline="staged", datapath="f64")
    mega = FHEClient(profile="test", pipeline="megakernel")
    msgs = _messages(staged.ctx, 3, seed=1)
    bs = staged.encode_encrypt_batch(msgs)
    bm = mega.encode_encrypt_batch(msgs)
    np.testing.assert_array_equal(np.asarray(bs.c0), np.asarray(bm.c0))
    np.testing.assert_array_equal(np.asarray(bs.c1), np.asarray(bm.c1))
    got = mega.decrypt_decode_batch(bm.truncated(2))
    np.testing.assert_allclose(
        got, staged.decrypt_decode_batch(bs.truncated(2)), atol=1e-12)
    assert boot_precision_bits(msgs, got) >= BOOT_PREC_BITS


def test_megakernel_matches_core_reference_encrypt(tiny_mega_client):
    """Megakernel ciphertexts == device-Fourier encoder + core encryptor
    rows for the nonce layout nonce0 + batch_idx (transitively pins the
    whole stack: core == staged == megakernel)."""
    client = tiny_mega_client
    ctx = client.ctx
    msgs = _messages(ctx, 1, seed=7)
    nonce0 = client._nonce
    batch = client.encode_encrypt_batch(msgs)
    # the eager per-message reference (device-Fourier encode + core
    # encrypt); one row — the nonce0 + batch_idx layout itself is pinned
    # by test_nonce_layout_b1_vs_b16_bit_identical
    pt = encoder.encode(msgs[0], ctx, fourier="device")
    ct = encryptor.encrypt(pt, client.keys.pk, ctx, nonce=nonce0)
    np.testing.assert_array_equal(np.asarray(batch.c0[0]),
                                  np.asarray(ct.c0))
    np.testing.assert_array_equal(np.asarray(batch.c1[0]),
                                  np.asarray(ct.c1))


def test_megakernel_per_row_scales(tiny_mega_client):
    """decrypt_batch on a list with per-ciphertext scales drives the
    megakernel with a (B, 1) traced scale operand."""
    client = tiny_mega_client
    msgs = _messages(client.ctx, 2, seed=5)
    cts = [client.encode_encrypt_batch(msgs[i:i + 1])[0] for i in range(2)]
    two = [encryptor.Ciphertext(c0=ct.c0[:2], c1=ct.c1[:2], n_limbs=2,
                                scale=ct.scale) for ct in cts]
    got = client.decrypt_batch(two)
    np.testing.assert_allclose(got, msgs, atol=1e-4)


# ---------------------------------------------------------------------------
# PRNG determinism: the traced-nonce contract (PR 1, now pinned)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipeline", ["staged", "megakernel"])
def test_nonce_layout_b1_vs_b16_bit_identical(pipeline, tiny_device_client,
                                              tiny_mega_client):
    """Same seed/nonce base => bit-identical ciphertexts whether the batch
    is encrypted as 16 B=1 launches or one B=16 launch."""
    client = (tiny_device_client if pipeline == "staged"
              else tiny_mega_client)
    msgs = _messages(client.ctx, 16, seed=11)
    client._nonce = 0
    rows = [client.encode_encrypt_batch(msgs[i:i + 1]) for i in range(16)]
    client._nonce = 0
    full = client.encode_encrypt_batch(msgs)
    c0_rows = np.concatenate([np.asarray(r.c0) for r in rows])
    c1_rows = np.concatenate([np.asarray(r.c1) for r in rows])
    np.testing.assert_array_equal(c0_rows, np.asarray(full.c0))
    np.testing.assert_array_equal(c1_rows, np.asarray(full.c1))


def test_same_nonce_base_across_pipelines_bit_identical(tiny_device_client,
                                                        tiny_mega_client):
    """staged and megakernel clients walked from the same nonce base
    produce the same ciphertext sequence, batch after batch."""
    staged, mega = tiny_device_client, tiny_mega_client
    staged._nonce = mega._nonce = 300
    for k in range(3):
        msgs = _messages(staged.ctx, 1, seed=20 + k)
        bs = staged.encode_encrypt_batch(msgs)
        bm = mega.encode_encrypt_batch(msgs)
        np.testing.assert_array_equal(np.asarray(bs.c0), np.asarray(bm.c0))
        np.testing.assert_array_equal(np.asarray(bs.c1), np.asarray(bm.c1))
    assert staged._nonce == mega._nonce == 303


# ---------------------------------------------------------------------------
# argument validation
# ---------------------------------------------------------------------------


def test_pipeline_arg_validated():
    with pytest.raises(ValueError, match="staged.*megakernel"):
        FHEClient(profile="tiny", pipeline="fused")
    with pytest.raises(ValueError, match="requires fourier='device'"):
        FHEClient(profile="tiny", fourier="host", pipeline="megakernel")
