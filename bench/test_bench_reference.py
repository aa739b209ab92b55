"""The plain reference against the program at small rings on the CPU:
the same randomness, transforms and keys bit for bit, the program's
ciphertexts and decodings within the check's numbers, and the float32
control far outside them."""

import ast
import json
import os

import numpy as np
import pytest

import reference as R

HERE = os.path.dirname(os.path.abspath(__file__))


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "reference.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for node in ast.walk(tree)
             if isinstance(node, ast.Import) for a in node.names]
    names += [node.module or "" for node in ast.walk(tree)
              if isinstance(node, ast.ImportFrom)]
    assert not [n for n in names if n.split(".")[0] == "repro"]


def _config(profile):
    from repro.core.context import get_context
    with open(os.path.join(HERE, "testdata", "tiny.json")) as f:
        streams = json.load(f)["prng_streams"]
    ctx = get_context(profile)
    p = ctx.params
    return ctx, {"logn": p.logn, "n_limbs": p.n_limbs,
                 "delta_bits": p.delta_bits, "seed": hex(p.seed),
                 "moduli": list(ctx.q_list), "prng_streams": streams}


@pytest.fixture(scope="module")
def tiny():
    return _config("tiny")


def test_samplers_match_the_program(tiny):
    from repro.core import prng
    ctx, config = tiny
    p, n = ctx.params, ctx.params.n
    ref = R.Reference(config)
    v, e0, e1 = ref.draws(3)
    assert np.array_equal(v, np.asarray(prng.zo(p.seed, 0x10000 + 48, n)))
    assert np.array_equal(e0, np.asarray(prng.cbd(p.seed, 0x20000 + 48, n)))
    assert np.array_equal(e1, np.asarray(prng.cbd(p.seed, 0x30000 + 48, n)))
    assert np.array_equal(R.ternary(p.seed, 0x100, n),
                          np.asarray(prng.ternary(p.seed, 0x100, n)))
    q = ctx.q_list[1]
    assert np.array_equal(R.uniform_mod(p.seed, 0x1001, n, q),
                          np.asarray(prng.uniform_mod_q(p.seed, 0x1001, n,
                                                        q)))


def test_ntt_matches_the_program_and_inverts(tiny):
    import jax.numpy as jnp
    from repro.core import ntt
    ctx, config = tiny
    ref = R.Reference(config)
    x = np.random.default_rng(0).integers(
        0, 1 << 29, (ctx.params.n_limbs, ctx.params.n)).astype(np.uint64)
    x %= ref.ntt.q[:, None]
    got = ref.ntt.forward(x, ref.rows)
    want = np.asarray(ntt.ntt_stacked(jnp.asarray(x), ctx.stacked_plans()))
    assert np.array_equal(got, want)
    assert np.array_equal(ref.ntt.inverse(got, ref.rows), x)


def test_keys_match_the_program(tiny):
    from repro.core import encryptor
    ctx, config = tiny
    sk, pk = encryptor.keygen(ctx)
    s_hat, a_hat, b_hat = R.Reference(config).keygen()

    def plain(mont):
        rows = []
        for i, q in enumerate(ctx.q_list):
            rinv = pow(1 << 32, -1, int(q))
            rows.append(np.asarray(mont[i]).astype(object) * rinv % int(q))
        return np.stack(rows).astype(np.uint64)

    assert np.array_equal(plain(sk.s_mont), s_hat)
    assert np.array_equal(plain(pk.a_mont), a_hat)
    assert np.array_equal(plain(pk.b_mont), b_hat)


@pytest.fixture(scope="module")
def served(tiny):
    """Three encryptions by the program's default client, nonces 7-9,
    and their two-limb decryptions."""
    from repro.fhe_client.client import FHEClient
    ctx, _config = tiny
    client = FHEClient("tiny")
    rng = np.random.default_rng(5)
    z = rng.uniform(-1, 1, (3, ctx.params.n_slots)) \
        + 1j * rng.uniform(-1, 1, (3, ctx.params.n_slots))
    client.nonce = 7
    cts = client.encode_encrypt_batch(z)
    dec = client.decrypt_decode_batch(cts.truncated(2))
    return z, np.asarray(cts.c0), np.asarray(cts.c1), dec


def test_program_ciphertexts_check_out(tiny, served):
    _ctx, config = tiny
    ref = R.Reference(config)
    z, c0, c1, _dec = served
    for i in range(3):
        c0_ref, c1_ref = ref.encrypt(z[i], 7 + i)
        assert np.array_equal(c1_ref, c1[i])
        assert ref.encrypt_gaps(z[i], 7 + i, c0[i], c1[i]) == {
            "c1_mismatch": 0, "c0_gap": 0}
        wrong = ref.encrypt_gaps(z[i], 8 + i, c0[i], c1[i])
        assert wrong["c1_mismatch"] > c1[i].size // 2
        assert wrong["c0_gap"] > 1 << 20


def test_program_decryptions_check_out(tiny, served):
    _ctx, config = tiny
    ref = R.Reference(config)
    z, c0, c1, dec = served
    for i in range(3):
        want = ref.decrypt(c0[i], c1[i])
        assert np.max(np.abs(want - dec[i])) < 1e-9
        assert np.max(np.abs(want - z[i])) < 2.0 ** -19.29


def test_float32_control_reads_far_off(tiny, served):
    _ctx, config = tiny
    ref = R.Reference(config)
    low = R.Reference(config, precision="float32")
    z, c0, c1, _dec = served
    c0_low, c1_low = low.encrypt(z[0], 7)
    gaps = ref.encrypt_gaps(z[0], 7, c0_low, c1_low)
    assert gaps["c1_mismatch"] == 0 and gaps["c0_gap"] > 1000
    gap = np.max(np.abs(low.decrypt(c0[0], c1[0]) - ref.decrypt(c0[0],
                                                                c1[0])))
    assert gap > 1e-8


def test_reference_refuses_a_wrong_modulus(tiny):
    _ctx, config = tiny
    bad = dict(config, moduli=[config["moduli"][0] + 2]
               + config["moduli"][1:])
    with pytest.raises(ValueError):
        R.Reference(bad)
