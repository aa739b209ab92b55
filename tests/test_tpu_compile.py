"""Ahead-of-time compiles of the default client kernels for a TPU v5e.

Interpret mode checks neither tiling nor VMEM, so these tests hand the
device-default megakernels (encode+encrypt and decrypt+decode, df32
datapath) to the chip's own compiler for a described ``v5e:2x2``
topology. Nothing runs: a compile that passes proves the kernels lower and
fit, not that they are fast or correct (``chip_smoke.py`` is the chip
run).

Width: ``n14`` (N = 2^14, 24 limbs), whose tiles are as aligned as the
paper's N = 2^16 and whose compiles fit the suite's time budget; the
paper-width compiles take 30-50 s each and run in ``chip_smoke.py``.
Batch, with the batch block the client picks for it: encrypt at 16
ciphertexts (the service's largest bucket), in blocks of 8 rows, so the
grid walks two batch blocks and the VMEM scratch filled at limb 0 is
reused across them; decrypt at 2 (the bucket ``chip_smoke.py`` decrypts),
whose compile at 16 would take the file past its time budget.

Memory: the compiled ``tpu_custom_call`` records the VMEM limit the
kernel set (``scoped_memory_configs``) and the VMEM the chip's compiler
allotted it (``used_scoped_memory_configs``); each test reads both.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file.
"""

import json
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.context import get_context
from repro.kernels import client_stream

PROFILE = "n14"
ENC_BATCH = 16
DEC_BATCH = 2
VMEM_SPACE = "1"                 # XLA:TPU's memory space number of VMEM


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back; keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _vmem_bytes(configs) -> int:
    sizes = [int(c["size"]) for c in configs
             if c["memory_space"] == VMEM_SPACE]
    assert len(sizes) == 1, configs
    return sizes[0]


def _check(compiled):
    """One Mosaic kernel, compiled with the kernel's VMEM limit, using a
    nonzero share of VMEM within it."""
    line, = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    config = json.JSONDecoder().raw_decode(
        line[line.index("backend_config=") + len("backend_config="):])[0]
    limit = _vmem_bytes(config["scoped_memory_configs"])
    used = _vmem_bytes(config["used_scoped_memory_configs"])
    assert limit == client_stream.VMEM_LIMIT
    assert 0 < used <= limit


def test_encode_encrypt_megakernel_compiles(one_chip, no_persistent_cache):
    ctx = get_context(PROFILE)
    p = ctx.params
    bb = client_stream.stream_batch_block(
        ENC_BATCH, p.n, client_stream._encrypt_vmem_bytes)
    assert ENC_BATCH // bb > 1  # more than one batch block

    def encrypt(rh, rl, ih, il, b, a, nonce0):
        return client_stream.encode_encrypt_stream(
            (rh, rl, ih, il), b, a, ctx, seed=p.seed, nonce0=nonce0,
            interpret=False, datapath="df32")

    planes = ((ENC_BATCH, p.n_slots), jnp.float32)
    keys = ((p.n_limbs, p.n), jnp.uint32)
    _check(_compile(encrypt, [planes] * 4 + [keys] * 2
                    + [((), jnp.uint32)], one_chip))


def test_decrypt_decode_megakernel_compiles(one_chip, no_persistent_cache):
    ctx = get_context(PROFILE)
    p = ctx.params

    def decrypt(c0, c1, s, scale):
        return client_stream.decrypt_decode_stream(
            c0, c1, s, ctx, scale, interpret=False, datapath="df32")

    cts = ((DEC_BATCH, 2, p.n), jnp.uint32)
    _check(_compile(decrypt, [cts, cts, ((p.n_limbs, p.n), jnp.uint32),
                              ((DEC_BATCH, 1), jnp.float32)], one_chip))
