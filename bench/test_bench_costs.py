"""Kernel byte counts from shapes, and the table of peaks."""

import pytest

import costs


def test_encrypt_bytes_match_the_aot_compile_at_paper_bucket_16():
    # the AOT compile of the encode+encrypt core at `paper` (N = 2^16,
    # 24 limbs), batch 16, for v5e: 21.0 MB of arguments (four f32 slot
    # planes and the two public-key polynomials) and 201.3 MB of output
    planes_and_key = 4 * 16 * 32768 * 4 + 2 * 24 * 65536 * 4
    outputs = 2 * 16 * 24 * 65536 * 4
    assert planes_and_key == 20_971_520
    assert outputs == 201_326_592
    assert costs.encrypt_bytes(16, 65536, 24) == planes_and_key + outputs


def test_decrypt_bytes_from_shapes():
    n, batch = 32768, 16
    got = costs.decrypt_bytes(batch, n)
    assert got == (2 * batch * 2 * n + 2 * n + batch + 4 * batch * n // 2) * 4
    assert costs.call_bytes("dec", batch, n, 24) == got


def test_call_bytes_refuses_unknown_kind():
    with pytest.raises(ValueError):
        costs.call_bytes("rotate", 1, 1024, 2)


def test_kernel_names_map_to_kinds():
    assert set(costs.KERNELS.values()) == {"enc", "dec"}


def test_peaks_of_v5e_are_sourced():
    p = costs.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
