"""flops.py against counts made by hand at a small size."""

import pytest

import flops as F

DM = {"d": 64, "f": 96, "h": 4, "kv": 2, "hd": 16, "e": 8, "k": 2, "v": 256, "layers": 2,
      "theta": 1e6, "eps": 1e-5}


def test_active_params():
    # per layer: q 64*64, k and v 64*32 each, o 64*64, router 64*8,
    # two experts of three 64x96 matrices, two norms of 64
    per_layer = 4096 + 2 * 2048 + 4096 + 512 + 2 * 3 * 6144 + 128
    assert F.active_params(DM) == 2 * per_layer + 64 * 256 + 64


def test_decode_flops():
    n = F.active_params(DM)
    # one token at context 10: 2 flops per active parameter plus
    # 4 * (4 heads * 16) * 10 per layer
    assert F.decode_flops(DM, [10]) == 2 * n + 2 * 4 * 64 * 10
    # two slots sum
    assert F.decode_flops(DM, [10, 3]) == 4 * n + 2 * 4 * 64 * 13


def test_unknown_device_is_an_error():
    assert F.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        F.peaks("cpu")
