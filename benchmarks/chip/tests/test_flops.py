"""flops.py against counts made by hand at a small size, and against the
counts it gave both configurations before ``dims`` carried ``e_held``."""

import importlib
import json
import os

import pytest

import flops as F

HERE = os.path.dirname(os.path.abspath(__file__))
DM = {"d": 64, "f": 96, "h": 4, "kv": 2, "hd": 16, "e": 8, "e_held": 8, "k": 2, "v": 256,
      "layers": 2, "theta": 1e6, "eps": 1e-5}
# one token's expert parameters with every expert held: 2 layers x 2
# choices x three 64x96 matrices
EXPERTS = 2 * 2 * 3 * 64 * 96

# decode_flops of each configuration's dims at these contexts, as the
# harness counted them before the held share (every expert held)
CONTEXTS = ([1], [1020], [2304] * 32, list(range(100, 3300, 100)))
DECODE = {
    "mixtral-8x7b.serve": (1839407104, 1872797696, 61275897856, 60590129152),
    "mixtral-8x7b.train-ep4": (1050779648, 1067474944, 34832384000, 34489499648),
}


def configuration_dims(name: str) -> dict:
    """``dims`` of a configuration file, by the reference it names."""
    with open(os.path.join(os.path.dirname(HERE), "configs", name + ".json")) as f:
        conf = json.load(f)
    return importlib.import_module("reference." + conf["reference"]).dims(conf)


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


@pytest.mark.parametrize("name", sorted(DECODE))
def test_decode_flops_at_the_configurations(name):
    dm = configuration_dims(name)
    assert dm["e_held"] == dm["e"]
    assert tuple(F.decode_flops(dm, c) for c in CONTEXTS) == DECODE[name]


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_held_share_scales_the_expert_term_alone(held):
    # the router still scores all 8 experts; of a token's 2 choices,
    # 2 * held / 8 land on this chip
    dm = dict(DM, e_held=held)
    assert F.active_params(DM) - F.active_params(dm) == EXPERTS * (8 - held) // 8
    for ctx in ([10], [10, 3], [64] * 5):
        assert F.decode_flops(DM, ctx) - F.decode_flops(dm, ctx) == (
            2 * len(ctx) * EXPERTS * (8 - held) // 8)


def test_held_share_counts_expected_choices():
    # 32 of 128 experts held, top-8: each token's expected 2 choices on
    # this chip count as 2 whole experts, with the router at 128 outputs
    qwen = {"d": 4096, "f": 1536, "h": 64, "kv": 4, "hd": 128, "e": 128, "e_held": 32, "k": 8,
            "v": 151936, "layers": 6}
    two = dict(qwen, k=2, e_held=128)
    assert F.active_params(qwen) == F.active_params(two)
    assert F.decode_flops(qwen, [2304] * 32) == F.decode_flops(two, [2304] * 32)


def test_unknown_device_is_an_error():
    assert F.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        F.peaks("cpu")
