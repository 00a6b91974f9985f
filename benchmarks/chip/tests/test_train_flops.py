"""flops.train_flops against a count made by hand at a small size, and
against the counts it gave both configurations before ``dims`` carried
``e_held``."""

import pytest

import flops as F
from test_flops import configuration_dims

DM = {"d": 64, "f": 96, "h": 4, "kv": 2, "hd": 16, "e": 8, "e_held": 8, "k": 2, "v": 256,
      "layers": 2, "theta": 1e6, "eps": 1e-5}

# train_flops of each configuration's dims at (batch, sequence), as the
# harness counted them before the held share (every expert held)
SHAPES = ((1, 2048), (2, 8192), (4, 4096))
TRAIN = {
    "mixtral-8x7b.serve": (11507375013888, 97006802436096, 93708267552768),
    "mixtral-8x7b.train-ep4": (6559019040768, 54946053488640, 53296786046976),
}


def test_train_flops():
    # per layer: q 64x64, k and v 64x32 each, o 64x64, router 64x8, two
    # experts of three 64x96 matrices, two norms of 64; then head and norm
    n = F.active_params(DM)
    assert n == 2 * (4096 + 4096 + 4096 + 512 + 36864 + 128) + 16384 + 64
    # one sequence of 4 tokens: forward 2 flops per active parameter per
    # token, and attention 4 * (4 heads * 16) per layer per position of
    # context, contexts 1 + 2 + 3 + 4 = 10; backward twice the forward
    forward = 2 * n * 4 + 2 * 4 * 64 * 10
    assert F.train_flops(DM, 1, 4) == 3 * forward
    assert F.train_flops(DM, 3, 4) == 9 * forward


def test_train_flops_at_the_cell():
    dm = {"d": 4096, "f": 14336, "h": 32, "kv": 8, "hd": 128, "e": 8, "e_held": 8, "k": 2,
          "v": 32000, "layers": 1, "theta": 1e6, "eps": 1e-5}
    # 525.4M active parameters with the head: 6 * 525.4M * 16384 tokens,
    # plus attention: about 54.95 TFLOP per step of 2 x 8192 tokens
    assert abs(F.train_flops(dm, 2, 8192) / 54.95e12 - 1) < 0.002


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_train_flops_at_the_configurations(name):
    dm = configuration_dims(name)
    assert tuple(F.train_flops(dm, b, s) for b, s in SHAPES) == TRAIN[name]


@pytest.mark.parametrize("held", [2, 4])
def test_train_held_share_scales_the_expert_term_alone(held):
    # 2 layers x 2 choices x three 64x96 matrices per token, of which
    # held / 8 are counted; forward and backward, 2 flops each, 3 passes
    dm = dict(DM, e_held=held)
    experts = 2 * 2 * 3 * 64 * 96
    for b, s in ((1, 4), (3, 16)):
        assert F.train_flops(DM, b, s) - F.train_flops(dm, b, s) == (
            3 * 2 * b * s * experts * (8 - held) // 8)
