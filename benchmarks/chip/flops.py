"""Operations of the model step, counted from shapes (``dims`` as
``reference.mixtral.dims`` gives them), and the chip's peaks.

Model FLOPs per token are 2 times the active non-embedding parameters
(forward), the LM head included, plus attention's 4 * d_attn * context
per layer.  Capacity padding is not counted.
"""

from __future__ import annotations

import json
import os

def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def active_params(dm: dict) -> int:
    """Active non-embedding parameters per token, LM head included."""
    d, f, q, kv = dm["d"], dm["f"], dm["h"] * dm["hd"], dm["kv"] * dm["hd"]
    per_layer = d * q + 2 * d * kv + q * d + d * dm["e"] + dm["k"] * 3 * d * f + 2 * d
    return dm["layers"] * per_layer + d * dm["v"] + d


def attention_flops(dm: dict, context: int) -> int:
    """Forward attention FLOPs of one token that attends to ``context``
    positions, over all layers."""
    return dm["layers"] * 4 * dm["h"] * dm["hd"] * context


def decode_flops(dm: dict, contexts) -> int:
    """One decode step: each live slot's token at its context length."""
    return sum(2 * active_params(dm) + attention_flops(dm, c) for c in contexts)
