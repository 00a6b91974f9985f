"""Logical-axis sharding rules (t5x/MaxText style).

Model code annotates tensors with *logical* axis names ("batch", "embed",
"heads", ...).  A rules table maps logical names to physical mesh axes
("pod", "data", "model").  ``shard(x, *logical)`` applies a
``with_sharding_constraint`` when a mesh is active, and is a no-op
otherwise, so the same model code runs single-device tests and 512-chip
dry-runs.

Divisibility fallback: if a tensor dimension is not divisible by the
mapped mesh-axis size (e.g. qwen2's 12 heads over a 16-way model axis),
the rule is dropped for that dimension (replication) rather than forcing
GSPMD padding.  This is a deliberate policy — see DESIGN.md §4.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from collections.abc import Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "auto_mesh",
    "axis_rules",
    "current_rules",
    "logical_to_spec",
    "shard",
]


def auto_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis in Auto mode.

    The model code places tensors with ``with_sharding_constraint`` and
    leaves the rest to the compiler's propagation, which needs Auto axes;
    ``jax.make_mesh`` defaults to Explicit axes, under which those
    constraints are refused."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )

# logical name -> physical mesh axis (or tuple of axes), tried in order.
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),  # data parallel over pod x data
    "seq": None,  # sequence usually unsharded in training
    "seq_kv": ("model",),  # decode KV-cache sequence axis (MQA fallback)
    "longseq": ("data", "model"),  # 500k-context decode: shard cache seq hard
    "embed": None,
    "heads": ("model",),  # TP over attention heads
    "kv_heads": ("model",),
    "head_dim": None,
    "mlp": ("model",),  # TP over FFN hidden
    "vocab": ("model",),
    "expert": ("model",),  # EP over experts
    "expert_mlp": None,  # per-expert FFN width stays local under EP
    "conv": None,
    "state": None,
    "inner": ("model",),  # mamba d_inner / rwkv channel TP
    "stage": None,  # layer-stack axis (pipeline parallelism maps it to 'pod')
    "fsdp": None,  # ZeRO-3 weight axis: ('data',) for big-model train/serve
    "fsdp_moe": None,  # like fsdp but for expert weights (disabled under 2D-EP)
    "seq_act": None,  # Megatron-SP residual sharding: ('model',) in big train
}


@dataclasses.dataclass(frozen=True)
class AxisRules:
    rules: dict[str, tuple[str, ...] | str | None]
    mesh: Mesh | None

    def axis_size(self, phys: str | tuple[str, ...]) -> int:
        if self.mesh is None:
            return 1
        if isinstance(phys, str):
            phys = (phys,)
        size = 1
        for p in phys:
            size *= dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[p]
        return size


class _State(threading.local):
    def __init__(self):
        self.stack: list[AxisRules] = []


_STATE = _State()


@contextlib.contextmanager
def axis_rules(mesh: Mesh | None, rules: dict | None = None):
    """Activate a mesh + logical rules for model code in this context."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _STATE.stack.append(AxisRules(rules=merged, mesh=mesh))
    try:
        yield _STATE.stack[-1]
    finally:
        _STATE.stack.pop()


def current_rules() -> AxisRules | None:
    return _STATE.stack[-1] if _STATE.stack else None


def logical_to_spec(
    logical: Sequence[str | None], shape: Sequence[int] | None = None
) -> P:
    """Map logical axis names to a PartitionSpec under the active rules.

    If ``shape`` is given, any mapping whose mesh-axis size does not divide
    the dimension is dropped (replicated) — the divisibility fallback.
    Physical axes already used by an earlier dimension are dropped too
    (PartitionSpec must not repeat mesh axes).
    """
    ar = current_rules()
    if ar is None or ar.mesh is None:
        return P()
    parts: list = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            parts.append(None)
            continue
        phys = ar.rules.get(name)
        if phys is None:
            parts.append(None)
            continue
        phys_t = (phys,) if isinstance(phys, str) else tuple(phys)
        # drop axes not in this mesh (e.g. 'pod' on a single-pod mesh) and
        # axes already consumed by an earlier dimension
        phys_t = tuple(
            p for p in phys_t if p in ar.mesh.axis_names and p not in used
        )
        if not phys_t:
            parts.append(None)
            continue
        if shape is not None and shape[i] % ar.axis_size(phys_t) != 0:
            # divisibility fallback: try a prefix of the axes, else replicate
            while phys_t and shape[i] % ar.axis_size(phys_t) != 0:
                phys_t = phys_t[:-1]
            if not phys_t:
                parts.append(None)
                continue
        used.update(phys_t)
        parts.append(phys_t[0] if len(phys_t) == 1 else phys_t)
    return P(*parts)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint under the active rules (no-op w/o mesh)."""
    ar = current_rules()
    if ar is None or ar.mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} names for rank-{x.ndim} tensor")
    spec = logical_to_spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ar.mesh, spec))

