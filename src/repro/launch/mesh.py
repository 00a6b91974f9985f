"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never initializes jax device state — the dry-run sets XLA_FLAGS
before first jax use.
"""

from __future__ import annotations

import jax

from repro.parallel import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (data, model), or 2 pods with a leading 'pod' axis.

    The 'model' axis carries TP + EP (+ the scheduled A2A); 'data' carries
    DP + FSDP; 'pod' carries cross-pod DP (gradient all-reduce over DCI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]  # single-pod mesh on a 512-device backend
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return auto_mesh(shape, axes, devices=devices)


def make_debug_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device CPU tests."""
    return auto_mesh(shape, axes)
