"""Figure 1: MoE expert compute time vs token batch size — the knee.

Two curves:
1. The paper's profiling-based model (250us floor, linear >= 256 tokens).
2. An *actual CPU profile* of an expert-sized matmul via JAX, demonstrating
   the knee phenomenon is real on this host too (fixed dispatch overheads
   dominate small batches), then re-fit with ``fit_knee``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import KNEE, emit
from repro.core import fit_knee

BATCHES = [1, 4, 16, 64, 128, 256, 512, 1024, 2048, 4096]


def _profile_cpu_expert(d_model: int = 512, d_ff: int = 1024) -> tuple[list, list]:
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    w1 = jax.random.normal(k1, (d_model, d_ff), jnp.float32) * 0.02
    w2 = jax.random.normal(k2, (d_ff, d_model), jnp.float32) * 0.02

    @jax.jit
    def expert(x):
        return jnp.maximum(x @ w1, 0.0) @ w2

    times = []
    for b in BATCHES:
        x = jax.random.normal(k3, (b, d_model), jnp.float32)
        expert(x).block_until_ready()  # compile + warm
        reps = 50 if b <= 256 else 10
        t0 = time.perf_counter()
        for _ in range(reps):
            expert(x).block_until_ready()
        times.append((time.perf_counter() - t0) / reps * 1e6)
    return BATCHES, times


def run() -> None:
    # Paper's model
    for b in BATCHES:
        emit(f"fig1.model_knee.b{b}", float(KNEE(b)), "us(model)")
    knee_ratio = KNEE(1) / (KNEE(4096) / 4096)
    emit("fig1.model_floor_vs_pertoken", knee_ratio, "tokens-of-overhead-at-b1")

    # Real CPU profile (phenomenon check + fit)
    batches, times = _profile_cpu_expert()
    for b, t in zip(batches, times):
        emit(f"fig1.cpu_profile.b{b}", t, "us(measured)")
    fitted = fit_knee(np.array(batches), np.array(times))
    emit("fig1.cpu_fitted_floor_us", fitted.floor_us, "fixed-overhead")
    emit("fig1.cpu_fitted_per_token_us", fitted.per_token_us, "slope")
    # Knee exists: small-batch time per token >> large-batch time per token.
    eff_1 = times[0] / 1
    eff_big = times[-1] / batches[-1]
    emit("fig1.cpu_knee_inefficiency_x", eff_1 / eff_big, "b1-vs-b4096-per-token")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
