"""Pipeline-parallel correctness: gpipe over 4 stages == sequential.

Run via tests/test_multidevice.py (8 fake devices).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel import auto_mesh
from repro.parallel.pipeline import gpipe


def stage_fn(params, x):
    """Residual MLP stage (shape-preserving)."""
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return x + h @ params["w2"]


def main() -> None:
    assert jax.device_count() == 8, (
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    mesh = auto_mesh((2, 4), ("data", "pipe"))
    p_stages, d = 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w1": jax.random.normal(keys[0], (p_stages, d, 32)) * 0.3,
        "b1": jax.random.normal(keys[1], (p_stages, 32)) * 0.1,
        "w2": jax.random.normal(keys[2], (p_stages, 32, d)) * 0.3,
    }
    n_micro, mb = 6, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (n_micro, mb, d))

    # sequential reference: apply the 4 stages in order to each microbatch
    ref = x
    for s in range(p_stages):
        ps = jax.tree.map(lambda a: a[s], params)
        ref = jax.vmap(lambda xm: stage_fn(ps, xm))(ref)

    out = jax.jit(
        lambda p, x: gpipe(stage_fn, p, x, mesh=mesh, axis="pipe", n_micro=n_micro)
    )(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    print(f"OK gpipe({p_stages} stages, {n_micro} microbatches) == sequential")

    # bubble sanity: ticks = M + P - 1 (structural property of the schedule)
    assert n_micro + p_stages - 1 == 9
    print("ALL PIPELINE CHECKS PASSED")


if __name__ == "__main__":
    main()
