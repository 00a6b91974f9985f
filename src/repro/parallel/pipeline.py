"""GPipe-style pipeline parallelism over a mesh axis.

Thematically this is the same primitive as the scheduled A2A: a pipeline
is a *static circuit schedule* where every tick holds the same matching
(rank p -> p+1) — the shift 1-factorization applied to activations
instead of expert tokens.

``gpipe(stage_fn, stage_params, x, mesh, axis, n_micro)`` runs P stages
(one per rank along ``axis``) over M microbatches with the classic
fill-drain schedule: T = M + P - 1 ticks, bubble fraction (P-1)/(M+P-1).
Stages must be shape-preserving (residual-block semantics — exactly our
transformer periods).

The default production mesh keeps 'pod' as a DP axis (DESIGN.md §5b);
this module makes PP available for deeper-than-memory models and is
correctness-tested against sequential execution in multidev_pipeline.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe"]


def gpipe(stage_fn, stage_params, x, *, mesh, axis: str, n_micro: int):
    """Pipeline-parallel application of P stacked stages.

    stage_fn: (params_for_one_stage, x_mb) -> y_mb (same shape).
    stage_params: pytree with leading dim P (one slice per stage).
    x: [M, mb, ...] microbatched input (M == n_micro).
    Returns [M, mb, ...] outputs of the final stage.
    """
    p_stages = mesh.shape[axis]
    assert x.shape[0] == n_micro, (x.shape, n_micro)
    ticks = n_micro + p_stages - 1

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),  # microbatches replicated along the pipe axis
    )
    out_specs = P()

    def body(params_block, xs):
        me = jax.lax.axis_index(axis)
        my_params = jax.tree.map(lambda a: a[0], params_block)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # rank 0 injects microbatch t (while t < M)
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False
            )
            is_first = me == 0
            buf = jnp.where(jnp.logical_and(is_first, t < n_micro), inject, buf)
            y = stage_fn(my_params, buf)
            # last rank emits microbatch t - (P-1) when valid
            m_idx = t - (p_stages - 1)
            valid = jnp.logical_and(me == p_stages - 1, m_idx >= 0)
            upd = jnp.where(valid, y, jax.lax.dynamic_index_in_dim(
                outs, jnp.maximum(m_idx, 0), axis=0, keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, upd, jnp.maximum(m_idx, 0), axis=0
            )
            # shift activations down the pipe (rank p -> p+1)
            shifted = jax.lax.ppermute(
                y, axis, perm=[(i, i + 1) for i in range(p_stages - 1)]
            )
            return shifted, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # broadcast the last rank's outputs to everyone (replicated result)
        mask = (me == p_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return fn(stage_params, x)
