"""Public wrapper for the grouped expert GEMM: block-size selection,
backend-based interpret selection, and a shape-fit fallback.

On CPU the kernel body runs in ``interpret=True`` mode; on TPU
``interpret=False`` is selected automatically from
``jax.default_backend()``.  Block sizes come from a small table keyed on
``(C, d, f)`` — entries the v5e compiler accepts (compiled for a
described v5e chip; none has been timed) — and anything not in the
table uses the divisor/VMEM-budget heuristic.  Table hits pass the same
VMEM check as the heuristic.  Shapes the kernel cannot tile at all (C
or f with no usable block divisor) fall back to the einsum oracle, so
``moe_gemm`` is always safe to call.
"""

from __future__ import annotations

import functools

import jax

import jax.numpy as jnp

from repro.kernels.moe_gemm.kernel import (
    dgrad_vmem_bytes,
    fwd_vmem_bytes,
    moe_gemm_grouped_pallas,
    moe_gemm_grouped_pallas_dgrad,
    moe_gemm_grouped_pallas_wgrad,
    moe_gemm_pallas,
    wgrad_vmem_bytes,
)
from repro.kernels.moe_gemm.ref import moe_gemm_ref

# Block shapes per (C, d, f) that compile for v5e — the MoE launcher's
# common cells (capacity x d_model x d_ff_expert).  Values are
# (block_c, block_f): the widest row block for which the forward and
# the backward (which shares block_c) both fit the VMEM budget, since
# every row block streams the expert's whole weights once.
AUTOTUNE_TABLE: dict[tuple[int, int, int], tuple[int, int]] = {
    # Mixtral-8x7B: d=4096, f=14336
    (256, 4096, 14336): (256, 512),
    (512, 4096, 14336): (512, 256),
    (1024, 4096, 14336): (512, 256),
    (2048, 4096, 14336): (512, 256),
    # DBRX (dbrx_132b): d=6144, f=10752
    (256, 6144, 10752): (256, 256),
    (512, 6144, 10752): (256, 256),
    (1024, 6144, 10752): (256, 256),
    (2048, 6144, 10752): (256, 256),
    # Qwen3-MoE fine-grained experts (qwen3_moe_235b): d=4096, f=1536
    (256, 4096, 1536): (256, 512),
    (512, 4096, 1536): (512, 256),
    (1024, 4096, 1536): (512, 256),
    (2048, 4096, 1536): (512, 256),
    # test/bench shapes
    (128, 64, 128): (128, 128),
    (256, 128, 256): (128, 128),
}

# Backward block_f per (C, d, f).  The backward shares the forward's
# block_c (dgrad and wgrad index the same scalar-prefetched occupancy
# table), but wgrad holds three f32 accumulators (12 * d * block_f
# bytes), so the forward's wide f tiles blow VMEM — the backward runs a
# narrower f tile.
AUTOTUNE_TABLE_BWD: dict[tuple[int, int, int], int] = {
    (256, 4096, 14336): 128,
    (512, 4096, 14336): 128,
    (1024, 4096, 14336): 128,
    (2048, 4096, 14336): 128,
    (256, 6144, 10752): 128,
    (512, 6144, 10752): 128,
    (1024, 6144, 10752): 128,
    (2048, 6144, 10752): 128,
    (256, 4096, 1536): 128,
    (512, 4096, 1536): 128,
    (1024, 4096, 1536): 128,
    (2048, 4096, 1536): 128,
    (128, 64, 128): 128,
    (256, 128, 256): 128,
}

# VMEM working-set budget (bytes) for block selection.  The kernel asks
# for its estimate plus a quarter (``vmem_limit_bytes``): at most 60 MiB,
# well inside the most a launch may request (``VMEM_LIMIT_MAX``).
_VMEM_BUDGET = 48 * 1024 * 1024


def _bwd_vmem_bytes(bc: int, bf: int, d: int, dtype_bytes: int) -> int:
    """The backward's working set: the larger of its two launches."""
    return max(
        dgrad_vmem_bytes(bc, bf, d, dtype_bytes),
        wgrad_vmem_bytes(bc, bf, d, dtype_bytes),
    )


def _divisor_blocks(dim: int, floor: int) -> list[int]:
    """Usable block sizes for ``dim``: divisors, largest first."""
    return [b for b in (1024, 512, 256, 128, 64, 32, 16, 8) if b >= floor and dim % b == 0]


def select_block_sizes(
    c: int,
    d: int,
    f: int,
    *,
    dtype_bytes: int = 2,
    interpret: bool = False,
) -> tuple[int, int] | None:
    """Pick (block_c, block_f) for the grid, or None if untileable.

    A table hit wins if its VMEM working set fits the budget; otherwise
    take the largest divisor blocks (row block first) whose working set
    fits.  Compiled TPU mode requires MXU-friendly blocks (>=128 on both
    tile dims); interpret mode only needs divisors.
    """
    hit = AUTOTUNE_TABLE.get((c, d, f))
    if (
        hit is not None
        and c % hit[0] == 0
        and f % hit[1] == 0
        and fwd_vmem_bytes(*hit, d, dtype_bytes) <= _VMEM_BUDGET
    ):
        return hit
    floor = 8 if interpret else 128
    cands_c = _divisor_blocks(c, floor) or ([c] if (interpret and c > 0) else [])
    cands_f = _divisor_blocks(f, floor) or ([f] if (interpret and f > 0) else [])
    for bc in cands_c:
        for bf in cands_f:
            if fwd_vmem_bytes(bc, bf, d, dtype_bytes) <= _VMEM_BUDGET:
                return bc, bf
    return None


def select_backward_block_f(
    c: int,
    d: int,
    f: int,
    block_c: int,
    *,
    dtype_bytes: int = 2,
    interpret: bool = False,
) -> int | None:
    """Pick the backward kernels' block_f, or None if the backward
    cannot be tiled (callers fall back to the einsum-oracle VJP).

    ``block_c`` is fixed to the forward's choice — dgrad and wgrad index
    the forward's scalar-prefetched occupancy table, which is laid out
    per forward row block.  A table hit wins if it fits the VMEM budget;
    otherwise the largest f divisor whose dgrad and wgrad working sets
    fit."""
    bc = min(block_c, c)
    if c % bc:
        return None
    hit = AUTOTUNE_TABLE_BWD.get((c, d, f))
    if (
        hit is not None
        and f % hit == 0
        and _bwd_vmem_bytes(bc, hit, d, dtype_bytes) <= _VMEM_BUDGET
    ):
        return hit
    floor = 8 if interpret else 128
    cands_f = _divisor_blocks(f, floor) or ([f] if (interpret and f > 0) else [])
    for bf in cands_f:
        if _bwd_vmem_bytes(bc, bf, d, dtype_bytes) <= _VMEM_BUDGET:
            return bf
    return None


def _pallas_bwd(meta_i, x, w_gate, w_up, w_down, g, *, block_c, bwd_block_f, interpret):
    """The real Pallas backward: dgrad + wgrad launches sharing the
    forward's occupancy table (dark row blocks contribute nothing — the
    exact VJP of the occupancy-skipped primal).  Cotangent and grads in
    the primal dtypes; both kernels accumulate in f32."""
    g = g.astype(x.dtype)
    dx = moe_gemm_grouped_pallas_dgrad(
        g, x, meta_i, w_gate, w_up, w_down,
        block_c=block_c, block_f=bwd_block_f, interpret=interpret,
    )
    dwg, dwu, dwd = moe_gemm_grouped_pallas_wgrad(
        g, x, meta_i, w_gate, w_up, w_down,
        block_c=block_c, block_f=bwd_block_f, interpret=interpret,
    )
    return dx, dwg, dwu, dwd


@functools.lru_cache(maxsize=None)
def _differentiable_kernel(
    block_c: int, block_f: int, interpret: bool, bwd_block_f: int | None = None
):
    """Pallas forward + Pallas backward (the kernel body uses a scratch
    accumulator + pl.when, which Pallas AD cannot transpose — the
    backward is its own pair of dgrad/wgrad launches, run at full
    occupancy here since the ungrouped forward computes every row).
    ``bwd_block_f=None`` keeps the einsum-oracle backward — the parity
    reference, and the fallback for shapes the backward cannot tile."""

    @jax.custom_vjp
    def fn(x, w_gate, w_up, w_down):
        return moe_gemm_pallas(
            x, w_gate, w_up, w_down,
            block_c=block_c, block_f=block_f, interpret=interpret,
        )

    def fwd(x, w_gate, w_up, w_down):
        out = moe_gemm_pallas(
            x, w_gate, w_up, w_down,
            block_c=block_c, block_f=block_f, interpret=interpret,
        )
        return out, (x, w_gate, w_up, w_down)

    def bwd_oracle(residuals, g):
        _, vjp = jax.vjp(moe_gemm_ref, *residuals)
        return vjp(g)

    def bwd_pallas(residuals, g):
        x, w_gate, w_up, w_down = residuals
        e, c, _ = x.shape
        bc = min(block_c, c)
        meta_i = jnp.full((e * (c // bc),), bc, jnp.int32)  # all occupied
        return _pallas_bwd(
            meta_i, x, w_gate, w_up, w_down, g,
            block_c=block_c, bwd_block_f=bwd_block_f, interpret=interpret,
        )

    fn.defvjp(fwd, bwd_oracle if bwd_block_f is None else bwd_pallas)
    return fn


@functools.lru_cache(maxsize=None)
def _differentiable_grouped_kernel(
    block_c: int, block_f: int, interpret: bool, bwd_block_f: int | None = None
):
    """Grouped-launch forward (block-skip metadata prologue) + Pallas
    backward reusing the SAME metadata: dgrad keeps the forward grid,
    wgrad transposes it, and both skip the row blocks the forward
    skipped — exact, since a dark block's output is constant zeros.
    ``meta`` rides as a float32 array so the custom_vjp can hand back an
    ordinary zero cotangent (occupancy counts carry no gradient); the
    kernels consume it as int32 scalar-prefetch.  ``bwd_block_f=None``
    keeps the einsum-oracle backward (parity reference + untileable-
    shape fallback; note the oracle differentiates rows the forward
    never computed, so it only matches when their cotangents are
    zero — which gate-weighted combines guarantee)."""

    @jax.custom_vjp
    def fn(meta, x, w_gate, w_up, w_down):
        return moe_gemm_grouped_pallas(
            x, meta.astype(jnp.int32), w_gate, w_up, w_down,
            block_c=block_c, block_f=block_f, interpret=interpret,
        )

    def fwd(meta, x, w_gate, w_up, w_down):
        return fn(meta, x, w_gate, w_up, w_down), (meta, x, w_gate, w_up, w_down)

    def bwd_oracle(residuals, g):
        meta, *primals = residuals
        _, vjp = jax.vjp(moe_gemm_ref, *primals)
        return (jnp.zeros_like(meta), *vjp(g))

    def bwd_pallas(residuals, g):
        meta, x, w_gate, w_up, w_down = residuals
        grads = _pallas_bwd(
            meta.astype(jnp.int32), x, w_gate, w_up, w_down, g,
            block_c=block_c, bwd_block_f=bwd_block_f, interpret=interpret,
        )
        return (jnp.zeros_like(meta), *grads)

    fn.defvjp(fwd, bwd_oracle if bwd_block_f is None else bwd_pallas)
    return fn


def row_block_meta(row_valid, block_c: int):
    """Fold an ``[E, C]`` slot-validity mask into the grouped kernel's
    scalar-prefetch metadata: per-(expert, row-block) occupancy counts,
    ``[E * C/block_c]`` (f32 so the custom_vjp hands back an ordinary
    zero cotangent).

    This is the *phase-block* metadata hook of the pipelined dispatch:
    each phase's envelope-sized block carries its own occupancy table, so
    a phase launch skips the MXU passes of row blocks the schedule left
    dark (envelope padding), exactly like the fused launch skips
    capacity padding.  Validity must be the explicit admitted-slot mask,
    never the gate sign — a zero-gate admitted token still occupies its
    row.
    """
    e, c = row_valid.shape
    return (
        row_valid.reshape(e, c // block_c, block_c)
        .sum(axis=-1)
        .astype(jnp.float32)
        .ravel()
    )


def moe_gemm(
    x, w_gate, w_up, w_down, *,
    block_c=None, block_f=None, interpret=None, row_valid=None,
):
    """Grouped expert SwiGLU: x [E, C, d] -> [E, C, d].

    ``block_c``/``block_f`` override the autotune table; ``interpret``
    defaults to True off-TPU.  Falls back to the einsum oracle when the
    shape cannot be tiled.  Differentiable: forward runs the kernel,
    backward runs the Pallas dgrad/wgrad kernels at the forward's
    ``block_c`` with ``select_backward_block_f``'s f tile (shapes whose
    backward cannot be tiled keep the einsum-oracle VJP).

    ``row_valid`` ([E, C] bool) is the grouped-launch metadata: True rows
    hold real admitted tokens.  It is reduced to per-row-block occupancy
    counts (the kernel's scalar-prefetched group-metadata prologue) so
    fully padded blocks skip their MXU passes.  The hint changes *which*
    rows are computed, never the value of valid rows — invalid rows are
    either zeros (skipped block) or garbage-that-gets-gated (partially
    occupied block), and every caller weights combine output by gates
    that are zero exactly on invalid slots.  The einsum fallback ignores
    the hint (it computes everything).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    e, c, d = x.shape
    f = w_gate.shape[-1]
    if block_c is None or block_f is None:
        picked = select_block_sizes(
            c, d, f, dtype_bytes=x.dtype.itemsize, interpret=interpret
        )
        if picked is None:
            return moe_gemm_ref(x, w_gate, w_up, w_down)
        block_c = block_c or picked[0]
        block_f = block_f or picked[1]
    if c % min(block_c, c) or f % min(block_f, f):
        return moe_gemm_ref(x, w_gate, w_up, w_down)
    bc = int(min(block_c, c))
    bwd_bf = select_backward_block_f(
        c, d, f, bc, dtype_bytes=x.dtype.itemsize, interpret=interpret
    )
    if row_valid is not None:
        meta = row_block_meta(row_valid, bc)
        return _differentiable_grouped_kernel(
            int(block_c), int(block_f), bool(interpret), bwd_bf
        )(meta, x, w_gate, w_up, w_down)
    return _differentiable_kernel(
        int(block_c), int(block_f), bool(interpret), bwd_bf
    )(x, w_gate, w_up, w_down)
