"""Pallas TPU kernel: grouped expert SwiGLU GEMM — the MoE compute hot
spot fed by the scheduled dispatch (DESIGN.md §2.2).

Grid: (E, C/BC, F/BF) with the expert-FFN width F as the innermost
(arbitrary/accumulation) axis.  Each step:

    g   = x_blk @ w_gate_blk          [BC, BF]   (MXU)
    u   = x_blk @ w_up_blk            [BC, BF]   (MXU)
    h   = silu(g) * u                 (VPU, f32)
    acc += h @ w_down_blk             [BC, d]    (MXU, f32 accumulator)

VMEM working set: Mosaic double-buffers every input and output block
across grid steps, so each counts twice; the f32 accumulators and the
[BC, BF] f32 intermediates count once (``*_vmem_bytes`` below).  Forward
at d=4096, BC=256, BF=512, bf16: (2 + 3*4) MiB inputs x2 + 2 MiB out x2
+ 4 MiB acc + 1.5 MiB = 37.5 MiB — past the 16 MiB default scoped
limit, so the launch asks for more (``vmem_limit_bytes``).
All matmul dims are multiples of 128 (MXU-aligned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1024 * 1024
# Mosaic's default scoped-VMEM limit on v4/v5e; a kernel whose working
# set exceeds it must raise the limit or the compiler refuses it.
_SCOPED_VMEM_DEFAULT = 16 * _MIB
# The most any launch asks for: v5e cores hold 128 MiB of VMEM, and the
# compiler keeps some of it for internal scratch.
VMEM_LIMIT_MAX = 96 * _MIB


def fwd_vmem_bytes(bc: int, bf: int, d: int, dtype_bytes: int) -> int:
    """Forward working set: double-buffered x/w_gate/w_up/w_down input
    blocks and output block, the f32 accumulator, the g/u/h tiles."""
    blocks = bc * d + 3 * d * bf
    return (
        2 * blocks * dtype_bytes
        + 2 * bc * d * dtype_bytes
        + 4 * bc * d
        + 4 * 3 * bc * bf
    )


def dgrad_vmem_bytes(bc: int, bf: int, d: int, dtype_bytes: int) -> int:
    """dgrad working set: double-buffered go/x row blocks, three weight
    tiles and the dx block, the f32 accumulator, seven [BC, BF] f32
    intermediates of the SwiGLU backward."""
    blocks = 2 * bc * d + 3 * d * bf
    return (
        2 * blocks * dtype_bytes
        + 2 * bc * d * dtype_bytes
        + 4 * bc * d
        + 4 * 7 * bc * bf
    )


def wgrad_vmem_bytes(bc: int, bf: int, d: int, dtype_bytes: int) -> int:
    """wgrad working set: double-buffered go/x row blocks, three weight
    tiles and three weight-grad blocks, three f32 accumulators
    ([d, BF] x2 + [BF, d]), seven [BC, BF] f32 intermediates."""
    blocks = 2 * bc * d + 3 * d * bf
    return (
        2 * blocks * dtype_bytes
        + 2 * 3 * d * bf * dtype_bytes
        + 4 * 3 * d * bf
        + 4 * 7 * bc * bf
    )


def _compiler_params(interpret: bool, vmem_bytes: int):
    """Mosaic grid semantics: expert and row-block dims are parallel, the
    accumulation dim (last) is sequential.  This is the double-buffer
    hook for phase-pipelined dispatch: Mosaic pipelines block copies
    across grid steps (fetch block k+1's VMEM tiles while block k is on
    the MXU), so each phase's envelope-sized launch overlaps its own HBM
    traffic — and, marked parallel, independent row blocks of the next
    phase's launch need not serialize behind this one.  A working set
    past the default scoped limit raises ``vmem_limit_bytes`` to it plus
    a quarter for what the estimate leaves out.  Interpret mode (CPU) has
    no Mosaic pipeline and takes no params."""
    if interpret:
        return None
    limit = None
    if vmem_bytes > _SCOPED_VMEM_DEFAULT:
        limit = min(-(-vmem_bytes * 5 // 4 // _MIB) * _MIB, VMEM_LIMIT_MAX)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit,
    )


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, out_ref, acc_ref, *, n_fblocks):
    fb = pl.program_id(2)

    @pl.when(fb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # [BC, d]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(fb == n_fblocks - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _grouped_kernel(
    meta_ref, x_ref, wg_ref, wu_ref, wd_ref, out_ref, acc_ref, *,
    n_fblocks, n_cblocks,
):
    """Grouped-launch body: one kernel serves every expert group of the
    whole (scheduled or dense) MoE buffer.  ``meta_ref`` is the group
    metadata prologue — a scalar-prefetched [E * C/BC] table of per-row-
    block occupancy counts (how many rows of the block hold real routed
    tokens, derived from the schedule table's admitted slots).  Blocks
    with zero occupancy skip all three MXU passes and emit zeros: padded
    capacity stops costing compute, which is exactly the small-batch
    fragmentation the per-phase launches suffered from."""
    eb = pl.program_id(0)
    cb = pl.program_id(1)
    fb = pl.program_id(2)
    occupied = meta_ref[eb * n_cblocks + cb] > 0

    @pl.when(fb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occupied)
    def _compute():
        x = x_ref[0]  # [BC, d]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(
            h, wd_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(fb == n_fblocks - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "interpret")
)
def moe_gemm_grouped_pallas(
    x,
    block_meta,
    w_gate,
    w_up,
    w_down,
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = True,
):
    """One grouped launch over [E, C, d] with per-row-block skip metadata.

    ``block_meta``: [E * (C // block_c)] int32 — occupancy count of each
    (expert, row-block); 0 means the block holds no admitted tokens and
    its compute is skipped (output rows are zeros).  Rows of partially
    occupied blocks are all computed; callers weight outputs by the
    combine gates, which are zero for non-admitted slots, so skipped or
    computed garbage rows never reach the residual stream.
    """
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    assert c % bc == 0 and f % bf == 0, (c, bc, f, bf)
    n_fblocks = f // bf
    n_cblocks = c // bc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, n_cblocks, n_fblocks),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, i, k, m: (e, i, 0)),
            pl.BlockSpec((1, d, bf), lambda e, i, k, m: (e, 0, k)),
            pl.BlockSpec((1, d, bf), lambda e, i, k, m: (e, 0, k)),
            pl.BlockSpec((1, bf, d), lambda e, i, k, m: (e, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e, i, k, m: (e, i, 0)),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _grouped_kernel, n_fblocks=n_fblocks, n_cblocks=n_cblocks
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, fwd_vmem_bytes(bc, bf, d, x.dtype.itemsize)
        ),
    )(block_meta, x, w_gate, w_up, w_down)


# ------------------------------------------------------------- backward
# Real Pallas backward for the grouped launch (the custom_vjp's einsum-
# oracle re-linearization replaced on supported shapes).  Both kernels
# share the forward's scalar-prefetched group-metadata prologue — the
# SAME [E * C/BC] occupancy table, so they must run at the forward's
# block_c — and its occupancy skip.  The skip is *exact* in the
# backward: a dark row block's forward output is constant zeros, so its
# cotangent contributes nothing to dx (the rows are dead) nor to the
# weight gradients (d out/d w is zero there) — more faithful to the
# primal kernel than the oracle backward, which differentiates rows the
# forward never computed.
#
# Math per expert (f32 throughout; silu'(a) = s + a*s*(1-s)):
#     a = x @ wg        u = x @ wu        s = sigmoid(a)
#     dh  = go @ wd^T
#     da  = dh * u * s * (1 + a * (1 - s))
#     du  = dh * s * a
#     dx  = da @ wg^T + du @ wu^T                      (dgrad)
#     dwg = x^T @ da    dwu = x^T @ du    dwd = h^T @ go  (wgrad)
# dgrad keeps the forward grid (E, C/BC, F/BF): F is the contraction,
# accumulated in the same [BC, d] f32 scratch.  wgrad transposes the
# grid to (E, F/BF, C/BC) — C is its contraction — and holds three f32
# accumulators ([d, BF] x2 + [BF, d] = 12*d*BF bytes), which is why the
# backward gets its own, smaller block_f (ops.select_backward_block_f).

_F32 = jnp.float32


def _silu_grads(x, go, wg, wu, wd):
    """Shared dgrad/wgrad prologue on one (row-block, f-block) tile:
    recompute the SwiGLU activations and backprop through them.
    Returns (da [BC, BF], du [BC, BF], h [BC, BF]) in f32."""
    a = jnp.dot(x, wg, preferred_element_type=_F32)
    u = jnp.dot(x, wu, preferred_element_type=_F32)
    s = jax.nn.sigmoid(a)
    dh = jax.lax.dot_general(
        go, wd, (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )
    da = dh * u * s * (1.0 + a * (1.0 - s))
    du = dh * s * a
    return da, du, s * a * u


def _grouped_dgrad_kernel(
    meta_ref, go_ref, x_ref, wg_ref, wu_ref, wd_ref, dx_ref, acc_ref, *,
    n_fblocks, n_cblocks,
):
    eb = pl.program_id(0)
    cb = pl.program_id(1)
    fb = pl.program_id(2)
    occupied = meta_ref[eb * n_cblocks + cb] > 0

    @pl.when(fb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occupied)
    def _compute():
        x = x_ref[0]
        da, du, _ = _silu_grads(x, go_ref[0], wg_ref[0], wu_ref[0], wd_ref[0])
        # dx += da @ wg^T + du @ wu^T (contract the F tile)
        acc_ref[...] += jax.lax.dot_general(
            da, wg_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32,
        )
        acc_ref[...] += jax.lax.dot_general(
            du, wu_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32,
        )

    @pl.when(fb == n_fblocks - 1)
    def _flush():
        dx_ref[0] = acc_ref[...].astype(dx_ref.dtype)


def _grouped_wgrad_kernel(
    meta_ref, go_ref, x_ref, wg_ref, wu_ref, wd_ref,
    dwg_ref, dwu_ref, dwd_ref, awg_ref, awu_ref, awd_ref, *,
    n_fblocks, n_cblocks,
):
    eb = pl.program_id(0)
    cb = pl.program_id(2)  # C is the innermost (accumulation) axis here
    occupied = meta_ref[eb * n_cblocks + cb] > 0

    @pl.when(cb == 0)
    def _init():
        awg_ref[...] = jnp.zeros_like(awg_ref)
        awu_ref[...] = jnp.zeros_like(awu_ref)
        awd_ref[...] = jnp.zeros_like(awd_ref)

    @pl.when(occupied)
    def _compute():
        x = x_ref[0]
        go = go_ref[0]
        da, du, h = _silu_grads(x, go, wg_ref[0], wu_ref[0], wd_ref[0])
        # contract the row block: dwg/dwu [d, BF], dwd [BF, d]
        awg_ref[...] += jax.lax.dot_general(
            x, da, (((0,), (0,)), ((), ())), preferred_element_type=_F32
        )
        awu_ref[...] += jax.lax.dot_general(
            x, du, (((0,), (0,)), ((), ())), preferred_element_type=_F32
        )
        awd_ref[...] += jax.lax.dot_general(
            h.astype(x.dtype), go, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32,
        )

    @pl.when(cb == n_cblocks - 1)
    def _flush():
        dwg_ref[0] = awg_ref[...].astype(dwg_ref.dtype)
        dwu_ref[0] = awu_ref[...].astype(dwu_ref.dtype)
        dwd_ref[0] = awd_ref[...].astype(dwd_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "interpret")
)
def moe_gemm_grouped_pallas_dgrad(
    go,
    x,
    block_meta,
    w_gate,
    w_up,
    w_down,
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = True,
):
    """dx for the grouped launch: grid (E, C/BC, F/BF), occupancy-
    skipped row blocks (dark blocks' dx is exactly zero — their forward
    output was constant).  ``block_c`` must be the forward's (the meta
    table is indexed per forward row block); ``block_f`` is the
    backward's own tile (see ``ops.select_backward_block_f``)."""
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    assert c % bc == 0 and f % bf == 0, (c, bc, f, bf)
    n_fblocks = f // bf
    n_cblocks = c // bc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, n_cblocks, n_fblocks),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, i, k, m: (e, i, 0)),  # go
            pl.BlockSpec((1, bc, d), lambda e, i, k, m: (e, i, 0)),  # x
            pl.BlockSpec((1, d, bf), lambda e, i, k, m: (e, 0, k)),  # wg
            pl.BlockSpec((1, d, bf), lambda e, i, k, m: (e, 0, k)),  # wu
            pl.BlockSpec((1, bf, d), lambda e, i, k, m: (e, k, 0)),  # wd
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e, i, k, m: (e, i, 0)),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _grouped_dgrad_kernel, n_fblocks=n_fblocks, n_cblocks=n_cblocks
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, dgrad_vmem_bytes(bc, bf, d, x.dtype.itemsize)
        ),
    )(block_meta, go, x, w_gate, w_up, w_down)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "interpret")
)
def moe_gemm_grouped_pallas_wgrad(
    go,
    x,
    block_meta,
    w_gate,
    w_up,
    w_down,
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = True,
):
    """(dwg, dwu, dwd) for the grouped launch: grid (E, F/BF, C/BC) —
    the row dim is the contraction here, accumulated across three f32
    VMEM scratch tiles and flushed on the last row block.  Shares the
    forward's meta table (same ``block_c``); dark row blocks contribute
    nothing to any weight gradient, exactly like the primal."""
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    assert c % bc == 0 and f % bf == 0, (c, bc, f, bf)
    n_fblocks = f // bf
    n_cblocks = c // bc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, n_fblocks, n_cblocks),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, j, i, m: (e, i, 0)),  # go
            pl.BlockSpec((1, bc, d), lambda e, j, i, m: (e, i, 0)),  # x
            pl.BlockSpec((1, d, bf), lambda e, j, i, m: (e, 0, j)),  # wg
            pl.BlockSpec((1, d, bf), lambda e, j, i, m: (e, 0, j)),  # wu
            pl.BlockSpec((1, bf, d), lambda e, j, i, m: (e, j, 0)),  # wd
        ],
        out_specs=[
            pl.BlockSpec((1, d, bf), lambda e, j, i, m: (e, 0, j)),  # dwg
            pl.BlockSpec((1, d, bf), lambda e, j, i, m: (e, 0, j)),  # dwu
            pl.BlockSpec((1, bf, d), lambda e, j, i, m: (e, j, 0)),  # dwd
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bf), jnp.float32),
            pltpu.VMEM((d, bf), jnp.float32),
            pltpu.VMEM((bf, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _grouped_wgrad_kernel, n_fblocks=n_fblocks, n_cblocks=n_cblocks
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((e, d, f), w_gate.dtype),
            jax.ShapeDtypeStruct((e, d, f), w_up.dtype),
            jax.ShapeDtypeStruct((e, f, d), w_down.dtype),
        ),
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, wgrad_vmem_bytes(bc, bf, d, x.dtype.itemsize)
        ),
    )(block_meta, go, x, w_gate, w_up, w_down)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "interpret")
)
def moe_gemm_pallas(
    x,
    w_gate,
    w_up,
    w_down,
    *,
    block_c: int = 128,
    block_f: int = 128,
    interpret: bool = True,
):
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    assert c % bc == 0 and f % bf == 0, (c, bc, f, bf)
    n_fblocks = f // bf
    grid = (e, c // bc, n_fblocks)
    return pl.pallas_call(
        functools.partial(_kernel, n_fblocks=n_fblocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, i, k: (e, i, 0)),
            pl.BlockSpec((1, d, bf), lambda e, i, k: (e, 0, k)),
            pl.BlockSpec((1, d, bf), lambda e, i, k: (e, 0, k)),
            pl.BlockSpec((1, bf, d), lambda e, i, k: (e, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e, i, k: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, fwd_vmem_bytes(bc, bf, d, x.dtype.itemsize)
        ),
    )(x, w_gate, w_up, w_down)
