"""Mixture-of-Experts FFN: ONE pipeline over pluggable dispatch fabrics.

The layer is a single route -> admit -> ``fabric.dispatch`` -> grouped
``moe_gemm`` -> ``fabric.combine`` pipeline; everything interconnect-
specific lives behind the ``repro.parallel.fabric`` registry, selected
by name via ``MoECfg.dispatch``:

* ``dense``   — no-A2A EP (psum combine); the single-device fallback and
  the *virtual* fabric when handed a traced ``ScheduleTable`` row.
* ``a2a``     — token-sharded EP, one monolithic ``all_to_all`` (the
  paper's baseline).
* ``ppermute`` — static ``A2ASchedule`` decomposed into ppermute phases
  (plan baked into the executable; a plan change recompiles).
* ``phase_pipelined`` — traced ``ScheduleTable`` row against a static
  phase envelope: plans swap without recompiling, phase k's grouped GEMM
  overlaps phase k+1's transfer, admission and buffer geometry read the
  same envelope-clamped caps so no admitted token is ever dropped.
* ``ragged_a2a`` — same geometry, ``jax.lax.ragged_all_to_all`` movement
  carrying exactly the live envelope bytes per pair (dense-emulation
  fallback off-TPU).

``dispatch="scheduled"`` is a legacy alias resolved by schedule type
(``A2ASchedule`` -> ppermute, ``ScheduleTable`` -> phase_pipelined).
Unknown names raise listing the registered fabrics; handing a backend
the wrong schedule flavor raises naming the backend that rejected it.

Routing: top-k softmax gating with capacity-factor token dropping
(GShard-style), gates optionally renormalized over the selected k.

A layer that is dropless by shape and has enough rows per expert skips
the padded ``[E, cap, d]`` buckets: its ``t * k`` routed choices
are sorted by expert and the SwiGLU runs as one grouped GEMM over
exactly those rows (``_sorted_body``; the rule is ``_sorted_path``).
Token-slot geometry (packing, admission, phase-slot math) is shared by
every backend — see ``repro.parallel.fabric.geometry``; this module
re-exports the old underscore names for its tests.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.configs.base import ModelConfig
from repro.core.hierarchical import HierarchicalTable
from repro.core.schedule import ScheduleTable
from repro.parallel import current_rules
from repro.parallel.fabric import geometry as _geom
from repro.parallel.fabric.base import (
    FabricContext,
    get_fabric,
    resolve_fabric,
)
from repro.models.layers import COMPUTE_DTYPE, cast, dense_init
from repro.scopes import scope

EP_AXIS = "model"

# ---------------------------------------------------------- legacy aliases
# The packing/admission helpers moved to repro.parallel.fabric.geometry
# (every backend shares them — that is the parity matrix's foundation);
# tests and external callers keep the historic names.
_round8 = _geom.round8
_group = _geom.group_tokens
_pack_slots = _geom.pack_slots
_ungroup = _geom.ungroup
_rank_in_group = _geom.rank_in_group
_admission = _geom.admission_mask
_phase_serving = _geom.phase_serving
_phase_slot_assign = _geom.phase_slot_assign
_routing_counts = _geom.routing_counts
_stats = _geom.stats_tree

# Rows per expert from which the sorted pipeline beats the padded einsum
# on TPU v5e.  By the roofline alone it would be about 240: a bf16 GEMM
# that streams an expert's weights does ``rows`` FLOP per weight byte,
# and the ridge is 197e12 FLOP/s / 819e9 B/s; below it the weight read
# sets the time and the padding rows are free.  Measured at Mixtral
# widths (``benchmarks/expert_gemm_sweep.py``, the whole layer, cap = t,
# the sorted GEMMs by ``gmm`` at ``GMM_TILING``): padded / sorted 4.78 /
# 5.14 ms at 128 rows per expert, 5.41 / 5.73 at 256, 9.66 / 6.98 at
# 512, 19.65 / 9.32 at 1024.  The grouped GEMM costs a little more than
# the einsum's weight read, so the bound is the measured crossing.
ROWS_COMPUTE_BOUND = 512

# Row, contraction and output tiles of the sorted path's grouped GEMM.
# The whole layer at Mixtral widths on v5e, 1024 / 2048 rows per
# expert: ``jax.lax.ragged_dot`` 13.5 / 19.1 ms; ``gmm`` at 256/1024/1024
# 9.3 / 14.9, at 512/1024/1024 12.2 / 16.3, at 128/1024/1024 12.1 /
# 21.4, at 512/512/1024 12.7 / 17.2.
GMM_TILING = (256, 1024, 1024)


def moe_init(key: jax.Array, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, d, e, scale=0.02),
        "w_gate": jax.random.normal(kg, (e, d, f), jnp.float32) * d**-0.5,
        "w_up": jax.random.normal(ku, (e, d, f), jnp.float32) * d**-0.5,
        "w_down": jax.random.normal(kd, (e, f, d), jnp.float32) * f**-0.5,
    }


def _router(params: dict, cfg: ModelConfig, x: jax.Array):
    """x: [T, d] -> (expert ids [T, k], gates [T, k] f32)."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32))
    vals, idx = jax.lax.top_k(logits, m.top_k)
    if m.router_norm_topk:
        gates = jax.nn.softmax(vals, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), gates


def _expert_ffn(
    params: dict,
    x: jax.Array,
    e_slice=None,
    *,
    use_pallas: bool = False,
    row_valid: jax.Array | None = None,
) -> jax.Array:
    """Batched SwiGLU over expert groups.  x: [E, C, d] -> [E, C, d].

    ``use_pallas`` routes through the ``kernels/moe_gemm`` Pallas kernel
    (the TPU hot spot; interpret mode off-TPU) with block sizes from its
    autotune table; shapes the kernel cannot tile fall back here.  The
    einsum form is the portable/XLA path and the kernel's correctness
    oracle.  ``row_valid`` ([E, C] bool) is the grouped launch's
    block-skip metadata (rows holding real admitted tokens) — a compute
    hint, never a value change on valid rows.
    """
    if e_slice is not None:  # already-local expert slices (inside shard_map)
        wg, wu, wd = e_slice
    else:
        wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if use_pallas:
        from repro.kernels.moe_gemm import moe_gemm

        return moe_gemm(x, cast(wg), cast(wu), cast(wd), row_valid=row_valid)
    g = jnp.einsum("ecd,edf->ecf", x, cast(wg))
    u = jnp.einsum("ecd,edf->ecf", x, cast(wu))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, cast(wd))


def _expert_block(ctx: FabricContext, wg, wu, wd, blk, live):
    """Grouped expert compute over one fabric block [G, C, d].

    The pipeline's single GEMM stage: every fabric's dispatched blocks —
    whether one fused buffer or one block per phase — pass through here,
    so the Pallas grouped launch (and its block-skip metadata, when the
    fabric shipped a validity mask) serves all backends.  Under 2D expert
    sharding the tokens gather over 'data' around the local f-shard GEMM
    and the partial outputs reduce-scatter back — bounded per call by one
    block, which is what keeps the phase fabrics' peak memory at one
    envelope slot."""
    m = ctx.cfg.moe
    row_valid = live if m.use_pallas else None
    if not ctx.two_d:
        return _expert_ffn(
            None, blk, e_slice=(wg, wu, wd), use_pallas=m.use_pallas,
            row_valid=row_valid,
        )
    gathered = jax.lax.all_gather(blk, "data", axis=1, tiled=True)
    if row_valid is not None:
        row_valid = jax.lax.all_gather(live, "data", axis=1, tiled=True)
    y_part = _expert_ffn(
        None, gathered, e_slice=(wg, wu, wd), use_pallas=m.use_pallas,
        row_valid=row_valid,
    )
    return jax.lax.psum_scatter(
        y_part, "data", scatter_dimension=1, tiled=True
    )


def _grouped_matmul(x, w, group_sizes) -> jax.Array:
    """Rows of ``x`` [N, k], grouped in order by ``group_sizes`` [G],
    times their group's ``w`` [G, k, n] -> [N, n] in ``x``'s dtype, f32
    accumulation (megablox ``gmm``; interpreted off the TPU).  ``gmm``
    wants whole row tiles: N is padded to one and the padding (in no
    group) cut off again."""
    tm, tk, tn = GMM_TILING
    n = x.shape[0]
    tiling = (tm, min(tk, w.shape[1]), min(tn, w.shape[2]))
    y = gmm(
        jnp.pad(x, ((0, -n % tm), (0, 0))), w, group_sizes, x.dtype, tiling,
        interpret=jax.default_backend() != "tpu",
    )
    return y[:n]


def _expert_ffn_sorted(xs, group_sizes, wg, wu, wd, layer=None) -> jax.Array:
    """The SwiGLU of ``_expert_ffn`` over rows sorted by expert.

    xs: [N, d], rows of expert e contiguous and ``group_sizes[e]`` long;
    one grouped GEMM per projection, so only the N routed rows are
    multiplied.  Same operands, accumulation and f32 SiLU as the padded
    einsum.

    ``layer`` (traced int): the weights are a whole stack's, ``[L, E,
    ...]`` (``StackExperts``), and the rows are layer ``layer``'s.  The
    stack's ``L * E`` experts then form the groups, the other layers'
    groups empty, so the GEMMs read the layer's weights in place."""
    if layer is not None:
        n_layers, e = wg.shape[:2]
        wg, wu, wd = (w.reshape(n_layers * e, *w.shape[2:]) for w in (wg, wu, wd))
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), jnp.int32), group_sizes, (layer * e,)
        )
    g = _grouped_matmul(xs, cast(wg), group_sizes)
    u = _grouped_matmul(xs, cast(wu), group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    return _grouped_matmul(h, cast(wd), group_sizes)


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


class StackExperts(NamedTuple):
    """One MoE position's expert weights for a whole layer stack, ``[L,
    E, ...]`` each, and the layer (traced int) that a call runs.  A
    prefill's layer scan hands these to the sorted pipeline in place of
    its per-layer slice: a grouped GEMM cannot fuse a scan's slice of
    its weights, so the slice would copy them (``hold_stack_experts``)."""

    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array
    layer: jax.Array | None = None

    def sliced(self) -> dict:
        """The layer's own weights, as the padded pipeline takes them."""
        return {k: w[self.layer] for k, w in zip(_EXPERT_WEIGHTS, self[:3])}


# --------------------------------------------------------------- pipeline
def _one_device() -> bool:
    """No mesh shards this layer: its tokens and experts live on one
    device (the dense fabric would shard its padded buckets otherwise)."""
    ar = current_rules()
    return ar is None or ar.mesh is None or ar.mesh.size == 1


def _sorted_path(cfg: ModelConfig, t: int, row) -> bool:
    """Does a ``t``-token layer run the sorted pipeline (else the padded
    one)?  All of: (a) no schedule row — a row's admission masks and
    wire semantics live in the fabric path — and no mesh; (b) not the
    Pallas kernel's path; (c) dropless by shape: ``cap >= t``, and since
    a token's k choices are distinct experts no bucket can overflow;
    (d) enough rows per expert that the padding rows cost more than the
    grouped GEMM's own overhead, ``cap >= ROWS_COMPUTE_BOUND``: below
    that the padded einsum (a decode step's) stays as it is."""
    m = cfg.moe
    cap = _geom.bucket_capacity(t, m)
    return (
        row is None
        and _one_device()
        and not m.use_pallas
        and cap >= t
        and cap >= ROWS_COMPUTE_BOUND
    )


def _as_row(schedule):
    """What the dense fabric makes of ``schedule``: a table or row stays
    (its admission semantics run), anything else (None, a static
    ``A2ASchedule``) is no row."""
    return schedule if isinstance(schedule, (ScheduleTable, HierarchicalTable)) else None


def expert_path(cfg: ModelConfig, t: int, schedule=None) -> str:
    """``"sorted"`` or ``"padded"``: the expert pipeline a ``t``-token
    MoE layer on one device takes when handed ``schedule``."""
    return "sorted" if _sorted_path(cfg, t, _as_row(schedule)) else "padded"


def expert_rows(cfg: ModelConfig, t: int, schedule=None) -> tuple[int, int]:
    """(rows the expert GEMMs compute, routed rows) of one MoE layer of
    ``t`` tokens on one device, by the rule that picks its path: the
    sorted path computes exactly the ``t * k`` routed rows, the padded
    one all ``E * cap`` bucket slots."""
    m = cfg.moe
    routed = t * m.top_k
    if expert_path(cfg, t, schedule) == "sorted":
        return routed, routed
    return m.n_experts * _geom.bucket_capacity(t, m), routed


def hold_stack_experts(params: dict, cfg: ModelConfig, t: int, schedule=None):
    """Split a stack's expert weights off one MoE position's params
    (``[L, ...]`` leaves, as a layer scan takes them) where its
    ``t``-token layers run the sorted pipeline.

    Returns (the params to scan, ``StackExperts`` or None).  Weights not
    yet in the compute dtype stay in the scan: their cast copies each
    layer's weights whatever the path, so holding them saves nothing."""
    ws = tuple(params[k] for k in _EXPERT_WEIGHTS)
    if expert_path(cfg, t, schedule) != "sorted" or any(
        w.dtype != COMPUTE_DTYPE for w in ws
    ):
        return params, None
    rest = {k: v for k, v in params.items() if k not in _EXPERT_WEIGHTS}
    return rest, StackExperts(*ws)


def _sorted_body(
    ctx: FabricContext, x_loc, wr, wg, wu, wd, *, return_stats,
    token_weight=None, layer=None,
):
    """The dropless pipeline of one device (``_sorted_path``): route ->
    sort the ``t * k`` choices by expert -> one grouped GEMM over them ->
    gate-weighted sum of each token's k rows.  Values and stats equal
    ``_pipeline_body``'s on the dense fabric; nothing is dropped.
    ``layer``: see ``_expert_ffn_sorted``."""
    m = ctx.cfg.moe
    t, d = x_loc.shape
    tk = t * m.top_k
    with scope("moe/router"):
        idx, gates = _router({"router": {"w": wr}}, ctx.cfg, x_loc)
    with scope("moe/pack"):
        order, sizes = _geom.sort_by_group(idx.reshape(-1), m.n_experts)
        xs = x_loc[order // m.top_k]  # [t*k, d], grouped by expert
    with scope("moe/expert_ffn"):
        ys = _expert_ffn_sorted(xs, sizes.astype(jnp.int32), wg, wu, wd, layer)
    with scope("moe/combine"):
        # back to (token, choice) order, then each token's k rows summed
        unsort = jnp.zeros((tk,), jnp.int32).at[order].set(
            jnp.arange(tk, dtype=jnp.int32)
        )
        y = ys[unsort].astype(jnp.float32).reshape(t, m.top_k, d)
        y_loc = (y * gates[..., None]).sum(axis=1)  # [t, d] f32
    if not return_stats:
        return y_loc
    counts = _routing_counts(idx, m.n_experts, weight=token_weight)
    return y_loc, _stats(counts[None, :], tk, tk)  # every choice computed


def _pipeline_body(
    fabric, ctx: FabricContext, x_loc, wr, wg, wu, wd, *, return_stats, ep,
    token_weight=None,
):
    """THE MoE pipeline — one body for every fabric.

    route -> pack (fabric geometry + admission) -> fabric.dispatch ->
    grouped expert GEMM per block -> fabric.combine -> weighted scatter
    back to the residual stream.  ``ep`` only selects the stats leading
    dims (EP stats carry a (batch-shard, source-rank) prefix).
    ``token_weight`` ([t] f32, stats-only) scales each token's routing
    count — the serving engine's slot-liveness mask, so vacated decode
    slots never count as demand."""
    m = ctx.cfg.moe
    t = x_loc.shape[0]
    with scope("moe/router"):
        idx, gates = _router({"router": {"w": wr}}, ctx.cfg, x_loc)
    with scope("moe/pack"):
        packed = fabric.pack(ctx, x_loc, idx, gates)
        # wire codec: quantize the wire-crossing slots on both fabric legs
        # (bf16 passthrough is the identity — see fabric.codec)
        packed = fabric.wire_encode(ctx, packed)
    with scope("moe/dispatch"):
        blocks, state = fabric.dispatch(ctx, packed)
    with scope("moe/expert_ffn"):
        ys = [_expert_block(ctx, wg, wu, wd, blk, live) for blk, live in blocks]
    with scope("moe/combine"):
        y_slots = fabric.combine(ctx, packed, state, ys)
        y_slots = fabric.wire_decode(ctx, packed, y_slots)
        y_loc = _ungroup(y_slots, packed.pos, packed.gate, t)  # [t, d] f32
    if not return_stats:
        return y_loc
    counts = _routing_counts(idx, m.n_experts, weight=token_weight)
    counts = counts[None, None, :] if ep else counts[None, :]
    return y_loc, _stats(counts, packed.admitted, packed.live)


def _moe_virtual(
    params, cfg: ModelConfig, x, fabric, schedule, return_stats,
    token_weight=None, experts: StackExperts | None = None,
):
    """Run the pipeline without a mesh (the dense/virtual fabric), or
    the sorted pipeline where ``_sorted_path`` allows it.  ``experts``:
    the expert weights as a stack and a layer index, in place of
    ``params``' own (``hold_stack_experts``)."""
    b, s, d = x.shape
    t = b * s
    ctx = FabricContext(
        cfg=cfg, n=1, e_local=cfg.moe.n_experts, axis=None, me=None,
        schedule=schedule, two_d=False, t_local=t,
    )
    tw = None if token_weight is None else token_weight.reshape(t)
    wr = params["router"]["w"]
    if _sorted_path(cfg, t, schedule):
        if experts is None:
            ws, layer = tuple(params[k] for k in _EXPERT_WEIGHTS), None
        else:
            ws, layer = experts[:3], experts.layer
        res = _sorted_body(
            ctx, x.reshape(t, d), wr, *ws, return_stats=return_stats,
            token_weight=tw, layer=layer,
        )
    else:
        if experts is not None:
            params = {**params, **experts.sliced()}
        res = _pipeline_body(
            fabric, ctx, x.reshape(t, d), wr,
            *(params[k] for k in _EXPERT_WEIGHTS),
            return_stats=return_stats, ep=False, token_weight=tw,
        )
    if not return_stats:
        return res.astype(x.dtype).reshape(b, s, d)
    y, stats = res
    return y.astype(x.dtype).reshape(b, s, d), stats


def _moe_ep_pipeline(
    params, cfg: ModelConfig, x, fabric, schedule, return_stats,
    token_weight=None,
):
    """Run the pipeline token-sharded under shard_map over the EP axis.

    One wrapper for every mesh fabric: a static ``A2ASchedule`` rides the
    closure (baked into the executable — the ppermute backend's
    contract), while a traced ``ScheduleTable`` row enters as replicated
    shard_map *inputs*, so a re-planned table reaches this executable
    without recompiling (its static envelope stays in the pytree aux =
    the jit cache key)."""
    m = cfg.moe
    ar = current_rules()
    mesh = ar.mesh
    n = _ep_size()
    e_local = m.n_experts // n
    b, s, d = x.shape

    rule_b = ar.rules.get("batch") or ()
    rule_b = (rule_b,) if isinstance(rule_b, str) else tuple(rule_b)
    batch_axes = tuple(a for a in rule_b if a in mesh.axis_names)
    from jax.sharding import PartitionSpec as P

    # 2D expert sharding: the expert FFN width lives sharded over 'data'
    # inside the shard_map (no ZeRO-3 regather of expert weights); the
    # received token blocks are all-gathered over 'data' before the GEMM
    # and outputs reduce-scattered back (tokens are far smaller than
    # expert weights at microbatch granularity — EXPERIMENTS.md §Perf C).
    two_d = bool(m.expert_2d) and "data" in mesh.axis_names
    w_f_spec = (
        P(EP_AXIS, None, "data") if two_d else P(EP_AXIS, None, None)
    )
    w_d_spec = (
        P(EP_AXIS, "data", None) if two_d else P(EP_AXIS, None, None)
    )
    is_row = isinstance(schedule, (ScheduleTable, HierarchicalTable))
    if is_row:
        row_leaves, row_def = jax.tree_util.tree_flatten(schedule)
    else:
        row_leaves, row_def = (), None
    rep = P()  # schedule row leaves: replicated everywhere
    has_w = token_weight is not None
    in_specs = (
        P(batch_axes, EP_AXIS, None),  # x sequence-sharded over the EP axis
        P(None, None),  # router w
        w_f_spec,  # w_gate [E, d, f]
        w_f_spec,  # w_up
        w_d_spec,  # w_down [E, f, d]
        *([rep] * len(row_leaves)),
        # stats-only liveness weight [B, S]: sharded like x's token dims
        *([P(batch_axes, EP_AXIS)] if has_w else []),
    )
    out_specs = P(batch_axes, EP_AXIS, None)
    if return_stats:
        # routing counts: each (batch shard, EP rank) contributes a
        # [1, 1, E] row; globally [batch_shards, n, E], summed over the
        # batch axis outside the shard_map.  Dropped counts ride the same
        # layout without the expert dim.
        out_specs = (
            out_specs,
            {
                "routing": P(batch_axes, EP_AXIS, None),
                "dropped": P(batch_axes, EP_AXIS),
            },
        )

    def body(xb, wr, wg, wu, wd, *rest):
        if has_w:
            leaves, wtok = rest[:-1], rest[-1]
        else:
            leaves, wtok = rest, None
        sched = (
            jax.tree_util.tree_unflatten(row_def, leaves)
            if is_row
            else schedule
        )
        me = jax.lax.axis_index(EP_AXIS)
        bl, s_loc, _ = xb.shape
        ctx = FabricContext(
            cfg=cfg, n=n, e_local=e_local, axis=EP_AXIS, me=me,
            schedule=sched, two_d=two_d, t_local=bl * s_loc,
        )
        res = _pipeline_body(
            fabric, ctx, xb.reshape(bl * s_loc, d), wr, wg, wu, wd,
            return_stats=return_stats, ep=True,
            token_weight=None if wtok is None else wtok.reshape(bl * s_loc),
        )
        if not return_stats:
            return res.astype(xb.dtype).reshape(bl, s_loc, d)
        y, stats = res
        return y.astype(xb.dtype).reshape(bl, s_loc, d), stats

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    res = fn(
        x,
        params["router"]["w"],
        params["w_gate"],
        params["w_up"],
        params["w_down"],
        *row_leaves,
        *([token_weight] if has_w else []),
    )
    if not return_stats:
        return res
    y, stats = res
    return y, jax.tree.map(lambda a: a.sum(axis=0), stats)  # [n, E] / [n]


# ------------------------------------------------------- legacy entry point
def _moe_dense(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    row: ScheduleTable | HierarchicalTable | None = None,
    *,
    return_stats: bool = False,
):
    """The dense/virtual fabric, directly (tests + parity oracles)."""
    fabric = get_fabric("dense")
    return _moe_virtual(
        params, cfg, x, fabric, fabric.validate_schedule(row, n=1),
        return_stats,
    )


def _ep_size() -> int:
    ar = current_rules()
    if ar is None or ar.mesh is None:
        return 1
    return ar.axis_size((EP_AXIS,))


def _ep_feasible(cfg: ModelConfig, x: jax.Array) -> bool:
    """Token-sharded EP enters the shard_map sequence-sharded over the EP
    axis (Megatron-SP style: no replication, no bwd all-reduce), so the
    sequence must split evenly; decode steps (S=1) fall back to dense
    (no-A2A) EP."""
    ar = current_rules()
    if ar is None or ar.mesh is None:
        return False
    n = _ep_size()
    rule_b = ar.rules.get("batch") or ()
    rule_b = (rule_b,) if isinstance(rule_b, str) else tuple(rule_b)
    batch_axes = tuple(a for a in rule_b if a in ar.mesh.axis_names)
    bs = ar.axis_size(batch_axes) if batch_axes else 1
    b, s, _ = x.shape
    return b % bs == 0 and s % n == 0


def moe_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    schedule=None,
    return_stats: bool = False,
    token_weight: jax.Array | None = None,
    experts: StackExperts | None = None,
):
    """Apply the MoE FFN through the fabric named by ``cfg.moe.dispatch``.

    ``schedule`` is whatever the resolved fabric consumes: a static
    ``A2ASchedule`` (ppermute; baked into the executable) or a traced
    ``ScheduleTable`` *row* (phase_pipelined / ragged_a2a;
    swap-without-recompile) — the ``scheduled`` alias resolves by
    schedule type.  Off-mesh (or on shapes the EP shard_map cannot
    split) every backend falls back to the ``dense`` virtual fabric,
    which still executes a row's admission semantics.

    With ``return_stats`` the layer additionally returns the fabric
    stats contract: ``routing`` ``[n_src, E]`` realized routing counts
    (f32; one row per EP source rank, a single row off-mesh) — the
    controller loop's observation signal, host-fetched off the critical
    path — and ``dropped`` ``[n_src]``, the count of plan-admitted
    tokens cut at packing (zero by construction on the envelope fabrics
    apart from local capacity-factor overflow).

    ``token_weight`` ([B, S] f32, optional, stats-only) scales each
    token's contribution to ``routing`` — the serving engine passes its
    decode-slot liveness mask so vacated slots in a static-shape batch
    never register as expert demand.  The forward values are untouched.

    ``experts`` (``hold_stack_experts``) carries the expert weights as a
    whole stack's and the layer to run, in place of ``params``' own.
    """
    m = cfg.moe
    mode = m.dispatch
    if (
        isinstance(schedule, (ScheduleTable, HierarchicalTable))
        and not schedule.is_row
    ):
        raise ValueError(
            "moe_apply consumes per-layer rows — pass table.row(l) (the "
            "stack's scan slices rows automatically)"
        )
    if mode != "scheduled":
        get_fabric(mode)  # unknown names fail fast, listing the registry
    n = _ep_size()
    if n == 1 or mode == "dense" or not _ep_feasible(cfg, x):
        fabric = get_fabric("dense")
        return _moe_virtual(
            params, cfg, x, fabric, fabric.validate_schedule(schedule, n=1),
            return_stats, token_weight=token_weight, experts=experts,
        )
    if experts is not None:
        params = {**params, **experts.sliced()}
    fabric = resolve_fabric(mode, schedule)
    sched = fabric.validate_schedule(schedule, n=n)
    if not fabric.uses_mesh:
        return _moe_virtual(
            params, cfg, x, fabric, sched, return_stats,
            token_weight=token_weight,
        )
    return _moe_ep_pipeline(
        params, cfg, x, fabric, sched, return_stats,
        token_weight=token_weight,
    )
