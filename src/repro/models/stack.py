"""Layer stack: scan-over-periods so HLO size is O(period), not O(depth).

A *period* is the repeating layer pattern (1 for uniform models; 8 for
Jamba's 1-attention-per-7-mamba interleave with alternating MoE).  Params
for period-position ``j`` are stacked over ``n_periods`` and consumed by
``lax.scan``; caches/states are stacked the same way and scanned as
xs/ys.  Remat ('block') checkpoints each period.

Per-layer MoE schedules ride the same scan: a ``ScheduleTable`` (fixed
shape ``[L, K_max, n]`` pytree) reshapes to per-period rows and scans as
xs alongside the params, so distinct per-layer plans cost O(period) HLO
and swap without recompiling — on the train, prefill, AND decode paths.
(The old static-``A2ASchedule``-per-layer form forced the stack to unroll
and a compile per swap; it is gone.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hierarchical import HierarchicalTable
from repro.core.schedule import ScheduleTable
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import rwkv as rk
from repro.models.layers import (
    gelu_mlp_apply,
    gelu_mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    swiglu_apply,
    swiglu_init,
)
from repro.models.moe import hold_stack_experts, moe_apply, moe_init
from repro.scopes import scope

# each block's mixer sublayer (its norm and residual too) runs under its
# kind's scope, and its FFN sublayer under "moe" or "ffn"; the layer scan
# runs under "stack", so what the scan itself does is under no layer
MIXER_SCOPES = {"attn": "attention", "mamba": "mamba", "rwkv6": "rwkv"}


def _ffn_scope(cfg: ModelConfig, j: int) -> str:
    return "moe" if cfg.ffn_kind(j) == "moe" else "ffn"


# ----------------------------------------------------------- single block
def block_init(key: jax.Array, cfg: ModelConfig, j: int) -> dict:
    kind = cfg.layer_kind(j)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": rmsnorm_init(cfg.d_model)}
    if kind == "attn":
        p["mixer"] = attn.attn_init(k1, cfg)
    elif kind == "mamba":
        p["mixer"] = mb.mamba_init(k1, cfg)
    elif kind == "rwkv6":
        p["mixer"] = rk.rwkv_init(k1, cfg)
        p["ln2"] = rmsnorm_init(cfg.d_model)
        return p  # rwkv channel-mix lives inside mixer params
    else:
        raise ValueError(kind)
    p["ln2"] = rmsnorm_init(cfg.d_model)
    if cfg.ffn_kind(j) == "moe":
        p["ffn"] = moe_init(k2, cfg)
    elif cfg.ffn_gelu:
        p["ffn"] = gelu_mlp_init(k3, cfg.d_model, cfg.d_ff)
    else:
        p["ffn"] = swiglu_init(k3, cfg.d_model, cfg.d_ff)
    return p


def moe_positions(cfg: ModelConfig) -> list[int]:
    """Period positions carrying an MoE FFN (the param/stat layout is
    periodic, so ``ffn_kind(j)`` for j in [0, period) covers all layers)."""
    return [j for j in range(cfg.period) if cfg.ffn_kind(j) == "moe"]


def _ffn_apply(
    p, cfg, j, x, schedule, collect_stats=False, token_weight=None, experts=None,
):
    """Returns (y, routing-stats-or-None).  ``token_weight`` ([B, S] f32)
    is the stats-only liveness weight, and ``experts`` the expert weights
    held out of a layer scan, both forwarded to ``moe_apply``."""
    if cfg.ffn_kind(j) == "moe":
        out = moe_apply(
            p["ffn"], cfg, x, schedule=schedule, return_stats=collect_stats,
            token_weight=token_weight, experts=experts,
        )
        return out if collect_stats else (out, None)
    if cfg.ffn_gelu:
        return gelu_mlp_apply(p["ffn"], x), None
    return swiglu_apply(p["ffn"], x), None


def block_train(p, cfg: ModelConfig, j: int, x, schedule, *, collect_stats=False):
    """One layer in Megatron-SP form: the residual stream x stays
    sequence-sharded ('seq_act' rule); mixers that need cross-token access
    gather a bf16 copy and their output is constrained back to
    sequence-sharded so the out-proj psum lowers to a reduce-scatter.
    MoE FFNs consume the sequence-sharded stream directly (the EP
    shard_map is sequence-sharded over the same axis — zero extra comm).
    All constraints are no-ops without a mesh.

    Returns (x, stats) — stats is the MoE layer's realized routing counts
    when ``collect_stats`` (None for dense FFNs / rwkv channel-mix)."""
    from repro.parallel import shard

    def seq_sharded(t):
        return shard(t, "batch", "seq_act", "embed")

    kind = cfg.layer_kind(j)
    with scope(MIXER_SCOPES[kind]):
        h = rmsnorm_apply(p["ln1"], x, eps=cfg.norm_eps)
        if kind == "attn":
            x = seq_sharded(x + attn.attn_train(p["mixer"], cfg, h))
        elif kind == "mamba":
            y, _ = mb.mamba_seq(p["mixer"], cfg, h)
            x = seq_sharded(x + y)
        else:  # rwkv6
            y, _ = rk.rwkv_time_mix(p["mixer"], cfg, h)
            x = seq_sharded(x + y)
            h2 = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
            y2, _ = rk.rwkv_channel_mix(p["mixer"], h2)
            return seq_sharded(x + y2), None
    with scope(_ffn_scope(cfg, j)):
        h = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
        y, stats = _ffn_apply(p, cfg, j, h, schedule, collect_stats)
        return seq_sharded(x + y), stats


def block_cache(cfg: ModelConfig, j: int, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Zeroed cache/state for one block (no leading period dim)."""
    kind = cfg.layer_kind(j)
    if kind == "attn":
        return attn.init_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return mb.mamba_init_state(cfg, batch, dtype)
    return rk.rwkv_init_state(cfg, batch, dtype)


def block_prefill(p, cfg, j, x, cache, schedule, experts=None):
    kind = cfg.layer_kind(j)
    with scope(MIXER_SCOPES[kind]):
        h = rmsnorm_apply(p["ln1"], x, eps=cfg.norm_eps)
        if kind == "attn":
            y, cache = attn.attn_prefill(p["mixer"], cfg, h, cache)
            x = x + y
        elif kind == "mamba":
            y, (hs, tail) = mb.mamba_seq(p["mixer"], cfg, h)
            cache = (hs, tail.astype(cache[1].dtype))
            x = x + y
        else:  # rwkv6
            y, (x_tm, s) = rk.rwkv_time_mix(p["mixer"], cfg, h)
            x = x + y
            h2 = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
            y2, x_cm = rk.rwkv_channel_mix(p["mixer"], h2)
            x = x + y2
            return x, (x_tm.astype(cache[0].dtype), s, x_cm.astype(cache[2].dtype))
    with scope(_ffn_scope(cfg, j)):
        h = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
        x = x + _ffn_apply(p, cfg, j, h, schedule, experts=experts)[0]
        return x, cache


def block_decode(
    p, cfg, j, x, cache, step, schedule, *,
    collect_stats=False, token_weight=None,
):
    """One decode layer.  Returns ``(x, cache)`` by default; with
    ``collect_stats`` returns ``(x, cache, stats)`` where stats is the
    MoE layer's realized routing counts (None for dense FFNs / rwkv
    channel-mix) — the serving engine's observation signal, weighted by
    the slot-liveness mask ``token_weight``."""
    kind = cfg.layer_kind(j)
    with scope(MIXER_SCOPES[kind]):
        h = rmsnorm_apply(p["ln1"], x, eps=cfg.norm_eps)
        if kind == "attn":
            y, cache = attn.attn_decode(p["mixer"], cfg, h, cache, step)
            x = x + y
        elif kind == "mamba":
            y, cache = mb.mamba_step(p["mixer"], cfg, h, cache)
            x = x + y
        else:  # rwkv6
            x_tm, s, x_cm = cache
            y, (x_tm2, s2) = rk.rwkv_time_mix(
                p["mixer"], cfg, h, state=(x_tm.astype(h.dtype), s)
            )
            x = x + y
            h2 = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
            y2, x_cm2 = rk.rwkv_channel_mix(
                p["mixer"], h2, state=x_cm.astype(h2.dtype)
            )
            x = x + y2
            cache = (x_tm2.astype(x_tm.dtype), s2, x_cm2.astype(x_cm.dtype))
            return (x, cache, None) if collect_stats else (x, cache)
    with scope(_ffn_scope(cfg, j)):
        h = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps)
        y, stats = _ffn_apply(
            p, cfg, j, h, schedule, collect_stats, token_weight
        )
        x = x + y
        return (x, cache, stats) if collect_stats else (x, cache)


# ------------------------------------------------------------------ stack
def stack_init(key: jax.Array, cfg: ModelConfig) -> dict:
    period, n_p = cfg.period, cfg.n_periods
    out = {}
    for j in range(period):
        keys = jax.random.split(jax.random.fold_in(key, j), n_p)
        out[f"pos{j}"] = jax.vmap(lambda k: block_init(k, cfg, j))(keys)
    return out


def stack_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Caches stacked over periods: leaf shapes [n_periods, ...]."""
    out = {}
    for j in range(cfg.period):
        one = block_cache(cfg, j, batch, max_len, dtype)
        out[f"pos{j}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods, *a.shape)), one
        )
    return out


def _schedule_rows(schedule, cfg: ModelConfig):
    """Split ``schedule`` into (shared, rows-for-scan).

    ``rows`` is the ``ScheduleTable`` reshaped to ``[n_periods, mpp, ...]``
    leaves (mpp = MoE positions per period) so ``lax.scan`` slices one
    period's rows per step; ``shared`` is the legacy single
    ``A2ASchedule``/None broadcast to every MoE layer.  Sequences of
    static schedules are gone — they forced the stack to unroll (HLO
    O(depth)) and a recompile per swap.
    """
    if isinstance(schedule, (list, tuple)):
        raise TypeError(
            "per-layer schedules are a traced ScheduleTable now "
            "(core.ScheduleTable.from_schedules); static per-layer "
            "A2ASchedule sequences forced the stack to unroll"
        )
    if not isinstance(schedule, (ScheduleTable, HierarchicalTable)):
        return schedule, None
    positions = moe_positions(cfg)
    expected = cfg.n_periods * len(positions)
    if schedule.num_layers != expected:
        raise ValueError(
            f"table has {schedule.num_layers} rows for {expected} MoE layers"
        )
    rows = jax.tree.map(
        lambda a: a.reshape(cfg.n_periods, len(positions), *a.shape[1:]),
        schedule,
    )
    return None, rows


def _position_schedule(prow, shared, positions, j):
    """Schedule for period-position ``j``: its table row (leaves indexed
    inside the scanned period) or the shared static schedule."""
    if prow is not None and j in positions:
        i = positions.index(j)
        return jax.tree.map(lambda a: a[i], prow)
    return shared


def stack_train(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    schedule,
    *,
    collect_stats: bool = False,
    unroll: bool = False,
):
    """Run the training stack.

    ``schedule`` is None, one static ``A2ASchedule`` shared by every MoE
    layer, or a ``ScheduleTable`` with one row per MoE layer (layer
    order).  All three ride ``lax.scan`` — the table's rows scan as xs
    alongside the stacked params, so per-layer plans keep HLO O(period)
    and re-planned tables swap into the same executable.

    ``unroll`` runs the same per-period body as a Python loop (HLO
    O(depth)) — the scan path's parity oracle and a compile-count
    debugging aid, not a production path.

    With ``collect_stats`` returns ``(x, stats)`` where stats is the
    per-layer MoE stats pytree in layer order: ``routing``
    ``[n_moe_layers, n_src, E]`` realized routing counts and ``dropped``
    ``[n_moe_layers, n_src]`` admitted-but-cut token counts.
    """
    shared, rows = _schedule_rows(schedule, cfg)
    positions = moe_positions(cfg)

    def period_fn(x, pparams, prow):
        stats = []
        for j in range(cfg.period):
            x, st = block_train(
                pparams[f"pos{j}"], cfg, j, x,
                _position_schedule(prow, shared, positions, j),
                collect_stats=collect_stats,
            )
            if st is not None:
                stats.append(st)
        return x, tuple(stats)

    if cfg.remat == "block":
        period_fn = jax.checkpoint(period_fn)

    from repro.parallel import shard

    x = shard(x, "batch", "seq_act", "embed")
    if unroll:
        stats_flat = []
        for p in range(cfg.n_periods):
            pparams = jax.tree.map(lambda a: a[p], params)
            prow = None if rows is None else jax.tree.map(lambda a: a[p], rows)
            x, sts = period_fn(x, pparams, prow)
            x = shard(x, "batch", "seq_act", "embed")
            stats_flat.extend(sts)
        if not collect_stats:
            return x
        return x, jax.tree.map(lambda *ls: jnp.stack(ls), *stats_flat)

    def scan_fn(carry, xs):
        # the scan carry is the saved (checkpointed) residual: keep it
        # sequence-sharded under the 'seq_act' rule (no-op by default)
        pparams, prow = xs
        out, stats = period_fn(carry, pparams, prow)
        return shard(out, "batch", "seq_act", "embed"), stats

    with scope("stack"):
        x, stats = jax.lax.scan(scan_fn, x, (params, rows))
    if not collect_stats:
        return x
    # stats: tuple (per MoE period position) of stat pytrees with leading
    # [n_periods, ...] leaves; flatten to [n_moe_layers, ...] leaves in
    # global layer order.
    flat = [
        jax.tree.map(lambda a, p=p: a[p], st)
        for p in range(cfg.n_periods)
        for st in stats
    ]
    return x, jax.tree.map(lambda *ls: jnp.stack(ls), *flat)


def stack_prefill(params, cfg: ModelConfig, x, caches, schedule):
    shared, rows = _schedule_rows(schedule, cfg)
    positions = moe_positions(cfg)
    # MoE positions whose layers run the sorted pipeline read their expert
    # weights whole, by layer index, where the scan's slice would copy them
    held = {}
    for j in positions:
        ffn, experts = hold_stack_experts(
            params[f"pos{j}"]["ffn"], cfg, x.shape[0] * x.shape[1], schedule
        )
        if experts is not None:
            params = {**params, f"pos{j}": {**params[f"pos{j}"], "ffn": ffn}}
            held[j] = experts
    layers = (jnp.arange(cfg.n_periods),) if held else ()

    def scan_fn(carry, inp):
        pparams, pcache, prow, *layer = inp
        new = {}
        for j in range(cfg.period):
            carry, c = block_prefill(
                pparams[f"pos{j}"], cfg, j, carry, pcache[f"pos{j}"],
                _position_schedule(prow, shared, positions, j),
                experts=held[j]._replace(layer=layer[0]) if j in held else None,
            )
            new[f"pos{j}"] = c
        return carry, new

    with scope("stack"):
        x, caches = jax.lax.scan(scan_fn, x, (params, caches, rows, *layers))
    return x, caches


def stack_decode(
    params, cfg: ModelConfig, x, caches, step, schedule, *,
    collect_stats: bool = False, token_weight=None,
):
    """One decode step through the stack.

    ``step`` is a scalar or a ``[B]`` per-slot position vector (see
    ``attn.attn_decode``).  With ``collect_stats`` returns
    ``(x, caches, stats)`` — the same per-layer MoE stats pytree as
    ``stack_train`` (``routing`` ``[n_moe_layers, n_src, E]`` /
    ``dropped`` ``[n_moe_layers, n_src]``), riding the period scan as ys
    exactly like the train path; ``token_weight`` ([B, 1] f32) masks
    vacated serving slots out of the counts.  Stats is None for MoE-free
    configs."""
    shared, rows = _schedule_rows(schedule, cfg)
    positions = moe_positions(cfg)

    def scan_fn(carry, inp):
        pparams, pcache, prow = inp
        new = {}
        stats = []
        for j in range(cfg.period):
            out = block_decode(
                pparams[f"pos{j}"], cfg, j, carry, pcache[f"pos{j}"], step,
                _position_schedule(prow, shared, positions, j),
                collect_stats=collect_stats, token_weight=token_weight,
            )
            if collect_stats:
                carry, c, st = out
                if st is not None:
                    stats.append(st)
            else:
                carry, c = out
            new[f"pos{j}"] = c
        return carry, (new, tuple(stats))

    with scope("stack"):
        x, (caches, stats) = jax.lax.scan(scan_fn, x, (params, caches, rows))
    if not collect_stats:
        return x, caches
    # stats: tuple (per MoE period position) of stat pytrees with leading
    # [n_periods, ...] leaves; flatten to [n_moe_layers, ...] layer order
    # (same contract as stack_train).
    flat = [
        jax.tree.map(lambda a, p=p: a[p], st)
        for p in range(cfg.n_periods)
        for st in stats
    ]
    if not flat:
        return x, caches, None
    return x, caches, jax.tree.map(lambda *ls: jnp.stack(ls), *flat)
