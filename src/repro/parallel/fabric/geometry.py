"""Token geometry shared by every fabric backend.

A fabric moves *slots*, not tokens: the router's (token, choice) pairs
are packed into a shape-static slot space (buckets for the uniform
fabrics, phase-major blocks for the envelope fabrics), the fabric
carries the slots, and the combine path scatter-adds processed slots
back onto the residual stream.  Everything here is pure slot math — no
collectives, no mesh — so it is unit-testable on one device and shared
verbatim by all backends (which is what makes the cross-fabric parity
matrix meaningful: the backends can only differ in *movement*, never in
admission or packing semantics).

Moved out of ``models/moe.py`` by the fabric refactor; ``models.moe``
re-exports the old underscore names for its tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import ScheduleTable

__all__ = [
    "round8",
    "bucket_capacity",
    "sort_by_group",
    "group_tokens",
    "pack_slots",
    "ungroup",
    "rank_in_group",
    "pod_of",
    "same_pod",
    "wire_mask_buckets",
    "admission_mask",
    "phase_serving",
    "phase_slot_assign",
    "routing_counts",
    "stats_tree",
]


def pod_of(idx, pod_size: int):
    """Group (pod) index of a rank — or virtual-rank — index array.

    The two-level fabric's sub-axis split: ranks ``[p * pod_size,
    (p + 1) * pod_size)`` form pod ``p``.  Works on python ints, numpy,
    and traced arrays (``pod_size`` is static)."""
    return idx // pod_size


def same_pod(src, dst, pod_size: int):
    """Elementwise (broadcasting) — do ``src`` and ``dst`` share a pod?
    The hierarchical backends' seam test: crossings where this is False
    ride the inter (circuit) level and its wire codec; everything else
    stays on the fast intra links."""
    return pod_of(src, pod_size) == pod_of(dst, pod_size)


def round8(x):
    """max(8, ceil to a multiple of 8) — scalar int or int array."""
    r = np.maximum(8, -(-np.asarray(x) // 8) * 8)
    return int(r) if r.ndim == 0 else r


def bucket_capacity(t: int, moe) -> int:
    """Slots per expert bucket of a ``t``-token layer: the choices an
    expert would get under even routing, times ``moe.capacity_factor``,
    rounded up to a multiple of 8."""
    return round8(math.ceil(t * moe.top_k / moe.n_experts * moe.capacity_factor))


def sort_by_group(key, n_groups: int):
    """Stable sort of elements by group id.

    ``key``: [N] int group ids.  Returns (order [N] — the element indices
    grouped by id, ascending, arrival order kept within a group — and
    counts [n_groups] int32, the size of each group).  The one sort both
    expert pipelines share: the padded path ranks choices into bucket
    slots from it, the sorted path runs its grouped GEMM over it."""
    return jnp.argsort(key, stable=True), jnp.bincount(key, length=n_groups)


def group_tokens(x, key, gates, n_buckets: int, cap: int, admitted=None):
    """Pack tokens into per-bucket slots.

    x: [T, d]; key: [T*k] bucket id per (token, choice); gates: [T*k];
    admitted: [T*k] bool — choices the schedule plan admits (None = all).
    Returns (buf [n_buckets, cap, d], pos [n_buckets, cap] int32 (-1 pad),
    gate [n_buckets, cap], live [n_buckets, cap] bool).  Tokens beyond a
    bucket's capacity are dropped (standard capacity-factor semantics).

    ``live`` is the *explicit* slot-validity mask: a slot is live iff it
    holds a real admitted token — independent of the gate value, so an
    admitted choice whose router gate is exactly 0.0 still counts as live
    (it must reach expert compute and the drop accounting; the old
    ``gate > 0`` liveness inference conflated it with padding).
    """
    tk = key.shape[0]
    t = x.shape[0]
    token_of = jnp.arange(tk, dtype=jnp.int32) // (tk // t)
    order, counts = sort_by_group(key, n_buckets)
    skey = key[order]
    starts = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    rank = jnp.arange(tk) - starts[skey]
    fits = rank < cap
    slot = jnp.where(fits, skey * cap + rank, n_buckets * cap)
    buf = jnp.zeros((n_buckets * cap + 1, x.shape[1]), x.dtype)
    buf = buf.at[slot].set(x[token_of[order]])
    pos = jnp.full((n_buckets * cap + 1,), -1, jnp.int32)
    pos = pos.at[slot].set(token_of[order])
    gat = jnp.zeros((n_buckets * cap + 1,), jnp.float32)
    gat = gat.at[slot].set(gates[order])
    adm = (
        jnp.ones((tk,), bool) if admitted is None else admitted.reshape(-1)
    )
    liv = jnp.zeros((n_buckets * cap + 1,), bool)
    liv = liv.at[slot].set(adm[order])
    return (
        buf[:-1].reshape(n_buckets, cap, -1),
        pos[:-1].reshape(n_buckets, cap),
        gat[:-1].reshape(n_buckets, cap),
        liv[:-1].reshape(n_buckets, cap),
    )


def pack_slots(x, slot, gates, admitted, n_slots: int):
    """Direct-slot twin of ``group_tokens`` for precomputed assignments.

    ``slot``: [T*k] int32 flat slot per (token, choice) — collision-free
    for kept choices by construction (ranks are unique per bucket);
    ``n_slots`` is the dump slot for cut choices.  Returns flat
    (buf [n_slots, d], pos [n_slots] (-1 pad), gate [n_slots],
    live [n_slots] bool) — ``live`` marks slots holding real *admitted*
    tokens (explicit validity, not the gate sign)."""
    tk = slot.shape[0]
    t = x.shape[0]
    token_of = jnp.arange(tk, dtype=jnp.int32) // (tk // t)
    buf = jnp.zeros((n_slots + 1, x.shape[1]), x.dtype).at[slot].set(x[token_of])
    pos = jnp.full((n_slots + 1,), -1, jnp.int32).at[slot].set(token_of)
    gat = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(gates)
    liv = jnp.zeros((n_slots + 1,), bool).at[slot].set(admitted)
    return buf[:-1], pos[:-1], gat[:-1], liv[:-1]


def ungroup(y, pos, gate, t: int):
    """Weighted scatter-add of processed slots back to [T, d] (f32)."""
    yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
    pf = pos.reshape(-1)
    gf = gate.reshape(-1)
    safe = jnp.where(pf >= 0, pf, t)
    out = jnp.zeros((t + 1, y.shape[-1]), jnp.float32)
    out = out.at[safe].add(yf * gf[:, None])
    return out[:t]


def rank_in_group(key: jax.Array) -> jax.Array:
    """Arrival rank of each element within its group.

    ``key``: [N] int group ids.  Returns [N] int32 — the element's index
    among same-key elements in original order, i.e. exactly the bucket
    slot ``group_tokens`` will assign it.  One stable argsort + a cummax
    over segment starts (no LAP, no segment loops).
    """
    n = key.shape[0]
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    idxs = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]]
    )
    first = jax.lax.cummax(jnp.where(is_start, idxs, 0))
    return jnp.zeros_like(idxs).at[order].set(idxs - first)


def wire_mask_buckets(live: jax.Array, e_local: int, me) -> jax.Array:
    """Wire-crossing slots in a ``[n * e_local, cap]`` bucket layout.

    A slot crosses the fabric iff it is live AND its bucket's
    destination rank (``bucket // e_local``) is not ``me`` — local
    buckets never leave the rank and padding never ships payload, so
    neither belongs to the wire codec's domain (mirroring how admission
    never clips local traffic).  Shared by every uniform-bucket backend
    (a2a, ppermute, the phase-pipelined monolithic fallback)."""
    dst = jnp.arange(live.shape[0], dtype=jnp.int32) // e_local
    return live & (dst != me)[:, None]


def admission_mask(
    idx: jax.Array,
    gates: jax.Array,
    row: ScheduleTable,
    n_experts: int,
    *,
    src: jax.Array,
):
    """Enforce a traced schedule row's planned capacities on the gates.

    ``idx``/``gates``: [T, k] routing choices; ``src``: [T*k] source rank
    of each flattened choice (a constant inside the EP shard_map, the
    virtual-fabric fold on a single device).  A choice is *admitted* if
    its arrival rank within its (src, expert) bucket is below the pair's
    planned per-expert capacity (``ScheduleTable.pair_caps``, clamped to
    the table's phase envelope when it carries one) — the same prefix of
    slots the static ppermute path would ship; everything beyond gets its
    gate zeroed, which is indistinguishable from the static path
    returning zeros for unshipped slots.  Local (src == dst) traffic
    never crosses the fabric and is never clipped.

    Returns ``(gates, admitted)`` — the masked gates AND the [T*k] bool
    admission mask itself, so callers can track admitted tokens
    explicitly (liveness and drop accounting must not be inferred from
    the gate sign: a gate can legitimately be exactly 0.0).
    """
    n_v = row.n
    e_local = n_experts // n_v
    e_flat = idx.reshape(-1)
    dst = e_flat // e_local
    cap_pair = row.pair_caps(e_local)  # [n_v, n_v] per-expert slot units
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    cap_flat = jnp.where(src == dst, big, cap_pair[src, dst])
    rank = rank_in_group(src * jnp.int32(n_experts) + e_flat)
    admitted = rank < cap_flat
    return gates * admitted.reshape(gates.shape), admitted


def phase_serving(row: ScheduleTable, e_local: int, me):
    """Rank ``me``'s phase-major serving plan from a traced schedule row.

    Returns (per-phase arrays, length K_max):
      on_k    [K] bool  — rank ``me`` participates in phase k,
      dst_k   [K] int32 — its destination that phase (identity padding
                          elsewhere),
      serve   [K] int32 — per-expert slots phase k carries for the pair
                          (``phase_slot_caps`` clamped to the envelope,
                          zero when off),
      cum     [K, n]    — inclusive per-destination cumulative slots,
      cum_lo  [K, n]    — exclusive (phase start offset per destination).

    ``cum[-1]`` is exactly ``pair_caps(e_local)[me]`` — admission and the
    phase slotting read the same numbers, which is what makes the
    pipelined path drop-free by construction (every admitted choice's
    in-bucket rank falls inside some phase's [cum_lo, cum) window).
    BvN-style multi-phase pairs fall out for free: their later phases
    pick up the next slice of the pair's rank range.
    """
    k_max, n = row.perms.shape
    kk = jnp.arange(k_max)
    on_k = (kk < row.n_phases) & row.valid[:, me]
    dst_k = row.perms[:, me]
    serve = jnp.where(on_k, row.phase_slot_caps(e_local), 0).astype(jnp.int32)
    serve_mat = (
        jnp.zeros((k_max, n), jnp.int32).at[kk, dst_k].add(serve)
    )
    cum = jnp.cumsum(serve_mat, axis=0)
    return on_k, dst_k, serve, cum, cum - serve_mat


def phase_slot_assign(
    row: ScheduleTable,
    e_local: int,
    me,
    e_flat: jax.Array,
    rank: jax.Array,
    *,
    c_local: int,
):
    """Assign every routing choice a flat slot in the phase-major buffer.

    Layout: ``[phase-0 block | ... | phase-(K-1) block | local block]``
    where phase k's block is ``[e_local, env_k]`` slots (``env_k`` the
    static envelope slot size) and the local block ``[e_local, c_local]``.
    ``e_flat``: [T*k] expert ids; ``rank``: arrival rank within expert.

    Returns (slot [T*k] int32 — the dump slot for cut choices, admitted
    [T*k] bool, bases tuple of static python ints, env_slots tuple,
    n_slots int, on_k [K] bool, dst_k [K] int32 — the serving plan, so
    the dispatch loop doesn't recompute it).  Remote choices are admitted
    iff their rank fits the pair's total planned (envelope-clamped)
    slots — and then always land inside their phase block: the envelope
    sized the buffer from the same numbers, so the monolithic path's
    over-promise drop cannot happen.
    """
    env_slots = row.envelope_slots(e_local)
    k_max, n = row.perms.shape
    bases = []
    off = 0
    for ck in env_slots:
        bases.append(off)
        off += e_local * ck
    s_remote = off
    n_slots = s_remote + e_local * c_local
    on_k, dst_k, serve, cum, cum_lo = phase_serving(row, e_local, me)

    dst = e_flat // e_local
    le = e_flat % e_local
    local = dst == me
    admitted = local | (rank < cum[-1][dst])
    # phase of a remote choice: the k whose [cum_lo, cum) window holds its
    # rank — count the phases whose inclusive cum it has already passed
    ph = (rank[None, :] >= cum[:, dst]).sum(axis=0)
    ph_c = jnp.clip(ph, 0, k_max - 1)
    base_arr = jnp.asarray(bases, jnp.int32)
    env_arr = jnp.asarray(env_slots, jnp.int32)
    slot_in = rank - cum_lo[ph_c, dst]
    remote_slot = base_arr[ph_c] + le * env_arr[ph_c] + slot_in
    local_slot = s_remote + le * c_local + rank
    slot = jnp.where(
        local,
        jnp.where(rank < c_local, local_slot, n_slots),
        jnp.where(admitted, remote_slot, n_slots),
    ).astype(jnp.int32)
    return slot, admitted, tuple(bases), env_slots, n_slots, on_k, dst_k


def routing_counts(
    idx: jax.Array, n_experts: int, weight: jax.Array | None = None
) -> jax.Array:
    """Realized per-expert routing demand from [T, k] expert ids.

    Counts are pre-capacity-drop (the controller plans for demand, not for
    what the current schedule happened to admit) and carry no gradient —
    top-k indices are already non-differentiable.

    ``weight`` ([T] f32, optional) scales each token's contribution —
    the serving engine passes its slot-liveness mask here so vacated
    decode slots (whose garbage tokens still traverse the static-shape
    batch) never pollute the controller's demand signal."""
    if weight is None:
        return (
            jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        )
    w = jnp.broadcast_to(
        weight.astype(jnp.float32)[:, None], idx.shape
    ).reshape(-1)
    return jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(w)


def stats_tree(counts: jax.Array, admitted, live) -> dict:
    """The MoE layer's aux-stats pytree — the fabric stats *contract*:
    every backend returns ``{"routing", "dropped"}`` with these exact
    semantics, which is what the cross-fabric parity matrix asserts.

    ``routing`` is the realized pre-drop demand (``routing_counts`` with
    the caller's leading source-shard dims); ``dropped`` = choices the
    schedule plan admitted that packing still cut (no slot in the
    shape-static buffer) — the silent divergence the monolithic traced
    path suffers when a plan over-promises the uniform capacity-factor
    bucket; phase-pipelined dispatch drives it to zero by construction
    (local capacity-factor overflow is still counted).  Both are f32 and
    gradient-free."""
    adm = jnp.asarray(admitted).sum().astype(jnp.float32)
    packed = jnp.asarray(live).sum().astype(jnp.float32)
    dropped = jax.lax.stop_gradient(adm - packed)
    # match the routing counts' leading (source-shard) dims
    return {
        "routing": counts,
        "dropped": dropped.reshape((1,) * (counts.ndim - 1)),
    }
