"""Batched auction LAP in pure JAX — the device-resident solver.

The host re-plan path is LAP-bound on scipy ``linear_sum_assignment``
(Jonker-Volgenant) solved one matrix at a time; this module provides the
traced twin: a Jacobi (synchronous-bidding) **auction** with epsilon
scaling [Bertsekas '88], expressed as ONE ``lax.while_loop`` so it

* jits (no host sync inside a solve),
* vmaps over layers and phases (the controller re-plans every MoE layer
  of the stack in one batched call), and
* runs inside ``lax.cond`` — the in-graph re-plan of
  ``core.device_controller``.

Exactness contract: costs are scaled by ``n + 1`` and the epsilon
schedule is kept integer (``eps_final = 1`` in scaled units), so for
**integer-valued** cost matrices the returned matching's weight equals
scipy's optimum exactly (epsilon-complementary slackness gives a gap
``< n * eps_final = n < n + 1`` scaled, i.e. ``< 1`` unscaled).  Token
counts are integers, so the planner path is exact; on arbitrary float
matrices (EMA-smoothed traffic) the matching is epsilon-optimal with a
sub-token gap, which the selector's drop tolerance absorbs.  All
arithmetic stays integer-valued, hence exact in f32 below ``2**24``.

Why no Pallas kernel: one bidding round is ``[n, n]`` elementwise work
plus two row/column reductions at ``n <= 64`` — XLA fuses it into a
couple of kernels already, and the while-loop carry is tiny.  A custom
kernel would only relocate the launch overhead (see docs/perf.md).

``greedy_phases_jax`` stacks the solver into the traced twin of the
greedy max-weight decomposition + ``plan_schedule`` pipeline: a
``lax.scan`` over ``k_max`` phase slots, each solving the batched LAP on
the residual stack and clearing the matched pairs in full (the
``min_fill = 0`` semantics every in-graph re-plan uses).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.scopes import scope

__all__ = [
    "auction_lap",
    "auction_lap_batch",
    "greedy_phases_jax",
    "matching_weight",
]

# Bidding rounds are cheap; the cap is a tracing-side safety net far
# above what epsilon scaling needs at n <= 64 (observed: < 400 rounds).
_MAX_ROUNDS = 20_000


def _solve(a: jax.Array, max_rounds: int) -> jax.Array:
    """Core epsilon-scaling Jacobi auction on one scaled [n, n] matrix.

    Returns ``perm`` (int32, ``perm[i]`` = column assigned to row i)
    maximizing ``a[i, perm[i]].sum()`` to within ``n * eps_final``.
    """
    n = a.shape[0]
    neg = jnp.float32(-(3.0 * n + 4.0)) * jnp.maximum(
        jnp.abs(a).max(), 1.0
    )  # below any reachable value/bid
    eps_final = jnp.float32(1.0)
    # Integer epsilon schedule: start at ~span/4, shrink 6x per scaling
    # phase, floor at 1 — every intermediate stays integer-valued.
    span = a.max() - a.min()
    eps0 = jnp.maximum(jnp.floor(span / 4.0), eps_final)
    idx = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, _, curr, eps, it = state
        done = (curr >= 0).all() & (eps <= eps_final)
        return ~done & (it < max_rounds)

    def body(state):
        p, owner, curr, eps, it = state
        unassigned = curr < 0
        # Values net of price; each unassigned person bids its best
        # object up by (best - second best + eps).
        v = a - p[None, :]
        best_j = jnp.argmax(v, axis=1).astype(jnp.int32)
        v1 = jnp.max(v, axis=1)
        v2 = jnp.max(
            jnp.where(idx[None, :] == best_j[:, None], neg, v), axis=1
        )
        bid = p[best_j] + (v1 - v2) + eps
        # Win matrix: person i's bid lands on column best_j[i]; objects
        # take the highest bid.  All-assigned => no bids => no-op body
        # (this is what makes vmap-over-while_loop safe).
        bids = jnp.where(
            unassigned[:, None] & (idx[None, :] == best_j[:, None]),
            bid[:, None],
            neg,
        )
        top = jnp.max(bids, axis=0)
        winner = jnp.argmax(bids, axis=0).astype(jnp.int32)
        has_bid = top > neg
        # Evict prior owners of re-auctioned objects, then assign the
        # winners.  A person bids on exactly one object, so winners of
        # distinct objects are distinct (scatter is conflict-free).
        evict_at = jnp.where(has_bid & (owner >= 0), owner, n)
        curr = curr.at[evict_at].set(-1, mode="drop")
        assign_at = jnp.where(has_bid, winner, n)
        curr = curr.at[assign_at].set(
            jnp.where(has_bid, idx, 0), mode="drop"
        )
        owner = jnp.where(has_bid, winner, owner)
        p = jnp.where(has_bid, top, p)
        # Epsilon phase transition: all assigned at a coarse eps =>
        # shrink eps, keep prices, restart the assignment.
        shrink = (curr >= 0).all() & (eps > eps_final)
        eps = jnp.where(
            shrink, jnp.maximum(jnp.floor(eps / 6.0), eps_final), eps
        )
        curr = jnp.where(shrink, -1, curr)
        owner = jnp.where(shrink, -1, owner)
        return p, owner, curr, eps, it + 1

    p0 = jnp.zeros((n,), jnp.float32)
    none = jnp.full((n,), -1, jnp.int32)
    _, _, curr, _, _ = jax.lax.while_loop(
        cond, body, (p0, none, none, eps0, jnp.int32(0))
    )
    # Round-cap repair (never taken in practice): pair leftover
    # unassigned persons with unowned objects in index order so the
    # result is always a valid permutation.
    taken = (
        jnp.zeros((n,), bool)
        .at[jnp.where(curr >= 0, curr, n)]
        .set(True, mode="drop")
    )
    free_sorted = jnp.sort(jnp.where(taken, n, idx))
    rank = jnp.cumsum(curr < 0) - 1
    fill = free_sorted[jnp.clip(rank, 0, n - 1)]
    return jnp.where(curr < 0, fill, curr).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("maximize", "max_rounds"))
def auction_lap(
    costs: jax.Array,
    mask: jax.Array | None = None,
    *,
    maximize: bool = True,
    max_rounds: int = _MAX_ROUNDS,
) -> jax.Array:
    """Solve one dense [n, n] assignment problem on device.

    Args:
      costs: [n, n] weights (``costs[i, j]`` = value of pairing row i
        with column j).
      mask: optional [n, n] bool, True = pair usable.  Masked pairs are
        driven to a large negative value so they are chosen only when a
        row has no usable column left (the matching must stay a full
        permutation — the planner's ``valid`` flags then mark such pairs
        dark, exactly like the scipy path on a masked residual).
      maximize: False negates the matrix first (min-cost assignment).

    Returns [n] int32 ``perm`` with ``perm[i]`` = assigned column.  For
    integer-valued ``costs`` the weight matches scipy
    ``linear_sum_assignment`` exactly; see module docstring.
    """
    a = jnp.asarray(costs, jnp.float32)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"expected square [n, n] costs, got {a.shape}")
    if not maximize:
        a = -a
    if mask is not None:
        n = a.shape[0]
        big = (jnp.abs(a).max() + 1.0) * (n + 1)
        a = jnp.where(jnp.asarray(mask, bool), a, -big)
    # Scale by n + 1 so eps_final = 1 guarantees exact optimality on
    # integer inputs (gap < n * eps_final < scaled unit).
    return _solve(a * (a.shape[0] + 1.0), max_rounds)


@functools.partial(jax.jit, static_argnames=("maximize", "max_rounds"))
def auction_lap_batch(
    costs: jax.Array,
    mask: jax.Array | None = None,
    *,
    maximize: bool = True,
    max_rounds: int = _MAX_ROUNDS,
) -> jax.Array:
    """Vmapped ``auction_lap`` over a [L, n, n] stack -> [L, n] perms.

    ``mask`` is one fabric-wide [n, n] availability shared by the whole
    stack (outages are physical, not per-layer), matching
    ``decompose_batch``'s link-mask contract.
    """
    a = jnp.asarray(costs, jnp.float32)
    if a.ndim != 3 or a.shape[1] != a.shape[2]:
        raise ValueError(f"expected [L, n, n] stack, got {a.shape}")
    if not maximize:
        a = -a
    if mask is not None:
        n = a.shape[1]
        big = (jnp.abs(a).max() + 1.0) * (n + 1)
        a = jnp.where(jnp.asarray(mask, bool)[None, :, :], a, -big)
    return jax.vmap(lambda m: _solve(m * (m.shape[0] + 1.0), max_rounds))(a)


def matching_weight(costs, perm) -> jax.Array:
    """Total weight of a matching: ``sum_i costs[i, perm[i]]`` (batched
    over any leading dims shared by ``costs`` [..., n, n] and ``perm``
    [..., n])."""
    costs = jnp.asarray(costs)
    perm = jnp.asarray(perm)
    picked = jnp.take_along_axis(costs, perm[..., :, None], axis=-1)
    return jnp.sum(picked[..., 0], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("k_max", "quantum", "min_cap", "slack", "max_rounds"),
)
def greedy_phases_jax(
    traffic: jax.Array,
    *,
    k_max: int,
    quantum: int = 8,
    min_cap: int = 8,
    slack: float = 1.0,
    mask: jax.Array | None = None,
    max_rounds: int = _MAX_ROUNDS,
) -> dict:
    """Traced greedy max-weight decomposition + ``plan_schedule`` twin.

    ``lax.scan`` over exactly ``k_max`` phase slots; slot k solves the
    batched LAP on the residual stack and clears the matched pairs in
    full (``min_fill = 0`` greedy — the semantics of every in-graph
    re-plan).  Residual left after ``k_max`` slots is planned drops,
    matching the host table's clip-to-k_max behaviour.

    Args:
      traffic: [L, n, n] nonnegative demand; the diagonal is ignored
        (local tokens never touch the fabric).
      mask: optional fabric-wide [n, n] bool (True = usable); masked
        pairs are never marked valid.  Callers wanting the host
        ``apply_link_mask`` semantics (displaced demand re-routed) apply
        them to ``traffic`` first — see
        ``device_controller.apply_link_mask_traced``.

    Returns a dict of table leaves, shapes matching ``ScheduleTable``:
      perms [L, k_max, n] i32, caps [L, k_max] i32 (token units, the
      ``plan_schedule`` rounding: ``round_up(max(ceil(max_sent * slack),
      min_cap), quantum)``; 0 on dark slots), valid [L, k_max, n] bool,
      n_phases [L] i32, sent [L, k_max, n] f32, residual [L, n, n] f32.
    """
    with scope("lap"):
        a = jnp.asarray(traffic, jnp.float32)
        L, n, _ = a.shape
        eye = jnp.eye(n, dtype=bool)
        a = jnp.where(eye[None], 0.0, a)
        usable = (
            jnp.asarray(mask, bool) & ~eye if mask is not None else ~eye
        )
        a = jnp.where(usable[None], a, 0.0)
        idx = jnp.arange(n, dtype=jnp.int32)

        def one_phase(residual, _):
            # Unpenalized solve, like the host greedy: dark/diagonal entries
            # are already zero in the residual, so the LAP parks rows on them
            # freely (weight 0) when that frees a column for real demand —
            # ``valid`` filtering keeps those pairs unrouted.  Penalizing
            # them instead (the standalone ``auction_lap`` mask contract)
            # would refuse phases that route demand while parking other rows
            # dark, stranding routable residual the host path admits.
            perms = auction_lap_batch(residual, max_rounds=max_rounds)
            sent = jnp.take_along_axis(residual, perms[:, :, None], axis=2)[
                :, :, 0
            ]
            valid = (
                (sent > 0)
                & (perms != idx[None, :])
                & usable[idx[None, :], perms]
            )
            sent = jnp.where(valid, sent, 0.0)
            residual = jnp.where(
                valid[:, :, None] & (idx[None, None, :] == perms[:, :, None]),
                0.0,
                residual,
            )
            # plan_schedule cap rounding on this slot (alloc == sent for
            # max-weight; dark slots keep cap 0 so the admission mask and
            # the bytes accounting both see them as free).
            mx = jnp.max(jnp.where(valid, sent, 0.0), axis=1)
            any_valid = valid.any(axis=1)
            cap = jnp.maximum(jnp.ceil(mx * slack), float(min_cap))
            cap = (-(-cap.astype(jnp.int32) // quantum)) * quantum
            cap = jnp.where(any_valid, cap, 0).astype(jnp.int32)
            return residual, (perms, cap, valid, sent)

        residual, (perms, caps, valid, sent) = jax.lax.scan(
            one_phase, a, None, length=k_max
        )
        # scan stacks on axis 0 -> [k_max, L, ...]; table layout is [L, k_max, ...]
        perms = jnp.swapaxes(perms, 0, 1)
        caps = jnp.swapaxes(caps, 0, 1)
        valid = jnp.swapaxes(valid, 0, 1)
        sent = jnp.swapaxes(sent, 0, 1)
        # Any positive residual yields a further matching with sent > 0, so
        # live slots form a prefix and the phase count is just the live count.
        n_phases = valid.any(axis=2).sum(axis=1).astype(jnp.int32)
        # Pad dark slots with the identity perm, like from_schedules.
        dark = ~valid.any(axis=2)
        perms = jnp.where(dark[:, :, None], idx[None, None, :], perms)
        return {
            "perms": perms.astype(jnp.int32),
            "caps": caps,
            "valid": valid,
            "n_phases": n_phases,
            "sent": sent,
            "residual": residual,
        }
