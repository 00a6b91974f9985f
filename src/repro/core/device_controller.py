"""Device-resident controller: observe → score → re-plan without host sync.

``core.runtime.ScheduleRuntime`` runs the controller loop on the host:
every step fetches the ``[L, n_src, E]`` routing counts (~642 µs/step of
the 644 µs/step controller total at the n=16 × 8-layer bench config) and
every cold re-plan serializes through scipy.  At decode-latency
timescales that round-trip is the whole budget.

This module re-expresses the loop as a pure function over an array
pytree so it rides *inside* the traced step:

* ``DeviceControllerState`` — the EMA'd traffic, the current plan's
  table leaves, and the hysteresis/cooldown/drift counters, all device
  arrays.  The state is a registered pytree: it is carried through the
  jitted step like the optimizer state, and swapping in a re-planned
  state never recompiles (same shapes, same static envelope).
* ``DeviceController.step`` — folds routing counts to rank traffic,
  EMA-smooths, scores the planned drop of the *current* plan against
  its traced cap matrix (the ``ScheduleSelector`` scoring rule), and
  fires the re-plan behind ``lax.cond`` on the traced drift signal:
  the batched auction LAP (``core.lap_jax.greedy_phases_jax``) rebuilds
  every layer's plan on device.  Steady-state steps execute only the
  scoring arithmetic — routing stats never leave the device.

Policy mapping from the host runtime (kept as the parity oracle):

* drop tolerance — identical: re-plan pressure when
  ``max(traffic − caps, 0).sum() / total > drop_tolerance``.
* hysteresis — the host rule is a *relative improvement* bar for
  switching library entries; there is no library on device (plans are
  rebuilt, not recalled), so hysteresis becomes **persistence**: the
  drift signal must hold for ``hysteresis_steps`` consecutive steps
  before a re-plan fires (same flap-damping intent, traced form).
* cooldown — identical: ``cooldown`` steps after a re-plan during which
  the drift signal cannot fire again (the EMA needs to settle).
* quarantine / health FSM — stays on the host (fabric switching
  rebuilds the step function, which is inherently a host decision).
  The state carries the anomaly inputs the FSM consumes — drop-spike
  counts and the last drop fraction — so the host reads them on the
  metrics cadence instead of every step (docs/robustness.md).

Link masks ride the state as a ``[n, n]`` bool leaf: a masked re-plan
scores and plans on the rerouted demand (``apply_link_mask_traced``, the
traced twin of ``core.faults.apply_link_mask``) and never marks a dark
pair valid — PR 6's masked re-plans keep working in-graph, at zero
recompiles (the mask is data, not structure).

**Schedule regime library (PR 10).**  PCCL-style pre-established
circuits: when ``DeviceControllerConfig.regime_slots > 0`` the state
carries a bank of pre-planned table pytrees (``lib_*`` leaves) plus one
normalized ``[n, n]`` reference traffic shape per entry.  When the drift
signal fires, the controller first nearest-matches the EMA'd traffic
shape against the library (relative-L1, the traced twin of
``ScheduleEntry.mismatch``); a match under ``regime_threshold``
**warm-swaps** the stored plan in by a dynamic gather — no LAP solve,
no recompile, and (the regime's circuits being pre-established) no
re-plan dark window — while a miss falls back to the cold
``greedy_phases_jax`` solve.  Regimes are loaded host-side via
``DeviceController.load_regimes`` (e.g. plans for the traffic regimes
the host selector library already knows); a degraded link mask disables
warm matching, since stored plans were routed for the healthy fabric.

``replan_penalty`` is the traced form of the reconfiguration-delay bar
(``CommModel.replan_dark_us``): a *cold* re-plan's best-case saving is
the whole current drop fraction, so the controller declines to fire one
when ``drop < replan_penalty`` — the dark window would outweigh the
saving.  Warm swaps are exempt (their circuits are pre-established).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lap_jax import greedy_phases_jax
from repro.core.schedule import ScheduleTable
from repro.scopes import scope

__all__ = [
    "DeviceControllerConfig",
    "DeviceControllerState",
    "DeviceController",
    "apply_link_mask_traced",
    "routing_to_traffic_traced",
]


@dataclasses.dataclass(frozen=True)
class DeviceControllerConfig:
    """Static (hashable) knobs of the in-graph controller.

    Everything here is baked into the executable; the tunable *state*
    (EMA, counters, the plan itself) lives in ``DeviceControllerState``.
    ``envelope`` is the static phase envelope of the emitted tables —
    the same aux data ``ScheduleRuntime`` derives, pinned at build time
    so every table the controller emits shares one executable.

    ``hysteresis_steps`` is the traced form of the host hysteresis (see
    module docstring); ``cooldown``/``drop_tolerance``/``ema`` match
    ``ControllerConfig`` field for field.

    ``regime_slots`` sizes the schedule regime library carried in the
    state (0 = no library, the pre-PR-10 behavior); ``regime_threshold``
    is the relative-L1 traffic-shape distance under which a library
    entry counts as a warm match.  ``replan_penalty`` is the
    drop-fraction-equivalent cost of a *cold* re-plan's reconfiguration
    dark window (``CommModel.replan_penalty``); 0 keeps the legacy
    always-worth-it rule.
    """

    n_ranks: int
    n_experts: int
    k_max: int
    ema: float = 0.3
    drop_tolerance: float = 0.05
    hysteresis_steps: int = 2
    cooldown: int = 5
    quantum: int = 8
    min_cap: int = 8
    slack: float = 1.1
    envelope: tuple[int, ...] | None = None
    drop_spike_frac: float = 0.25
    max_rounds: int = 20_000
    regime_slots: int = 0
    regime_threshold: float = 0.15
    replan_penalty: float = 0.0

    def __post_init__(self):
        if self.n_experts % self.n_ranks:
            raise ValueError(
                f"{self.n_experts} experts not divisible by "
                f"{self.n_ranks} ranks"
            )
        if self.k_max < 1:
            raise ValueError("k_max must be >= 1")
        if self.hysteresis_steps < 1:
            raise ValueError("hysteresis_steps must be >= 1")
        if self.regime_slots < 0:
            raise ValueError("regime_slots must be >= 0")
        if self.replan_penalty < 0.0:
            raise ValueError("replan_penalty must be >= 0")
        if self.envelope is not None and not isinstance(
            self.envelope, tuple
        ):
            object.__setattr__(
                self, "envelope", tuple(int(v) for v in self.envelope)
            )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DeviceControllerState:
    """The controller loop's carry: every leaf is a device array.

    Plan leaves (``perms``/``caps``/``valid``/``n_phases``) are exactly
    the ``ScheduleTable`` layout — ``DeviceController.table_of`` wraps
    them without copying.  Counters are int32 scalars; ``drop`` is the
    last scored planned-drop fraction (telemetry + FSM input).

    The ``lib_*`` leaves are the schedule regime library: ``R =
    config.regime_slots`` stacked plan pytrees plus one normalized
    ``[n, n]`` reference traffic shape per slot.  With ``R == 0`` they
    are zero-size arrays — same treedef, no memory, and the warm-match
    arithmetic is skipped at trace time.
    """

    smoothed: jax.Array  # [L, n, n] f32 EMA'd rank traffic
    perms: jax.Array  # [L, K, n] i32 current plan
    caps: jax.Array  # [L, K] i32 token-unit phase caps
    valid: jax.Array  # [L, K, n] bool
    n_phases: jax.Array  # [L] i32
    capmat: jax.Array  # [L, n, n] f32 planned pair capacity (derived
    # from the plan leaves; cached so steady-state scoring skips the
    # scatter — it only changes when a re-plan swaps the plan)
    link_mask: jax.Array  # [n, n] bool, True = usable
    steps: jax.Array  # i32 — observations folded in
    cooldown: jax.Array  # i32 — steps until a re-plan may fire again
    drift_streak: jax.Array  # i32 — consecutive over-tolerance steps
    replans: jax.Array  # i32 — in-graph re-plan count
    drop: jax.Array  # f32 — last planned-drop fraction
    drop_spikes: jax.Array  # i32 — FSM anomaly input (spike steps)
    admitted_dropped: jax.Array  # f32 — cumulative cut-token count
    lib_ref: jax.Array  # [R, n, n] f32 normalized reference traffic
    lib_perms: jax.Array  # [R, L, K, n] i32 stored plans
    lib_caps: jax.Array  # [R, L, K] i32
    lib_valid: jax.Array  # [R, L, K, n] bool
    lib_n_phases: jax.Array  # [R, L] i32
    lib_size: jax.Array  # i32 — filled slots (<= R)
    warm_swaps: jax.Array  # i32 — re-plans served from the library

    def tree_flatten(self):
        return (
            (
                self.smoothed,
                self.perms,
                self.caps,
                self.valid,
                self.n_phases,
                self.capmat,
                self.link_mask,
                self.steps,
                self.cooldown,
                self.drift_streak,
                self.replans,
                self.drop,
                self.drop_spikes,
                self.admitted_dropped,
                self.lib_ref,
                self.lib_perms,
                self.lib_caps,
                self.lib_valid,
                self.lib_n_phases,
                self.lib_size,
                self.warm_swaps,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def routing_to_traffic_traced(
    stats: jax.Array, *, n_ranks: int, n_experts: int
) -> jax.Array:
    """Traced twin of ``core.runtime.routing_to_traffic``.

    ``[L, n_src, E]`` counts → ``[L, n, n]`` rank traffic via the
    contiguous expert → rank placement.  Shapes are static at trace
    time, so the shard-count mapping is plain Python branching.
    """
    s = jnp.asarray(stats, jnp.float32)
    if s.ndim != 3 or s.shape[2] != n_experts:
        raise ValueError(
            f"expected [L, n_src, {n_experts}] stats, got {s.shape}"
        )
    L, n_src, _ = s.shape
    per_rank = s.reshape(L, n_src, n_ranks, n_experts // n_ranks).sum(-1)
    if n_src == n_ranks:
        return per_rank
    if n_ranks % n_src == 0:
        k = n_ranks // n_src
        return jnp.repeat(per_rank, k, axis=1) / k
    if n_src % n_ranks == 0:
        k = n_src // n_ranks
        return per_rank.reshape(L, n_ranks, k, n_ranks).sum(axis=2)
    raise ValueError(f"cannot map {n_src} source shards onto {n_ranks} ranks")


def apply_link_mask_traced(
    matrix: jax.Array, link_mask: jax.Array
) -> jax.Array:
    """Traced twin of ``core.faults.apply_link_mask``.

    Masked off-diagonal entries are zeroed and each source row's
    displaced demand is re-assigned proportionally over the row's
    surviving off-diagonal destinations (uniformly when the survivors
    carried none).  Rows with no surviving destination drop their
    demand (unroutable).  Batched over any leading dims; idempotent.
    """
    a = jnp.asarray(matrix, jnp.float32)
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    usable = jnp.asarray(link_mask, bool) & ~eye
    dead = (~usable) & ~eye
    displaced = jnp.where(dead, a, 0.0).sum(-1)  # [..., n]
    alive = jnp.where(usable, a, 0.0)
    row_alive = alive.sum(-1)
    n_usable = usable.sum(-1)  # [n]
    uniform = jnp.where(
        n_usable[:, None] > 0, usable / jnp.maximum(n_usable, 1)[:, None], 0.0
    )
    prop = jnp.where(
        row_alive[..., None] > 0,
        alive / jnp.maximum(row_alive, 1e-30)[..., None],
        uniform,
    )
    # the diagonal never routes over the fabric: keep it untouched
    return jnp.where(eye, a, alive + displaced[..., None] * prop)


def _cap_matrix(perms, caps, valid, n_phases) -> jax.Array:
    """Traced per-(src, dst) planned capacity, token units: the scoring
    twin of ``A2ASchedule.cap_matrix`` over the whole layer stack.
    ``[L, n, n]`` f32 from [L, K, n] plan leaves."""
    L, K, n = perms.shape
    on = (jnp.arange(K)[None, :] < n_phases[:, None])[:, :, None] & valid
    upd = jnp.where(on, caps[:, :, None].astype(jnp.float32), 0.0)
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (L, K, n))
    lyr = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None, None], (L, K, n))
    return (
        jnp.zeros((L, n, n), jnp.float32)
        .at[lyr.ravel(), src.ravel(), perms.ravel()]
        .add(upd.ravel())
    )


class DeviceController:
    """Builds and steps ``DeviceControllerState`` for one model.

    The controller itself is stateless (all state rides the pytree);
    holding it is holding the static config.  ``step`` is a pure
    function — jit it, close over it in a fused train/decode step, or
    scan it; the contract is one call per observed step.
    """

    def __init__(self, cfg: DeviceControllerConfig):
        self.cfg = cfg

    # ---------------------------------------------------------- lifecycle
    def init_state(
        self,
        table: ScheduleTable,
        traffic: np.ndarray | None = None,
        link_mask: np.ndarray | None = None,
    ) -> DeviceControllerState:
        """Seed device state from a host-planned table (the warm start).

        ``traffic`` ([L, n, n]) primes the EMA — pass the runtime's
        smoothed traffic when migrating mid-run; None starts cold (the
        first observation seeds the EMA, like the host runtime).
        """
        cfg = self.cfg
        n = cfg.n_ranks
        L = table.num_layers
        if table.k_max != cfg.k_max or table.n != n:
            raise ValueError(
                f"table is [{table.num_layers}, {table.k_max}, {table.n}], "
                f"config wants k_max={cfg.k_max}, n={n}"
            )
        if traffic is None:
            smoothed = jnp.zeros((L, n, n), jnp.float32)
            steps = jnp.int32(0)
        else:
            smoothed = jnp.asarray(traffic, jnp.float32)
            if smoothed.shape != (L, n, n):
                raise ValueError(
                    f"prime traffic shape {smoothed.shape} != {(L, n, n)}"
                )
            steps = jnp.int32(1)
        mask = (
            jnp.ones((n, n), bool)
            if link_mask is None
            else jnp.asarray(link_mask, bool)
        )
        perms = jnp.asarray(table.perms, jnp.int32)
        caps = jnp.asarray(table.caps, jnp.int32)
        valid = jnp.asarray(table.valid, bool)
        n_phases = jnp.asarray(table.n_phases, jnp.int32)
        R = cfg.regime_slots
        return DeviceControllerState(
            smoothed=smoothed,
            perms=perms,
            caps=caps,
            valid=valid,
            n_phases=n_phases,
            capmat=_cap_matrix(perms, caps, valid, n_phases),
            link_mask=mask,
            steps=steps,
            cooldown=jnp.int32(0),
            drift_streak=jnp.int32(0),
            replans=jnp.int32(0),
            drop=jnp.float32(0.0),
            drop_spikes=jnp.int32(0),
            admitted_dropped=jnp.float32(0.0),
            lib_ref=jnp.zeros((R, n, n), jnp.float32),
            lib_perms=jnp.zeros((R, L, cfg.k_max, n), jnp.int32),
            lib_caps=jnp.zeros((R, L, cfg.k_max), jnp.int32),
            lib_valid=jnp.zeros((R, L, cfg.k_max, n), bool),
            lib_n_phases=jnp.zeros((R, L), jnp.int32),
            lib_size=jnp.int32(0),
            warm_swaps=jnp.int32(0),
        )

    @classmethod
    def from_runtime(cls, runtime, **overrides):
        """Lift a host ``ScheduleRuntime`` into (controller, state).

        Copies the policy knobs, pins the runtime's current envelope as
        the static one, and primes the EMA from the runtime's smoothed
        traffic — the host loop keeps working as the parity oracle.
        """
        rcfg = runtime.cfg
        table = runtime.table()
        kw = dict(
            n_ranks=rcfg.n_ranks,
            n_experts=rcfg.n_experts,
            k_max=table.k_max,
            ema=rcfg.ema,
            drop_tolerance=rcfg.drop_tolerance,
            cooldown=rcfg.cooldown,
            envelope=table.envelope,
            drop_spike_frac=rcfg.drop_spike_frac,
        )
        plan_kwargs = getattr(runtime, "_plan_kwargs", None) or {}
        for k in ("quantum", "min_cap", "slack"):
            if k in plan_kwargs:
                kw[k] = plan_kwargs[k]
        kw.update(overrides)
        ctrl = cls(DeviceControllerConfig(**kw))
        state = ctrl.init_state(
            table,
            traffic=runtime._smoothed,
            link_mask=runtime._link_mask,
        )
        return ctrl, state

    def load_regimes(
        self,
        state: DeviceControllerState,
        tables: list[ScheduleTable],
        references,
    ) -> DeviceControllerState:
        """Fill the regime library from host pre-planned tables.

        ``tables``: one ``ScheduleTable`` per regime, planned at the
        config's ``k_max``/envelope (so a warm swap is shape-neutral).
        ``references``: matching ``[n, n]`` traffic matrices the plans
        were made for (e.g. ``DriftScenario.traffic`` draws, or the host
        selector library's ``ScheduleEntry.reference``) — stored
        normalized, diagonal zeroed, for the traced nearest-match.
        Host-called at load time; the returned state swaps into a
        running step with zero recompiles (same leaves, same shapes).
        """
        cfg = self.cfg
        R = cfg.regime_slots
        if R == 0:
            raise ValueError(
                "config.regime_slots == 0: size the library before "
                "loading regimes"
            )
        if len(tables) != len(references):
            raise ValueError(
                f"{len(tables)} tables vs {len(references)} references"
            )
        if len(tables) > R:
            raise ValueError(
                f"{len(tables)} regimes exceed regime_slots={R}"
            )
        n = cfg.n_ranks
        L, K = state.perms.shape[0], cfg.k_max
        lib_ref = np.zeros((R, n, n), np.float32)
        lib_perms = np.zeros((R, L, K, n), np.int32)
        lib_caps = np.zeros((R, L, K), np.int32)
        lib_valid = np.zeros((R, L, K, n), bool)
        lib_n_phases = np.zeros((R, L), np.int32)
        for r, (tab, ref) in enumerate(zip(tables, references)):
            if (tab.num_layers, tab.k_max, tab.n) != (L, K, n):
                raise ValueError(
                    f"regime {r} table is [{tab.num_layers}, {tab.k_max}, "
                    f"{tab.n}], library wants [{L}, {K}, {n}]"
                )
            if (
                tab.envelope is not None
                and cfg.envelope is not None
                and tuple(tab.envelope) != tuple(cfg.envelope)
            ):
                raise ValueError(
                    f"regime {r} envelope {tab.envelope} != config "
                    f"envelope {cfg.envelope}: a warm swap would not be "
                    f"shape-neutral"
                )
            a = np.asarray(ref, np.float64)
            if a.shape != (n, n):
                raise ValueError(
                    f"regime {r} reference shape {a.shape} != {(n, n)}"
                )
            a = a.copy()
            np.fill_diagonal(a, 0.0)
            lib_ref[r] = (a / max(a.sum(), 1e-9)).astype(np.float32)
            lib_perms[r] = np.asarray(tab.perms, np.int32)
            lib_caps[r] = np.asarray(tab.caps, np.int32)
            lib_valid[r] = np.asarray(tab.valid, bool)
            lib_n_phases[r] = np.asarray(tab.n_phases, np.int32)
        return dataclasses.replace(
            state,
            lib_ref=jnp.asarray(lib_ref),
            lib_perms=jnp.asarray(lib_perms),
            lib_caps=jnp.asarray(lib_caps),
            lib_valid=jnp.asarray(lib_valid),
            lib_n_phases=jnp.asarray(lib_n_phases),
            lib_size=jnp.int32(len(tables)),
        )

    # -------------------------------------------------------------- views
    def table_of(self, state: DeviceControllerState) -> ScheduleTable:
        """The state's plan as a ``ScheduleTable`` (no copies; offsets are
        zeros — max-weight plans are single-phase-pair)."""
        return ScheduleTable(
            perms=state.perms,
            caps=state.caps,
            valid=state.valid,
            offsets=jnp.zeros(state.perms.shape, jnp.int32),
            n_phases=state.n_phases,
            envelope=self.cfg.envelope,
        )

    # --------------------------------------------------------------- step
    def step(
        self,
        state: DeviceControllerState,
        routing: jax.Array,
        dropped: jax.Array | None = None,
    ) -> DeviceControllerState:
        """One observe → score → (cond) re-plan transition.  Pure/traced.

        ``routing``: this step's ``[L, n_src, E]`` realized counts (the
        MoE stats aux, still on device).  ``dropped``: optional
        admitted-but-cut counts (any shape; summed).  Steady-state cost
        is the fold + EMA + one scatter — the re-plan branch only runs
        when the traced drift signal fires.
        """
        with scope("controller"):
            traffic = routing_to_traffic_traced(
                routing, n_ranks=self.cfg.n_ranks, n_experts=self.cfg.n_experts
            )
            return self.step_traffic(state, traffic, dropped)

    def step_traffic(
        self,
        state: DeviceControllerState,
        traffic: jax.Array,
        dropped: jax.Array | None = None,
    ) -> DeviceControllerState:
        """``step`` on already-folded traffic ``[L, n, n]``.  Composed
        controllers (``HierarchicalDeviceController``) fold the routing
        once, split it in-graph, and step each level through here."""
        cfg = self.cfg
        n = cfg.n_ranks
        eye = jnp.eye(n, dtype=bool)
        traffic = jnp.where(eye[None], 0.0, traffic)
        smoothed = jnp.where(
            state.steps == 0,
            traffic,
            (1.0 - cfg.ema) * state.smoothed + cfg.ema * traffic,
        )
        # Score the routable demand against the CURRENT plan (the
        # selector rule): planned drop = overflow / total.  The cap
        # matrix rides the state — steady-state scoring never rebuilds it.
        routable = apply_link_mask_traced(smoothed, state.link_mask)
        capmat = state.capmat
        total = routable.sum()
        drop = jnp.where(
            total > 0,
            jnp.maximum(routable - capmat, 0.0).sum() / jnp.maximum(total, 1e-30),
            0.0,
        )
        over = drop > cfg.drop_tolerance
        streak = jnp.where(over, state.drift_streak + 1, 0)
        cooldown = jnp.maximum(state.cooldown - 1, 0)

        # Regime library nearest-match (traced ScheduleEntry.mismatch):
        # compare the EMA'd traffic *shape* (mean over layers, normalized)
        # against each stored reference.  A degraded link mask disables
        # warm matching — stored plans were routed for the healthy fabric.
        if cfg.regime_slots > 0:
            obs = routable.mean(axis=0)
            obs = obs / jnp.maximum(obs.sum(), 1e-30)
            dist = 0.5 * jnp.abs(obs[None] - state.lib_ref).sum(axis=(-2, -1))
            filled = jnp.arange(cfg.regime_slots) < state.lib_size
            dist = jnp.where(filled, dist, jnp.inf)
            best = jnp.argmin(dist)
            warm = (
                (state.lib_size > 0)
                & (dist[best] <= cfg.regime_threshold)
                & state.link_mask.all()
            )
        else:
            best = jnp.int32(0)
            warm = jnp.bool_(False)

        # Reconfiguration-aware bar: a cold re-plan's best-case saving is
        # the whole current drop; decline when the swap's dark window
        # (replan_penalty, drop-fraction units) costs more.  Warm swaps
        # ride pre-established circuits — no dark window, always worth it.
        worth = warm | (drop >= cfg.replan_penalty)
        fire = (
            over & (streak >= cfg.hysteresis_steps) & (cooldown == 0) & worth
        )

        def replan(_):
            def warm_take(_):
                perms = state.lib_perms[best]
                caps = state.lib_caps[best]
                valid = state.lib_valid[best]
                n_phases = state.lib_n_phases[best]
                return (
                    perms, caps, valid, n_phases,
                    _cap_matrix(perms, caps, valid, n_phases),
                )

            def cold(_):
                plan = greedy_phases_jax(
                    routable,
                    k_max=cfg.k_max,
                    quantum=cfg.quantum,
                    min_cap=cfg.min_cap,
                    slack=cfg.slack,
                    mask=state.link_mask,
                    max_rounds=cfg.max_rounds,
                )
                return (
                    plan["perms"],
                    plan["caps"],
                    plan["valid"],
                    plan["n_phases"],
                    _cap_matrix(
                        plan["perms"], plan["caps"], plan["valid"],
                        plan["n_phases"],
                    ),
                )

            if cfg.regime_slots > 0:
                return jax.lax.cond(warm, warm_take, cold, None)
            return cold(None)

        def keep(_):
            return (
                state.perms, state.caps, state.valid, state.n_phases,
                state.capmat,
            )

        perms, caps, valid, n_phases, capmat = jax.lax.cond(
            fire, replan, keep, None
        )
        dropped_total = (
            jnp.float32(0.0)
            if dropped is None
            else jnp.asarray(dropped, jnp.float32).sum()
        )
        routed = traffic.sum()
        spike = dropped_total > cfg.drop_spike_frac * jnp.maximum(routed, 1.0)
        return DeviceControllerState(
            smoothed=smoothed,
            perms=perms,
            caps=caps,
            valid=valid,
            n_phases=n_phases,
            capmat=capmat,
            link_mask=state.link_mask,
            steps=state.steps + 1,
            cooldown=jnp.where(fire, jnp.int32(cfg.cooldown), cooldown),
            drift_streak=jnp.where(fire, 0, streak),
            replans=state.replans + fire.astype(jnp.int32),
            drop=drop,
            drop_spikes=state.drop_spikes + spike.astype(jnp.int32),
            admitted_dropped=state.admitted_dropped + dropped_total,
            lib_ref=state.lib_ref,
            lib_perms=state.lib_perms,
            lib_caps=state.lib_caps,
            lib_valid=state.lib_valid,
            lib_n_phases=state.lib_n_phases,
            lib_size=state.lib_size,
            warm_swaps=state.warm_swaps + (fire & warm).astype(jnp.int32),
        )

    # ----------------------------------------------------------- incident
    def set_link_mask(
        self, state: DeviceControllerState, link_mask
    ) -> DeviceControllerState:
        """Adopt a new availability mask and re-plan immediately.

        Incident handling is host-driven (the health FSM decides), so
        this is a host-called helper: one batched device re-plan under
        the new mask, cooldown restarted.  The emitted table has the
        same shapes/envelope — swapping it into the step is compile-free.
        """
        cfg = self.cfg
        mask = jnp.asarray(link_mask, bool)
        routable = apply_link_mask_traced(state.smoothed, mask)
        plan = greedy_phases_jax(
            routable,
            k_max=cfg.k_max,
            quantum=cfg.quantum,
            min_cap=cfg.min_cap,
            slack=cfg.slack,
            mask=mask,
            max_rounds=cfg.max_rounds,
        )
        return dataclasses.replace(
            state,
            perms=plan["perms"],
            caps=plan["caps"],
            valid=plan["valid"],
            n_phases=plan["n_phases"],
            capmat=_cap_matrix(
                plan["perms"], plan["caps"], plan["valid"], plan["n_phases"]
            ),
            link_mask=mask,
            cooldown=jnp.int32(cfg.cooldown),
            drift_streak=jnp.int32(0),
            replans=state.replans + 1,
        )

    # ------------------------------------------------------------ metrics
    def metrics(self, state: DeviceControllerState) -> dict:
        """Host fetch of the controller telemetry — call on the logging
        cadence, never per step (this is the one device→host sync)."""
        return {
            "steps": int(state.steps),
            "device_replans": int(state.replans),
            "drop_fraction": float(state.drop),
            "drift_streak": int(state.drift_streak),
            "cooldown_left": int(state.cooldown),
            "drop_spikes": int(state.drop_spikes),
            "admitted_dropped": float(state.admitted_dropped),
            "link_masked": bool((~np.asarray(state.link_mask)).any()),
            "regime_library_size": int(state.lib_size),
            "regime_warm_swaps": int(state.warm_swaps),
        }
