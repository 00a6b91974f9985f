"""Scheduler fast-path benchmark: vectorized selector scoring + warm-started
batched decomposition vs the seed implementations, plus the end-to-end
controller loop under drifting traffic.

The measurements mirror the controller's hot paths:

* **observe steady-state** — ``ScheduleSelector.observe`` is called every
  training step with the realized routing counts; in steady state it only
  has to confirm the current schedule still serves.  Seed: a Python loop
  over the schedule's phases.  Fast: one vectorized clamp against the
  entry's precomputed ``[n, n]`` capacity matrix.
* **batched maxweight re-plan** — at a traffic-drift event the controller
  re-decomposes one matrix per MoE layer.  Seed: cold greedy max-weight
  per layer (one LAP solve per phase).  Fast:
  ``maxweight_decompose_batch`` warm-started from the previous step's
  matchings — steady-state support is unchanged, so the replay needs no
  LAP solves at all.  (Cold-vs-cold is also reported: the LAP solves
  dominate there, so it is roughly parity by construction — the cold fast
  path is bit-identical to the seed.)

* **controller end-to-end** — ``ScheduleRuntime.observe`` every step over
  a drifting traffic stream (regime shift + hotspot): the realistic
  observe+re-plan overhead the training loop pays per step, with the
  warm/cold plan split per drift event.

* **grouped launch** — one fused expert-FFN pass over the concatenated
  phase blocks vs K per-phase GEMMs (the ``ScheduleTable`` execution
  path vs the old per-phase fragmentation), plus the fraction of MXU row
  blocks the Pallas kernel's group-metadata prologue skips.

* **fault resilience** — the controller's observe cost and the ragged
  fabric's bytes per rank in the steady state vs under a 15% link
  outage (availability mask adopted), plus the one-shot masked re-plan
  cost — the degraded-fabric trend PR over PR (docs/robustness.md).

Parity is asserted inline (identical chosen entries / drop fractions,
bit-identical cold phases, warm replay delivering all demand).  Results
land in ``BENCH_scheduler.json`` at the repo root: the top-level fields
always describe the LATEST run, and every run also appends a timestamped
entry to the ``history`` list so the perf trajectory is tracked PR over
PR (ROADMAP: "persist trend lines").

Usage: PYTHONPATH=src python -m benchmarks.bench_scheduler
"""

from __future__ import annotations

import json
import os
import subprocess
import time

import numpy as np

from repro.core.maxweight import (
    maxweight_decompose_batch,
    maxweight_decompose_reference,
    warm_state_of,
)
from repro.core.selector import ScheduleSelector
from repro.core.traffic import RouterConfig, traffic_matrix

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_scheduler.json")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_sha() -> str | None:
    """Short SHA of HEAD, so history entries are attributable to a PR."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def _tier1_test_count() -> int | None:
    """Tier-1 test count for history attribution.

    REPRO_TIER1_COUNT (CI sets it to the passing count of the run that
    just gated this benchmark); None where it is unset.  No pytest child
    is started: this process has already used JAX, and a child that
    imports it would contend for the same accelerator."""
    env = os.environ.get("REPRO_TIER1_COUNT")
    try:
        return int(env) if env else None
    except ValueError:
        return None

N_RANKS = 64
LIBRARY = 8
LAYERS = 16


def _regime(seed: int, n: int = N_RANKS) -> np.ndarray:
    rng = np.random.default_rng(seed)
    router = RouterConfig("bench", n * 4, 2)
    return traffic_matrix(
        rng, router, np.full(n, 2048), n_ranks=n, skew_alpha=0.3
    )


def _reference_observe(sel: ScheduleSelector, smoothed, current, traffic):
    """The seed ``observe`` semantics (per-phase drop loops), run against
    the same library as the fast selector.  Returns the updated
    (smoothed, current, changed) without mutating the selector."""
    t = np.asarray(traffic, dtype=np.float64)
    smoothed = (
        t.copy()
        if smoothed is None
        else (1 - sel.ema) * smoothed + sel.ema * t
    )
    if current is not None:
        if current.drop_fraction_reference(smoothed) <= sel.drop_tolerance:
            return smoothed, current, False
    best, best_drop = None, float("inf")
    for e in sel.library:
        dr = e.drop_fraction_reference(smoothed)
        if dr < best_drop:
            best, best_drop = e, dr
    changed = best is not current
    return smoothed, best, changed


def bench_observe(steps: int = 200) -> dict:
    """Steady-state observe: library of LIBRARY regimes, live traffic
    jittering around regime 0."""
    regimes = [_regime(s) for s in range(LIBRARY)]
    sel = ScheduleSelector(N_RANKS, ema=1.0, drop_tolerance=0.05)
    for m in regimes:
        sel._plan(m, f"regime{len(sel.library)}")
    sel.current = sel.library[0]
    sel.ema = 0.3

    rng = np.random.default_rng(1)
    base = regimes[0]
    stream = [
        base * (1 + 0.02 * rng.standard_normal(base.shape)) for _ in range(steps)
    ]
    stream = [np.maximum(s, 0.0) for s in stream]

    # parity first: both paths must pick the same entries + drops
    smoothed, current = None, sel.library[0]
    sel_fast = ScheduleSelector(N_RANKS, ema=0.3, drop_tolerance=0.05)
    sel_fast.library = sel.library
    sel_fast.current = sel.library[0]
    for t in stream[:50]:
        smoothed, current, _ = _reference_observe(sel, smoothed, current, t)
        entry, _ = sel_fast.observe(t)
        assert entry is current, "fast selector diverged from reference"
        ref_drop = current.drop_fraction_reference(smoothed)
        fast_drop = current.drop_fraction(sel_fast.smoothed)
        assert ref_drop == fast_drop, (ref_drop, fast_drop)

    # timed: seed loop
    smoothed, current = None, sel.library[0]
    t0 = time.perf_counter()
    for t in stream:
        smoothed, current, _ = _reference_observe(sel, smoothed, current, t)
    t1 = time.perf_counter()
    # timed: fast selector
    sel_fast.smoothed = None
    sel_fast.current = sel.library[0]
    t2 = time.perf_counter()
    for t in stream:
        sel_fast.observe(t)
    t3 = time.perf_counter()

    seed_us = (t1 - t0) / steps * 1e6
    fast_us = (t3 - t2) / steps * 1e6
    return {
        "n": N_RANKS,
        "library": LIBRARY,
        "steps": steps,
        "seed_us_per_step": round(seed_us, 2),
        "fast_us_per_step": round(fast_us, 2),
        "speedup": round(seed_us / fast_us, 1),
        "parity": True,
    }


def bench_maxweight(reps: int = 5) -> dict:
    """Batched re-plan of LAYERS layer matrices at a steady-state drift
    event (support unchanged, weights jittered)."""
    rng = np.random.default_rng(2)
    mats = np.stack([_regime(100 + i).astype(np.float64) for i in range(LAYERS)])
    for i in range(LAYERS):
        np.fill_diagonal(mats[i], 0.0)

    # previous step's decompositions -> warm states
    prev = maxweight_decompose_batch(mats)
    states = [warm_state_of(d) for d in prev]
    drifted = mats * (1 + 0.02 * rng.random(mats.shape))
    drifted *= mats > 0  # steady state: support unchanged

    # parity: cold fast path is bit-identical to the seed implementation
    for i in range(LAYERS):
        ref = maxweight_decompose_reference(drifted[i])
        fast = maxweight_decompose_batch(drifted[i][None, :, :])[0]
        assert ref.num_phases == fast.num_phases
        for pr, pf in zip(ref.phases, fast.phases):
            assert np.array_equal(pr.perm, pf.perm)
            assert np.array_equal(pr.sent, pf.sent)
            assert np.array_equal(pr.alloc, pf.alloc)

    # seed: cold per-layer decomposition at every drift event
    t0 = time.perf_counter()
    for _ in range(reps):
        seed_ds = [maxweight_decompose_reference(drifted[i]) for i in range(LAYERS)]
    t1 = time.perf_counter()
    # fast: warm-started batch
    t2 = time.perf_counter()
    for _ in range(reps):
        warm_ds = maxweight_decompose_batch(drifted, warm_start=states)
    t3 = time.perf_counter()
    # cold fast batch, for the honest LAP-bound comparison
    t4 = time.perf_counter()
    for _ in range(reps):
        maxweight_decompose_batch(drifted)
    t5 = time.perf_counter()

    assert all(d.meta["warm_hit"] for d in warm_ds)
    for d, s in zip(warm_ds, seed_ds):
        d.verify()  # warm replay delivers all demand
        assert d.sent_total().sum() == s.sent_total().sum() or np.isclose(
            d.sent_total().sum(), s.sent_total().sum()
        )

    seed_ms = (t1 - t0) / reps * 1e3
    warm_ms = (t3 - t2) / reps * 1e3
    cold_ms = (t5 - t4) / reps * 1e3
    return {
        "layers": LAYERS,
        "n": N_RANKS,
        "reps": reps,
        "seed_ms": round(seed_ms, 2),
        "fast_warm_ms": round(warm_ms, 3),
        "fast_cold_ms": round(cold_ms, 2),
        "speedup": round(seed_ms / warm_ms, 1),
        "cold_speedup": round(seed_ms / cold_ms, 2),
        "cold_bit_identical": True,
        "warm_delivers_all_demand": True,
    }


def bench_controller(steps: int = 240) -> dict:
    """End-to-end controller loop under drift: a regime shift at
    steps/3 and an expert hotspot at 2*steps/3 stream through
    ``ScheduleRuntime.observe`` (per-layer grouping), measuring the
    observe+re-plan overhead the training loop pays per step.

    The host timer splits into ``fetch_us_per_step`` (materializing the
    device stats on the host) and ``score_us_per_step`` (EMA + selector
    scoring), and the same stream then drives the device-resident
    controller (PR 7): ``device_observe_us_per_step`` is the jitted
    observe -> score step cost with the re-plan branch untaken
    (acceptance: <= 100 us/step at this config), ``device_replan_ms``
    the one-shot batched JAX LAP re-plan of all layers."""
    from repro.core.drift import DriftScenario
    from repro.core.runtime import ControllerConfig, ScheduleRuntime

    n, e, layers = 16, 64, 8
    runtime = ScheduleRuntime(
        ControllerConfig(
            n_ranks=n, n_experts=e, ema=0.5, cooldown=5, group_by="layer"
        ),
        layers,
    )
    shift = DriftScenario("shift", e, shift_step=steps // 3, seed=3)
    hot = DriftScenario(
        "hotspot", e, shift_step=2 * steps // 3, window=steps, seed=3
    )
    rng = np.random.default_rng(4)
    tokens = 2048.0 * n

    stream = []
    for t in range(steps):
        probs = hot.expert_probs(t) if t >= 2 * steps // 3 else shift.expert_probs(t)
        noise = 1 + 0.02 * rng.standard_normal((layers, 1, e))
        stream.append(np.maximum(tokens * probs[None, None, :] * noise, 0.0))

    t0 = time.perf_counter()
    swaps = 0
    for t, stats in enumerate(stream):
        decision = runtime.observe(stats)
        swaps += bool(decision.changed)
    total_s = time.perf_counter() - t0

    s = runtime.summary()
    assert s["replan_events"] >= 2, s  # both drift events must register
    assert s["decompose_calls"] == s["replan_events"], s
    assert s["warm_hits"] > 0, s  # steady-state re-plans hit the warm path

    # ---- device-resident controller over the same stream (PR 7) ----
    import jax
    import jax.numpy as jnp

    from repro.core import DeviceController

    ctrl, state = DeviceController.from_runtime(runtime)

    # the controller rides the fused train step, so its in-graph cost is
    # what matters — model that with ONE executable scanning the stream
    # (a per-call Python loop would mostly time jit dispatch overhead)
    @jax.jit
    def run_stream(st, stats_seq):
        return jax.lax.scan(
            lambda s, x: (ctrl.step(s, x), ()), st, stats_seq
        )[0]

    # steady-state row: a driftless stream (same regime, same noise) —
    # the re-plan branch must stay untaken, so this times exactly the
    # per-step observe -> score overhead the fused train step carries
    base = shift.expert_probs(0)
    steady_seq = jnp.asarray(
        np.stack(
            [
                np.maximum(
                    tokens
                    * base[None, None, :]
                    * (1 + 0.02 * rng.standard_normal((layers, 1, e))),
                    0.0,
                )
                for _ in range(steps)
            ]
        ),
        jnp.float32,
    )
    drift_seq = jnp.asarray(np.stack(stream), jnp.float32)
    # compile + let the controller adapt to the steady regime (the host
    # runtime's EMA ended on the hotspot regime, so the first pass may
    # legitimately re-plan once)
    state = run_stream(state, steady_seq)
    jax.block_until_ready(state)
    replans_before = int(state.replans)
    t0 = time.perf_counter()
    end_state = run_stream(state, steady_seq)
    jax.block_until_ready(end_state)
    device_us = (time.perf_counter() - t0) / steps * 1e6
    assert int(end_state.replans) == replans_before, (
        "steady stream must not fire the re-plan branch"
    )
    # acceptance: the on-device steady-state observe must be
    # decode-latency compatible at this config
    assert device_us <= 100, f"device observe {device_us:.1f}us/step > 100us"
    # the drift stream through the same executable: in-graph re-plans
    # fire (hysteresis-gated), zero recompiles
    drift_end = run_stream(end_state, drift_seq)
    device_replans = int(drift_end.replans) - replans_before
    assert device_replans >= 1, "drift must fire the in-graph re-plan"
    cache = getattr(run_stream, "_cache_size", lambda: 1)()
    assert cache == 1, f"in-graph re-plans must not retrace ({cache})"
    state = drift_end
    # one-shot cost of the drift-triggered branch: a full batched-LAP
    # re-plan of every layer under the current mask (set_link_mask runs
    # exactly that path host-called)
    mask = np.asarray(state.link_mask)
    ctrl.set_link_mask(state, mask)  # warm-up compile
    t0 = time.perf_counter()
    jax.block_until_ready(ctrl.set_link_mask(state, mask).perms)
    device_replan_ms = (time.perf_counter() - t0) * 1e3

    return {
        "n": n,
        "experts": e,
        "layers": layers,
        "steps": steps,
        "total_us_per_step": round(total_s / steps * 1e6, 2),
        "observe_us_per_step": s["observe_us_per_step"],
        "fetch_us_per_step": s["fetch_us_per_step"],
        "score_us_per_step": s["score_us_per_step"],
        "replan_ms_per_event": s["replan_ms_per_event"],
        "replan_events": s["replan_events"],
        "decompose_calls": s["decompose_calls"],
        "warm_hits": s["warm_hits"],
        "cold_plans": s["cold_plans"],
        "swaps": swaps,
        "device_observe_us_per_step": round(device_us, 2),
        "device_replan_ms": round(device_replan_ms, 2),
        "device_replans": device_replans,
    }


def bench_grouped_launch(reps: int = 30) -> dict:
    """Grouped-launch vs per-phase expert GEMM — the compute-fragmentation
    cost the ``ScheduleTable`` path removes.

    A skewed K-phase schedule hands the expert FFN K small [E, C_k, d]
    blocks; the array-native path concatenates them into ONE [E, sum C_k,
    d] launch (with the Pallas kernel's group-metadata prologue skipping
    row blocks that hold no admitted tokens).  Timed through XLA (the
    einsum path — the interpret-mode Pallas kernel cannot be timed
    honestly on CPU); the additional skip-fraction field is a *derived*
    structural number at a stated hypothetical occupancy, not a
    measurement (TPU numbers pending)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.moe_gemm import moe_gemm_ref

    e, d, f = 8, 256, 512
    caps = [8, 8, 16, 16, 24, 32, 64, 88]  # K=8 phases, skewed (Fig 2 shape)
    key = jax.random.PRNGKey(0)
    blocks = [
        jax.random.normal(jax.random.fold_in(key, i), (e, c, d), jnp.float32)
        for i, c in enumerate(caps)
    ]
    wg = jax.random.normal(jax.random.PRNGKey(1), (e, d, f), jnp.float32) * 0.05
    wu = jax.random.normal(jax.random.PRNGKey(2), (e, d, f), jnp.float32) * 0.05
    wd = jax.random.normal(jax.random.PRNGKey(3), (e, f, d), jnp.float32) * 0.05
    x_cat = jnp.concatenate(blocks, axis=1)

    per_phase = jax.jit(
        lambda bs, wg, wu, wd: [moe_gemm_ref(b, wg, wu, wd) for b in bs]
    )
    grouped = jax.jit(moe_gemm_ref)

    jax.block_until_ready(per_phase(blocks, wg, wu, wd))
    jax.block_until_ready(grouped(x_cat, wg, wu, wd))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(per_phase(blocks, wg, wu, wd))
    t1 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(grouped(x_cat, wg, wu, wd))
    t2 = time.perf_counter()

    # parity: the grouped result is the per-phase results, concatenated
    y_pp = jnp.concatenate(per_phase(blocks, wg, wu, wd), axis=1)
    assert bool(jnp.allclose(y_pp, grouped(x_cat, wg, wu, wd), atol=1e-5))

    # structural (not measured) companion number: at a hypothetical 40%
    # contiguous slot occupancy per expert and BC=64, the fraction of MXU
    # row blocks the kernel's metadata prologue would skip.  Clearly
    # labeled as derived — the timing above is the XLA einsum path.
    c_tot = int(x_cat.shape[1])
    bc = 64
    occ_frac = 0.4
    blocks_total = c_tot // bc
    blocks_live = -(-int(occ_frac * c_tot) // bc)
    per_us = (t1 - t0) / reps * 1e6
    grp_us = (t2 - t1) / reps * 1e6
    return {
        "experts": e,
        "d": d,
        "f": f,
        "phases": len(caps),
        "tokens_per_expert": c_tot,
        "per_phase_us": round(per_us, 1),
        "grouped_us": round(grp_us, 1),
        "speedup": round(per_us / grp_us, 2),
        "launches_per_phase_path": len(caps),
        "launches_grouped": 1,
        "meta_skip_fraction_at_40pct_occupancy": round(
            1 - blocks_live / blocks_total, 3
        ),
        "parity": True,
    }


def bench_bytes_moved() -> dict:
    """Dark-fiber bytes per dispatch fabric for one skewed MoE layer.

    Derived (not timed) from the plan, via each registered fabric's own
    ``dispatch_tokens`` accounting — the number its wire actually
    carries per rank per layer:

    * **a2a** — every remote pair padded to the uniform bucket, sized
      no-drop (``max(cap_uni, hottest planned pair)``, what the static
      path does): ``(n-1) * that`` slots per rank.
    * **ppermute** — the plan's own caps (the floor baking the plan into
      the executable achieves; dark pairs ship nothing).
    * **phase_pipelined** — the live plan bytes: ``envelope[k]`` slots
      per live phase slot, zero on dark pairs (what the plan asks the
      wire to carry).  Its dense *emulation* additionally pads every
      live phase onto a full all_to_all buffer — ``(n-1) * envelope[k]``
      per live phase slot; that emulation tax is reported side by side
      under ``fabrics_padded`` instead of masquerading as plan traffic
      (it used to inflate this row ~39x on this config).
    * **ragged_a2a** — exactly the live envelope bytes per pair (the
      ``phase_env`` legacy metric): the ragged transfer's send/recv
      sizes are zero on dark pairs, so the TPU wire matches what a
      circuit fabric would carry.
    * **dense** — zero dispatch bytes (it pays a [T, d] all-reduce
      instead, reported separately as ``dense_allreduce_mb_per_rank``).
    * **hierarchical** — the same draw planned two-level (schema v5):
      pod-local traffic on the electrical intra fabric, the off-block
      remainder on the circuit-scheduled inter fabric; reported as an
      ``{"intra", "inter"}`` split.  Acceptance: the inter row must not
      exceed the off-block-diagonal share of ``ragged_a2a``'s bytes —
      planning only the seam-crossing demand can't cost more wire than
      the flat plan already spends crossing the seam.

    The legacy ``monolithic/phase_env/static_ppermute`` keys are kept so
    the PR-over-PR trend lines stay continuous.
    """
    from repro.core import (
        WIRE_DTYPES,
        a2a_dispatch_tokens,
        decompose,
        hierarchical_plan,
        phase_dispatch_tokens,
        phase_envelope,
        plan_schedule,
        wire_bytes_per_token,
    )
    from repro.parallel.fabric import get_fabric

    n, d_model, dtype_bytes = 16, 4096, 2
    tokens_per_rank = 2048
    # heavily skewed demand (dirichlet alpha 0.05) — the regime where the
    # paper's decomposition matters: a few hot pairs, many near-dark ones
    rng = np.random.default_rng(7)
    router = RouterConfig("bench-bytes", n * 4, 2)
    regime = traffic_matrix(
        rng, router, np.full(n, tokens_per_rank), n_ranks=n, skew_alpha=0.05
    )
    sched = plan_schedule(decompose(regime, "maxweight", min_fill=0.1))
    env = phase_envelope([sched], sched.num_phases, slack=1.5)

    cap_uni = max(8, -(-tokens_per_rank // n // 8) * 8)  # capacity factor 1.0
    cap_nodrop = max(cap_uni, int(sched.pair_capacity()))
    mono = a2a_dispatch_tokens(n, cap_nodrop)
    phase = phase_dispatch_tokens(sched.valid, env)
    static = phase_dispatch_tokens(sched.valid, sched.caps)
    token_b = d_model * dtype_bytes
    to_mb = lambda t: round(float(np.mean(t)) * token_b / 2**20, 3)
    fabric_tokens = {
        "dense": get_fabric("dense").dispatch_tokens(n=n),
        "a2a": get_fabric("a2a").dispatch_tokens(n=n, cap_uniform=cap_nodrop),
        "ppermute": get_fabric("ppermute").dispatch_tokens(
            n=n, schedule=sched
        ),
        "phase_pipelined": get_fabric("phase_pipelined").dispatch_tokens(
            n=n, schedule=sched, envelope=env
        ),
        "ragged_a2a": get_fabric("ragged_a2a").dispatch_tokens(
            n=n, schedule=sched, envelope=env
        ),
    }
    # hierarchical (schema v5): same draw planned two-level with the
    # SAME decomposition knobs as the flat plan (min_fill prunes low-
    # fill phases at both levels); each level's own envelope rides its
    # child table, the composed fabric sums them
    pod_size = 4
    htab = hierarchical_plan(
        regime, pod_size, n_layers=1,
        decompose_kwargs={"min_fill": 0.1},
    )
    hier_tokens = get_fabric("hierarchical").dispatch_tokens_split(
        n=n, schedule=htab.row(0)
    )
    # the off-block-diagonal share of the flat ragged plan: envelope
    # slots whose live phase permutation crosses the pod seam — the wire
    # budget the flat plan already spends on inter-host traffic
    pod_of = np.arange(n) // pod_size
    cross = pod_of[np.asarray(sched.perms)] != pod_of[None, :]
    off_block = phase_dispatch_tokens(np.asarray(sched.valid) & cross, env)
    # the single-device dense emulation's padded figure, side by side
    # with the live plan bytes (the gap is the emulation tax)
    padded_tokens = {
        "phase_pipelined": get_fabric(
            "phase_pipelined"
        ).dispatch_tokens_padded(n=n, envelope=env),
    }
    # per-wire-dtype rows (schema v4): the same slot counts priced at
    # each registered codec's wire format (payload + per-slot scale
    # sidecar) — the bf16 row reproduces the legacy ``fabrics`` table.
    # The hierarchical split prices like the fabric's dispatch_bytes:
    # intra slots always ride the electrical links at compute width
    # (the codec never touches them), only inter slots take the codec.
    def _wire_row(w: str) -> dict:
        at = lambda t, fmt: round(
            float(np.mean(t))
            * wire_bytes_per_token(d_model, fmt, dtype_bytes)
            / 2**20,
            3,
        )
        row = {k: at(v, w) for k, v in fabric_tokens.items()}
        row["hierarchical"] = {
            "intra": at(hier_tokens["intra"], "bf16"),
            "inter": at(hier_tokens["inter"], w),
        }
        return row

    wire_mb = {w: _wire_row(w) for w in sorted(WIRE_DTYPES)}
    out = {
        "n": n,
        "phases": sched.num_phases,
        "tokens_per_rank": tokens_per_rank,
        "d_model": d_model,
        "monolithic_mb_per_rank": to_mb(mono),
        "phase_env_mb_per_rank": to_mb(phase),
        "static_ppermute_mb_per_rank": to_mb(static),
        "saving_vs_monolithic": round(
            1.0 - float(np.mean(phase)) / mono, 3
        ),
        "envelope_overhead_vs_static": round(
            float(np.mean(phase)) / max(float(np.mean(static)), 1e-9), 3
        ),
        # per-fabric rows via the registry's own accounting (schema v2;
        # the hierarchical intra/inter split is the schema v5 addition)
        "fabrics": {
            **{k: to_mb(v) for k, v in fabric_tokens.items()},
            "hierarchical": {
                "intra": to_mb(hier_tokens["intra"]),
                "inter": to_mb(hier_tokens["inter"]),
            },
        },
        "pod_size": pod_size,
        "ragged_off_block_mb_per_rank": to_mb(off_block),
        # dense-emulation padded bytes next to the live rows (schema v3)
        "fabrics_padded": {k: to_mb(v) for k, v in padded_tokens.items()},
        # per-wire-dtype bytes rows (schema v4)
        "wire": wire_mb,
        "dense_allreduce_mb_per_rank": round(
            tokens_per_rank * n * token_b / 2**20, 3
        ),
        "derived": True,  # modeled circuit bytes, not a wire measurement
    }
    assert out["phase_env_mb_per_rank"] < out["monolithic_mb_per_rank"], out
    assert (
        out["static_ppermute_mb_per_rank"] <= out["phase_env_mb_per_rank"]
    ), out
    # acceptance: both traced fabrics report the live envelope byte
    # count (they execute the same plan; only the emulation pads),
    # strictly below the monolithic a2a no-drop bucket on this skewed
    # draw, and the padded emulation figure strictly above the live one
    fx = out["fabrics"]
    assert fx["ragged_a2a"] == out["phase_env_mb_per_rank"], out
    assert fx["phase_pipelined"] == out["phase_env_mb_per_rank"], out
    assert fx["ragged_a2a"] < fx["a2a"], out
    assert fx["a2a"] == out["monolithic_mb_per_rank"], out
    assert fx["ppermute"] <= fx["ragged_a2a"], out
    assert out["fabrics_padded"]["phase_pipelined"] > fx["phase_pipelined"], out
    # acceptance: quantized wire rows at or below 0.55x the bf16
    # envelope bytes on this skewed draw (payload 8x smaller, the f32
    # per-slot scale sidecar accounted honestly), bf16 row unchanged
    assert out["wire"]["bf16"] == fx, out
    for w in ("fp8", "int8"):
        assert (
            out["wire"][w]["ragged_a2a"]
            <= 0.55 * out["wire"]["bf16"]["ragged_a2a"]
        ), out
    # acceptance (schema v5): planning only the seam-crossing demand
    # must not cost more inter-host wire than the flat ragged plan
    # already spends crossing the seam on this skewed draw — and that
    # off-block share is itself a fraction of the full ragged row
    hier = fx["hierarchical"]
    assert hier["inter"] <= out["ragged_off_block_mb_per_rank"], out
    assert out["ragged_off_block_mb_per_rank"] <= fx["ragged_a2a"], out
    # the codec prices only the inter seam: intra is bf16 under every
    # wire dtype, inter shrinks with the quantized payload
    for w in ("fp8", "int8"):
        assert out["wire"][w]["hierarchical"]["intra"] == hier["intra"], out
        assert out["wire"][w]["hierarchical"]["inter"] < hier["inter"], out
    return out


def bench_faults(steps: int = 120) -> dict:
    """Resilience trend (PR 6): what a link outage costs the controller.

    Three numbers, steady vs degraded:

    * **observe us/step** — the per-step controller overhead before the
      outage vs after the availability mask is adopted (masked re-plans
      route around the dark pairs, so the hot path must stay hot).
    * **masked re-plan ms** — the one-shot cost of adopting the mask:
      ``set_link_mask`` forces a full re-plan of every layer group under
      the mask plus the table rebuild.
    * **MB/rank** — ragged-fabric bytes of the preferred plan vs the
      masked plan for the same skewed regime (``apply_link_mask``
      preserves row sums, so the wire carries the same demand over fewer
      pairs; the delta is capacity rounding + extra phases).
    """
    from repro.core import (
        FaultScenario,
        check_schedule_mask,
        decompose,
        phase_envelope,
        plan_schedule,
    )
    from repro.core.runtime import ControllerConfig, ScheduleRuntime
    from repro.parallel.fabric import get_fabric

    n, e, layers = 16, 64, 8
    scenario = FaultScenario(
        "dead_link", n_ranks=n, onset=0, outage_frac=0.15, seed=5
    )
    mask = scenario.link_mask(0)

    runtime = ScheduleRuntime(
        ControllerConfig(
            n_ranks=n, n_experts=e, ema=0.5, cooldown=5, group_by="layer"
        ),
        layers,
    )
    rng = np.random.default_rng(6)
    tokens = 2048.0 * n
    probs = rng.dirichlet(np.full(e, 0.5))
    stream = [
        np.maximum(
            tokens
            * probs[None, None, :]
            * (1 + 0.02 * rng.standard_normal((layers, 1, e))),
            0.0,
        )
        for _ in range(2 * steps)
    ]

    warm = 10
    for t in stream[:warm]:
        runtime.observe(t)  # settle the EMA + first plan
    t0 = time.perf_counter()
    for t in stream[warm:steps]:
        runtime.observe(t)
    steady_s = (time.perf_counter() - t0) / (steps - warm)

    t0 = time.perf_counter()
    runtime.set_link_mask(mask)
    runtime.table()
    replan_ms = (time.perf_counter() - t0) * 1e3

    t0 = time.perf_counter()
    for t in stream[steps:]:
        runtime.observe(t)
    degraded_s = (time.perf_counter() - t0) / steps

    # the masked plans must never route a dark pair (raises on violation)
    check_schedule_mask(runtime.schedules, mask, backend="phase_pipelined")
    m = runtime.metrics()
    assert m["masked_replans"] >= 1 and m["link_masked"], m

    # bytes: the same skewed regime planned free vs under the mask,
    # through the ragged fabric's own live-envelope accounting
    d_model, dtype_bytes = 4096, 2
    regime = traffic_matrix(
        np.random.default_rng(7),
        RouterConfig("bench-faults", n * 4, 2),
        np.full(n, 2048),
        n_ranks=n,
        skew_alpha=0.05,
    )
    d_free = decompose(regime, "maxweight", min_fill=0.1)
    d_mask = decompose(regime, "maxweight", min_fill=0.1, link_mask=mask)
    s_free = plan_schedule(d_free)
    s_mask = plan_schedule(d_mask)
    check_schedule_mask(s_mask, mask, backend="ragged_a2a")
    ragged = get_fabric("ragged_a2a")
    to_mb = lambda t: round(
        float(np.mean(t)) * d_model * dtype_bytes / 2**20, 3
    )
    free_mb = to_mb(
        ragged.dispatch_tokens(
            n=n,
            schedule=s_free,
            envelope=phase_envelope([s_free], s_free.num_phases, slack=1.5),
        )
    )
    mask_mb = to_mb(
        ragged.dispatch_tokens(
            n=n,
            schedule=s_mask,
            envelope=phase_envelope([s_mask], s_mask.num_phases, slack=1.5),
        )
    )
    return {
        "n": n,
        "experts": e,
        "layers": layers,
        "steps": steps,
        "outage_frac": scenario.outage_frac,
        "dark_pairs": len(scenario.dead_pairs),
        "steady_us_per_step": round(steady_s * 1e6, 2),
        "degraded_us_per_step": round(degraded_s * 1e6, 2),
        "masked_replan_ms": round(replan_ms, 2),
        "steady_mb_per_rank": free_mb,
        "degraded_mb_per_rank": mask_mb,
        "steady_phases": s_free.num_phases,
        "degraded_phases": s_mask.num_phases,
        "unroutable_tokens": float(
            d_mask.meta.get("unroutable_tokens", 0.0)
        ),
        "masked_plans_avoid_dark_pairs": True,
    }


def run() -> dict:
    from benchmarks.bench_schema import (
        SCHEMA_VERSION,
        validate_document,
        validate_entry,
    )

    results = {
        "observe_steady_state": bench_observe(),
        "maxweight_batch": bench_maxweight(),
        "controller": bench_controller(),
        "grouped_launch": bench_grouped_launch(),
        "bytes_moved": bench_bytes_moved(),
        "faults": bench_faults(),
    }
    results["meta"] = {
        "unit_note": "observe in us/step; decomposition in ms per re-plan "
        "event (16-layer stack); controller in us/step end-to-end; "
        "grouped_launch in us per expert-FFN pass",
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "git_sha": _git_sha(),
        "tier1_tests": _tier1_test_count(),
    }
    # Trend lines: keep the latest run at the top level, append every run
    # to the history list (prior history is preserved across runs).  Each
    # entry is stamped with the git SHA + tier-1 test count so the trend
    # line is attributable PR over PR.
    prior = []
    if os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH) as f:
                prior = json.load(f).get("history", [])
        except (json.JSONDecodeError, OSError):
            prior = []
    entry = {
        "timestamp": results["meta"]["timestamp"],
        "schema_version": SCHEMA_VERSION,
        "git_sha": results["meta"]["git_sha"],
        "tier1_tests": results["meta"]["tier1_tests"],
        "observe_steady_state": results["observe_steady_state"],
        "maxweight_batch": results["maxweight_batch"],
        "controller": results["controller"],
        "grouped_launch": results["grouped_launch"],
        "bytes_moved": results["bytes_moved"],
        "faults": results["faults"],
    }
    # schema-gate the append BEFORE touching the file: a malformed entry
    # must fail the bench (and CI), never corrupt the trajectory
    errors = validate_entry(entry, "new entry", require_current=True)
    results["history"] = prior + [entry]
    errors += validate_document({"history": results["history"]})
    if errors:
        raise RuntimeError(
            "refusing to append malformed benchmark history:\n  "
            + "\n  ".join(errors)
        )
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2)
    obs, mw = results["observe_steady_state"], results["maxweight_batch"]
    ctl = results["controller"]
    print(
        f"observe steady-state: {obs['seed_us_per_step']}us -> "
        f"{obs['fast_us_per_step']}us  ({obs['speedup']}x)"
    )
    print(
        f"maxweight batch ({mw['layers']}x n={mw['n']}): {mw['seed_ms']}ms -> "
        f"warm {mw['fast_warm_ms']}ms ({mw['speedup']}x), "
        f"cold {mw['fast_cold_ms']}ms ({mw['cold_speedup']}x)"
    )
    print(
        f"controller ({ctl['layers']} layers, n={ctl['n']}): "
        f"{ctl['total_us_per_step']}us/step end-to-end, "
        f"{ctl['replan_events']} re-plan events "
        f"({ctl['warm_hits']} warm / {ctl['cold_plans']} cold), "
        f"re-plan {ctl['replan_ms_per_event']}ms/event"
    )
    print(
        f"device controller: host observe {ctl['observe_us_per_step']}us "
        f"(fetch {ctl['fetch_us_per_step']} + score "
        f"{ctl['score_us_per_step']}) -> on-device "
        f"{ctl['device_observe_us_per_step']}us/step "
        f"({ctl['device_replans']} in-graph re-plans, 0 recompiles; "
        f"batched-LAP re-plan {ctl['device_replan_ms']}ms one-shot)"
    )
    gl = results["grouped_launch"]
    print(
        f"grouped launch (E={gl['experts']}, {gl['phases']} phases): "
        f"per-phase {gl['per_phase_us']}us -> grouped {gl['grouped_us']}us "
        f"({gl['speedup']}x, {gl['launches_per_phase_path']} -> 1 launches; "
        f"derived: meta would skip "
        f"{gl['meta_skip_fraction_at_40pct_occupancy']:.0%} of row blocks "
        f"at 40% occupancy)"
    )
    bm = results["bytes_moved"]
    print(
        f"bytes moved (n={bm['n']}, {bm['phases']} phases, derived): "
        f"monolithic {bm['monolithic_mb_per_rank']}MB/rank -> phase-env "
        f"{bm['phase_env_mb_per_rank']}MB ({bm['saving_vs_monolithic']:.0%} "
        f"saved; static ppermute floor {bm['static_ppermute_mb_per_rank']}MB)"
    )
    fmt_row = lambda v: (
        "+".join(f"{lvl}:{mb}" for lvl, mb in v.items())
        if isinstance(v, dict)
        else v
    )
    print(
        "per-fabric MB/rank: "
        + ", ".join(f"{k}={fmt_row(v)}" for k, v in sorted(bm["fabrics"].items()))
        + f" (pod_size={bm['pod_size']}, ragged off-block share "
        f"{bm['ragged_off_block_mb_per_rank']}MB)"
    )
    ft = results["faults"]
    print(
        f"faults (n={ft['n']}, {ft['dark_pairs']} dark pairs): observe "
        f"{ft['steady_us_per_step']}us -> {ft['degraded_us_per_step']}us/step "
        f"degraded, masked re-plan {ft['masked_replan_ms']}ms one-shot; "
        f"bytes {ft['steady_mb_per_rank']}MB -> {ft['degraded_mb_per_rank']}MB"
        f"/rank ({ft['steady_phases']} -> {ft['degraded_phases']} phases)"
    )
    print(f"wrote {os.path.abspath(OUT_PATH)} ({len(results['history'])} history entries)")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
