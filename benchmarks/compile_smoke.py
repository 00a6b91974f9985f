"""Compile-count smoke: per-layer scheduled stacks must trace ONE layer
body, not depth-many — for EVERY registered dispatch fabric.

Array-native schedules (``core.ScheduleTable``) exist so per-layer plans
ride ``lax.scan`` — before them, distinct per-layer ``A2ASchedule``
objects forced the stack to unroll (HLO O(depth)) and every drift swap
recompiled.  This smoke guards the properties per fabric:

1. **O(period) HLO**: for each registered fabric, the lowered HLO of a
   depth-8 MoE model must contain a while loop (the scan) and the SAME
   number of dot ops as a depth-2 model — one traced period body
   regardless of depth.
2. **Zero-recompile swaps** (asserted on ``phase_pipelined``, the traced
   production backend): calling the jitted loss with a re-planned table
   (same shapes) must not grow the executable cache.
3. **Phase-envelope policy** (PR 4): tables carrying a phase envelope
   swap compile-free while plans fit the envelope (the envelope is
   static pytree aux, so it IS the cache key), and growing the envelope
   retraces exactly once — the one deliberate recompile of the
   phase-pipelined dispatch path.
4. **Adaptive envelope shrink** (PR 5): with
   ``ControllerConfig.envelope_decay`` a sustained-underused envelope
   shrinks, and the shrink costs exactly the same single recompile.
5. **Degraded-fabric swaps** (PR 6): adopting a link-availability mask
   (masked re-plan around dark pairs) and lifting it again are plain
   table swaps under the frozen envelope — the fault path costs ZERO
   recompiles end to end.
6. **Fused device-controller step** (PR 7): the train step with the
   in-graph observe -> score -> re-plan loop lowers to ONE executable
   and drift-triggered in-graph re-plans cause ZERO recompiles.
7. **Quantized-wire swaps** (PR 8): with ``MoECfg.wire_dtype="fp8"``
   the wire codec is static config (QDQ ops traced into the step, not
   traced data), so quantized phase_pipelined/ragged_a2a steps must
   swap re-planned tables at ZERO recompiles, exactly like bf16.
8. **Hierarchical dual-table swaps** (PR 9): a ``HierarchicalTable``
   carries BOTH levels' plans as one pytree (per-level envelopes are
   the static aux): an intra-only re-plan and a both-level re-plan must
   each swap into the jitted step at ZERO recompiles, and in the fused
   device-controller step an intra-only drift must fire only the intra
   ``lax.cond`` — the inter phase-plan leaves pass through untouched
   (no inter re-plan, no retrace).
9. **Serving engine executables** (PR 10): ``repro.serve.ServeEngine``
   compiles ONE decode executable for its slot batch and keeps it
   across continuous-batching admissions, slot recycling, drift-fired
   in-graph re-plans, AND schedule-regime warm swaps from the device
   state's regime library (prefill and admit stay at one executable
   per shape too).

Exit code != 0 on regression, so CI fails fast.

Usage: PYTHONPATH=src python -m benchmarks.compile_smoke
"""

from __future__ import annotations

import re
import sys

import jax
import numpy as np


def _model(n_layers: int, dispatch: str = "scheduled", wire_dtype: str = "bf16"):
    from repro.configs.base import ModelConfig, MoECfg
    from repro.models import Model

    return Model(
        ModelConfig(
            name=f"smoke-{dispatch}-{wire_dtype}-{n_layers}",
            family="moe",
            n_layers=n_layers,
            d_model=32,
            n_heads=4,
            n_kv_heads=2,
            d_ff=64,
            vocab_size=128,
            moe=MoECfg(
                n_experts=8, top_k=2, d_ff_expert=32, dispatch=dispatch,
                wire_dtype=wire_dtype,
            ),
            remat="none",
        )
    )


def _table(n_layers: int, n_ranks: int = 4, seed: int = 0, envelope=None):
    from repro.core import ScheduleTable, decompose, plan_schedule

    rng = np.random.default_rng(seed)
    scheds = []
    for _ in range(n_layers):
        m = rng.random((n_ranks, n_ranks)) * 500
        np.fill_diagonal(m, 0)
        scheds.append(plan_schedule(decompose(m, "maxweight")))
    return ScheduleTable.from_schedules(
        scheds, k_max=n_ranks, clip=True, envelope=envelope
    )


def _htraffics(n_layers: int, n_ranks: int = 4, seed: int = 0):
    rng = np.random.default_rng(seed)
    ms = []
    for _ in range(n_layers):
        m = rng.random((n_ranks, n_ranks)) * 500
        np.fill_diagonal(m, 0)
        ms.append(m)
    return np.stack(ms)


def _htable(n_layers: int, seed: int = 0, pod_size: int = 2):
    from repro.core import hierarchical_plan

    return hierarchical_plan(_htraffics(n_layers, seed=seed), pod_size)


def _schedule_for(fabric: str, n_layers: int):
    """A schedule the fabric consumes on a single device (where mesh
    fabrics run through the virtual dense fallback — the traced-row
    geometry and the envelope cache-key semantics still apply)."""
    from repro.parallel.fabric import get_fabric

    if fabric in ("dense", "a2a"):
        return None
    if fabric == "hierarchical":
        return _htable(n_layers)  # the composed two-level table
    if get_fabric(fabric).schedule_kind == "static":
        return None  # static plans can't ride the scan as traced rows
    envelope = "auto" if get_fabric(fabric).requires_envelope else None
    return _table(n_layers, envelope=envelope)


def _dots_and_whiles(model, table) -> tuple[int, int]:
    import jax.numpy as jnp

    tokens = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    hlo = (
        jax.jit(lambda p, b, s: model.loss(p, b, schedule=s))
        .lower(model.init(jax.random.PRNGKey(0)), batch, table)
        .compiler_ir("hlo")
        .as_hlo_text()
    )
    return len(re.findall(r"= \S+ dot\(", hlo)), hlo.count(" while(")


def main() -> int:
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.parallel.fabric import fabric_names

    # 1. O(period) HLO for every registered fabric.  On this single
    # device the mesh fabrics lower through the shared virtual dense
    # fallback, so fabrics whose schedule signature matches produce the
    # SAME lowering — lower once per signature and assert per fabric
    # (the mesh-side scan bodies are exercised in the slow multidev
    # lane, not here).
    lowered: dict[tuple, tuple] = {}
    for fabric in fabric_names():
        sched2 = _schedule_for(fabric, 2)
        key = (
            sched2 is None,
            getattr(sched2, "envelope", None) is not None,
            type(sched2).__name__,  # HierarchicalTable lowers its own body
        )
        if key not in lowered:
            lowered[key] = (
                _dots_and_whiles(_model(2, fabric), sched2),
                _dots_and_whiles(_model(8, fabric), _schedule_for(fabric, 8)),
            )
        shallow, deep = lowered[key]
        print(
            f"[{fabric}] depth-2: {shallow[0]} dots, {shallow[1]} whiles; "
            f"depth-8: {deep[0]} dots, {deep[1]} whiles"
        )
        if deep[1] < 1:
            print(f"FAIL: [{fabric}] depth-8 lowered without a scan while")
            return 1
        if deep[0] != shallow[0]:
            print(
                f"FAIL: [{fabric}] dot count scales with depth "
                f"({shallow[0]} -> {deep[0]}): the per-layer stack is "
                "unrolling instead of scanning one layer body"
            )
            return 1

    # 2. zero-recompile swap on the traced production backend
    model, table = _model(4, "phase_pipelined"), _table(4, seed=1)
    tokens = jnp.zeros((2, 16), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    params = model.init(jax.random.PRNGKey(0))
    f = jax.jit(lambda p, b, s: model.loss(p, b, schedule=s))
    f(params, batch, table)
    f(params, batch, _table(4, seed=2))
    cache = getattr(f, "_cache_size", lambda: 1)()
    print(f"executable cache after table swap: {cache}")
    if cache != 1:
        print("FAIL: a schedule-table swap recompiled the step")
        return 1

    # 3. phase-envelope policy: swaps within the envelope reuse the
    # executable; an envelope growth retraces exactly once
    g = jax.jit(lambda p, b, s: model.loss(p, b, schedule=s))
    # one shared envelope generous enough for both swap tables
    caps = np.maximum(
        np.asarray(_table(4, seed=1).caps).max(axis=0),
        np.asarray(_table(4, seed=2).caps).max(axis=0),
    )
    env = tuple(int(-(-int(v) // 8) * 8) for v in caps)
    g(params, batch, _table(4, seed=1, envelope=env))
    g(params, batch, _table(4, seed=2, envelope=env))
    # direct call on purpose: a getattr fallback would return the pass
    # value if jax ever drops the attr, making the guard vacuous
    cache_env = g._cache_size()
    print(f"executable cache after in-envelope swap: {cache_env}")
    if cache_env != 1:
        print("FAIL: a swap within the phase envelope recompiled the step")
        return 1
    grown = tuple(v + 8 for v in env)
    g(params, batch, _table(4, seed=2, envelope=grown))
    cache_grow = g._cache_size()
    print(f"executable cache after envelope growth: {cache_grow}")
    if cache_grow != 2:
        print("FAIL: an envelope growth must retrace exactly once")
        return 1

    # 4. adaptive envelope shrink: sustained underuse shrinks the
    # envelope and the shrink is the ONE counted recompile
    from repro.core import ControllerConfig, ScheduleRuntime

    model_s = _model(2, "phase_pipelined")
    params_s = model_s.init(jax.random.PRNGKey(0))
    rt = ScheduleRuntime(
        ControllerConfig(
            n_ranks=4, n_experts=8, ema=1.0, cooldown=0,
            envelope_slack=1.5, envelope_decay=0.5, shrink_patience=2,
        ),
        2,
    )
    hot = np.full((4, 4), 10.0)
    hot[:, 0] = 4000.0
    np.fill_diagonal(hot, 0.0)
    rt.prime(hot)
    h = jax.jit(lambda p, b, s: model_s.loss(p, b, schedule=s))
    h(params_s, batch, rt.table())
    env_hot = sum(rt.table().envelope)
    i = 0
    while rt.metrics()["envelope_shrinks"] == 0 and i < 12:
        probs = np.full(8, 0.01)
        probs[[2, 4, 6, 3, 5, 7][i % 6]] = 1.0  # cooled, rotating regime
        rt.observe(
            np.broadcast_to(400.0 * probs / probs.sum(), (2, 1, 8))
        )
        rt.table()
        i += 1
    m = rt.metrics()
    env_cold = sum(rt.table().envelope)
    if m["envelope_shrinks"] != 1 or env_cold >= env_hot:
        print(
            f"FAIL: sustained underuse must shrink the envelope "
            f"(shrinks={m['envelope_shrinks']}, {env_hot} -> {env_cold})"
        )
        return 1
    h(params_s, batch, rt.table())
    cache_shrink = h._cache_size()
    print(
        f"executable cache after envelope shrink: {cache_shrink} "
        f"(envelope {env_hot} -> {env_cold} slots)"
    )
    if cache_shrink != 2:
        print("FAIL: an envelope shrink must retrace exactly once")
        return 1
    h(params_s, batch, rt.table())
    if h._cache_size() != 2:
        print("FAIL: post-shrink tables must reuse the shrunk executable")
        return 1

    # 5. degraded-fabric policy: a masked re-plan (outage adopted) and
    # the later mask lift (outage cleared) each force a full re-plan,
    # but the envelope is frozen while masked and the re-planned rows
    # keep the table's static geometry — both directions are compile-free
    model_f = _model(2, "phase_pipelined")
    params_f = model_f.init(jax.random.PRNGKey(0))
    rt_f = ScheduleRuntime(
        ControllerConfig(
            n_ranks=4, n_experts=8, ema=1.0, cooldown=0, envelope_slack=2.0
        ),
        2,
    )
    rt_f.prime(np.full((4, 4), 400.0))
    k = jax.jit(lambda p, b, s: model_f.loss(p, b, schedule=s))
    k(params_f, batch, rt_f.table())
    dark = np.ones((4, 4), dtype=bool)
    dark[0, 1] = dark[2, 3] = False
    rt_f.set_link_mask(dark)
    k(params_f, batch, rt_f.table())
    rt_f.set_link_mask(None)
    k(params_f, batch, rt_f.table())
    m_f = rt_f.metrics()
    cache_fault = k._cache_size()
    print(
        f"executable cache after masked re-plan + mask lift: {cache_fault} "
        f"({m_f['masked_replans']} masked re-plan)"
    )
    if m_f["masked_replans"] != 1:
        print("FAIL: adopting the availability mask must re-plan once")
        return 1
    if cache_fault != 1:
        print(
            "FAIL: the degraded-fabric path (mask adopt + lift) must be "
            "compile-free table swaps"
        )
        return 1

    # 6. device-resident controller (PR 7): the fused train step — loss,
    # optimizer, and the in-graph observe -> score -> re-plan loop — must
    # lower to ONE executable, and a drift-triggered in-graph re-plan
    # (the lax.cond branch actually firing) must cause ZERO recompiles
    from repro.core import DeviceController
    from repro.optim import AdamW, cosine_schedule
    from repro.train.train_step import make_train_step

    model_d = _model(2, "phase_pipelined")
    rt_d = ScheduleRuntime(
        ControllerConfig(n_ranks=4, n_experts=8, ema=1.0, cooldown=0), 2
    )
    # prime from a hotspot demand estimate: all capacity piles onto one
    # column, leaving every other pair at min_cap — the model's roughly
    # uniform realized routing overflows those pairs, so the traced
    # drift signal fires a real in-graph re-plan within the first steps
    # (hysteresis_steps=1, no cooldown)
    skew = np.full((4, 4), 1.0)
    skew[:, 0] = 500.0
    np.fill_diagonal(skew, 0.0)
    rt_d.prime(skew)
    ctrl, ctrl_state = DeviceController.from_runtime(
        rt_d, hysteresis_steps=1, cooldown=0
    )
    opt_d = AdamW(lr=cosine_schedule(1e-3, 2, 8))
    fused = jax.jit(make_train_step(model_d, opt_d, controller=ctrl))
    params_d = model_d.init(jax.random.PRNGKey(0))
    opt_state_d = opt_d.init(params_d)
    ef_d = {}
    tokens_d = jnp.zeros((8, 32), jnp.int32)
    batch_d = {"tokens": tokens_d, "targets": tokens_d}
    for _ in range(6):
        params_d, opt_state_d, ef_d, ctrl_state, _metrics = fused(
            params_d, opt_state_d, ef_d, batch_d, ctrl_state
        )
    replans_d = int(ctrl_state.replans)
    cache_fused = fused._cache_size()
    print(
        f"executable cache after {replans_d} drift-triggered in-graph "
        f"re-plans in the fused controller step: {cache_fused}"
    )
    if replans_d < 1:
        print(
            "FAIL: the primed-vs-realized routing mismatch must fire the "
            "in-graph re-plan (the cond branch never ran)"
        )
        return 1
    if cache_fused != 1:
        print(
            "FAIL: the fused controller step must stay ONE executable "
            "across in-graph re-plans"
        )
        return 1

    # 7. low-precision wire (PR 8): the wire codec is static config —
    # QDQ ops traced into the step once, never traced data — so a
    # quantized model must keep the exact swap economics of bf16:
    # re-planned tables (and in-envelope ragged tables) swap at ZERO
    # recompiles.  Asserted on phase_pipelined (monolithic tables) and
    # ragged_a2a (envelope tables; dense-emulation fallback off-TPU).
    for fabric_q, env_q in (("phase_pipelined", None), ("ragged_a2a", env)):
        model_q = _model(4, fabric_q, wire_dtype="fp8")
        params_q = model_q.init(jax.random.PRNGKey(0))
        q = jax.jit(
            lambda p, b, s, m=model_q: m.loss(p, b, schedule=s)
        )
        q(params_q, batch, _table(4, seed=1, envelope=env_q))
        q(params_q, batch, _table(4, seed=2, envelope=env_q))
        cache_q = q._cache_size()
        print(
            f"executable cache after fp8-wire table swap "
            f"[{fabric_q}]: {cache_q}"
        )
        if cache_q != 1:
            print(
                f"FAIL: [{fabric_q}] a table swap under wire_dtype=fp8 "
                "recompiled the step — the codec must stay static config"
            )
            return 1

    # 8. hierarchical dual-table swaps (PR 9): the composed table's two
    # levels swap independently into the SAME executable, and in the
    # fused controller step an intra-only drift fires only the intra
    # re-plan cond — the inter plan leaves pass through untouched
    from repro.core import (
        HierarchicalDeviceController,
        HierarchicalRuntime,
        hierarchical_decompose,
        plan_schedule,
    )

    model_h = _model(4, "hierarchical")
    params_h = model_h.init(jax.random.PRNGKey(0))
    htab = _htable(4, seed=1)
    w = jax.jit(lambda p, b, s: model_h.loss(p, b, schedule=s))
    w(params_h, batch, htab)
    intra_scheds, inter_scheds = [], []
    for mat in _htraffics(4, seed=1) * 0.7:
        i_d, e_d = hierarchical_decompose(mat, 2)
        intra_scheds.append(plan_schedule(i_d))
        inter_scheds.append(plan_schedule(e_d))
    alt_intra = htab.update(intra=htab.intra.update(intra_scheds))
    w(params_h, batch, alt_intra)
    cache_hi = w._cache_size()
    alt_both = alt_intra.update(inter=htab.inter.update(inter_scheds))
    w(params_h, batch, alt_both)
    cache_hb = w._cache_size()
    print(
        f"executable cache after hierarchical intra-only swap: {cache_hi}; "
        f"after dual-table swap: {cache_hb}"
    )
    if cache_hi != 1 or cache_hb != 1:
        print(
            "FAIL: hierarchical dual-table swaps must reuse the one "
            "executable (per-level envelopes are the static aux)"
        )
        return 1

    # fused step: prime the intra level off-estimate (the realized
    # routing will drift it) while the inter level is primed with the
    # EXACT realized inter traffic — only the intra cond may fire
    from repro.core.runtime import routing_to_traffic

    model_h2 = _model(2, "hierarchical")
    params_h2 = model_h2.init(jax.random.PRNGKey(0))
    tokens_h = jnp.zeros((8, 32), jnp.int32)
    batch_h = {"tokens": tokens_h, "targets": tokens_h}
    probe = _htable(2, seed=1)
    _, aux_h = model_h2.loss_and_stats(params_h2, batch_h, schedule=probe)
    realized = routing_to_traffic(
        np.asarray(aux_h["routing"]), n_ranks=4, n_experts=8
    )
    from repro.core.hierarchical import same_pod_mask as _same_pod

    same = _same_pod(4, 2)
    skew_h = realized.copy()
    skew_h[:, same] = 1.0  # intra estimate far off the realized counts
    for layer in skew_h:
        layer[0, 1] = layer[2, 3] = 500.0
        np.fill_diagonal(layer, 0.0)
    hrt = HierarchicalRuntime(
        ControllerConfig(n_ranks=4, n_experts=8, ema=1.0, cooldown=0),
        2, pod_size=2,
    )
    hrt.prime(skew_h)  # per-layer: the inter estimate is exact
    hctrl, hstate = HierarchicalDeviceController.from_runtime(
        hrt, hysteresis_steps=1, cooldown=0
    )
    inter0 = jax.tree.leaves(hctrl.inter.table_of(hstate.inter))
    # lr=0 freezes the router: realized routing is identical every step,
    # so the ONLY drift is the skewed intra estimate — the cleanest
    # intra-only-drift stimulus
    opt_h = AdamW(lr=0.0)
    fused_h = jax.jit(make_train_step(model_h2, opt_h, controller=hctrl))
    opt_state_h = opt_h.init(params_h2)
    ef_h = {}
    for _ in range(6):
        params_h2, opt_state_h, ef_h, hstate, _m = fused_h(
            params_h2, opt_state_h, ef_h, batch_h, hstate
        )
    intra_replans = int(hstate.intra.replans)
    inter_replans = int(hstate.inter.replans)
    cache_hf = fused_h._cache_size()
    inter1 = jax.tree.leaves(hctrl.inter.table_of(hstate.inter))
    inter_same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(inter0, inter1)
    )
    print(
        f"fused hierarchical step: {intra_replans} intra re-plans, "
        f"{inter_replans} inter re-plans, cache {cache_hf}, "
        f"inter plan leaves unchanged: {inter_same}"
    )
    if intra_replans < 1:
        print(
            "FAIL: the skewed intra estimate vs realized routing must "
            "fire the intra in-graph re-plan"
        )
        return 1
    if inter_replans != 0 or not inter_same:
        print(
            "FAIL: intra-only drift must leave the inter phase plan "
            "untouched (no inter re-plan, identical plan leaves)"
        )
        return 1
    if cache_hf != 1:
        print(
            "FAIL: the fused hierarchical controller step must stay ONE "
            "executable across intra-only drift re-plans"
        )
        return 1

    # 9. serving engine (PR 10): the continuous-batching decode loop is
    # ONE executable end to end — across ragged admissions, slot
    # recycling, drift-fired cold re-plans, and regime warm swaps
    from repro.configs.base import ModelConfig, MoECfg
    from repro.serve import Request, ServeEngine

    cfg_s = ModelConfig(
        name="serve-smoke", family="moe", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
        moe=MoECfg(
            n_experts=8, top_k=2, d_ff_expert=32, dispatch="scheduled"
        ),
        remat="none",
    )
    eng = ServeEngine(
        cfg_s, decode_slots=16, max_len=32, buckets=(8,), n_ranks=4,
        regime_slots=2, regime_threshold=0.3, drop_tolerance=0.01,
        hysteresis_steps=1, cooldown=2, ema=0.8, host_observe_every=10,
        # smoke-scale decode traffic needs finer solver caps than the
        # training-scale defaults for drift pressure to register
        plan_overrides=dict(quantum=1, min_cap=1, slack=1.0), seed=0,
    )
    state0 = eng._state
    rng_s = np.random.default_rng(0)
    pool = rng_s.integers(0, cfg_s.vocab_size, 8)

    def _phase(n=32):
        return [
            Request(
                prompt=rng_s.choice(pool, 6), max_new_tokens=8, arrival=0.0
            )
            for _ in range(n)
        ]

    eng.run(_phase())
    m1 = eng.metrics()
    if m1["controller"]["device_replans"] < 1:
        print(
            "FAIL: serving the concentrated mix against the "
            "uniform-primed plan must fire an in-graph re-plan"
        )
        return 1
    eng.capture_regime()
    # rewind the device plan to the uniform-primed initial state with
    # the library kept: re-serving the same mix must overflow the stale
    # plan and the fire must warm-swap the captured regime table
    eng._state = eng._ctrl.load_regimes(
        state0, eng._bank_tables, eng._bank_refs
    )
    eng.run(_phase())
    m2 = eng.metrics()
    warm = m2["controller"]["regime_warm_swaps"]
    comp = m2["compile"]
    print(
        f"serve engine: {m1['controller']['device_replans']} cold "
        f"re-plans, then {warm} regime warm swap(s); executables "
        f"decode={comp['decode_executables']} "
        f"prefill={comp['prefill_executables']} "
        f"admit={comp['admit_executables']}"
    )
    if warm < 1:
        print(
            "FAIL: the regime return must warm-swap the captured table "
            "(the library nearest-match never fired)"
        )
        return 1
    if (
        comp["decode_executables"] != 1
        or comp["prefill_executables"] != 1
        or comp["admit_executables"] != 1
    ):
        print(
            "FAIL: the serving engine must keep ONE executable per step "
            "function across admissions, slot recycling, and regime "
            "warm swaps"
        )
        return 1

    print(
        "OK: depth-L scan traces one layer body for every fabric "
        f"({', '.join(fabric_names())}; single-device lowering — mesh "
        "bodies run in the slow multidev lane); table swaps are "
        "compile-free (in-envelope swaps included; envelope growth AND "
        "adaptive shrink each retrace once; masked fault re-plans swap "
        "free both ways; the fused device-controller step is one "
        "executable with in-graph re-plans at zero recompiles; fp8-wire "
        "phase_pipelined/ragged steps swap tables at zero recompiles; "
        "hierarchical dual tables swap both levels at zero recompiles "
        "with intra drift never retracing the inter plan; the serving "
        "engine's decode/prefill/admit executables survive continuous "
        "batching, slot recycling, and regime warm swaps)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
