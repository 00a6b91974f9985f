"""Degraded-fabric resilience (PR 6): fault scenarios, link-mask-aware
planning, the fault-injection wrapper fabric, and the chaos runs.

Three layers of guarantee, each asserted here:

* **Planning** — ``apply_link_mask`` conserves every row's demand while
  zeroing dark pairs, and masked ``decompose``/``decompose_batch`` plans
  never route a dead link (property-tested over random scenarios).
* **Correctness under faults** — a masked plan is still just a plan:
  ``moe_apply`` on a masked row must match the dense pair-caps oracle on
  values *and* grads with zero admitted-token drops, for any sampled
  fault pattern (the fabric may degrade; the math may not).
* **Recovery** — the end-to-end chaos run injects a link flap mid-train:
  the loop must roll back, quarantine, fall back along the declared
  chain, re-plan under the mask without recompiling, and probe its way
  back to the preferred fabric once the fault clears.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ModelConfig, MoECfg
from repro.core import (
    ControllerConfig,
    FabricFaultError,
    FaultScenario,
    ScheduleRuntime,
    ScheduleTable,
    apply_link_mask,
    check_schedule_mask,
    decompose,
    decompose_batch,
    fault_hook,
    plan_schedule,
)
from repro.models import moe
from repro.parallel.fabric import (
    DEGRADATION_CHAIN,
    FABRICS,
    get_fabric,
    next_fabric,
    wrap_faulty,
)

N_V = 4


def _cfg(dispatch: str = "dense", **moe_kw):
    kw = dict(
        n_experts=8, top_k=2, d_ff_expert=32, dispatch=dispatch,
        capacity_factor=8.0,
    )
    kw.update(moe_kw)
    return ModelConfig(
        name="faults-test",
        family="moe",
        n_layers=1,
        d_model=32,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        vocab_size=128,
        moe=MoECfg(**kw),
        remat="none",
    )


def _traffic(seed: int, scale: float = 400.0, n: int = N_V) -> np.ndarray:
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) * scale
    np.fill_diagonal(m, 0)
    return m


def _masked_row(seed: int, mask: np.ndarray):
    plan = plan_schedule(decompose(_traffic(seed), "maxweight", link_mask=mask))
    return ScheduleTable.from_schedules([plan], k_max=N_V, envelope="auto").row(0)


def _routed_caps(sched, n: int = N_V) -> np.ndarray:
    """[n, n] per-pair capacity a schedule actually grants."""
    caps = np.zeros((n, n))
    perms = np.asarray(sched.perms)
    valid = np.asarray(sched.valid)
    cap = np.asarray(sched.caps)
    for k in range(perms.shape[0]):
        for i in range(n):
            if valid[k, i]:
                caps[i, perms[k, i]] += cap[k] if cap.ndim == 1 else cap[k, i]
    return caps


class TestFaultScenario:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultScenario("meteor_strike", n_ranks=4)

    def test_deterministic_in_seed(self):
        a = FaultScenario("dead_link", n_ranks=8, n_links=5, seed=7)
        b = FaultScenario("dead_link", n_ranks=8, n_links=5, seed=7)
        assert a.dead_pairs == b.dead_pairs
        for step in (0, 19, 20, 21, 100):
            np.testing.assert_array_equal(a.link_mask(step), b.link_mask(step))

    def test_dead_link_timeline(self):
        sc = FaultScenario("dead_link", n_ranks=4, onset=10, n_links=2, seed=1)
        assert not sc.active(9)
        assert sc.active(10) and sc.active(10_000)
        assert sc.link_mask(9).all()
        m = sc.link_mask(10)
        assert (~m).sum() == 2
        assert m.diagonal().all()
        for i, j in sc.dead_pairs:
            assert i != j and not m[i, j]

    def test_link_flap_recovers(self):
        sc = FaultScenario("link_flap", n_ranks=4, onset=5, window=3, seed=0)
        assert sc.link_mask(4).all()
        assert not sc.link_mask(5).all()
        assert not sc.link_mask(7).all()
        assert sc.link_mask(8).all()

    def test_slow_link_keeps_mask_clean(self):
        sc = FaultScenario(
            "slow_link", n_ranks=4, onset=2, window=4, slow_factor=8.0, seed=3
        )
        assert sc.link_mask(3).all()  # degraded, not dark
        slow = sc.slow_matrix(3)
        assert slow.max() == 8.0
        assert (slow >= 1.0).all()
        assert sc.slow_matrix(0).max() == 1.0
        assert sc.slow_matrix(6).max() == 1.0

    def test_dark_window_defaults(self):
        sc = FaultScenario("dark_window", n_ranks=4, dark_window_us=500.0)
        assert sc.dark_window_steps >= 1
        assert not sc.active(100)
        assert sc.link_mask(100).all()

    def test_outage_frac_overrides_n_links(self):
        sc = FaultScenario(
            "dead_link", n_ranks=8, onset=0, n_links=1, outage_frac=0.25, seed=0
        )
        assert len(sc.dead_pairs) == round(0.25 * 8 * 7)

    def test_never_kills_every_pair(self):
        sc = FaultScenario(
            "dead_link", n_ranks=2, onset=0, outage_frac=0.99, seed=0
        )
        m = sc.link_mask(0)
        assert (m & ~np.eye(2, dtype=bool)).any()


class TestApplyLinkMask:
    def test_conserves_row_demand(self):
        m = _traffic(0)
        sc = FaultScenario("dead_link", n_ranks=N_V, onset=0, n_links=3, seed=2)
        mask = sc.link_mask(0)
        out = apply_link_mask(m, mask)
        np.testing.assert_allclose(out.sum(axis=1), m.sum(axis=1))
        assert (out[~mask] == 0).all()

    def test_idempotent(self):
        m = _traffic(1)
        mask = FaultScenario(
            "dead_link", n_ranks=N_V, onset=0, n_links=4, seed=5
        ).link_mask(0)
        once = apply_link_mask(m, mask)
        np.testing.assert_allclose(apply_link_mask(once, mask), once)

    def test_unroutable_row_recorded(self):
        # row 0 loses every off-diagonal destination
        m = _traffic(2, n=3)
        mask = np.ones((3, 3), dtype=bool)
        mask[0, 1] = mask[0, 2] = False
        meta = {}
        out = apply_link_mask(m, mask, meta=meta)
        assert (out[0, 1:] == 0).all()
        np.testing.assert_allclose(meta["unroutable_tokens"], m[0, 1:].sum())

    def test_uniform_redistribution_when_survivors_idle(self):
        # all of row 0's demand targets the dead pair: survivors carried
        # nothing, so the displaced demand splits uniformly
        m = np.zeros((N_V, N_V))
        m[0, 1] = 90.0
        mask = np.ones((N_V, N_V), dtype=bool)
        mask[0, 1] = False
        out = apply_link_mask(m, mask)
        np.testing.assert_allclose(out[0], [0.0, 0.0, 45.0, 45.0])

    def test_shape_errors(self):
        with pytest.raises(ValueError, match="square demand matrix"):
            apply_link_mask(np.ones((2, 3)), np.ones((2, 3), bool))
        with pytest.raises(ValueError, match="does not match demand"):
            apply_link_mask(np.ones((3, 3)), np.ones((2, 2), bool))


class TestMaskedPlanning:
    @given(st.integers(min_value=0, max_value=10**6))
    @settings(max_examples=15, deadline=None)
    def test_masked_plan_never_routes_dark_pairs(self, seed):
        sc = FaultScenario(
            "dead_link",
            n_ranks=N_V,
            onset=0,
            n_links=1 + seed % (N_V * (N_V - 1) - 1),
            seed=seed,
        )
        mask = sc.link_mask(0)
        d = decompose(_traffic(seed), "maxweight", link_mask=mask)
        assert d.meta.get("link_masked") is True
        caps = _routed_caps(plan_schedule(d))
        assert (caps[~mask] == 0).all(), (seed, np.argwhere(~mask))
        # and check_schedule_mask agrees the plan is clean
        check_schedule_mask(plan_schedule(d), mask, backend="test")

    def test_unmasked_plan_trips_the_guard(self):
        sched = plan_schedule(decompose(_traffic(0), "maxweight"))
        caps = _routed_caps(sched)
        # pick a pair the plan actually uses and declare it dark
        i, j = map(int, np.argwhere(caps > 0)[0])
        mask = np.ones((N_V, N_V), dtype=bool)
        mask[i, j] = False
        with pytest.raises(FabricFaultError) as e:
            check_schedule_mask(
                sched, mask, backend="ragged_a2a",
                next_fabric="phase_pipelined", step=12,
            )
        err = e.value
        assert err.backend == "ragged_a2a"
        assert err.pair == (i, j)
        assert err.phase is not None and err.step == 12
        assert err.next_fabric == "phase_pipelined"
        np.testing.assert_array_equal(err.link_mask, mask)
        msg = str(err)
        assert f"link ({i} -> {j}) is dark at step 12" in msg
        assert "phase_pipelined" in msg and "degradation chain" in msg

    def test_no_fallback_message(self):
        sched = plan_schedule(decompose(_traffic(0), "maxweight"))
        i, j = map(int, np.argwhere(_routed_caps(sched) > 0)[0])
        mask = np.ones((N_V, N_V), dtype=bool)
        mask[i, j] = False
        with pytest.raises(FabricFaultError, match="no fallback fabric"):
            check_schedule_mask(sched, mask, backend="dense", next_fabric=None)

    def test_all_up_mask_is_free(self):
        sched = plan_schedule(decompose(_traffic(0), "maxweight"))
        check_schedule_mask(sched, np.ones((N_V, N_V), bool), backend="x")

    def test_decompose_batch_shares_one_mask(self):
        mask = FaultScenario(
            "dead_link", n_ranks=N_V, onset=0, n_links=3, seed=9
        ).link_mask(0)
        stack = np.stack([_traffic(s) for s in range(3)])
        decs = decompose_batch(stack, "maxweight", link_mask=mask)
        for d in decs:
            assert d.meta.get("link_masked") is True
            caps = _routed_caps(plan_schedule(d))
            assert (caps[~mask] == 0).all()

    def test_generic_strategies_masked_too(self):
        mask = FaultScenario(
            "dead_link", n_ranks=N_V, onset=0, n_links=2, seed=4
        ).link_mask(0)
        for strategy in ("bvn", "bvn-bottleneck", "shift"):
            d = decompose(_traffic(3), strategy, link_mask=mask)
            for ph in d.phases:
                perm = np.asarray(ph.perm)
                sent = np.asarray(ph.sent)
                for i in range(N_V):
                    if not mask[i, perm[i]]:
                        # BVN peeling leaves float residue on zeroed pairs
                        assert sent[i] < 1e-9, (strategy, i, int(perm[i]))


class TestChaosParity:
    """A masked plan is still a plan: values, grads, and zero drops must
    match the dense pair-caps oracle for any sampled fault pattern."""

    @given(st.integers(min_value=0, max_value=10**6))
    @settings(max_examples=4, deadline=None)
    def test_masked_row_matches_dense_oracle(self, seed):
        sc = FaultScenario(
            "link_flap",
            n_ranks=N_V,
            onset=0,
            window=1,
            n_links=1 + seed % 6,
            seed=seed,
        )
        row = _masked_row(seed, sc.link_mask(0))
        cfg = _cfg("phase_pipelined")
        params = moe.moe_init(jax.random.PRNGKey(seed % 97), cfg)
        x = jax.random.normal(
            jax.random.PRNGKey(seed % 89 + 1), (2, 16, 32), jnp.float32
        )

        y, st_f = moe.moe_apply(
            params, cfg, x, schedule=row, return_stats=True
        )
        y_ref, st_ref = moe._moe_dense(
            params, _cfg(), x, row, return_stats=True
        )
        np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
        # the fabric degraded; no admitted token may be dropped
        assert float(np.asarray(st_f["dropped"]).sum()) == 0.0, seed
        assert float(np.asarray(st_ref["dropped"]).sum()) == 0.0
        np.testing.assert_allclose(
            np.asarray(st_f["routing"]), np.asarray(st_ref["routing"])
        )

        def loss_fab(p):
            return jnp.sum(moe.moe_apply(p, cfg, x, schedule=row) ** 2)

        def loss_ref(p):
            return jnp.sum(moe._moe_dense(p, _cfg(), x, row) ** 2)

        g_f = jax.grad(loss_fab)(params)
        g_r = jax.grad(loss_ref)(params)
        flat_f, _ = jax.tree_util.tree_flatten(g_f)
        flat_r, _ = jax.tree_util.tree_flatten(g_r)
        for a, b in zip(flat_f, flat_r):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


class TestDegradationChain:
    def test_chain_ends_at_dense(self):
        assert DEGRADATION_CHAIN[-1] == "dense"
        assert next_fabric("dense") is None

    def test_chain_walk(self):
        for a, b in zip(DEGRADATION_CHAIN, DEGRADATION_CHAIN[1:]):
            assert next_fabric(a) == b

    def test_unknown_and_wrapped_names(self):
        assert next_fabric("warp_drive") == "dense"
        assert next_fabric("faulty:ragged_a2a") == next_fabric("ragged_a2a")


class TestFaultInjectionFabric:
    def test_wrap_registers_and_mirrors_flags(self):
        sc = FaultScenario("dead_link", n_ranks=N_V, onset=0, seed=0)
        name = wrap_faulty("ragged_a2a", sc)
        try:
            fab = get_fabric(name)
            base = get_fabric("ragged_a2a")
            assert name == "faulty:ragged_a2a"
            assert fab.uses_mesh == base.uses_mesh
            assert fab.schedule_kind == base.schedule_kind
            assert fab.requires_envelope == base.requires_envelope
        finally:
            FABRICS.pop(name, None)

    def test_wrapper_refuses_dark_schedule(self):
        sched = plan_schedule(decompose(_traffic(0), "maxweight"))
        caps = _routed_caps(sched)
        i, j = map(int, np.argwhere(caps > 0)[0])
        # scenario whose sampled pair is (i, j): brute-force a seed
        seed = next(
            s for s in range(500)
            if FaultScenario(
                "dead_link", n_ranks=N_V, onset=0, n_links=1, seed=s
            ).dead_pairs == ((i, j),)
        )
        sc = FaultScenario("dead_link", n_ranks=N_V, onset=0, n_links=1, seed=seed)
        name = wrap_faulty("ppermute", sc)
        try:
            fab = get_fabric(name)
            fab.advance(5)
            with pytest.raises(FabricFaultError) as e:
                fab.check_transfers(sched)
            assert e.value.backend == "ppermute"
            assert e.value.pair == (i, j)
            assert fab.faults_raised == 1
            # before onset the same schedule passes
            fab.advance(-1)
            fab.check_transfers(sched)
            # masked plans pass during the outage
            fab.advance(5)
            masked = plan_schedule(
                decompose(_traffic(0), "maxweight", link_mask=sc.link_mask(5))
            )
            fab.check_transfers(masked)
            assert fab.validate_schedule(masked, n=N_V) is not None
        finally:
            FABRICS.pop(name, None)


class TestFaultHook:
    def _runtime(self, **kw):
        cfg = dict(
            n_ranks=N_V, n_experts=8, ema=1.0, cooldown=0,
            fallback_chain=("ragged_a2a", "dense"),
        )
        cfg.update(kw)
        rt = ScheduleRuntime(ControllerConfig(**cfg), 1)
        rt.prime(_traffic(0, scale=1000.0))
        return rt

    def test_hook_raises_then_clears(self):
        rt = self._runtime()
        caps = _routed_caps(rt.schedules[0])
        i, j = map(int, np.argwhere(caps > 0)[0])
        seed = next(
            s for s in range(500)
            if FaultScenario(
                "link_flap", n_ranks=N_V, onset=3, window=2, seed=s
            ).dead_pairs == ((i, j),)
        )
        sc = FaultScenario("link_flap", n_ranks=N_V, onset=3, window=2, seed=seed)
        hook = fault_hook(sc, rt, backend="ragged_a2a")
        hook(0)  # healthy: no-op
        assert rt.link_mask is None
        with pytest.raises(FabricFaultError) as e:
            hook(3)
        assert e.value.next_fabric == "dense"
        # the loop hands the error to the runtime: mask adopted, replanned
        rt.record_fault(e.value)
        assert rt.link_mask is not None
        assert rt.metrics()["fabric_faults"] == 1
        hook(4)  # same outage, plans now routed around it: no-op
        hook(5)  # fault cleared: mask lifted, replan back to preferred
        assert rt.link_mask is None

    def test_hook_adopts_mask_silently_when_plans_avoid_it(self):
        # traffic with NO demand on pair (0, 1): the plan never routes
        # it, so darkening it must not raise — the mask is adopted
        # silently so future re-plans keep avoiding it
        rt = ScheduleRuntime(
            ControllerConfig(
                n_ranks=N_V, n_experts=8, ema=1.0, cooldown=0,
                fallback_chain=("ragged_a2a", "dense"),
            ),
            1,
        )
        m = _traffic(0, scale=1000.0)
        m[0, 1] = 0.0
        rt.prime(m)
        caps = _routed_caps(rt.schedules[0])
        assert caps[0, 1] == 0
        seed = next(
            s for s in range(2000)
            if FaultScenario(
                "dead_link", n_ranks=N_V, onset=0, seed=s
            ).dead_pairs == ((0, 1),)
        )
        sc = FaultScenario("dead_link", n_ranks=N_V, onset=0, seed=seed)
        hook = fault_hook(sc, rt, backend="ragged_a2a")
        hook(0)  # no raise: plans never touch the dark pair
        assert rt.link_mask is not None
        assert rt.metrics()["masked_replans"] == 1


class TestChaosEndToEnd:
    def test_link_flap_training_recovers(self, tmp_path):
        """The acceptance run: a seeded link flap mid-train must (1) be
        surfaced as a ``FabricFaultError`` the loop rolls back from,
        (2) quarantine the preferred fabric and fall back along the
        declared chain, (3) re-plan under the availability mask without
        recompiling the step, and (4) probe back to the preferred fabric
        once the fault clears — finishing HEALTHY with finite losses."""
        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        N, E = 4, 8
        cfg = ModelConfig(
            name="fault-e2e",
            family="moe",
            n_layers=2,
            d_model=32,
            n_heads=4,
            n_kv_heads=2,
            d_ff=64,
            vocab_size=128,
            moe=MoECfg(
                n_experts=E, top_k=2, d_ff_expert=32,
                dispatch="phase_pipelined",
            ),
            remat="none",
        )
        model = Model(cfg)
        rt = ScheduleRuntime(
            ControllerConfig(
                n_ranks=N,
                n_experts=E,
                ema=1.0,
                cooldown=2,
                envelope_slack=2.0,  # recovery re-plan must fit the envelope
                fallback_chain=("phase_pipelined", "dense"),
                quarantine_after=2,
                probe_backoff=4,
                recover_after=2,
            ),
            model.n_moe_layers,
        )
        rt.prime(np.full((N, N), 50.0))
        sc = FaultScenario(
            "link_flap", n_ranks=N, onset=8, window=6, n_links=2, seed=3
        )
        rt.attach_faults(sc)

        res = train_loop(
            model,
            DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8),
            TrainLoopConfig(
                steps=30,
                ckpt_dir=str(tmp_path),
                ckpt_every=4,
                peak_lr=5e-3,
                warmup=5,
                log_every=2,
            ),
            runtime=rt,
            failure_hook=fault_hook(sc, rt, backend="phase_pipelined"),
        )
        ctl = res["controller"]
        # (1) the fault fired and consumed exactly one failure budget slot
        assert res["failures"] >= 1
        assert ctl["fabric_faults"] >= 1
        # (2) quarantine + fallback: the FSM demoted, the loop rebuilt
        # the step for the fallback and again for the recovery
        assert ctl["quarantines"] >= 1
        assert ctl["fabric_switches"] >= 2
        # (3) masked re-plan happened, and every recompile is accounted
        # for by a deliberate envelope change — the fault/fallback
        # machinery itself (masked swaps, quarantine, probing) adds ZERO
        # (the controlled zero-recompile masked-swap check lives in
        # benchmarks/compile_smoke.py where traffic is held fixed)
        assert ctl["masked_replans"] >= 1
        budget = ctl["envelope_growths"] + ctl["envelope_shrinks"]
        assert ctl["compiles"] <= budget, ctl
        # (4) fully recovered: preferred fabric, no mask, HEALTHY
        assert ctl["final_dispatch"] == "phase_pipelined"
        assert not ctl["fallback_active"]
        assert not ctl["link_masked"]
        assert ctl["health_state"] == "HEALTHY"
        assert ctl["active_fabric"] == "phase_pipelined"
        losses = [h["loss"] for h in res["history"]]
        assert losses and all(np.isfinite(losses)), losses
        steps = [h["step"] for h in res["history"]]
        assert len(steps) == len(set(steps))  # rollback never double-logged
