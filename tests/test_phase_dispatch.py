"""Phase-pipelined traced dispatch (PR 4): envelope geometry, drop
observability, explicit slot validity, and the no-admitted-token-dropped
property.

The EP fabric itself is exercised in ``tests/multidev_moe.py`` (slow
lane, 8 emulated devices); everything here runs on one device — the
phase-slot math is pure, the envelope is static pytree aux (so its
zero-recompile/one-recompile behavior shows on the dense virtual-fabric
path too), and the drop counter rides the ordinary stats aux output.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ModelConfig, MoECfg
from repro.core import (
    ScheduleTable,
    decompose,
    phase_envelope,
    plan_schedule,
)
from repro.models import moe

N_V = 4


def _moe_cfg(**moe_kw):
    kw = dict(n_experts=8, top_k=2, d_ff_expert=32, dispatch="scheduled")
    kw.update(moe_kw)
    return ModelConfig(
        name="phase-test",
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


def _plan(seed: int, scale: float = 300.0, n: int = N_V):
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) * scale
    np.fill_diagonal(m, 0)
    return plan_schedule(decompose(m, "maxweight"))


class TestEnvelope:
    def test_auto_envelope_covers_plans(self):
        scheds = [_plan(s) for s in range(3)]
        t = ScheduleTable.from_schedules(scheds, k_max=N_V, envelope="auto")
        env = np.asarray(t.envelope)
        for s in scheds:
            k = min(s.num_phases, N_V)
            assert (env[:k] >= np.asarray(s.caps[:k])).all()
        # rows and updates keep the envelope (same static aux = same
        # executable); update() with plans inside the envelope never grows
        assert t.row(0).envelope == t.envelope
        t2 = t.update([_plan(s, scale=100.0) for s in range(3)])
        assert t2.envelope == t.envelope

    def test_envelope_slots_match_pair_caps_scaling(self):
        s = _plan(7)
        t = ScheduleTable.from_schedules([s], k_max=N_V, envelope="auto")
        row = t.row(0)
        for e_local in (1, 2):
            env = row.envelope_slots(e_local)
            caps = np.asarray(row.phase_slot_caps(e_local))
            # planned caps always fit the envelope slots (no-drop invariant)
            assert (caps <= np.asarray(env)).all()
            # and an auto envelope from the same plan admits the full caps
            per_expert = -(-s.caps.astype(np.int64) // e_local)
            per_expert = np.maximum(8, -(-per_expert // 8) * 8)
            np.testing.assert_array_equal(caps[: s.num_phases], per_expert)

    def test_tight_envelope_clamps_admission(self):
        """A plan exceeding the envelope is clamped by ``pair_caps`` —
        admission and buffers agree, so nothing is over-promised."""
        s = _plan(3)
        tight = [8] * N_V
        t = ScheduleTable.from_schedules([s], k_max=N_V, envelope=tight)
        row = t.row(0)
        assert (np.asarray(row.phase_slot_caps(1)) <= 8).all()
        assert (np.asarray(row.pair_caps(1)) <= 8 * N_V).all()

    def test_envelope_validation(self):
        s = _plan(1)
        with pytest.raises(ValueError, match="slots"):
            ScheduleTable.from_schedules([s], k_max=N_V, envelope=[8, 8])
        with pytest.raises(ValueError, match=">= 0"):
            ScheduleTable.from_schedules(
                [s], k_max=N_V, envelope=[-8] * N_V
            )
        with pytest.raises(ValueError, match="envelope"):
            ScheduleTable.from_schedules([s], k_max=N_V, envelope="bogus")

    def test_envelope_is_jit_cache_key(self):
        """Swaps *within* the envelope reuse the executable; growing the
        envelope is the one deliberate recompile (static pytree aux)."""
        cfg = _moe_cfg(capacity_factor=8.0)
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)
        f = jax.jit(lambda p, x, r: moe.moe_apply(p, cfg, x, schedule=r))
        env = tuple(int(v) for v in phase_envelope([_plan(0), _plan(1)], N_V))
        r1 = ScheduleTable.from_schedules([_plan(0)], k_max=N_V, envelope=env)
        r2 = ScheduleTable.from_schedules([_plan(1)], k_max=N_V, envelope=env)
        f(params, x, r1.row(0))
        f(params, x, r2.row(0))
        assert f._cache_size() == 1, "swap within the envelope recompiled"
        grown = tuple(v + 8 for v in env)
        r3 = ScheduleTable.from_schedules(
            [_plan(1)], k_max=N_V, envelope=grown
        )
        f(params, x, r3.row(0))
        assert f._cache_size() == 2, "envelope growth must retrace (once)"


class TestDropObservability:
    """Satellite: the over-promise cut is counted, not silent."""

    def setup_method(self):
        self.x = jax.random.normal(
            jax.random.PRNGKey(2), (8, 64, 32), jnp.float32
        )

    def _run(self, capacity_factor):
        cfg = _moe_cfg(capacity_factor=capacity_factor)
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        # a generous plan admits (nearly) all demand; a tight uniform
        # bucket then cuts admitted tokens at grouping
        row = ScheduleTable.from_schedules(
            [_plan(11, scale=5000.0)], k_max=N_V
        ).row(0)
        y, stats = moe.moe_apply(
            params, cfg, self.x, schedule=row, return_stats=True
        )
        return float(np.asarray(stats["dropped"]).sum()), stats

    def test_overpromise_reports_nonzero_drops(self):
        """The formerly *silent* case: plan-admitted tokens cut by the
        capacity-factor bucket now show up in the stats aux."""
        dropped, stats = self._run(capacity_factor=0.25)
        assert dropped > 0, "over-promise cut must be observable"
        assert stats["routing"].shape == (1, 8)
        assert stats["dropped"].shape == (1,)

    def test_generous_bucket_reports_zero(self):
        dropped, _ = self._run(capacity_factor=8.0)
        assert dropped == 0.0

    def test_runtime_metrics_surface_drops(self):
        from repro.core import ControllerConfig, ScheduleRuntime

        rt = ScheduleRuntime(
            ControllerConfig(n_ranks=N_V, n_experts=8, ema=1.0), 1
        )
        rt.prime(np.full((N_V, N_V), 100.0))
        rt.table()  # the envelope materializes with the first table
        stats = {
            "routing": np.ones((1, 1, 8)),
            "dropped": np.array([[3.0]]),
        }
        rt.observe(stats)
        rt.observe(np.ones((1, 1, 8)), dropped=np.array([4.0]))
        m = rt.metrics()
        assert m["admitted_dropped"] == 7.0
        assert m["envelope"] is not None and len(m["envelope"]) == N_V
        assert m["envelope_growths"] == 0


class TestExplicitValidity:
    """Satellite: liveness is an explicit mask, not the gate sign."""

    def test_zero_gate_slot_stays_live(self):
        x = jnp.ones((4, 8), jnp.float32)
        key = jnp.array([0, 0, 1, 2, 2, 3, 1, 0], jnp.int32)
        gates = jnp.array(
            [0.5, 0.0, 1.0, 0.25, 0.0, 1.0, 0.5, 0.25], jnp.float32
        )
        buf, pos, gate, live = moe._group(x, key, gates, 4, 2)
        # every packed slot is live, including the gate == 0.0 ones:
        # liveness tracks token presence, not combine weight
        assert int(live.sum()) == int((np.asarray(pos) >= 0).sum())
        assert int(live.sum()) > int((np.asarray(gate) > 0).sum())
        # an admission mask takes precedence over presence (mask choice 0,
        # which holds a real slot — its slot must go dead)
        adm = jnp.array([False] + [True] * 7)
        *_, live2 = moe._group(x, key, gates, 4, 2, admitted=adm)
        assert int(live2.sum()) == int(live.sum()) - 1

    def test_zero_gate_token_matches_einsum_path(self):
        """Forward parity einsum vs pallas-grouped when a *selected*
        router gate underflows to exactly 0.0 (peaked logits without
        top-k renormalization) — the skip metadata must not treat the
        zero-gate token's row block as dead padding."""
        import repro.models.layers as layers

        cfg = _moe_cfg(capacity_factor=8.0, router_norm_topk=False)
        cfg_p = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, use_pallas=True)
        )
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = 2000.0 * jax.random.normal(
            jax.random.PRNGKey(3), (2, 16, 32), jnp.float32
        )
        # peaked logits: at least one selected gate must underflow to 0
        _, gates = moe._router(params, cfg, x.reshape(-1, 32))
        assert float(jnp.min(gates)) == 0.0, "case needs a hard-0 gate"
        y = moe.moe_apply(params, cfg, x)
        y_p = moe.moe_apply(params, cfg_p, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_p), atol=2e-4, rtol=2e-4
        )


class TestPhaseSlotProperty:
    """Property: within the envelope, no admitted token is ever dropped —
    every admitted remote choice gets a unique slot inside its phase
    block, across random tables and random routings."""

    @settings(max_examples=25, deadline=None)
    @given(
        st.integers(min_value=0, max_value=10_000),
        st.integers(min_value=1, max_value=2),
        st.integers(min_value=0, max_value=3),
    )
    def test_admitted_always_slotted(self, seed, e_local, me):
        rng = np.random.default_rng(seed)
        n = N_V
        n_experts = n * e_local
        m = rng.random((n, n)) * rng.integers(50, 2000)
        np.fill_diagonal(m, 0)
        row = ScheduleTable.from_schedules(
            [plan_schedule(decompose(m, "maxweight"))],
            k_max=n,
            envelope="auto",
        ).row(0)
        tk = int(rng.integers(8, 200))
        e_flat = jnp.asarray(
            rng.integers(0, n_experts, size=tk), jnp.int32
        )
        rank = moe._rank_in_group(e_flat)
        c_local = 1 + int(rng.integers(0, 64))
        slot, admitted, bases, env_slots, n_slots, _, _ = moe._phase_slot_assign(
            row, e_local, jnp.int32(me), e_flat, rank, c_local=c_local
        )
        slot = np.asarray(slot)
        admitted = np.asarray(admitted)
        rank = np.asarray(rank)
        e_np = np.asarray(e_flat)
        dst = e_np // e_local
        local = dst == me
        # 1. admission == the pair_caps prefix (traced-path semantics)
        caps = np.asarray(row.pair_caps(e_local))[me]
        np.testing.assert_array_equal(
            admitted, local | (rank < caps[dst])
        )
        # 2. every admitted REMOTE choice lands in a real slot — never the
        #    dump: the envelope sized the buffer from the admission caps
        assert (slot[admitted & ~local] < n_slots).all()
        # 3. slots are collision-free (each token its own slot)
        kept = slot[slot < n_slots]
        assert len(np.unique(kept)) == len(kept)
        # 4. each admitted remote choice sits inside some phase block of
        #    its own local-expert lane
        s_remote = n_slots - e_local * c_local
        for s_i, e_i in zip(slot[admitted & ~local], e_np[admitted & ~local]):
            k = int(np.searchsorted(np.asarray(bases), s_i, side="right")) - 1
            lo = bases[k] + (e_i % e_local) * env_slots[k]
            assert lo <= s_i < lo + env_slots[k]
            assert s_i < s_remote
        # 5. local choices never claim remote slots
        assert (slot[local & (slot < n_slots)] >= s_remote).all()
