"""Device-resident solver + controller tests (PR 7).

Property-tests the batched JAX auction LAP against the scipy
Jonker-Volgenant oracle (exact weight equality on integer matrices —
the module's headline contract), the traced greedy-phases planner
against per-phase LAP optimality on its own residual, the traced
link-mask/routing folds against their host twins, and the in-graph
observe -> score -> re-plan loop of ``DeviceController`` (hysteresis,
cooldown, masked re-plans, and the zero-recompile carry).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ControllerConfig,
    DeviceController,
    ScheduleRuntime,
    apply_link_mask,
    apply_link_mask_traced,
    auction_lap,
    auction_lap_batch,
    decompose_batch,
    greedy_phases_jax,
    matching_weight,
    routing_to_traffic,
    routing_to_traffic_traced,
)

N = 4  # fabric size of the controller tests (virtual ranks)
E = 8  # experts


def _int_matrix(rng, n, hi=1000):
    return rng.integers(0, hi, size=(n, n)).astype(np.float64)


def _scipy_weight(a, maximize=True):
    r, c = linear_sum_assignment(a, maximize=maximize)
    return float(np.asarray(a)[r, c].sum())


def _is_permutation(perm, n):
    return sorted(int(v) for v in np.asarray(perm)) == list(range(n))


# ------------------------------------------------------------- auction LAP
class TestAuctionLap:
    @given(
        st.integers(min_value=2, max_value=12),
        st.integers(min_value=0, max_value=2**31 - 1),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_permutation_and_scipy_weight(self, n, seed):
        """Integer matrices: valid permutation, weight == scipy exactly."""
        rng = np.random.default_rng(seed)
        a = _int_matrix(rng, n)
        perm = np.asarray(auction_lap(a))
        assert _is_permutation(perm, n)
        got = float(a[np.arange(n), perm].sum())
        assert got == _scipy_weight(a)

    def test_ties_stay_weight_optimal(self):
        """Heavily tied matrices: ties may break differently from scipy,
        but the matching weight must still be the optimum."""
        rng = np.random.default_rng(7)
        for _ in range(10):
            n = int(rng.integers(2, 10))
            a = rng.choice([0.0, 10.0, 20.0], size=(n, n))
            perm = np.asarray(auction_lap(a))
            assert _is_permutation(perm, n)
            assert float(a[np.arange(n), perm].sum()) == _scipy_weight(a)

    def test_minimize_matches_scipy(self):
        rng = np.random.default_rng(11)
        a = _int_matrix(rng, 8)
        perm = np.asarray(auction_lap(a, maximize=False))
        assert _is_permutation(perm, 8)
        got = float(a[np.arange(8), perm].sum())
        assert got == _scipy_weight(a, maximize=False)

    def test_float_matrices_within_subtoken_gap(self):
        """Arbitrary floats (EMA'd traffic): epsilon-optimal, gap < 1."""
        rng = np.random.default_rng(13)
        for _ in range(5):
            a = rng.random((10, 10)) * 500.0
            perm = np.asarray(auction_lap(a))
            got = float(a[np.arange(10), perm].sum())
            opt = _scipy_weight(a)
            assert opt - 1.0 <= got <= opt + 1e-3

    def test_link_mask_matches_scipy_on_penalized_matrix(self):
        """Masked solves are the same LAP instance scipy would see with
        dark pairs driven to the module's -big penalty: equal weight, and
        dark pairs only used when a row has no usable column left."""
        rng = np.random.default_rng(17)
        for _ in range(8):
            n = int(rng.integers(3, 10))
            a = _int_matrix(rng, n, hi=300)
            mask = rng.random((n, n)) < 0.7
            # keep one full permutation usable so darks are avoidable
            keep = rng.permutation(n)
            mask[np.arange(n), keep] = True
            perm = np.asarray(auction_lap(a, mask))
            assert _is_permutation(perm, n)
            assert mask[np.arange(n), perm].all()
            big = (np.abs(a).max() + 1.0) * (n + 1)
            pen = np.where(mask, a, -big)
            got = float(pen[np.arange(n), perm].sum())
            assert got == _scipy_weight(pen)

    def test_rejects_non_square(self):
        with pytest.raises(ValueError, match="square"):
            auction_lap(np.zeros((3, 4)))


class TestAuctionLapBatch:
    @given(
        st.integers(min_value=2, max_value=8),
        st.integers(min_value=0, max_value=2**31 - 1),
    )
    @settings(max_examples=15, deadline=None)
    def test_property_vmapped_parity_per_layer(self, n, seed):
        """Every layer of the vmapped solve matches its own scipy solve."""
        rng = np.random.default_rng(seed)
        stack = np.stack([_int_matrix(rng, n) for _ in range(4)])
        perms = np.asarray(auction_lap_batch(stack))
        assert perms.shape == (4, n)
        for l in range(4):
            assert _is_permutation(perms[l], n)
            got = float(stack[l][np.arange(n), perms[l]].sum())
            assert got == _scipy_weight(stack[l])

    def test_shared_mask_applies_to_every_layer(self):
        rng = np.random.default_rng(23)
        n = 6
        stack = np.stack([_int_matrix(rng, n, hi=200) for _ in range(3)])
        mask = np.ones((n, n), bool)
        mask[0, 1] = mask[3, 4] = False
        keep = rng.permutation(n)
        mask[np.arange(n), keep] = True
        perms = np.asarray(auction_lap_batch(stack, mask))
        for l in range(3):
            assert mask[np.arange(n), perms[l]].all()
            big = (np.abs(stack).max() + 1.0) * (n + 1)
            pen = np.where(mask, stack[l], -big)
            got = float(pen[np.arange(n), perms[l]].sum())
            assert got == _scipy_weight(pen)

    def test_rejects_wrong_rank(self):
        with pytest.raises(ValueError, match=r"\[L, n, n\]"):
            auction_lap_batch(np.zeros((4, 4)))


class TestMatchingWeight:
    def test_known_value_and_batching(self):
        a = np.arange(9, dtype=np.float64).reshape(3, 3)
        perm = np.array([2, 0, 1])
        assert float(matching_weight(a, perm)) == a[0, 2] + a[1, 0] + a[2, 1]
        stack = np.stack([a, 2 * a])
        w = np.asarray(matching_weight(stack, np.stack([perm, perm])))
        np.testing.assert_allclose(w, [12.0, 24.0])


# --------------------------------------------------------- traced planner
class TestGreedyPhasesJax:
    def _traffic(self, rng, L=3, n=6, hi=400):
        a = rng.integers(0, hi, size=(L, n, n)).astype(np.float64)
        for l in range(L):
            np.fill_diagonal(a[l], 0.0)
        return a

    def test_table_leaf_shapes_and_dtypes(self):
        rng = np.random.default_rng(3)
        a = self._traffic(rng)
        L, n = a.shape[0], a.shape[1]
        k = n
        plan = greedy_phases_jax(a, k_max=k)
        assert plan["perms"].shape == (L, k, n)
        assert plan["perms"].dtype == jnp.int32
        assert plan["caps"].shape == (L, k)
        assert plan["caps"].dtype == jnp.int32
        assert plan["valid"].shape == (L, k, n)
        assert plan["n_phases"].shape == (L,)
        # live slots form a prefix; dark slots carry identity perms, cap 0
        valid = np.asarray(plan["valid"])
        live = valid.any(axis=2)
        for l in range(L):
            nl = int(plan["n_phases"][l])
            assert live[l, :nl].all() and not live[l, nl:].any()
            np.testing.assert_array_equal(
                np.asarray(plan["perms"])[l, nl:],
                np.broadcast_to(np.arange(n), (k - nl, n)),
            )
            assert not np.asarray(plan["caps"])[l, nl:].any()

    def test_each_phase_is_lap_optimal_on_its_own_residual(self):
        """Slot k's matching is a scipy-optimal LAP solve of the residual
        the jax path itself carried into slot k (min_fill=0 greedy)."""
        rng = np.random.default_rng(5)
        a = self._traffic(rng)
        L, n = a.shape[0], a.shape[1]
        plan = greedy_phases_jax(a, k_max=n)
        perms = np.asarray(plan["perms"])
        valid = np.asarray(plan["valid"])
        sent = np.asarray(plan["sent"])
        for l in range(L):
            resid = a[l].copy()
            for k in range(n):
                # unpenalized, like the host greedy: diagonal entries are
                # zero in the residual, so parking on them is free
                got = float(resid[np.arange(n), perms[l, k]].sum())
                assert got == _scipy_weight(resid), (l, k)
                # sent is the residual at the matched usable pairs
                np.testing.assert_array_equal(
                    sent[l, k],
                    np.where(valid[l, k], resid[np.arange(n), perms[l, k]], 0.0),
                )
                resid[np.arange(n)[valid[l, k]], perms[l, k][valid[l, k]]] = 0.0

    def test_conservation_and_full_admission(self):
        """sent + residual == traffic; k_max = n clears every matrix."""
        rng = np.random.default_rng(9)
        a = self._traffic(rng)
        plan = greedy_phases_jax(a, k_max=a.shape[1])
        sent_total = np.asarray(plan["sent"]).sum()
        resid = np.asarray(plan["residual"])
        np.testing.assert_allclose(sent_total + resid.sum(), a.sum())
        np.testing.assert_allclose(resid, 0.0)

    def test_caps_follow_plan_schedule_rounding(self):
        rng = np.random.default_rng(15)
        a = self._traffic(rng)
        q, mc, slack = 8, 8, 1.1
        plan = greedy_phases_jax(
            a, k_max=a.shape[1], quantum=q, min_cap=mc, slack=slack
        )
        sent = np.asarray(plan["sent"])
        valid = np.asarray(plan["valid"])
        caps = np.asarray(plan["caps"])
        for l in range(a.shape[0]):
            for k in range(a.shape[1]):
                if not valid[l, k].any():
                    assert caps[l, k] == 0
                    continue
                want = max(int(np.ceil(sent[l, k].max() * slack)), mc)
                want = -(-want // q) * q
                assert caps[l, k] == want

    def test_masked_pairs_never_valid(self):
        rng = np.random.default_rng(21)
        a = self._traffic(rng)
        n = a.shape[1]
        mask = np.ones((n, n), bool)
        mask[0, 1] = mask[2, 5] = mask[4, 0] = False
        plan = greedy_phases_jax(a, k_max=n, mask=mask)
        perms = np.asarray(plan["perms"])
        valid = np.asarray(plan["valid"])
        for l in range(a.shape[0]):
            for k in range(n):
                on = valid[l, k]
                assert mask[np.arange(n)[on], perms[l, k][on]].all()

    def test_k_max_clip_leaves_planned_drops(self):
        rng = np.random.default_rng(27)
        a = self._traffic(rng, L=2, n=8)
        plan = greedy_phases_jax(a, k_max=2)
        assert np.asarray(plan["residual"]).sum() > 0
        assert int(np.asarray(plan["n_phases"]).max()) == 2


class TestDecomposeBatchJaxBackend:
    def _unique_stack(self, rng, L=3, n=6):
        """Distinct integer entries -> generically unique optima, so the
        two backends' greedy paths coincide phase for phase."""
        vals = rng.choice(100_000, size=L * n * n, replace=False)
        a = vals.reshape(L, n, n).astype(np.float64)
        for l in range(L):
            np.fill_diagonal(a[l], 0.0)
        return a

    def test_jax_backend_matches_scipy_path(self):
        rng = np.random.default_rng(31)
        a = self._unique_stack(rng)
        ref = decompose_batch(a, "maxweight")
        got = decompose_batch(a, "maxweight", backend="jax")
        for d_ref, d_got in zip(ref, got):
            assert d_got.meta["lap_backend"] == "jax"
            assert d_got.num_phases == d_ref.num_phases
            sp_ref, sp_got = d_ref.stacked(), d_got.stacked()
            # zero-residual rows admit many equal-weight matchings, so
            # perms are compared only where tokens actually move
            np.testing.assert_allclose(sp_got.sent, sp_ref.sent)
            moving = sp_ref.sent > 0
            np.testing.assert_array_equal(
                sp_got.perms[moving], sp_ref.perms[moving]
            )

    def test_jax_backend_respects_link_mask(self):
        rng = np.random.default_rng(37)
        a = self._unique_stack(rng, L=2, n=6)
        mask = np.ones((6, 6), bool)
        mask[0, 1] = mask[3, 2] = False
        out = decompose_batch(a, "maxweight", backend="jax", link_mask=mask)
        for d in out:
            assert d.meta.get("link_masked")
            sp = d.stacked()
            for k in range(sp.num_phases):
                on = sp.sent[k] > 0
                assert mask[np.arange(6)[on], sp.perms[k][on]].all()

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            decompose_batch(np.zeros((1, 4, 4)), "maxweight", backend="tpu")


# ------------------------------------------------------------ traced twins
class TestTracedTwins:
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_property_link_mask_parity_with_host(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 9))
        a = rng.random((n, n)) * 300.0
        np.fill_diagonal(a, rng.random(n) * 50.0)
        mask = rng.random((n, n)) < 0.6
        np.fill_diagonal(mask, True)
        want = apply_link_mask(a, mask)
        got = np.asarray(apply_link_mask_traced(a, mask))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_link_mask_traced_idempotent_and_batched(self):
        rng = np.random.default_rng(41)
        a = rng.random((3, 5, 5)) * 100.0
        mask = rng.random((5, 5)) < 0.5
        np.fill_diagonal(mask, True)
        once = np.asarray(apply_link_mask_traced(a, mask))
        twice = np.asarray(apply_link_mask_traced(once, mask))
        np.testing.assert_allclose(twice, once, rtol=1e-5, atol=1e-5)
        for l in range(3):
            np.testing.assert_allclose(
                once[l], apply_link_mask(a[l], mask), rtol=1e-5, atol=1e-5
            )

    @pytest.mark.parametrize("n_src", [1, N, 2 * N])
    def test_routing_fold_parity_with_host(self, n_src):
        rng = np.random.default_rng(43)
        stats = rng.integers(0, 50, size=(3, n_src, E)).astype(np.float64)
        want = routing_to_traffic(stats, n_ranks=N, n_experts=E)
        got = np.asarray(
            routing_to_traffic_traced(stats, n_ranks=N, n_experts=E)
        )
        np.testing.assert_allclose(got, want, rtol=1e-6)


# -------------------------------------------------------- device controller
def _runtime(L=2, **cfg_kw):
    kw = dict(n_ranks=N, n_experts=E, ema=1.0, cooldown=0)
    kw.update(cfg_kw)
    return ScheduleRuntime(ControllerConfig(**kw), L)


def _stats_of(traffic):
    """[L, n, n] rank traffic -> [L, n, E] routing counts folding back to
    exactly that traffic (each rank's share split over its experts)."""
    t = np.asarray(traffic, dtype=np.float64)
    L, n, _ = t.shape
    e_local = E // n
    stats = np.repeat(t / e_local, e_local, axis=2)
    np.testing.assert_allclose(
        routing_to_traffic(stats, n_ranks=n, n_experts=E), t
    )
    return stats


def _hot_traffic(L=2, hot=3, scale=600.0):
    """Hotspot column traffic: everything wants rank ``hot``."""
    t = np.full((L, N, N), 4.0)
    t[:, :, hot] = scale
    for l in range(L):
        np.fill_diagonal(t[l], 0.0)
    return t


def _flat_traffic(L=2, scale=100.0):
    t = np.full((L, N, N), scale)
    for l in range(L):
        np.fill_diagonal(t[l], 0.0)
    return t


class TestDeviceController:
    def test_from_runtime_adopts_table_and_policy(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt)
        tbl = rt.table()
        dev = ctrl.table_of(state)
        np.testing.assert_array_equal(np.asarray(dev.perms), np.asarray(tbl.perms))
        np.testing.assert_array_equal(np.asarray(dev.caps), np.asarray(tbl.caps))
        np.testing.assert_array_equal(np.asarray(dev.valid), np.asarray(tbl.valid))
        assert dev.envelope == tbl.envelope
        assert ctrl.cfg.ema == rt.cfg.ema
        assert ctrl.cfg.drop_tolerance == rt.cfg.drop_tolerance
        assert int(state.steps) == 1  # primed EMA counts as an observation

    def test_steady_state_never_replans(self):
        rt = _runtime()
        flat = _flat_traffic()
        rt.prime(flat[0])
        ctrl, state = DeviceController.from_runtime(rt)
        stats = _stats_of(flat)
        for _ in range(8):
            state = ctrl.step(state, stats)
        m = ctrl.metrics(state)
        assert m["device_replans"] == 0
        assert m["drop_fraction"] <= ctrl.cfg.drop_tolerance

    def test_drift_fires_in_graph_replan_and_absorbs_it(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt, hysteresis_steps=2)
        stats = _stats_of(_hot_traffic())
        for _ in range(4):
            state = ctrl.step(state, stats)
        m = ctrl.metrics(state)
        assert m["device_replans"] >= 1
        # the re-planned table absorbs the hotspot: drop back under tol
        assert m["drop_fraction"] <= ctrl.cfg.drop_tolerance

    def test_hysteresis_counts_consecutive_steps(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt, hysteresis_steps=3)
        hot = _stats_of(_hot_traffic())
        state = ctrl.step(state, hot)  # streak 1
        assert ctrl.metrics(state)["device_replans"] == 0
        state = ctrl.step(state, hot)  # streak 2
        assert ctrl.metrics(state)["device_replans"] == 0
        state = ctrl.step(state, hot)  # streak 3 -> fires
        assert ctrl.metrics(state)["device_replans"] == 1

    def test_cooldown_blocks_refire(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(
            rt, hysteresis_steps=1, cooldown=50
        )
        # alternate hotspots so drift pressure persists after each re-plan
        a = _stats_of(_hot_traffic(hot=3))
        b = _stats_of(_hot_traffic(hot=0))
        state = ctrl.step(state, a)
        assert ctrl.metrics(state)["device_replans"] == 1
        for i in range(6):
            state = ctrl.step(state, b if i % 2 == 0 else a)
        assert ctrl.metrics(state)["device_replans"] == 1  # cooldown holds

    def test_stepping_is_one_executable(self):
        """Steady and drift steps (the re-plan included) share one
        compiled step — the cond is data, not structure."""
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt, hysteresis_steps=1)
        step = jax.jit(ctrl.step)
        flat = jnp.asarray(_stats_of(_flat_traffic()))
        hot = jnp.asarray(_stats_of(_hot_traffic()))
        for _ in range(3):
            state = step(state, flat)
        state = step(state, hot)
        state = step(state, hot)
        assert ctrl.metrics(state)["device_replans"] >= 1
        assert step._cache_size() == 1

    def test_set_link_mask_replans_off_dark_pairs(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt)
        mask = np.ones((N, N), bool)
        mask[0, 2] = mask[2, 0] = False
        state = ctrl.set_link_mask(state, mask)
        m = ctrl.metrics(state)
        assert m["device_replans"] == 1 and m["link_masked"]
        perms = np.asarray(state.perms)
        valid = np.asarray(state.valid)
        L, K, _ = perms.shape
        for l in range(L):
            for k in range(K):
                on = valid[l, k]
                assert mask[np.arange(N)[on], perms[l, k][on]].all()
        # scoring after the mask uses the rerouted demand: steady flat
        # traffic stays under tolerance on the masked plan
        state = ctrl.step(state, _stats_of(_flat_traffic()))
        assert ctrl.metrics(state)["drop_fraction"] <= ctrl.cfg.drop_tolerance

    def test_metrics_is_plain_host_telemetry(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state = DeviceController.from_runtime(rt)
        m = ctrl.metrics(state)
        assert set(m) == {
            "steps", "device_replans", "drop_fraction", "drift_streak",
            "cooldown_left", "drop_spikes", "admitted_dropped", "link_masked",
            "regime_library_size", "regime_warm_swaps",
        }
        assert isinstance(m["steps"], int)
        assert isinstance(m["drop_fraction"], float)
        assert m["link_masked"] is False

    def test_state_is_a_pytree_with_array_leaves(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        _, state = DeviceController.from_runtime(rt)
        leaves = jax.tree.leaves(state)
        assert len(leaves) == len(dataclasses.fields(state))
        roundtrip = jax.tree.unflatten(jax.tree.structure(state), leaves)
        assert isinstance(roundtrip, type(state))


class TestDeviceTrainLoop:
    def test_device_controller_rides_the_fused_step(self, tmp_path):
        """End to end: the in-graph loop absorbs router drift with zero
        recompiles and zero per-step host fetches of routing stats."""
        from test_schedule_table import N_V, _moe_cfg

        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = _moe_cfg(n_layers=2)
        model = Model(cfg)
        rt = ScheduleRuntime(
            ControllerConfig(n_ranks=N_V, n_experts=8, ema=1.0, cooldown=2),
            model.n_moe_layers,
        )
        tokens = 8 * 32 * 2
        rt.prime(np.full((N_V, N_V), tokens / N_V**2))
        ctrl, state0 = DeviceController.from_runtime(rt, hysteresis_steps=1)
        res = train_loop(
            model,
            DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8),
            TrainLoopConfig(
                steps=10, ckpt_dir=str(tmp_path), ckpt_every=20,
                peak_lr=1e-3, warmup=4, log_every=5,
            ),
            device_controller=ctrl,
            device_ctrl_state=state0,
        )
        ctl = res["controller"]
        assert ctl["mode"] == "device"
        assert ctl["compiles"] == 0, ctl
        assert ctl["steps"] == 10 + 1, ctl  # primed state counts step 0
        assert np.isfinite(res["final_loss"])
        assert "device_ctrl_state" in res
        # telemetry rides the logging cadence, not the step
        assert all("device_replans" in h for h in res["history"])
        assert all("drop_fraction" in h for h in res["history"])

    def test_device_mode_validation(self):
        from test_schedule_table import N_V, _moe_cfg

        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = _moe_cfg(n_layers=2)
        model = Model(cfg)
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl, state0 = DeviceController.from_runtime(rt)
        data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
        loop = TrainLoopConfig(steps=2, ckpt_dir="/tmp/x", ckpt_every=20)
        with pytest.raises(ValueError, match="mutually exclusive"):
            train_loop(
                model, data, loop,
                runtime=rt, device_controller=ctrl, device_ctrl_state=state0,
            )
        with pytest.raises(ValueError, match="initial state"):
            train_loop(model, data, loop, device_controller=ctrl)


# ------------------------------------------------------ schedule regime bank
def _regime_ctrl(**cfg_kw):
    """Flat-primed controller with an (empty) 2-slot regime library."""
    rt = _runtime()
    rt.prime(_flat_traffic()[0])
    kw = dict(hysteresis_steps=1, cooldown=0, regime_slots=2,
              regime_threshold=0.25)
    kw.update(cfg_kw)
    return DeviceController.from_runtime(rt, **kw)


def _hot_regime_entry(ctrl, state):
    """Cold-solve the hotspot regime once and snapshot (table, reference)
    — the capture pattern the serving engine uses."""
    hot = _stats_of(_hot_traffic())
    s = state
    for _ in range(3):
        s = ctrl.step(s, hot)
    assert ctrl.metrics(s)["device_replans"] >= 1
    tab = jax.tree.map(np.asarray, ctrl.table_of(s))
    ref = np.asarray(s.smoothed).mean(axis=0)
    return tab, ref, hot


class TestRegimeLibrary:
    def test_load_regimes_validation(self):
        rt = _runtime()
        rt.prime(_flat_traffic()[0])
        ctrl0, state0 = DeviceController.from_runtime(rt)
        tab = jax.tree.map(np.asarray, ctrl0.table_of(state0))
        ref = _flat_traffic()[0]
        with pytest.raises(ValueError, match="regime_slots"):
            ctrl0.load_regimes(state0, [tab], [ref])
        ctrl, state = _regime_ctrl()
        with pytest.raises(ValueError, match="tables vs"):
            ctrl.load_regimes(state, [tab], [ref, ref])
        with pytest.raises(ValueError, match="exceed regime_slots"):
            ctrl.load_regimes(state, [tab] * 3, [ref] * 3)
        with pytest.raises(ValueError, match="reference shape"):
            ctrl.load_regimes(state, [tab], [np.ones((N + 1, N + 1))])
        loaded = ctrl.load_regimes(state, [tab], [ref])
        assert ctrl.metrics(loaded)["regime_library_size"] == 1

    def test_warm_swap_replays_stored_plan_bit_identical(self):
        ctrl, state = _regime_ctrl()
        tab, ref, hot = _hot_regime_entry(ctrl, state)
        state = ctrl.load_regimes(state, [tab], [ref])
        for _ in range(3):
            state = ctrl.step(state, hot)
        m = ctrl.metrics(state)
        assert m["regime_warm_swaps"] >= 1
        np.testing.assert_array_equal(np.asarray(state.perms), tab.perms)
        np.testing.assert_array_equal(np.asarray(state.caps), tab.caps)
        np.testing.assert_array_equal(np.asarray(state.valid), tab.valid)
        np.testing.assert_array_equal(
            np.asarray(state.n_phases), tab.n_phases
        )
        # the warm plan absorbs the regime it was planned for
        assert m["drop_fraction"] <= ctrl.cfg.drop_tolerance

    def test_unrecognized_regime_cold_solves(self):
        # library holds only the FLAT regime; hotspot traffic is far from
        # it in shape, so the fire must take the cold branch
        ctrl, state = _regime_ctrl(regime_threshold=0.05)
        flat_tab = jax.tree.map(np.asarray, ctrl.table_of(state))
        state = ctrl.load_regimes(
            state, [flat_tab], [_flat_traffic()[0]]
        )
        hot = _stats_of(_hot_traffic())
        for _ in range(3):
            state = ctrl.step(state, hot)
        m = ctrl.metrics(state)
        assert m["device_replans"] >= 1
        assert m["regime_warm_swaps"] == 0
        # and the cold solve absorbed the hotspot anyway
        assert m["drop_fraction"] <= ctrl.cfg.drop_tolerance

    def test_degraded_link_mask_disables_warm_matching(self):
        # stored plans were routed for the healthy fabric: with a dark
        # link the fire must re-solve under the mask, not warm-swap
        ctrl, state = _regime_ctrl()
        tab, ref, hot = _hot_regime_entry(ctrl, state)
        state = ctrl.load_regimes(state, [tab], [ref])
        mask = np.ones((N, N), bool)
        mask[0, 1] = mask[1, 0] = False
        state = ctrl.set_link_mask(state, mask)
        replans0 = ctrl.metrics(state)["device_replans"]
        for _ in range(3):
            state = ctrl.step(state, hot)
        m = ctrl.metrics(state)
        assert m["device_replans"] > replans0
        assert m["regime_warm_swaps"] == 0

    def test_replan_penalty_blocks_cold_but_not_warm(self):
        hot = _stats_of(_hot_traffic())
        # penalty above any achievable drop saving: cold fires are never
        # worth the dark window, so the controller rides the stale plan
        ctrl, state = _regime_ctrl(replan_penalty=0.99)
        for _ in range(4):
            state = ctrl.step(state, hot)
        m = ctrl.metrics(state)
        assert m["device_replans"] == 0
        assert m["drop_fraction"] > ctrl.cfg.drop_tolerance  # pressure real
        # a warm swap rides pre-established circuits (no dark window):
        # the same penalty does not block it
        ctrl2, state2 = _regime_ctrl(replan_penalty=0.99)
        tab, ref, _ = _hot_regime_entry(_regime_ctrl()[0], _regime_ctrl()[1])
        state2 = ctrl2.load_regimes(state2, [tab], [ref])
        for _ in range(4):
            state2 = ctrl2.step(state2, hot)
        m2 = ctrl2.metrics(state2)
        assert m2["regime_warm_swaps"] >= 1
        assert m2["device_replans"] >= 1
