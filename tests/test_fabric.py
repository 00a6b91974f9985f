"""Fabric API (PR 5): the registry, the error paths, and the
cross-fabric parity matrix.

The real EP movement is exercised on an 8-device mesh in
``tests/multidev_fabric.py`` (slow lane); everything here runs on one
device, where every mesh backend resolves through the shared *virtual*
dense fallback — which is itself part of the parity matrix: all
registered fabrics must agree on values, grads, and the
``{routing, dropped}`` stats contract because they share one pipeline
and one geometry module, and the single-device virtual fabric must
execute a traced row's admission semantics identically to the pair-caps
oracle.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoECfg
from repro.core import (
    ScheduleTable,
    decompose,
    hierarchical_plan,
    plan_schedule,
)
from repro.models import moe
from repro.parallel.fabric import (
    FABRICS,
    consumes_schedule,
    fabric_names,
    get_fabric,
    resolve_fabric,
)

N_V = 4
ALL_FABRICS = ("dense", "a2a", "ppermute", "phase_pipelined", "ragged_a2a")


def _cfg(dispatch: str = "dense", **moe_kw):
    kw = dict(
        n_experts=8, top_k=2, d_ff_expert=32, dispatch=dispatch,
        capacity_factor=8.0,
    )
    kw.update(moe_kw)
    return ModelConfig(
        name="fabric-test",
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


def _plan(seed: int, scale: float = 400.0, n: int = N_V):
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) * scale
    np.fill_diagonal(m, 0)
    return plan_schedule(decompose(m, "maxweight"))


def _row(seed: int = 0, envelope="auto"):
    return ScheduleTable.from_schedules(
        [_plan(seed)], k_max=N_V, envelope=envelope
    ).row(0)


def _htraffic(seed: int = 2, scale: float = 400.0, n: int = N_V):
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) * scale
    np.fill_diagonal(m, 0)
    return m


def _hrow(pod_size: int = 2, seed: int = 2):
    return hierarchical_plan(_htraffic(seed), pod_size, n_layers=1).row(0)


class TestRegistry:
    def test_all_five_registered(self):
        assert set(ALL_FABRICS) <= set(fabric_names())

    def test_unknown_dispatch_lists_registered_names(self):
        """Satellite: the error names every registered fabric."""
        with pytest.raises(ValueError) as e:
            get_fabric("photonic_tbd")
        msg = str(e.value)
        for name in fabric_names():
            assert name in msg, f"{name} missing from: {msg}"
        assert "scheduled" in msg  # the alias is documented too

    def test_moe_apply_unknown_dispatch(self):
        cfg = _cfg("warp_drive")
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jnp.zeros((1, 4, 32), jnp.float32)
        with pytest.raises(ValueError, match="registered fabrics"):
            moe.moe_apply(params, cfg, x)

    def test_scheduled_alias_resolution(self):
        from repro.parallel.fabric import (
            PhasePipelinedFabric,
            PPermuteFabric,
        )

        assert isinstance(
            resolve_fabric("scheduled", _plan(0)), PPermuteFabric
        )
        assert isinstance(
            resolve_fabric("scheduled", _row()), PhasePipelinedFabric
        )
        with pytest.raises(ValueError, match="A2ASchedule or ScheduleTable"):
            resolve_fabric("scheduled", None)

    def test_consumes_schedule_capabilities(self):
        from repro.parallel.fabric import consumes_table

        assert not consumes_schedule("dense")
        assert not consumes_schedule("a2a")
        for name in ("ppermute", "phase_pipelined", "ragged_a2a", "scheduled"):
            assert consumes_schedule(name), name
        # ppermute needs a schedule but cannot take the controller's
        # traced rows (plans are baked into its executable)
        assert not consumes_table("ppermute")
        for name in ("phase_pipelined", "ragged_a2a", "scheduled"):
            assert consumes_table(name), name
        with pytest.raises(ValueError, match="registered fabrics"):
            consumes_schedule("warp_drive")

    def test_as_fabric_schedule_adapts_static_plans(self):
        from repro.parallel.fabric import as_fabric_schedule

        plan = _plan(0)
        assert as_fabric_schedule("ppermute", plan, 3) is plan
        assert as_fabric_schedule("scheduled", plan, 3) is plan
        t = as_fabric_schedule("ragged_a2a", plan, 3)
        assert isinstance(t, ScheduleTable)
        assert t.num_layers == 3 and t.envelope is not None
        assert as_fabric_schedule("phase_pipelined", t, 3) is t

    def test_train_loop_refuses_runtime_for_static_fabric(self):
        """A controller runtime cannot swap a baked-in ppermute plan —
        the loop must refuse up front, naming the traced alternatives,
        instead of trace-failing max_failures+1 times."""
        from repro.core import ControllerConfig, ScheduleRuntime
        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = _cfg("ppermute")
        model = Model(cfg)
        rt = ScheduleRuntime(
            ControllerConfig(n_ranks=N_V, n_experts=8), model.n_moe_layers
        )
        rt.prime(np.full((N_V, N_V), 100.0))
        with pytest.raises(ValueError, match="phase_pipelined"):
            train_loop(
                model,
                DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                           global_batch=2),
                TrainLoopConfig(steps=1, ckpt_dir="/tmp/fab_pp_ck"),
                runtime=rt,
            )


class TestScheduleMisuse:
    """Satellite: row/schedule misuse errors name the rejecting backend."""

    def test_ppermute_rejects_row_by_name(self):
        with pytest.raises(ValueError, match="ppermute"):
            get_fabric("ppermute").validate_schedule(_row(), n=N_V)

    def test_ppermute_requires_schedule(self):
        with pytest.raises(ValueError, match="ppermute"):
            get_fabric("ppermute").validate_schedule(None, n=N_V)

    def test_row_backends_reject_static_schedule_by_name(self):
        for name in ("phase_pipelined", "ragged_a2a"):
            with pytest.raises(ValueError, match=name):
                get_fabric(name).validate_schedule(_plan(0), n=N_V)

    def test_row_backends_reject_full_table_by_name(self):
        table = ScheduleTable.from_schedules([_plan(0), _plan(1)], k_max=N_V)
        for name in ("phase_pipelined", "ragged_a2a"):
            with pytest.raises(ValueError, match=name):
                get_fabric(name).validate_schedule(table, n=N_V)

    def test_rank_mismatch_names_backend(self):
        row = _row()
        with pytest.raises(ValueError, match="phase_pipelined.*4 ranks"):
            get_fabric("phase_pipelined").validate_schedule(row, n=8)

    def test_ragged_requires_envelope(self):
        with pytest.raises(ValueError, match="ragged_a2a.*envelope"):
            get_fabric("ragged_a2a").validate_schedule(
                _row(envelope=None), n=N_V
            )

    def test_moe_apply_still_rejects_full_table(self):
        cfg = _cfg("phase_pipelined")
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        table = ScheduleTable.from_schedules([_plan(0)], k_max=N_V)
        with pytest.raises(ValueError, match="row"):
            moe.moe_apply(params, cfg, jnp.zeros((1, 4, 32)), schedule=table)

    def test_errors_name_the_fallback_fabric(self):
        """PR 6 satellite: every schedule-rejection error states the next
        fabric in the degradation chain, so a failing config tells the
        operator what to fall back to without a docs round-trip."""
        from repro.parallel.fabric import DEGRADATION_CHAIN, next_fabric

        cases = [
            ("ppermute", _row()),
            ("phase_pipelined", _plan(0)),
            ("ragged_a2a", _plan(0)),
            ("ragged_a2a", _row(envelope=None)),
        ]
        for name, bad in cases:
            with pytest.raises(ValueError) as e:
                get_fabric(name).validate_schedule(bad, n=N_V)
            nxt = next_fabric(name)
            assert nxt in DEGRADATION_CHAIN
            assert f"next fabric is {nxt!r}" in str(e.value), (name, str(e.value))

    def test_end_of_chain_says_so(self):
        """dense is the chain's floor: its rejections must say there is
        nowhere left to fall."""
        table = ScheduleTable.from_schedules([_plan(0), _plan(1)], k_max=N_V)
        with pytest.raises(ValueError, match="end of degradation chain"):
            get_fabric("dense").validate_schedule(table, n=N_V)


class TestParityMatrixSingleDevice:
    """The parity matrix on one device: every registered fabric resolves
    through the shared virtual dense fallback, so values, grads, and the
    stats contract must agree bit-for-bit across all of them — and with
    the explicit dense oracle."""

    def setup_method(self):
        self.x = jax.random.normal(
            jax.random.PRNGKey(1), (4, 32, 32), jnp.float32
        )
        self.params = moe.moe_init(jax.random.PRNGKey(0), _cfg())

    def _sched_for(self, name):
        if name in ("phase_pipelined", "ragged_a2a"):
            return _row(seed=2)
        if name == "ppermute":
            return _plan(2)
        return None

    @pytest.mark.parametrize("name", ALL_FABRICS)
    def test_values_grads_stats_match_dense(self, name):
        cfg = _cfg(name)
        y_ref, st_ref = moe._moe_dense(
            self.params, _cfg(), self.x, return_stats=True
        )
        y, st = moe.moe_apply(
            self.params, cfg, self.x, schedule=self._sched_for(name),
            return_stats=True,
        )
        if name in ("phase_pipelined", "ragged_a2a"):
            # the row clips gates on the virtual fabric: compare against
            # the dense oracle given the SAME row
            y_ref, st_ref = moe._moe_dense(
                self.params, _cfg(), self.x, self._sched_for(name),
                return_stats=True,
            )
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))
        assert set(st) == {"routing", "dropped"}  # the stats contract
        assert st["routing"].shape == (1, 8)
        assert st["dropped"].shape == (1,)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        g = jax.grad(
            lambda p: (moe.moe_apply(
                p, cfg, self.x, schedule=self._sched_for(name)
            ) ** 2).sum()
        )(self.params)
        g_ref = jax.grad(
            lambda p: (moe._moe_dense(
                p, _cfg(), self.x,
                self._sched_for(name)
                if name in ("phase_pipelined", "ragged_a2a")
                else None,
            ) ** 2).sum()
        )(self.params)
        for ga, gr in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(gr))

    def test_row_fabrics_agree_with_each_other(self):
        """phase_pipelined and ragged_a2a share geometry by construction;
        the virtual fallback must not break that."""
        row = _row(seed=3)
        outs = [
            moe.moe_apply(
                self.params, _cfg(name), self.x, schedule=row,
                return_stats=True,
            )
            for name in ("phase_pipelined", "ragged_a2a")
        ]
        np.testing.assert_allclose(
            np.asarray(outs[0][0]), np.asarray(outs[1][0])
        )
        for a, b in zip(
            jax.tree.leaves(outs[0][1]), jax.tree.leaves(outs[1][1])
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_virtual_fabric_row_clips_like_pair_caps_oracle(self):
        """The single-device virtual fabric executes the row's admission
        exactly as pair_caps promises (a tight plan must bind)."""
        tiny = np.full((N_V, N_V), 1.0)
        np.fill_diagonal(tiny, 0)
        row = ScheduleTable.from_schedules(
            [plan_schedule(decompose(tiny, "maxweight"), min_cap=1, quantum=1)]
        ).row(0)
        for name in ("phase_pipelined", "scheduled"):
            y_row = moe.moe_apply(
                self.params, _cfg(name), self.x, schedule=row
            )
            y_free = moe._moe_dense(self.params, _cfg(), self.x)
            assert not np.allclose(
                np.asarray(y_row), np.asarray(y_free), atol=1e-6
            ), name


class TestHierarchicalSingleDevice:
    """PR 9: the composed fabric's single-device leg of the parity
    matrix.  On one device ``hierarchical`` resolves through the same
    virtual dense fallback as the flat traced fabrics, reading admission
    from the HierarchicalTable's summed per-level pair caps and the wire
    mask from the pod seam — values, grads, and the stats contract must
    match the dense oracle handed the same composed row."""

    def setup_method(self):
        self.x = jax.random.normal(
            jax.random.PRNGKey(1), (4, 32, 32), jnp.float32
        )
        self.params = moe.moe_init(jax.random.PRNGKey(0), _cfg())

    @pytest.mark.parametrize("pod_size", (2, 4))
    def test_values_grads_stats_match_dense_oracle(self, pod_size):
        row = _hrow(pod_size)
        cfg = _cfg("hierarchical", pod_size=pod_size)
        y, st = moe.moe_apply(
            self.params, cfg, self.x, schedule=row, return_stats=True
        )
        y_ref, st_ref = moe._moe_dense(
            self.params, _cfg(), self.x, row, return_stats=True
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))
        assert set(st) == {"routing", "dropped"}
        assert st["routing"].shape == (1, 8)
        assert st["dropped"].shape == (1,)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        g = jax.grad(
            lambda p: (
                moe.moe_apply(p, cfg, self.x, schedule=row) ** 2
            ).sum()
        )(self.params)
        g_ref = jax.grad(
            lambda p: (moe._moe_dense(p, _cfg(), self.x, row) ** 2).sum()
        )(self.params)
        for ga, gr in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(gr))

    def test_admission_binds_like_flat_row(self):
        """A tight two-level plan must clip gates: the composed table's
        summed per-level pair caps feed the same admission mask the flat
        row fabrics use."""
        tiny = np.full((N_V, N_V), 1.0)
        np.fill_diagonal(tiny, 0)
        row = hierarchical_plan(
            tiny, 2, n_layers=1, min_cap=1, quantum=1
        ).row(0)
        y_row = moe.moe_apply(
            self.params, _cfg("hierarchical"), self.x, schedule=row
        )
        y_free = moe._moe_dense(self.params, _cfg(), self.x)
        assert not np.allclose(
            np.asarray(y_row), np.asarray(y_free), atol=1e-6
        )

    def test_wire_crosses_only_the_pod_seam(self):
        """fp8 quantizes only inter-pod slots: one pod covering every
        rank makes the codec a bit-exact no-op, two pods engage it
        within the documented tolerance, and routing/drop stats stay
        bit-identical either way (admission precedes the codec)."""
        row4 = _hrow(4)
        y4 = moe.moe_apply(
            self.params, _cfg("hierarchical", pod_size=4), self.x,
            schedule=row4,
        )
        y4_q = moe.moe_apply(
            self.params,
            _cfg("hierarchical", pod_size=4, wire_dtype="fp8"),
            self.x, schedule=row4,
        )
        np.testing.assert_array_equal(np.asarray(y4_q), np.asarray(y4))
        row2 = _hrow(2)
        y2, st2 = moe.moe_apply(
            self.params, _cfg("hierarchical"), self.x, schedule=row2,
            return_stats=True,
        )
        y2_q, st2_q = moe.moe_apply(
            self.params, _cfg("hierarchical", wire_dtype="fp8"), self.x,
            schedule=row2, return_stats=True,
        )
        err = float(jnp.abs(y2_q - y2).max())
        assert 0.0 < err <= TestWireDtypeParity.VALUE_TOL["fp8"], err
        for a, b in zip(jax.tree.leaves(st2_q), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBytesAccounting:
    """Per-fabric ``dispatch_tokens``: the acceptance ordering —
    ragged == phase-pipelined == live envelope bytes, strictly below
    both the dense-emulation padded figure (the emulation tax, reported
    separately via ``dispatch_tokens_padded``) and the monolithic a2a
    bucket on a skewed plan."""

    def test_ordering_on_skewed_plan(self):
        from repro.core.cost_models import phase_dispatch_tokens

        rng = np.random.default_rng(11)
        n = 8
        m = rng.random((n, n))
        m[0, 1] = 60.0  # one hot pair, many near-dark ones
        np.fill_diagonal(m, 0)
        sched = plan_schedule(decompose(m, "maxweight", min_fill=0.1))
        from repro.core.schedule import phase_envelope

        env = phase_envelope([sched], sched.num_phases, slack=1.5)
        cap_uni = 64
        cap_nodrop = max(cap_uni, sched.pair_capacity())
        a2a = get_fabric("a2a").dispatch_tokens(n=n, cap_uniform=cap_nodrop)
        ragged = get_fabric("ragged_a2a").dispatch_tokens(
            n=n, schedule=sched, envelope=env
        )
        live = get_fabric("phase_pipelined").dispatch_tokens(
            n=n, schedule=sched, envelope=env
        )
        emul = get_fabric("phase_pipelined").dispatch_tokens_padded(
            n=n, envelope=env
        )
        static = get_fabric("ppermute").dispatch_tokens(n=n, schedule=sched)
        dense = get_fabric("dense").dispatch_tokens(n=n)
        # both traced fabrics carry exactly the live envelope bytes
        assert ragged == pytest.approx(
            float(np.mean(phase_dispatch_tokens(sched.valid, env)))
        )
        assert live == ragged
        assert dense == 0.0
        assert static <= ragged < emul
        assert ragged < a2a, (ragged, a2a)


class TestRaggedFallback:
    def test_fallback_is_emulation_off_tpu(self, monkeypatch):
        from repro.parallel.fabric import ragged_available

        # off-TPU, unforced, the backend runs the parent's dense
        # emulation; forcing it selects the primitive
        monkeypatch.delenv("REPRO_FORCE_RAGGED", raising=False)
        assert not ragged_available()
        monkeypatch.setenv("REPRO_FORCE_RAGGED", "1")
        assert ragged_available()


class TestEnvelopeShrink:
    """Satellite: ControllerConfig.envelope_decay — sustained underuse
    shrinks the envelope; a shrink is the one counted recompile."""

    def _runtime(self, decay, patience=2):
        from repro.core import ControllerConfig, ScheduleRuntime

        return ScheduleRuntime(
            ControllerConfig(
                n_ranks=N_V, n_experts=8, ema=1.0, cooldown=0,
                envelope_slack=1.5, envelope_decay=decay,
                shrink_patience=patience,
            ),
            1,
        )

    @staticmethod
    def _hot_prime():
        """A hot-column regime: rank 0's experts soak ~4000 tokens/pair,
        everything else trickles — the envelope is sized for the spike."""
        m = np.full((N_V, N_V), 10.0)
        m[:, 0] = 4000.0
        np.fill_diagonal(m, 0.0)
        return m

    def _drive(self, rt, scale, steps, start=0):
        """Cool the regime: the hot expert rotates at a much lower
        scale, so each rotation misses the current plan (the cold
        pair's min-cap slots drop hard) and triggers a rebuild whose
        plans need far less than the primed envelope."""
        for i in range(start, start + steps):
            probs = np.full(8, 0.01)
            # rotate among ranks 1-3 only: revisiting rank 0 would
            # re-adopt the primed hot plan, whose caps legitimately
            # regrow the envelope (plans, not traffic, size buffers)
            probs[[2, 4, 6, 3, 5, 7][i % 6]] = 1.0
            probs /= probs.sum()
            rt.observe(scale * probs[None, None, :])
            rt.table()

    def test_shrink_after_sustained_underuse(self):
        rt = self._runtime(decay=0.5, patience=2)
        rt.prime(self._hot_prime())
        env_hot = rt.table().envelope
        self._drive(rt, scale=400.0, steps=8)  # traffic cools way down
        m = rt.metrics()
        assert m["envelope_shrinks"] >= 1, m
        env_cold = rt.table().envelope
        assert sum(env_cold) < sum(env_hot), (env_hot, env_cold)
        # shrunk slots still cover the current plans (no-drop invariant)
        for s in rt.schedules:
            k = min(s.num_phases, len(env_cold))
            assert (np.asarray(env_cold[:k]) >= np.asarray(s.caps[:k])).all()

    def test_decay_zero_never_shrinks(self):
        rt = self._runtime(decay=0.0)
        rt.prime(self._hot_prime())
        rt.table()
        self._drive(rt, scale=400.0, steps=8)
        assert rt.metrics()["envelope_shrinks"] == 0

    def test_shrink_is_one_recompile(self):
        """The jit cache grows by exactly one when the (static aux)
        envelope shrinks — same contract as a growth."""
        rt = self._runtime(decay=0.5, patience=2)
        rt.prime(self._hot_prime())
        cfg = _cfg("phase_pipelined")
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 16, 32), jnp.float32)
        f = jax.jit(lambda p, x, r: moe.moe_apply(p, cfg, x, schedule=r))
        f(params, x, rt.table().row(0))
        steps = 0
        while rt.metrics()["envelope_shrinks"] == 0 and steps < 12:
            self._drive(rt, scale=400.0, steps=1, start=steps)
            steps += 1
        assert rt.metrics()["envelope_shrinks"] == 1
        f(params, x, rt.table().row(0))
        assert f._cache_size() == 2, "envelope shrink must retrace once"
        f(params, x, rt.table().row(0))
        assert f._cache_size() == 2

    def test_decay_validation(self):
        from repro.core import ControllerConfig

        with pytest.raises(ValueError, match="envelope_decay"):
            ControllerConfig(n_ranks=4, n_experts=8, envelope_decay=1.5)
        with pytest.raises(ValueError, match="shrink_patience"):
            ControllerConfig(
                n_ranks=4, n_experts=8, envelope_decay=0.5,
                shrink_patience=0,
            )

    def test_shrink_targets_window_peak(self):
        """The shrink target is the peak slacked need over the underuse
        window, not the last rebuild's need — every plan the window saw
        still fits the shrunk envelope (no grow/shrink thrash)."""
        rt = self._runtime(decay=0.5, patience=2)
        rt.prime(self._hot_prime())
        rt.table()  # materialize the hot envelope before the cool-down
        self._drive(rt, scale=400.0, steps=8)
        assert rt.metrics()["envelope_shrinks"] >= 1
        env = np.asarray(rt.table().envelope)
        growths_after = rt.envelope_growths
        # replaying the same cooled regime never regrows the envelope
        self._drive(rt, scale=400.0, steps=8)
        assert rt.envelope_growths == growths_after, (
            "post-shrink envelope must cover the cooled regime's plans"
        )
        assert (np.asarray(rt.table().envelope) <= env).all()


class TestWireCodecProperties:
    """PR 8 satellite: dequantize∘quantize properties of the wire codecs
    on adversarial slots — zeros, inf-adjacent magnitudes, single-token
    slots — straight against ``repro.parallel.fabric.codec``."""

    def test_registry_matches_pricing(self):
        from repro.core import WIRE_DTYPES
        from repro.parallel.fabric import CODECS, codec_names, get_codec

        assert set(CODECS) == set(WIRE_DTYPES)
        assert codec_names() == tuple(sorted(CODECS))
        with pytest.raises(ValueError, match="bf16.*fp8.*int8"):
            get_codec("fp4")

    def test_bf16_is_identity_passthrough(self):
        from repro.parallel.fabric import get_codec

        codec = get_codec("bf16")
        assert codec.is_identity
        buf = jnp.ones((3, 4, 8), jnp.bfloat16)
        wire = jnp.ones((3, 4), bool)
        assert codec.apply(buf, wire) is buf  # not merely equal: untouched

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_maskless_buffer_is_untouched(self, wire):
        from repro.parallel.fabric import get_codec

        buf = jnp.ones((2, 8), jnp.float32)
        assert get_codec(wire).apply(buf, None) is buf

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_zero_slots_round_trip_exactly(self, wire):
        """All-zero slots (envelope padding) must QDQ to exact zeros —
        the eps scale guard, not a 0/0 NaN."""
        from repro.parallel.fabric import get_codec

        codec = get_codec(wire)
        x = jnp.zeros((3, 5, 32), jnp.float32)
        q, scale = codec.encode(x)
        assert np.isfinite(np.asarray(scale)).all()
        assert (np.asarray(codec.qdq(x)) == 0.0).all()

    def test_int8_error_bounded_by_half_step(self):
        """Symmetric int8: round-off is at most half a quantization step
        of the slot's own amax — per-slot scales mean a hot slot cannot
        wash out a cold one."""
        from repro.parallel.fabric import get_codec

        codec = get_codec("int8")
        rng = np.random.default_rng(0)
        # wildly mixed per-slot magnitudes, including a near-zero slot
        x = rng.normal(size=(6, 32)) * (10.0 ** rng.integers(-4, 4, (6, 1)))
        x = jnp.asarray(x, jnp.float32)
        amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
        err = np.abs(np.asarray(codec.qdq(x)) - np.asarray(x))
        assert (err <= amax / 127.0 * 0.5 + 1e-6).all()

    def test_fp8_error_bounded_by_e4m3_resolution(self):
        """e4m3: half-ulp relative error (2^-4) for normals plus one
        subnormal step of the scaled format near zero."""
        from repro.parallel.fabric import get_codec

        codec = get_codec("fp8")
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(6, 32)) * 3.0, jnp.float32)
        _, scale = codec.encode(x)
        err = np.abs(np.asarray(codec.qdq(x)) - np.asarray(x))
        bound = 0.0625 * np.abs(np.asarray(x)) + np.asarray(scale)
        assert (err <= bound).all()

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_inf_adjacent_magnitudes_stay_finite(self, wire):
        """Slots touching the f32 range edge (3e38) must survive the
        wire: finite output, signs preserved, no e4m3fn overflow-NaN."""
        from repro.parallel.fabric import get_codec

        codec = get_codec(wire)
        x = jnp.asarray(
            [[3e38, -3e38, 1e-30, 0.0, -1.5, 2.5e37, -7e36, 1.0]],
            jnp.float32,
        )
        y = np.asarray(codec.qdq(x))
        assert np.isfinite(y).all()
        big = np.abs(np.asarray(x)) >= 1e37
        assert (np.sign(y[big]) == np.sign(np.asarray(x)[big])).all()
        # the amax element round-trips within codec resolution
        assert abs(y[0, 0] - 3e38) <= 0.0625 * 3e38

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_single_token_slots(self, wire):
        """A slot holding one scalar feature (d=1) is its own amax: the
        value maps to the codec's top code and round-trips tightly."""
        from repro.parallel.fabric import get_codec

        codec = get_codec(wire)
        x = jnp.asarray([[3.7], [-0.003], [1e5], [0.0]], jnp.float32)
        y = np.asarray(codec.qdq(x))
        err = np.abs(y - np.asarray(x))
        assert (err <= 0.01 * np.abs(np.asarray(x)) + 1e-9).all()

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_ste_gradient_is_identity(self, wire):
        """Gradients pass straight through the QDQ seam (STE) — wire
        noise is round-off, not a differentiable transform."""
        from repro.parallel.fabric import get_codec

        codec = get_codec(wire)
        buf = jax.random.normal(jax.random.PRNGKey(0), (4, 8), jnp.float32)
        mask = jnp.asarray([True, False, True, True])
        g = jax.grad(lambda b: (codec.apply(b, mask) * 3.0).sum())(buf)
        np.testing.assert_allclose(np.asarray(g), 3.0)


class TestWireDtypeParity:
    """PR 8: the wire_dtype axis of the parity matrix.  Quantized wires
    must track the bf16 values within the codec's documented tolerance,
    keep routing/drop stats bit-identical (admission precedes the
    codec), and leave fabrics where nothing crosses the wire exact."""

    # documented max-abs tolerance on unit-scale activations (d_model=32
    # MoE outputs; measured ~0.11 / ~0.023 on the seeded draw)
    VALUE_TOL = {"fp8": 0.25, "int8": 0.06}
    # grad tolerance relative to the bf16 grads' own max magnitude
    GRAD_RTOL = {"fp8": 0.08, "int8": 0.03}

    def setup_method(self):
        self.x = jax.random.normal(
            jax.random.PRNGKey(1), (4, 32, 32), jnp.float32
        )
        self.params = moe.moe_init(jax.random.PRNGKey(0), _cfg())

    def _sched_for(self, name):
        if name in ("phase_pipelined", "ragged_a2a"):
            return _row(seed=2)
        if name == "ppermute":
            return _plan(2)
        return None

    @pytest.mark.parametrize("name", ALL_FABRICS)
    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_values_track_bf16_within_codec_tolerance(self, name, wire):
        sched = self._sched_for(name)
        y_ref, st_ref = moe.moe_apply(
            self.params, _cfg(name), self.x, schedule=sched,
            return_stats=True,
        )
        y_q, st_q = moe.moe_apply(
            self.params, _cfg(name, wire_dtype=wire), self.x,
            schedule=sched, return_stats=True,
        )
        err = float(jnp.abs(y_q - y_ref).max())
        assert err <= self.VALUE_TOL[wire], (name, wire, err)
        # admission runs before the codec: routing and drop stats are
        # bit-identical, and the generous-capacity draw stays drop-free
        for a, b in zip(jax.tree.leaves(st_q), jax.tree.leaves(st_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(np.asarray(st_q["dropped"]).sum()) == 0.0
        if name in ("phase_pipelined", "ragged_a2a"):
            # a schedule row marks cross-virtual-rank slots: the codec
            # must actually engage, not silently no-op
            assert err > 0.0, (name, wire)
        else:
            # no wire mask on one device: quantization never touches
            # local traffic, so the output is bit-exact
            assert err == 0.0, (name, wire)

    @pytest.mark.parametrize("name", ["dense", "phase_pipelined"])
    def test_explicit_bf16_wire_is_bit_exact(self, name):
        sched = self._sched_for(name)
        y_def = moe.moe_apply(self.params, _cfg(name), self.x, schedule=sched)
        y_bf16 = moe.moe_apply(
            self.params, _cfg(name, wire_dtype="bf16"), self.x,
            schedule=sched,
        )
        np.testing.assert_array_equal(np.asarray(y_def), np.asarray(y_bf16))

    @pytest.mark.parametrize("wire", ["fp8", "int8"])
    def test_grads_track_bf16_within_tolerance(self, wire):
        """STE grads through the quantized wire stay close to the bf16
        grads (difference is quantization noise times loss curvature)."""
        row = self._sched_for("phase_pipelined")

        def loss(p, cfg):
            return (
                moe.moe_apply(p, cfg, self.x, schedule=row) ** 2
            ).sum()

        g_ref = jax.grad(loss)(self.params, _cfg("phase_pipelined"))
        g_q = jax.grad(loss)(
            self.params, _cfg("phase_pipelined", wire_dtype=wire)
        )
        scale = max(
            float(jnp.abs(g).max()) for g in jax.tree.leaves(g_ref)
        )
        for a, b in zip(jax.tree.leaves(g_q), jax.tree.leaves(g_ref)):
            assert np.isfinite(np.asarray(a)).all()
            err = float(jnp.abs(a - b).max())
            assert err <= self.GRAD_RTOL[wire] * scale, (wire, err, scale)

    def test_unknown_wire_dtype_raises_listing_codecs(self):
        cfg = _cfg("phase_pipelined", wire_dtype="fp4")
        with pytest.raises(ValueError, match="bf16.*fp8.*int8"):
            moe.moe_apply(
                self.params, cfg, self.x, schedule=self._sched_for(
                    "phase_pipelined"
                ),
            )

    def test_row_fabrics_agree_under_quantization(self):
        """phase_pipelined and ragged_a2a share pack geometry AND wire
        masks — their quantized outputs must agree exactly."""
        row = _row(seed=3)
        outs = [
            moe.moe_apply(
                self.params, _cfg(name, wire_dtype="fp8"), self.x,
                schedule=row,
            )
            for name in ("phase_pipelined", "ragged_a2a")
        ]
        np.testing.assert_array_equal(
            np.asarray(outs[0]), np.asarray(outs[1])
        )

    def test_dispatch_bytes_prices_the_wire(self):
        """Fabric.dispatch_bytes = slot count x wire format price: the
        quantized envelope bytes sit at the documented ratio."""
        from repro.core import wire_bytes_per_token

        row_sched = _plan(5, n=8)
        from repro.core.schedule import phase_envelope

        env = phase_envelope([row_sched], row_sched.num_phases, slack=1.5)
        fab = get_fabric("ragged_a2a")
        d_model = 4096
        toks = fab.dispatch_tokens(n=8, schedule=row_sched, envelope=env)
        for w in ("bf16", "fp8", "int8"):
            got = fab.dispatch_bytes(
                d_model=d_model, wire_dtype=w, n=8,
                schedule=row_sched, envelope=env,
            )
            assert got == pytest.approx(
                toks * wire_bytes_per_token(d_model, w)
            )
        bf16 = fab.dispatch_bytes(
            d_model=d_model, wire_dtype="bf16", n=8,
            schedule=row_sched, envelope=env,
        )
        for w in ("fp8", "int8"):
            q = fab.dispatch_bytes(
                d_model=d_model, wire_dtype=w, n=8,
                schedule=row_sched, envelope=env,
            )
            assert q <= 0.55 * bf16, (w, q, bf16)


class TestFabricDocsContract:
    def test_every_fabric_documents_itself(self):
        for name, fab in FABRICS.items():
            assert type(fab).__doc__ or fab.__module__, name
            assert fab.name == name
            assert fab.schedule_kind in (
                "none", "static", "row", "optional_row"
            )
