"""Simulator tests: invariants + the paper's qualitative claims (§4.2)."""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    CommModel,
    decompose,
    gen_trace,
    knee_model,
    linear_model,
    order_phases,
    simulate_decomposition,
    simulate_ideal,
    simulate_sequential,
)

COMM = CommModel(tokens_per_us=100.0, reconf_us=0.01)
KNEE = knee_model()
LINEAR = linear_model()


def _skewed(rng, n=8, scale=4000):
    m = np.floor(rng.random((n, n)) ** 4 * scale)
    np.fill_diagonal(m, 0.0)
    return m


class TestSimulatorInvariants:
    def test_zero_matrix(self):
        d = decompose(np.zeros((8, 8)), "maxweight")
        r = simulate_decomposition(d, KNEE, COMM)
        assert r.makespan_us == 0.0

    def test_makespan_at_least_compute(self):
        rng = np.random.default_rng(0)
        for strat in ("bvn", "maxweight", "shift"):
            m = _skewed(rng)
            d = decompose(m, strat)
            r = simulate_decomposition(d, KNEE, COMM)
            assert r.makespan_us >= r.compute_us - 1e-9

    def test_makespan_at_least_network_lower_bound(self):
        """Per-phase circuit hold times are a hard lower bound."""
        rng = np.random.default_rng(1)
        m = _skewed(rng)
        d = decompose(m, "maxweight")
        r = simulate_decomposition(d, KNEE, COMM)
        assert r.makespan_us >= r.dispatch_us - 1e-9

    def test_single_fabric_slower_or_equal_dual(self):
        rng = np.random.default_rng(2)
        for _ in range(5):
            m = _skewed(rng)
            d = decompose(m, "maxweight")
            dual = simulate_decomposition(d, KNEE, COMM, fabric="dual")
            single = simulate_decomposition(d, KNEE, COMM, fabric="single")
            assert single.makespan_us >= dual.makespan_us - 1e-6

    def test_ideal_lower_bounds_ring(self):
        rng = np.random.default_rng(3)
        for _ in range(5):
            m = _skewed(rng)
            assert (
                simulate_ideal(m, LINEAR, COMM).makespan_us
                <= simulate_sequential(m, LINEAR, COMM).makespan_us + 1e-6
            )

    def test_local_tokens_extend_compute(self):
        m = np.zeros((4, 4))
        m[0, 1] = 1000.0
        d = decompose(m, "maxweight")
        base = simulate_decomposition(d, LINEAR, COMM)
        heavy_local = simulate_decomposition(
            d, LINEAR, COMM, local_tokens=np.array([0.0, 1e6, 0.0, 0.0])
        )
        assert heavy_local.makespan_us > base.makespan_us

    @given(st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_property_overlap_never_hurts_with_linear_compute(self, seed):
        """With no fixed overhead, per-phase compute is free to pipeline:
        overlapped makespan <= non-overlapped."""
        rng = np.random.default_rng(seed)
        m = _skewed(rng, n=6)
        d = decompose(m, "maxweight")
        ovl = simulate_decomposition(d, LINEAR, COMM, overlap=True)
        seq = simulate_decomposition(d, LINEAR, COMM, overlap=False)
        assert ovl.makespan_us <= seq.makespan_us + 1e-6


class TestPaperClaims:
    """Trace-driven versions of the paper's §4.2 findings."""

    def _mean_makespan(self, mats, strat, compute, overlap=True):
        out = []
        for m in mats:
            d = decompose(m, strat)
            out.append(
                simulate_decomposition(
                    d,
                    compute,
                    COMM,
                    overlap=overlap,
                    local_tokens=d.meta["local_tokens"],
                ).makespan_us
            )
        return float(np.mean(out))

    def test_bvn_more_phases_than_maxweight(self):
        mats = gen_trace("mixtral-8x22b", "speed", iterations=8, seed=0)
        for m in mats:
            bvn = decompose(m, "bvn")
            mw = decompose(m, "maxweight")
            assert bvn.num_phases > mw.num_phases

    def test_small_batch_overlapped_bvn_worse_than_nonoverlapped(self):
        """Fig 3: with knee costs + small batches, overlapping BvN's tiny
        phases accumulates fixed overheads and loses to non-overlap."""
        mats = gen_trace("mixtral-8x22b", "mmlu", iterations=12, seed=1)
        ovl = self._mean_makespan(mats, "bvn", KNEE, overlap=True)
        seq = self._mean_makespan(mats, "bvn", KNEE, overlap=False)
        assert ovl > seq

    def test_large_batch_maxweight_beats_bvn(self):
        """Fig 4: large batches amortize the knee; MW's few dense phases
        win over BvN's fragmentation."""
        mats = gen_trace("mixtral-8x22b", "speed", iterations=12, seed=2)
        mw = self._mean_makespan(mats, "maxweight", KNEE)
        bvn = self._mean_makespan(mats, "bvn", KNEE)
        assert mw < bvn

    def test_large_batch_maxweight_approaches_ideal(self):
        """Fig 4: MW+overlap approaches (or beats) the non-overlapped
        congestion-free ideal."""
        mats = gen_trace("mixtral-8x22b", "speed", iterations=12, seed=3)
        mw = self._mean_makespan(mats, "maxweight", KNEE)
        ideal = float(
            np.mean([simulate_ideal(m, KNEE, COMM).makespan_us for m in mats])
        )
        assert mw <= 1.25 * ideal

    def test_small_batch_static_ring_competitive(self):
        """Fig 3: under small batches even the congestion-prone static ring
        can beat fragmented decompositions (BvN overlapped)."""
        mats = gen_trace("mixtral-8x22b", "mmlu", iterations=12, seed=4)
        ring = float(
            np.mean([simulate_sequential(m, KNEE, COMM).makespan_us for m in mats])
        )
        bvn_ovl = self._mean_makespan(mats, "bvn", KNEE, overlap=True)
        assert ring < bvn_ovl


class TestOrdering:
    @pytest.mark.parametrize("how", ["lpt", "spt", "johnson3", "asis"])
    def test_reorder_preserves_delivery(self, how):
        rng = np.random.default_rng(5)
        m = _skewed(rng)
        d = order_phases(decompose(m, "maxweight"), how)
        d.verify()

    def test_lpt_no_worse_than_spt_on_average(self):
        """Big-phases-first exposes long compute windows early (§3.3)."""
        rng = np.random.default_rng(6)
        lpt_wins = 0
        trials = 20
        for _ in range(trials):
            m = _skewed(rng)
            d = decompose(m, "maxweight")
            lpt = simulate_decomposition(order_phases(d, "lpt"), KNEE, COMM)
            spt = simulate_decomposition(order_phases(d, "spt"), KNEE, COMM)
            if lpt.makespan_us <= spt.makespan_us + 1e-9:
                lpt_wins += 1
        assert lpt_wins >= trials * 0.6
