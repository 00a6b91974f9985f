"""Unit + property tests for the decomposition algorithms (paper §3)."""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    bvn_coefficients,
    bvn_decompose,
    decompose,
    ideal_a2a_tokens,
    is_doubly_stochastic,
    maxweight_decompose,
    ring_a2a_tokens,
    sinkhorn,
)


def _rand_traffic(rng, n=8, density=0.6, scale=1000.0):
    m = rng.random((n, n)) * scale
    mask = rng.random((n, n)) < density
    m = m * mask
    np.fill_diagonal(m, 0.0)
    return np.floor(m)


# ---------------------------------------------------------------- sinkhorn
class TestSinkhorn:
    def test_doubly_stochastic_output(self):
        rng = np.random.default_rng(0)
        for _ in range(5):
            m = _rand_traffic(rng)
            s = sinkhorn(m)
            assert is_doubly_stochastic(s)

    def test_preserves_zero_pattern_up_to_eps(self):
        rng = np.random.default_rng(1)
        m = _rand_traffic(rng, density=0.4)
        s = sinkhorn(m)
        # zero entries only get the epsilon regularization mass
        zeros = (m == 0) & ~np.eye(8, dtype=bool)
        assert s[zeros].max() < 1e-3

    def test_rejects_negative(self):
        with pytest.raises(ValueError):
            sinkhorn(np.array([[1.0, -1.0], [1.0, 1.0]]))

    @given(st.integers(min_value=2, max_value=12), st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_property_always_bistochastic(self, n, seed):
        rng = np.random.default_rng(seed)
        m = rng.random((n, n)) * (rng.random((n, n)) < 0.7)
        s = sinkhorn(m)
        assert is_doubly_stochastic(s)


# --------------------------------------------------------------------- BvN
class TestBvN:
    def test_reconstructs_doubly_stochastic(self):
        rng = np.random.default_rng(2)
        s = sinkhorn(_rand_traffic(rng))
        coeffs = bvn_coefficients(s, tol=1e-9)
        recon = np.zeros_like(s)
        n = s.shape[0]
        for lam, perm in coeffs:
            recon[np.arange(n), perm] += lam
        assert np.allclose(recon, s, atol=1e-6)

    def test_marcus_ree_bound(self):
        rng = np.random.default_rng(3)
        for _ in range(5):
            s = sinkhorn(_rand_traffic(rng))
            coeffs = bvn_coefficients(s, tol=1e-9)
            n = s.shape[0]
            assert len(coeffs) <= (n - 1) ** 2 + 1

    def test_full_pipeline_delivers_demand(self):
        rng = np.random.default_rng(4)
        m = _rand_traffic(rng)
        d = bvn_decompose(m)
        d.verify()

    def test_bottleneck_fewer_or_equal_matchings(self):
        rng = np.random.default_rng(5)
        m = _rand_traffic(rng)
        plain = bvn_decompose(m)
        bneck = bvn_decompose(m, bottleneck=True)
        bneck.verify()
        assert bneck.meta["num_bvn_matchings"] <= plain.meta["num_bvn_matchings"] + 2

    def test_paper_claim_many_small_matchings_on_skewed_traffic(self):
        """§4.2: BvN produces many matchings with tiny coefficients on
        skewed MoE traffic (paper: up to 50 for n=8, coeffs ~0.03)."""
        rng = np.random.default_rng(6)
        n = 8
        # Heavy-tailed skew: a few dominant pairs + noise.
        m = np.floor(rng.random((n, n)) * 30)
        m[0, 1] = 4000
        m[2, 3] = 3500
        m[5, 6] = 2800
        np.fill_diagonal(m, 0)
        d = bvn_decompose(m)
        coeffs = d.meta["coefficients"]
        assert len(coeffs) > 12  # fragmented
        assert min(coeffs) < 0.05  # tiny matchings exist


# -------------------------------------------------------------- max-weight
class TestMaxWeight:
    def test_delivers_demand_exactly(self):
        rng = np.random.default_rng(7)
        for _ in range(5):
            m = _rand_traffic(rng)
            d = maxweight_decompose(m)
            d.verify()

    def test_On_matchings(self):
        """Paper §3.2/Fig 2: MW bounds matchings to O(n) (vs O(n^2) BvN)."""
        rng = np.random.default_rng(8)
        for _ in range(10):
            m = _rand_traffic(rng, density=1.0)  # fully dense worst case
            d = maxweight_decompose(m)
            assert d.num_phases <= m.shape[0] + 2

    def test_alloc_equals_sent_no_bubbles(self):
        rng = np.random.default_rng(9)
        m = _rand_traffic(rng)
        d = maxweight_decompose(m)
        for p in d.phases:
            np.testing.assert_allclose(p.alloc, p.sent)

    def test_first_matching_contains_max_entry(self):
        rng = np.random.default_rng(10)
        m = _rand_traffic(rng)
        d = maxweight_decompose(m)
        assert d.phases[0].sent.max() == m.max()

    def test_descending_phase_weight(self):
        rng = np.random.default_rng(11)
        m = _rand_traffic(rng)
        d = maxweight_decompose(m)
        weights = [p.sent.sum() for p in d.phases]
        assert all(weights[i] >= weights[i + 1] - 1e-9 for i in range(len(weights) - 1))

    @given(
        st.integers(min_value=2, max_value=10),
        st.integers(min_value=0, max_value=2**31 - 1),
        st.floats(min_value=0.1, max_value=1.0),
    )
    @settings(max_examples=30, deadline=None)
    def test_property_exact_delivery_and_On(self, n, seed, density):
        rng = np.random.default_rng(seed)
        m = np.floor(rng.random((n, n)) * 100 * (rng.random((n, n)) < density))
        np.fill_diagonal(m, 0.0)
        d = maxweight_decompose(m)
        d.verify()
        # each phase clears all selected entries: nnz shrinks by >= 1/phase,
        # and by ~n for dense rounds => never more than nnz phases
        assert d.num_phases <= max(int((m > 0).sum()), 1)


# ------------------------------------------------------------- decompose()
class TestDecomposeAPI:
    @pytest.mark.parametrize("strategy", ["bvn", "bvn-bottleneck", "maxweight", "shift"])
    def test_all_strategies_deliver(self, strategy):
        rng = np.random.default_rng(12)
        m = _rand_traffic(rng)
        np.fill_diagonal(m, 17.0)  # local traffic present
        d = decompose(m, strategy)
        off = m.copy()
        np.fill_diagonal(off, 0.0)
        np.testing.assert_allclose(d.sent_total(), off, atol=1e-6)
        np.testing.assert_allclose(d.meta["local_tokens"], np.full(8, 17.0))

    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError):
            decompose(np.eye(4), "nope")


# --------------------------------------------------------------- baselines
class TestBaselines:
    def test_ideal_bound(self):
        m = np.array([[0.0, 10.0], [4.0, 0.0]])
        assert ideal_a2a_tokens(m) == 10.0

    def test_ring_at_least_ideal(self):
        rng = np.random.default_rng(13)
        for _ in range(5):
            m = _rand_traffic(rng, n=6)
            assert ring_a2a_tokens(m) >= ideal_a2a_tokens(m) - 1e-6

    def test_ring_uniform_known_value(self):
        # n=4 uniform demand v: each node sends v to 3 others; opposite
        # node traffic (distance 2) splits across directions.  LP optimum
        # equals max link load = 2v (neighbor v + half of 2 distance-2
        # demands each way); NIC-normalized time doubles it.
        n, v = 4, 12.0
        m = np.full((n, n), v)
        np.fill_diagonal(m, 0.0)
        assert abs(ring_a2a_tokens(m, normalize_nic=False) - 2 * v) < 1e-6
        assert abs(ring_a2a_tokens(m) - 4 * v) < 1e-6

    def test_ring_single_demand_splits(self):
        # One demand between adjacent nodes: the LP splits it across both
        # (half-rate) directions -> same time as a full-rate direct link.
        n = 8
        m = np.zeros((n, n))
        m[0, 1] = 100.0
        assert abs(ring_a2a_tokens(m) - 100.0) < 1e-6


# ------------------------------------------------------------- hierarchical
class TestHierarchical:
    def _two_pod_traffic(self, seed=0, n=16, pod=8, locality=0.8):
        rng = np.random.default_rng(seed)
        m = np.floor(rng.random((n, n)) * 200)
        for i in range(n):
            for j in range(n):
                if (i // pod) != (j // pod):
                    m[i, j] = np.floor(m[i, j] * (1 - locality))
        np.fill_diagonal(m, 0.0)
        return m

    def test_split_partitions_traffic(self):
        from repro.core.hierarchical import split_traffic

        m = self._two_pod_traffic()
        intra, inter = split_traffic(m, 8)
        np.testing.assert_allclose(intra + inter, m)
        assert inter[:8, :8].sum() == 0 and intra[:8, 8:].sum() == 0

    def test_hierarchical_delivers_everything(self):
        from repro.core.hierarchical import hierarchical_decompose

        m = self._two_pod_traffic(seed=1)
        intra_d, inter_d = hierarchical_decompose(m, 8)
        intra_d.verify()
        inter_d.verify()
        total = intra_d.sent_total() + inter_d.sent_total()
        np.testing.assert_allclose(total, m, atol=1e-6)

    def test_hierarchical_beats_flat_on_local_traffic(self):
        """With slow inter-pod links and local-heavy traffic, pod-aware
        scheduling must win (beyond-paper claim, DESIGN.md §2.3)."""
        from repro.core import CommModel, linear_model
        from repro.core.hierarchical import simulate_hierarchical

        wins = 0
        for seed in range(5):
            m = self._two_pod_traffic(seed=seed, locality=0.9)
            res = simulate_hierarchical(
                m,
                8,
                linear_model(per_token_us=0.05),
                CommModel(tokens_per_us=100.0),   # fast ICI
                CommModel(tokens_per_us=10.0),    # 10x slower DCI
            )
            if res["speedup"] > 1.0:
                wins += 1
        assert wins >= 4, wins
