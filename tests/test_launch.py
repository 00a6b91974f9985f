"""Launch-layer unit tests: shape cells, applicability, schedule builder,
and the roofline math (no 512-device mesh needed)."""

import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.launch.shapes import CELLS, cell_applicable, input_specs


class TestCells:
    def test_assigned_grid_is_40_cells(self):
        total = len(ASSIGNED) * len(CELLS)
        assert total == 40

    def test_long_500k_applicability_matches_design(self):
        runnable = [
            a for a in ASSIGNED
            if cell_applicable(get_config(a), CELLS["long_500k"])[0]
        ]
        assert sorted(runnable) == [
            "h2o-danube-3-4b",  # SWA window-bounded cache
            "jamba-1.5-large-398b",  # mamba O(1) + 9 attn layers
            "rwkv6-7b",  # O(1) state
        ]

    def test_skips_have_reasons(self):
        ok, why = cell_applicable(get_config("granite-34b"), CELLS["long_500k"])
        assert not ok and "quadratic" in why

    @pytest.mark.parametrize("arch", ASSIGNED)
    @pytest.mark.parametrize("cell", list(CELLS))
    def test_input_specs_shapes(self, arch, cell):
        cfg = get_config(arch)
        c = CELLS[cell]
        specs = input_specs(cfg, c)
        if c.mode == "train":
            b, s = specs["tokens"].shape
            assert b == c.global_batch
            assert s + (cfg.frontend_tokens if cfg.frontend != "none" else 0) == c.seq_len
            assert specs["targets"].shape == specs["tokens"].shape
        elif c.mode == "prefill":
            assert specs["tokens"].shape[0] == c.global_batch
        else:
            assert specs["token"].shape == (c.global_batch,)
            assert specs["step"].shape == ()


class TestScheduleBuilder:
    def test_lossless_plan_has_no_planned_drops(self):
        from repro.launch.dryrun import build_schedule

        cfg = get_config("dbrx-132b")
        s = build_schedule(cfg, 16, 512, plan="lossless")
        s.validate()
        assert s.num_phases >= 16  # >= n for dense-ish traffic

    def test_v2_smaller_caps_than_literal(self):
        from repro.launch.dryrun import build_schedule

        cfg = get_config("qwen3-moe-235b-a22b")
        lit = build_schedule(cfg, 16, 512, plan="literal")
        v2 = build_schedule(cfg, 16, 512, plan="v2")
        assert v2.caps.sum() < lit.caps.sum()


class TestRooflineMath:
    def test_model_flops(self):
        from benchmarks.roofline import model_flops_per_device

        rec = {"arch": "granite-3-8b", "cell": "train_4k", "n_devices": 256}
        cfg = get_config("granite-3-8b")
        expect = 6 * cfg.param_count() * 256 * 4096 / 256
        assert model_flops_per_device(rec) == pytest.approx(expect)

    def test_dominant_term_and_fraction(self):
        from benchmarks.roofline import analyze

        rec = {
            "arch": "granite-3-8b",
            "cell": "train_4k",
            "mesh": "16x16",
            "n_devices": 256,
            "flops_per_device": 197e12,  # exactly 1s of compute
            "bytes_per_device": 819e9 * 2,  # 2s of memory
            "collectives": {"wire_total": int(50e9 * 0.5), "wire": {}},
        }
        r = analyze(rec)
        assert r["dominant"] == "memory"
        assert r["roofline_fraction"] == pytest.approx(0.5)


class TestHierarchicalProperty:
    def test_split_is_partition(self):
        from hypothesis import given, settings
        from hypothesis import strategies as st

        from repro.core import split_traffic

        @given(st.integers(min_value=0, max_value=2**31 - 1))
        @settings(max_examples=20, deadline=None)
        def prop(seed):
            rng = np.random.default_rng(seed)
            m = rng.random((16, 16)) * 100
            intra, inter = split_traffic(m, 4)
            np.testing.assert_allclose(intra + inter, m)
            assert float((intra * inter).sum()) == 0.0

        prop()
