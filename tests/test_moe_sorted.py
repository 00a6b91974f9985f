"""The sorted, dropless expert pipeline against the padded one.

A one-device MoE layer that is dropless by shape (``cap >= t``) with
enough rows per expert (``cap >= ROWS_COMPUTE_BOUND``) sorts its ``t *
k`` routed choices by expert and runs one grouped GEMM over exactly
those rows.  Its values, gradients
and stats must be the padded path's; the rule must keep decode-sized
layers, dropping capacity factors, schedule rows and the Pallas path
on the padded buffers; ``expert_rows`` must count the path taken.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoECfg
from repro.core import ScheduleTable, decompose, plan_schedule
from repro.models import Model, moe, stack
from repro.serve import Request, ServeEngine

D = 32


def _cfg(n_experts=8, top_k=2, capacity_factor=None, **moe_kw):
    cf = n_experts / top_k if capacity_factor is None else capacity_factor
    return ModelConfig(
        name="sorted-test",
        family="moe",
        n_layers=1,
        d_model=D,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        vocab_size=128,
        moe=MoECfg(
            n_experts=n_experts, top_k=top_k, d_ff_expert=48,
            capacity_factor=cf, dispatch="dense", **moe_kw,
        ),
        remat="none",
    )


def _inputs(cfg, t, routing="random", seed=0):
    """f32 params and [1, t, d] tokens.  ``routing``: "random"; "one"
    sends every token to experts 0..k-1 (expert 0 holds all t rows, the
    others none); "dark" leaves expert E-1 with no row."""
    params = moe.moe_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, t, D), jnp.float32)
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    if routing != "random":
        w = params["router"]["w"]
        x = x.at[..., 0].set(4.0)  # one shared direction the router reads
        if routing == "one":
            bias = jnp.where(jnp.arange(e) < k, 3.0, -3.0) - 0.1 * jnp.arange(e)
        else:
            bias = jnp.zeros((e,)).at[e - 1].set(-10.0)
        params = dict(params, router={**params["router"], "w": w.at[0].set(bias)})
    return params, x


def _apply(params, cfg, x, *, padded, monkeypatch, **kw):
    """``moe_apply`` on the path asked for, whatever the size: the
    rows-per-expert bound moved out of reach, or down to nothing."""
    with monkeypatch.context() as mp:
        mp.setattr(moe, "ROWS_COMPUTE_BOUND", 1 << 30 if padded else 1)
        return moe.moe_apply(params, cfg, x, **kw)


def _uses_grouped_gemm(cfg, t, **kw):
    """Does the traced layer call the sorted path's grouped GEMM?"""
    params, x = _inputs(cfg, t)
    text = str(jax.make_jaxpr(lambda p, x: moe.moe_apply(p, cfg, x, **kw))(params, x))
    return "name=gmm" in text


def _row(seed=0, n=4):
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) * 400.0
    np.fill_diagonal(m, 0)
    return ScheduleTable.from_schedules(
        [plan_schedule(decompose(m, "maxweight"))], k_max=n
    ).row(0)


# (E, k), t, capacity factor as a multiple of E/k, routing
CASES = [
    pytest.param(ek, t, cf_mult, "random", id=f"E{ek[0]}k{ek[1]}-t{t}-cf{cf_mult}x")
    for ek in [(8, 2), (16, 4)]
    for t in [256, 1024]
    for cf_mult in [1.0, 1.5]
] + [
    pytest.param((8, 2), 256, 1.0, "one", id="E8k2-t256-all-on-one"),
    pytest.param((16, 4), 256, 1.0, "one", id="E16k4-t256-all-on-one"),
    pytest.param((8, 2), 256, 1.0, "dark", id="E8k2-t256-dark-expert"),
    pytest.param((16, 4), 1024, 1.0, "dark", id="E16k4-t1024-dark-expert"),
]


def _case(ek, t, cf_mult, routing):
    e, k = ek
    cfg = _cfg(e, k, capacity_factor=cf_mult * e / k)
    params, x = _inputs(cfg, t, routing)
    return cfg, params, x


class TestParity:
    @pytest.mark.parametrize("ek,t,cf_mult,routing", CASES)
    def test_forward(self, ek, t, cf_mult, routing, monkeypatch):
        cfg, params, x = _case(ek, t, cf_mult, routing)
        y_sorted = _apply(params, cfg, x, padded=False, monkeypatch=monkeypatch)
        y_padded = _apply(params, cfg, x, padded=True, monkeypatch=monkeypatch)
        np.testing.assert_allclose(y_sorted, y_padded, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("ek,t,cf_mult,routing", CASES)
    def test_gradients(self, ek, t, cf_mult, routing, monkeypatch):
        cfg, params, x = _case(ek, t, cf_mult, routing)
        tgt = jax.random.normal(jax.random.PRNGKey(7), x.shape)

        def grads(padded):
            with monkeypatch.context() as mp:
                mp.setattr(moe, "ROWS_COMPUTE_BOUND", 1 << 30 if padded else 1)
                loss = lambda p, x: ((moe.moe_apply(p, cfg, x) - tgt) ** 2).sum()
                return jax.grad(loss, argnums=(0, 1))(params, x)

        (gp_sorted, gx_sorted), (gp_padded, gx_padded) = grads(False), grads(True)
        for a, b in ((gx_sorted, gx_padded), (gp_sorted["router"]["w"], gp_padded["router"]["w"])):
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6 * scale)
        # the layer multiplies by bf16 copies of the expert weights
        # (``layers.cast``), so their cotangents are bf16 on both paths: an
        # element may round to the neighbouring bf16 value
        for name in ("w_gate", "w_up", "w_down"):
            a, b = gp_sorted[name], gp_padded[name]
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, rtol=2.0**-7, atol=1e-5 * scale)

    @pytest.mark.parametrize("ek,t,cf_mult,routing", CASES)
    def test_stats(self, ek, t, cf_mult, routing, monkeypatch):
        cfg, params, x = _case(ek, t, cf_mult, routing)
        weight = (jnp.arange(t) % 3 != 0).astype(jnp.float32)[None]
        kw = dict(return_stats=True, token_weight=weight)
        _, st_sorted = _apply(params, cfg, x, padded=False, monkeypatch=monkeypatch, **kw)
        _, st_padded = _apply(params, cfg, x, padded=True, monkeypatch=monkeypatch, **kw)
        np.testing.assert_array_equal(st_sorted["routing"], st_padded["routing"])
        assert st_sorted["routing"].shape == (1, cfg.moe.n_experts)
        np.testing.assert_array_equal(st_sorted["dropped"], np.zeros((1,)))
        np.testing.assert_array_equal(st_padded["dropped"], np.zeros((1,)))

    def test_bf16_matches_padded(self, monkeypatch):
        """The serving dtype: same operands, same f32 SiLU and combine."""
        cfg = _cfg()
        params, x = _inputs(cfg, 256)
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)
        y_sorted = _apply(params, cfg, x, padded=False, monkeypatch=monkeypatch)
        y_padded = _apply(params, cfg, x, padded=True, monkeypatch=monkeypatch)
        assert y_sorted.dtype == y_padded.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(y_sorted, np.float32), np.asarray(y_padded, np.float32),
            rtol=2e-2, atol=2e-2,
        )


def _prefill(model, params, tokens, padded, monkeypatch):
    with monkeypatch.context() as mp:
        mp.setattr(moe, "ROWS_COMPUTE_BOUND", 1 << 30 if padded else 1)
        assert moe.expert_path(model.cfg, tokens.shape[1]) == ("padded" if padded else "sorted")
        return model.prefill(params, tokens, model.init_cache(1, tokens.shape[1]))


def _close_prefills(a, b):
    """Logits and caches of two prefills within a few bf16 steps
    (relative L2, 2**-8 each): the model runs in bf16, and a layer
    reading another layer's experts is off by O(1)."""
    pairs = [(a[0], b[0])] + list(zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])))
    for x, y in pairs:
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        assert np.linalg.norm(x - y) <= 2e-2 * np.linalg.norm(y)


class TestStackPrefill:
    @pytest.mark.parametrize("n_layers", [2, 3])
    def test_prefill_reads_each_layers_experts_in_place(self, n_layers, monkeypatch):
        """With weights in the serving dtype the prefill hands the sorted
        layers the whole stack's expert weights and a layer index.  Its
        logits and caches equal those of the same sorted prefill given
        each layer's slice by the scan; a layer reading another layer's
        experts is off by O(1)."""
        cfg = dataclasses.replace(_cfg(), n_layers=n_layers)
        model = Model(cfg)
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), model.init(jax.random.PRNGKey(0))
        )
        with monkeypatch.context() as mp:
            mp.setattr(moe, "ROWS_COMPUTE_BOUND", 1)
            _, held = moe.hold_stack_experts(params["stack"]["pos0"]["ffn"], cfg, 256)
        assert held is not None and held.w_gate.shape[0] == n_layers
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, cfg.vocab_size)
        in_place = _prefill(model, params, tokens, False, monkeypatch)
        with monkeypatch.context() as mp:
            mp.setattr(stack, "hold_stack_experts", lambda p, *a: (p, None))
            sliced = _prefill(model, params, tokens, False, monkeypatch)
        for x, y in zip(jax.tree.leaves(in_place), jax.tree.leaves(sliced)):
            np.testing.assert_allclose(
                np.asarray(x, np.float32), np.asarray(y, np.float32), rtol=1e-6, atol=1e-6
            )

    def test_weights_needing_a_cast_stay_in_the_scan(self, monkeypatch):
        """f32 expert weights are cast layer by layer inside the scan, as
        the padded path casts them: nothing is held, and the sorted
        prefill still equals the padded one."""
        cfg = dataclasses.replace(_cfg(), n_layers=2)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        with monkeypatch.context() as mp:
            mp.setattr(moe, "ROWS_COMPUTE_BOUND", 1)
            ffn, held = moe.hold_stack_experts(params["stack"]["pos0"]["ffn"], cfg, 256)
        assert held is None and ffn is params["stack"]["pos0"]["ffn"]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, cfg.vocab_size)
        _close_prefills(
            _prefill(model, params, tokens, False, monkeypatch),
            _prefill(model, params, tokens, True, monkeypatch),
        )

    def test_held_weights_on_the_padded_path_are_sliced(self):
        """Handed a stack and a layer, the padded pipeline runs that
        layer's own experts."""
        cfg = _cfg()
        params, x = _inputs(cfg, 32)
        other = moe.moe_init(jax.random.PRNGKey(7), cfg)
        stack = moe.StackExperts(
            *(jnp.stack([other[k], params[k]]) for k in ("w_gate", "w_up", "w_down")),
            layer=jnp.int32(1),
        )
        rest = {"router": params["router"]}
        assert moe.expert_path(cfg, 32) == "padded"
        np.testing.assert_array_equal(
            moe.moe_apply(rest, cfg, x, experts=stack), moe.moe_apply(params, cfg, x)
        )


# name, config, tokens, moe_apply kwargs, expected path; at capacity
# factor E/k, cap = t
BOUND = moe.ROWS_COMPUTE_BOUND
RULE_CASES = [
    ("decode-t32", _cfg(), 32, {}, "padded"),
    ("below-bound", _cfg(), BOUND // 2, {}, "padded"),
    ("at-bound", _cfg(), BOUND, {}, "sorted"),
    ("prefill-t2048", _cfg(), 2048, {}, "sorted"),
    ("e16k4-at-bound", _cfg(16, 4), BOUND, {}, "sorted"),
    ("cf-1.25", _cfg(capacity_factor=1.25), 2048, {}, "padded"),
    ("cf-below-dropless", _cfg(capacity_factor=3.5), 2048, {}, "padded"),
    ("schedule-row", _cfg(), 2048, {"schedule": "row"}, "padded"),
    ("use-pallas", _cfg(use_pallas=True), 2048, {}, "padded"),
]


class TestPathRule:
    @pytest.mark.parametrize(
        "cfg,t,kw,want", [c[1:] for c in RULE_CASES], ids=[c[0] for c in RULE_CASES]
    )
    def test_rule_and_rows_agree_with_path_taken(self, cfg, t, kw, want):
        kw = {k: (_row() if v == "row" else v) for k, v in kw.items()}
        assert moe.expert_path(cfg, t, kw.get("schedule")) == want
        computed, routed = moe.expert_rows(cfg, t, kw.get("schedule"))
        m = cfg.moe
        assert routed == t * m.top_k
        if want == "sorted":
            assert computed == routed
        else:
            cap = moe._geom.bucket_capacity(t, m)
            assert computed == m.n_experts * cap
        assert _uses_grouped_gemm(cfg, t, **kw) == (want == "sorted")

    def test_full_table_counts_as_row(self):
        table = ScheduleTable.from_schedules(
            [plan_schedule(decompose(np.ones((4, 4)) - np.eye(4), "maxweight"))],
            k_max=4,
        )
        assert moe.expert_path(_cfg(), 2048, table) == "padded"

    def test_decode_fill_is_a_quarter_at_dropless_capacity(self):
        computed, routed = moe.expert_rows(_cfg(), 32)
        assert (computed, routed) == (256, 64)


class TestEngineCounters:
    @pytest.mark.parametrize("bucket,fill", [(BOUND, 1.0), (64, 0.25)])
    def test_admissions_fill_expert_row_counters(self, bucket, fill):
        cfg = dataclasses.replace(_cfg(), n_layers=2)
        eng = ServeEngine(
            cfg, decode_slots=2, max_len=bucket + 8, buckets=(bucket,),
            controller="off",
        )
        rng = np.random.default_rng(0)
        reqs = [
            Request(prompt=rng.integers(0, 128, n), max_new_tokens=2, arrival=0.0)
            for n in (40, 17, 3)
        ]
        s = eng.run(reqs)["serve"]
        assert s["requests"]["completed"] == 3
        n_moe = 2  # every layer of the config is an MoE layer
        assert s["expert_rows_routed"] == 3 * n_moe * bucket * 2
        assert s["expert_rows_computed"] == s["expert_rows_routed"] / fill
        assert s["expert_row_fill"] == fill
