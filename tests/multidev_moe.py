"""Multi-device EP equivalence checks.  Run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
tests/test_multidevice.py) so the main pytest process keeps 1 device.

Checks, on a (data=2, model=4) mesh:
  1. a2a dispatch == dense dispatch (values + grads) when capacities are
     generous (no token drops).
  2. scheduled dispatch (max-weight plan from the *actual* traffic)
     == dense dispatch.
  3. shift schedule == a2a (the uniform 1-factorization is an unrolled
     all-to-all).
  4. Model-level: qwen3-smoke with a2a dispatch trains (finite loss/grads)
     under the mesh.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro.models.layers as layers

layers.COMPUTE_DTYPE = jnp.float32  # exact equivalence, not bf16 rounding

from repro.configs import smoke_config
from repro.configs.base import ModelConfig, MoECfg
from repro.core import decompose, plan_schedule, ring_schedule
from repro.models import moe
from repro.models.model import Model
from repro.parallel import auto_mesh, axis_rules


def make_cfg(dispatch: str) -> ModelConfig:
    return ModelConfig(
        name=f"moe-test-{dispatch}",
        family="moe",
        n_layers=1,
        d_model=32,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        vocab_size=97,
        moe=MoECfg(
            n_experts=8,
            top_k=2,
            d_ff_expert=48,
            capacity_factor=8.0,  # generous: no drops -> exact equivalence
            dispatch=dispatch,
        ),
    )


def traffic_from_routing(params, cfg, x, n):
    """Host-side replication of the EP path's routing -> traffic matrix."""
    t = x.shape[0] * x.shape[1]
    t_ep = t // n
    e_local = cfg.moe.n_experts // n
    xf = x.reshape(t, -1)
    mat = np.zeros((n, n))
    for i in range(n):
        chunk = xf[i * t_ep : (i + 1) * t_ep]
        idx, _ = moe._router(params, cfg, chunk)
        dest = np.asarray(idx // e_local).ravel()
        for ddev in dest:
            mat[i, ddev] += 1
    return mat


def main() -> None:
    assert jax.device_count() == 8, (
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )
    mesh = auto_mesh((2, 4), ("data", "model"))

    key = jax.random.PRNGKey(0)
    cfg = make_cfg("dense")
    params = moe.moe_init(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)

    with axis_rules(mesh):
        y_dense = jax.jit(lambda p, x: moe._moe_dense(p, cfg, x))(params, x)

        # --- a2a == dense -------------------------------------------------
        cfg_a2a = make_cfg("a2a")
        y_a2a = jax.jit(lambda p, x: moe.moe_apply(p, cfg_a2a, x))(params, x)
        np.testing.assert_allclose(
            np.asarray(y_a2a), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        print("OK a2a == dense")

        # --- EP routing stats match the host-side router replication ------
        y_st, stats_tree = jax.jit(
            lambda p, x: moe.moe_apply(p, cfg_a2a, x, return_stats=True)
        )(params, x)
        np.testing.assert_allclose(
            np.asarray(y_st), np.asarray(y_a2a), rtol=1e-5, atol=1e-5
        )
        stats = stats_tree["routing"]
        n_ep, e = 4, cfg.moe.n_experts
        assert stats.shape == (n_ep, e), stats.shape
        assert stats_tree["dropped"].shape == (n_ep,)
        # generous capacity: nothing is cut at grouping
        assert float(np.asarray(stats_tree["dropped"]).sum()) == 0.0
        # source rank i holds sequence chunk i (the EP shard_map is
        # sequence-sharded); replicate its router on the host
        s_loc = x.shape[1] // n_ep
        expect = np.zeros((n_ep, e))
        for i in range(n_ep):
            chunk = x[:, i * s_loc : (i + 1) * s_loc].reshape(-1, x.shape[-1])
            idx, _ = moe._router(params, cfg, chunk)
            expect[i] = np.bincount(np.asarray(idx).ravel(), minlength=e)
        np.testing.assert_allclose(np.asarray(stats), expect)
        print("OK EP routing stats == host-replicated router counts")

        # --- grads a2a == dense -------------------------------------------
        g_dense = jax.jit(
            jax.grad(lambda p, x: (moe._moe_dense(p, cfg, x) ** 2).sum())
        )(params, x)
        g_a2a = jax.jit(
            jax.grad(lambda p, x: (moe.moe_apply(p, cfg_a2a, x) ** 2).sum())
        )(params, x)
        for ka, (ga, gd) in enumerate(
            zip(jax.tree.leaves(g_a2a), jax.tree.leaves(g_dense))
        ):
            np.testing.assert_allclose(
                np.asarray(ga), np.asarray(gd), rtol=2e-4, atol=2e-4
            )
        print("OK grad(a2a) == grad(dense)")

        # --- scheduled (max-weight plan from actual traffic) == dense ------
        traffic = traffic_from_routing(params, cfg, x, n=4)
        sched = plan_schedule(
            decompose(traffic, "maxweight"), slack=1.5, quantum=8
        )
        cfg_s = make_cfg("scheduled")
        y_sched = jax.jit(
            lambda p, x: moe.moe_apply(p, cfg_s, x, schedule=sched)
        )(params, x)
        np.testing.assert_allclose(
            np.asarray(y_sched), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        print(f"OK scheduled({sched.num_phases} phases) == dense")

        # --- traced ScheduleTable row (array-native path) == dense ----------
        # Same plan as data: admission mask + one all_to_all + one grouped
        # GEMM launch must reproduce the static ppermute path's numerics
        # (generous caps: nothing clips on either path).  A re-planned
        # table must reuse the executable (zero recompiles).
        from repro.core import ScheduleTable

        table = ScheduleTable.from_schedules([sched], k_max=4, clip=True)
        apply_row = jax.jit(
            lambda p, x, r: moe.moe_apply(p, cfg_s, x, schedule=r)
        )
        y_row = apply_row(params, x, table.row(0))
        np.testing.assert_allclose(
            np.asarray(y_row), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        shift4 = ring_schedule(4, max(8, x.shape[0] * x.shape[1] // 4 * 2))
        y_row2 = apply_row(
            params, x, ScheduleTable.from_schedules([shift4], k_max=4).row(0)
        )
        np.testing.assert_allclose(
            np.asarray(y_row2), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        assert apply_row._cache_size() == 1, "table swap recompiled"
        print("OK traced-table row == dense (swap reused the executable)")

        # grads through the traced path match dense
        g_row = jax.jit(
            jax.grad(
                lambda p, x: (
                    moe.moe_apply(p, cfg_s, x, schedule=table.row(0)) ** 2
                ).sum()
            )
        )(params, x)
        for ga, gd in zip(jax.tree.leaves(g_row), jax.tree.leaves(g_dense)):
            np.testing.assert_allclose(
                np.asarray(ga), np.asarray(gd), rtol=2e-4, atol=2e-4
            )
        print("OK grad(traced-table) == grad(dense)")

        # --- over-promising plan: phase-pipelined traced dispatch -----------
        # Concentrated routing makes the plan promise a hot pair ~2x the
        # uniform capacity-factor bucket.  The static path grows its
        # buckets (c_max = max(cap_uni, pair max)) and ships everything;
        # the monolithic traced path silently cut the overflow (now it
        # counts it); the phase-pipelined path sizes per-phase buffers
        # from the envelope and must match the static path exactly.
        cfg_op = make_cfg("scheduled")
        cfg_op = dataclasses.replace(
            cfg_op, moe=dataclasses.replace(cfg_op.moe, capacity_factor=1.0)
        )
        wr = np.zeros((cfg_op.d_model, cfg_op.moe.n_experts))
        wr[:, 6], wr[:, 7] = 0.1, 0.05  # everything routes to rank 3
        params_op = {**params, "router": {"w": jnp.asarray(wr, jnp.float32)}}
        # batch is sharded over data=2 as well, so per-shard demand is
        # (b/2 * s/4) * top_k — size s so one expert's demand beats the
        # uniform bucket on every shard
        x2 = (
            jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (4, 32, cfg_op.d_model)))
            + 0.5
        )
        traffic2 = traffic_from_routing(params_op, cfg_op, x2, n=4)
        sched_op = plan_schedule(
            decompose(traffic2, "maxweight"), slack=1.2, quantum=8
        )
        t_ep2 = (x2.shape[0] // 2) * (x2.shape[1] // 4)  # per (data, model) shard
        # uniform capacity-factor bucket (per expert), as _moe_ep_table sizes it
        cap_uni = max(8, -(-int(np.ceil(t_ep2 * 2 / 8)) // 8) * 8)
        per_exp = -(-sched_op.caps.astype(np.int64) // 2)  # per-expert ceil
        per_exp = np.maximum(8, -(-per_exp // 8) * 8)
        assert per_exp.max() > cap_uni, (
            f"plan must over-promise the bucket ({per_exp.max()} <= {cap_uni})"
        )
        y_op_static = jax.jit(
            lambda p, x: moe.moe_apply(p, cfg_op, x, schedule=sched_op)
        )(params_op, x2)
        tbl_env = ScheduleTable.from_schedules(
            [sched_op], k_max=4, clip=True, envelope="auto"
        )
        apply_env = jax.jit(
            lambda p, x, r: moe.moe_apply(p, cfg_op, x, schedule=r, return_stats=True)
        )
        y_op_phase, st_phase = apply_env(params_op, x2, tbl_env.row(0))
        np.testing.assert_allclose(
            np.asarray(y_op_phase), np.asarray(y_op_static), rtol=1e-5, atol=1e-5
        )
        assert float(np.asarray(st_phase["dropped"]).sum()) == 0.0, (
            "phase-pipelined dispatch must not drop admitted tokens"
        )
        # the monolithic (no-envelope) path drops the overflow — and says so
        tbl_mono = ScheduleTable.from_schedules([sched_op], k_max=4, clip=True)
        y_op_mono, st_mono = jax.jit(
            lambda p, x, r: moe.moe_apply(p, cfg_op, x, schedule=r, return_stats=True)
        )(params_op, x2, tbl_mono.row(0))
        assert float(np.asarray(st_mono["dropped"]).sum()) > 0.0, (
            "monolithic over-promise cut must be observable"
        )
        assert not np.allclose(
            np.asarray(y_op_mono), np.asarray(y_op_static), atol=1e-5
        ), "monolithic path should diverge on an over-promising plan"
        # swaps within the envelope reuse the executable
        sched_alt = plan_schedule(
            decompose(traffic2 * 0.7, "maxweight"), slack=1.2, quantum=8
        )
        tbl_alt = tbl_env.update([sched_alt])
        apply_env(params_op, x2, tbl_alt.row(0))
        assert apply_env._cache_size() == 1, "phase-path table swap recompiled"
        # grads through the phase-pipelined path match the static path
        g_phase = jax.jit(
            jax.grad(
                lambda p, x: (
                    moe.moe_apply(p, cfg_op, x, schedule=tbl_env.row(0)) ** 2
                ).sum()
            )
        )(params_op, x2)
        g_static = jax.jit(
            jax.grad(
                lambda p, x: (moe.moe_apply(p, cfg_op, x, schedule=sched_op) ** 2).sum()
            )
        )(params_op, x2)
        for ga, gs in zip(jax.tree.leaves(g_phase), jax.tree.leaves(g_static)):
            np.testing.assert_allclose(
                np.asarray(ga), np.asarray(gs), rtol=2e-4, atol=2e-4
            )
        print(
            f"OK phase-pipelined traced dispatch == static on over-promising "
            f"plan (pair cap {int(per_exp.max())} vs bucket {cap_uni}; "
            f"monolithic dropped {float(np.asarray(st_mono['dropped']).sum()):.0f} "
            f"admitted tokens, phase path 0; swap compile-free; grads match)"
        )

        # --- shift schedule == a2a ------------------------------------------
        t_ep = x.shape[0] * x.shape[1] // 4
        cap = max(8, t_ep * cfg.moe.top_k)
        shift = ring_schedule(4, cap)
        y_shift = jax.jit(
            lambda p, x: moe.moe_apply(p, cfg_s, x, schedule=shift)
        )(params, x)
        np.testing.assert_allclose(
            np.asarray(y_shift), np.asarray(y_a2a), rtol=1e-5, atol=1e-5
        )
        print("OK shift-schedule == a2a")

        # --- executable BvN schedule (multi-phase pairs) == dense -----------
        from repro.core.bvn import bvn_decompose
        from repro.core.schedule import plan_schedule_bvn

        bvn_d = bvn_decompose(np.where(np.eye(4, dtype=bool), 0.0, traffic))
        bvn_sched = plan_schedule_bvn(bvn_d, quantum=8)
        y_bvn = jax.jit(
            lambda p, x: moe.moe_apply(p, cfg_s, x, schedule=bvn_sched)
        )(params, x)
        np.testing.assert_allclose(
            np.asarray(y_bvn), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        print(f"OK executable-BvN({bvn_sched.num_phases} phases) == dense")

        # --- 2D expert sharding (a2a + f-dim over data) == dense ------------
        cfg_2d = make_cfg("a2a")
        cfg_2d = dataclasses.replace(
            cfg_2d, moe=dataclasses.replace(cfg_2d.moe, expert_2d=True)
        )
        with axis_rules(mesh, {"expert_mlp": ("data",)}):
            y_2d = jax.jit(lambda p, x: moe.moe_apply(p, cfg_2d, x))(params, x)
        np.testing.assert_allclose(
            np.asarray(y_2d), np.asarray(y_dense), rtol=1e-5, atol=1e-5
        )
        g_2d = None
        with axis_rules(mesh, {"expert_mlp": ("data",)}):
            g_2d = jax.jit(
                jax.grad(lambda p, x: (moe.moe_apply(p, cfg_2d, x) ** 2).sum())
            )(params, x)
        for ga, gd in zip(jax.tree.leaves(g_2d), jax.tree.leaves(g_dense)):
            np.testing.assert_allclose(
                np.asarray(ga), np.asarray(gd), rtol=2e-4, atol=2e-4
            )
        print("OK 2D-expert-sharded a2a == dense (values + grads)")

        # --- model-level qwen3 smoke with a2a under the mesh ----------------
        qcfg = smoke_config("qwen3-moe-235b-a22b")
        qcfg = dataclasses.replace(
            qcfg, moe=dataclasses.replace(qcfg.moe, dispatch="a2a")
        )
        model = Model(qcfg)
        mparams = model.init(jax.random.PRNGKey(2))
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, qcfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1).at[:, -1].set(-1)}
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(mparams, batch)
        assert bool(jnp.isfinite(loss)), loss
        assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
        print(f"OK model-level a2a training step (loss={float(loss):.3f})")

    print("ALL MULTIDEVICE CHECKS PASSED")


if __name__ == "__main__":
    main()
