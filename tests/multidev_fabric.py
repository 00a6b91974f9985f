"""Cross-fabric parity matrix on a real (2 data x 4 model) mesh.

Run in a subprocess with 8 emulated devices (see test_multidevice.py).
Every registered fabric executes the SAME routing problem through the
one MoE pipeline; with generous capacity and a plan derived from the
actual traffic, values, grads, and the ``{routing, dropped}`` stats
contract must agree across all of them — the registry's core promise
(backends may only differ in movement and padding bytes).  The traced
backends (phase_pipelined, ragged_a2a) must additionally swap re-planned
tables into the SAME executable (zero recompiles).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

import repro.models.layers as layers

layers.COMPUTE_DTYPE = jnp.float32  # exact equivalence, not bf16 rounding

from repro.configs.base import ModelConfig, MoECfg
from repro.core import (
    HierarchicalTable,
    ScheduleTable,
    decompose,
    hierarchical_decompose,
    hierarchical_plan,
    plan_schedule,
)
from repro.models import moe
from repro.parallel import auto_mesh, axis_rules
from repro.parallel.fabric import fabric_names

N_EP = 4


def make_cfg(dispatch: str, pod_size: int = 2, wire_dtype: str = "bf16") -> ModelConfig:
    return ModelConfig(
        name=f"fabric-{dispatch}",
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
            pod_size=pod_size,
            wire_dtype=wire_dtype,
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

    cfg0 = make_cfg("dense")
    params = moe.moe_init(jax.random.PRNGKey(0), cfg0)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg0.d_model), jnp.float32)

    with axis_rules(mesh):
        traffic = traffic_from_routing(params, cfg0, x, N_EP)
        sched = plan_schedule(
            decompose(traffic, "maxweight"), slack=1.5, quantum=8,
        )
        table = ScheduleTable.from_schedules(
            [sched], k_max=N_EP, clip=True, envelope="auto"
        )
        htab = hierarchical_plan(traffic, 2, n_layers=1, slack=1.5, quantum=8)
        schedule_for = {
            "dense": None,
            "a2a": None,
            "ppermute": sched,
            "phase_pipelined": table.row(0),
            "ragged_a2a": table.row(0),
            "hierarchical": htab.row(0),
        }
        missing = set(fabric_names()) - set(schedule_for)
        assert not missing, f"parity matrix must cover new fabrics: {missing}"

        results = {}
        for name, schedule in schedule_for.items():
            cfg = make_cfg(name)
            # static A2ASchedules ride the closure (the ppermute
            # contract: plans are baked in); rows could be traced args
            y, stats = jax.jit(
                lambda p, x, cfg=cfg, s=schedule: moe.moe_apply(
                    p, cfg, x, schedule=s, return_stats=True
                )
            )(params, x)
            g = jax.jit(
                jax.grad(
                    lambda p, x, cfg=cfg, s=schedule: (
                        moe.moe_apply(p, cfg, x, schedule=s) ** 2
                    ).sum()
                )
            )(params, x)
            results[name] = (np.asarray(y), stats, g)
            print(f"ran {name}")

        y_ref, st_ref, g_ref = results["dense"]
        # dense is the single-row-stats oracle; EP stats fold to [n, E]
        ref_routing = np.asarray(st_ref["routing"]).sum(axis=0)
        for name, (y, st, g) in results.items():
            np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
            assert set(st) == {"routing", "dropped"}, (name, set(st))
            np.testing.assert_allclose(
                np.asarray(st["routing"]).sum(axis=0), ref_routing,
                rtol=1e-6, atol=1e-6,
            )
            assert float(np.asarray(st["dropped"]).sum()) == 0.0, name
            for ga, gr in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
                np.testing.assert_allclose(
                    np.asarray(ga), np.asarray(gr), rtol=2e-4, atol=2e-4
                )
            print(f"OK {name}: values + grads + stats == dense")

        # traced backends: re-planned tables swap with zero recompiles
        for name in ("phase_pipelined", "ragged_a2a"):
            cfg = make_cfg(name)
            f = jax.jit(
                lambda p, x, r, cfg=cfg: moe.moe_apply(p, cfg, x, schedule=r)
            )
            f(params, x, table.row(0))
            alt = table.update(
                [
                    plan_schedule(
                        decompose(
                            traffic_from_routing(params, cfg0, x, N_EP) * 0.7,
                            "maxweight",
                        ),
                        slack=1.5, quantum=8,
                    )
                ]
            )
            f(params, x, alt.row(0))
            assert f._cache_size() == 1, f"{name} table swap recompiled"
            print(f"OK {name}: in-envelope table swap reused the executable")

        # --- the ragged transfer code itself (the primitive is absent in
        # this container's jax): stub jax.lax.ragged_all_to_all with a
        # reference implementation built on all_to_all, force-enable the
        # ragged path, and re-assert parity — this pins _ragged_send's
        # traced peer/size wiring, not just the emulation fallback.
        from repro.parallel.fabric import ragged_a2a as ra

        def ragged_ref(operand, output, input_offsets, send_sizes,
                       output_offsets, recv_sizes, *, axis_name):
            # the backend's usage contract: offsets all zero, at most one
            # nonzero send (my whole block) / recv per rank per phase
            n = send_sizes.shape[0]
            dst = jnp.argmax(send_sizes)
            sending = send_sizes.sum() > 0
            buf = (
                jnp.zeros((n, *operand.shape), operand.dtype)
                .at[dst]
                .add(jnp.where(sending, operand, 0))
            )
            got = jax.lax.all_to_all(
                buf, axis_name, split_axis=0, concat_axis=0, tiled=True
            ).sum(axis=0)
            receiving = recv_sizes.sum() > 0
            return jnp.where(receiving, got, output)

        old_ragged = ra._RAGGED
        ra._RAGGED = ragged_ref
        os.environ["REPRO_FORCE_RAGGED"] = "1"
        try:
            assert ra.ragged_available()
            cfg_r = make_cfg("ragged_a2a")
            y_r, st_r = jax.jit(
                lambda p, x, r: moe.moe_apply(
                    p, cfg_r, x, schedule=r, return_stats=True
                )
            )(params, x, table.row(0))
            np.testing.assert_allclose(
                np.asarray(y_r), y_ref, rtol=1e-5, atol=1e-5
            )
            assert float(np.asarray(st_r["dropped"]).sum()) == 0.0
        finally:
            ra._RAGGED = old_ragged
            os.environ.pop("REPRO_FORCE_RAGGED", None)
        print("OK ragged_a2a (stubbed ragged_all_to_all) == dense")

        # --- hierarchical, pod_size=4: one pod == all traffic intra (the
        # inter level is dark) — parity must still hold
        htab4 = hierarchical_plan(traffic, 4, n_layers=1, slack=1.5, quantum=8)
        cfg_h4 = make_cfg("hierarchical", pod_size=4)
        y4, st4 = jax.jit(
            lambda p, x, r: moe.moe_apply(
                p, cfg_h4, x, schedule=r, return_stats=True
            )
        )(params, x, htab4.row(0))
        np.testing.assert_allclose(np.asarray(y4), y_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(st4["routing"]).sum(axis=0), ref_routing,
            rtol=1e-6, atol=1e-6,
        )
        assert float(np.asarray(st4["dropped"]).sum()) == 0.0
        print("OK hierarchical pod_size=4 (degenerate inter) == dense")

        # --- hierarchical dual-table swaps: an intra-only re-plan and a
        # both-level re-plan must each reuse the executable (per-level
        # envelopes are the static aux; updates keep them)
        cfg_h = make_cfg("hierarchical")
        fh = jax.jit(
            lambda p, x, r: moe.moe_apply(p, cfg_h, x, schedule=r)
        )
        fh(params, x, htab.row(0))
        i_d, e_d = hierarchical_decompose(traffic * 0.7, 2)
        alt_intra = htab.update(
            intra=htab.intra.update([plan_schedule(i_d, slack=1.5, quantum=8)])
        )
        fh(params, x, alt_intra.row(0))
        assert fh._cache_size() == 1, "intra-only table swap recompiled"
        alt_both = alt_intra.update(
            inter=htab.inter.update([plan_schedule(e_d, slack=1.5, quantum=8)])
        )
        fh(params, x, alt_both.row(0))
        assert fh._cache_size() == 1, "dual-table swap recompiled"
        print("OK hierarchical: intra-only + dual-table swaps reused the executable")

        # --- wire dtype crosses only the inter seam: with one pod (all
        # traffic intra-host) the fp8 codec must be a bit-exact no-op,
        # while with two pods the quantized inter slots shift the output
        # only within fp8 tolerance
        cfg_f4 = make_cfg("hierarchical", pod_size=4, wire_dtype="fp8")
        y4_f = jax.jit(
            lambda p, x, r: moe.moe_apply(p, cfg_f4, x, schedule=r)
        )(params, x, htab4.row(0))
        np.testing.assert_array_equal(np.asarray(y4_f), np.asarray(y4))
        cfg_f2 = make_cfg("hierarchical", pod_size=2, wire_dtype="fp8")
        y2_f, st2_f = jax.jit(
            lambda p, x, r: moe.moe_apply(
                p, cfg_f2, x, schedule=r, return_stats=True
            )
        )(params, x, htab.row(0))
        np.testing.assert_allclose(np.asarray(y2_f), y_ref, atol=0.25)
        np.testing.assert_allclose(
            np.asarray(st2_f["routing"]).sum(axis=0), ref_routing,
            rtol=1e-6, atol=1e-6,
        )
        print("OK hierarchical wire: intra bit-exact under fp8, inter within tolerance")

    print("ALL FABRIC MATRIX CHECKS PASSED")


if __name__ == "__main__":
    main()
