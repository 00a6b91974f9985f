"""End-to-end driver: train a ~180M-param MoE transformer for a few
hundred steps with the full production substrate — synthetic data
pipeline, AdamW + cosine schedule, remat, async checkpointing, and
fault-tolerant resume.

    PYTHONPATH=src python examples/train_moe.py --steps 200

With ``--drift`` the run closes the controller loop: a
``ScheduleRuntime`` observes each step's realized routing counts while a
workload drift (regime shift / expert hotspot / gradual skew) is injected
into the observations, and the runtime re-plans all MoE layers in one
``decompose_batch`` call per drift event:

    PYTHONPATH=src python examples/train_moe.py --steps 120 --drift shift

With ``--faults`` the run additionally injects a deterministic fabric
fault (a link flap, a dead link, ...) mid-train: the fault surfaces as a
``FabricFaultError`` the loop rolls back from, the runtime quarantines
the active fabric, falls back along the degradation chain, re-plans
around the dark pairs, and probes its way back once the fault clears
(docs/robustness.md):

    PYTHONPATH=src python examples/train_moe.py --steps 60 \
        --dispatch phase_pipelined --faults link_flap

On a multi-device host (XLA_FLAGS=--xla_force_host_platform_device_count=8)
pass --mesh to exercise distributed EP with the paper's scheduled dispatch.
Schedules are traced ``ScheduleTable`` input to the step, so the
controller's swaps pass re-planned arrays into the SAME executable —
the final report should show 0 compiles across every swap.
"""

import argparse
import dataclasses
import logging

from repro.configs.base import ModelConfig, MoECfg
from repro.data import DataConfig
from repro.models import Model
from repro.train import TrainLoopConfig, train_loop

logging.basicConfig(level=logging.INFO, format="%(message)s")


def small_moe(
    dispatch: str = "dense",
    *,
    n_layers: int = 12,
    d_model: int = 512,
    d_ff: int = 1024,
    wire_dtype: str = "bf16",
    pod_size: int = 2,
) -> ModelConfig:
    """~180M params at the defaults: mixtral-flavored, laptop-trainable.
    The size knobs let CI shrink it to a seconds-long smoke."""
    return ModelConfig(
        name="moe-180m",
        family="moe",
        n_layers=n_layers,
        d_model=d_model,
        n_heads=8,
        n_kv_heads=2,
        d_ff=d_ff,
        vocab_size=32000,
        moe=MoECfg(
            n_experts=8, top_k=2, d_ff_expert=d_ff, dispatch=dispatch,
            wire_dtype=wire_dtype, pod_size=pod_size,
        ),
        remat="none",
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="/tmp/repro_example_ckpt")
    ap.add_argument("--mesh", action="store_true", help="use all local devices")
    from repro.parallel.fabric import fabric_names

    ap.add_argument(
        "--dispatch",
        default=None,
        choices=(*fabric_names(), "scheduled"),
        help="MoE dispatch fabric (default: dense; a2a under --mesh); "
        "'scheduled' resolves by schedule type",
    )
    from repro.parallel.fabric import codec_names

    ap.add_argument(
        "--wire-dtype",
        default="bf16",
        choices=codec_names(),
        help="wire codec tokens ride the dispatch fabric in (fp8/int8 "
        "quantize cross-rank slots with per-slot scales; bf16 is the "
        "bit-exact passthrough)",
    )
    ap.add_argument(
        "--pod-size", type=int, default=2,
        help="ranks per pod for --dispatch=hierarchical (must divide the "
        "fabric size; pod-local traffic rides the electrical intra "
        "level, the remainder the circuit-scheduled inter level)",
    )
    ap.add_argument(
        "--drift",
        default="none",
        choices=("none", "shift", "hotspot", "skew"),
        help="close the controller loop and inject this routing drift",
    )
    ap.add_argument(
        "--drift-step", type=int, default=None,
        help="step at which the drift engages (default steps // 3)",
    )
    ap.add_argument(
        "--virtual-ranks", type=int, default=8,
        help="controller fabric size when no EP mesh is active",
    )
    ap.add_argument(
        "--faults",
        default="none",
        choices=("none", "dead_link", "link_flap", "slow_link", "dark_window"),
        help="inject this fabric fault and exercise the fallback chain",
    )
    ap.add_argument(
        "--fault-step", type=int, default=None,
        help="step at which the fault engages (default steps // 3)",
    )
    ap.add_argument(
        "--fault-window", type=int, default=None,
        help="fault episode length in steps (default steps // 5)",
    )
    ap.add_argument(
        "--fault-links", type=int, default=2,
        help="number of directed pairs the fault darkens",
    )
    ap.add_argument("--layers", type=int, default=12, help="model depth")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--d-ff", type=int, default=1024)
    args = ap.parse_args()

    dispatch = args.dispatch or ("a2a" if args.mesh else "dense")
    cfg = small_moe(
        dispatch, n_layers=args.layers, d_model=args.d_model,
        d_ff=args.d_ff, wire_dtype=args.wire_dtype, pod_size=args.pod_size,
    )
    model = Model(cfg)
    print(f"{cfg.name}: {cfg.param_count()/1e6:.0f}M params "
          f"({cfg.active_param_count()/1e6:.0f}M active)")

    mesh = None
    if args.mesh:
        import jax

        from repro.parallel import auto_mesh

        n = jax.device_count()
        mesh = auto_mesh((max(n // 4, 1), min(n, 4)), ("data", "model"))

    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch
    )
    loop_cfg = TrainLoopConfig(
        steps=args.steps,
        ckpt_dir=args.ckpt,
        ckpt_every=max(args.steps // 4, 10),
        peak_lr=3e-4,
        warmup=max(args.steps // 10, 10),
        log_every=10,
    )

    import numpy as np

    from repro.parallel.fabric import consumes_schedule, consumes_table

    # schedules execute on the mesh's EP ('model') axis when one is
    # active; --virtual-ranks only sizes the single-device fabric
    n_ranks = mesh.shape["model"] if mesh is not None else args.virtual_ranks
    # one uniform demand estimate drives both the static plan and the
    # runtime prime — the two paths must never diverge
    tokens = args.batch * args.seq * cfg.moe.top_k
    uniform = np.full((n_ranks, n_ranks), tokens / n_ranks**2)
    static_schedule = None
    if consumes_schedule(dispatch) and not consumes_table(dispatch):
        # ppermute bakes its plan into the executable: a controller
        # runtime cannot swap it, so drift makes no sense here — plan
        # one static schedule from the uniform demand estimate instead
        if args.drift != "none" or args.faults != "none":
            raise SystemExit(
                f"--drift/--faults need a table-consuming fabric "
                f"({dispatch!r} bakes its plan in); use --dispatch "
                "phase_pipelined or ragged_a2a"
            )
        from repro.core import decompose, plan_schedule

        static_schedule = plan_schedule(
            decompose(uniform, cfg.moe.schedule_strategy), slack=1.5
        )
        model = Model(cfg, static_schedule)
        print(f"static {static_schedule.num_phases}-phase {dispatch} plan")

    runtime = stats_hook = failure_hook = None
    if args.drift != "none" or args.faults != "none" or consumes_table(dispatch):
        from repro.core import (
            ControllerConfig,
            DriftScenario,
            HierarchicalRuntime,
            ScheduleRuntime,
        )

        fallback_chain = ()
        if args.faults != "none":
            # dense is the fabric-free floor every chain must reach
            fallback_chain = (
                (dispatch, "dense") if dispatch != "dense" else ()
            )
        ctrl_cfg = ControllerConfig(
            n_ranks=n_ranks,
            n_experts=cfg.moe.n_experts,
            ema=0.5,
            cooldown=5,
            # one schedule shared by all layers keeps the stack
            # scan-friendly; "layer" plans one schedule per MoE layer
            group_by="model",
            fallback_chain=fallback_chain,
            quarantine_after=2,
            probe_backoff=max(2, args.steps // 10),
            recover_after=2,
        )
        if dispatch == "hierarchical":
            # the composed fabric's controller: one runtime per level,
            # observations split at the pod seam (intra drift never
            # forces a circuit re-plan)
            runtime = HierarchicalRuntime(
                ctrl_cfg, model.n_moe_layers, pod_size=cfg.moe.pod_size
            )
        else:
            runtime = ScheduleRuntime(ctrl_cfg, model.n_moe_layers)
        if consumes_table(dispatch):
            # table-consuming fabrics need a plan before the first step
            runtime.prime(uniform)
        if args.drift != "none":
            scenario = DriftScenario(
                args.drift,
                cfg.moe.n_experts,
                shift_step=args.drift_step or args.steps // 3,
                window=max(args.steps // 4, 10),
            )
            stats_hook = scenario.stats_hook
            print(f"drift scenario: {args.drift} @ step {scenario.shift_step}")
        if args.faults != "none":
            from repro.core import FaultScenario, fault_hook

            fault_scenario = FaultScenario(
                args.faults,
                n_ranks=n_ranks,
                onset=args.fault_step or args.steps // 3,
                window=args.fault_window or max(args.steps // 5, 2),
                n_links=args.fault_links,
            )
            runtime.attach_faults(fault_scenario)
            failure_hook = fault_hook(fault_scenario, runtime, backend=dispatch)
            print(
                f"fault scenario: {args.faults} @ step {fault_scenario.onset} "
                f"(pairs {fault_scenario.dead_pairs}), chain "
                f"{fallback_chain or '(none)'}"
            )

    if args.mesh:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.parallel import axis_rules

        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=dispatch)
        )
        model = Model(cfg, static_schedule)

        def shard_batch(b):
            return {
                k: jax.device_put(
                    v, NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
                )
                for k, v in b.items()
            }

        with axis_rules(mesh):
            res = train_loop(
                model, data_cfg, loop_cfg, shard_batch=shard_batch,
                runtime=runtime, stats_hook=stats_hook,
                failure_hook=failure_hook,
            )
    else:
        res = train_loop(
            model, data_cfg, loop_cfg, runtime=runtime,
            stats_hook=stats_hook, failure_hook=failure_hook,
        )

    if not res["history"]:
        print(f"\nnothing to do: checkpoint in {args.ckpt} is already at "
              f"step {res['final_step']} >= --steps (delete it to retrain)")
        return
    first, last = res["history"][0]["loss"], res["history"][-1]["loss"]
    steps_s = 1.0 / max(res["history"][-1]["dt_s"], 1e-9)
    print(f"\nloss {first:.3f} -> {last:.3f} over {res['final_step']} steps "
          f"({steps_s:.1f} steps/s at the tail)")
    if "controller" in res:
        c = res["controller"]
        print(
            f"controller: {c['replan_events']} re-plan events "
            f"({c['decompose_calls']} decompose_batch calls, "
            f"{c['warm_hits']} warm / {c['cold_plans']} cold plans), "
            f"{c['swaps']} swaps, {c['compiles']} compiles, "
            f"observe {c['observe_us_per_step']}us/step"
        )
        if args.faults != "none":
            print(
                f"faults: {c['fabric_faults']} raised, "
                f"{c['quarantines']} quarantines "
                f"({c['probe_failures']} failed probes), "
                f"{c['masked_replans']} masked re-plans, "
                f"{res['failures']} rollbacks, state {c['health_state']} "
                f"on {c['final_dispatch']}"
            )
    losses = [h["loss"] for h in res["history"]]
    assert all(np.isfinite(losses)), "non-finite loss in history"
    if args.faults in ("dead_link", "link_flap") and "controller" in res:
        c = res["controller"]
        assert c["quarantines"] >= 1, "fault never quarantined"
        assert c["fabric_faults"] >= 1, "fault never surfaced"
    if args.faults == "link_flap" and "controller" in res:
        # the flap cleared: the run must end recovered on the preferred
        # fabric with the mask lifted
        assert c["final_dispatch"] == dispatch, c["final_dispatch"]
        assert not c["fallback_active"] and not c["link_masked"], c
    assert last < first, "training did not reduce loss"
    print("OK")


if __name__ == "__main__":
    main()
