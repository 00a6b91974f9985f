"""Multi-device training-loop checks (run via tests/test_multidevice.py):

1. distributed MoE training runs under a (data=4, model=2) mesh with
   sharded params/optimizer + batch sharding,
2. fault tolerance: an injected failure rolls back to the last checkpoint
   and the final state matches the failure-free run exactly
   (deterministic data replay),
3. elastic restart: the same checkpoint restores onto a different mesh
   layout (data=2, model=4) and training continues.
"""

from __future__ import annotations

import dataclasses
import shutil

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.data import DataConfig
from repro.models import Model
from repro.parallel import auto_mesh, axis_rules
from repro.train import TrainLoopConfig, train_loop

CKPT = "/tmp/repro_multidev_ckpt"


def make_model():
    cfg = smoke_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="a2a")
    )
    return cfg, Model(cfg)


def batch_sharder(mesh):
    def shard_batch(b):
        out = {}
        for k, v in b.items():
            spec = P("data", *([None] * (v.ndim - 1)))
            out[k] = jax.device_put(v, NamedSharding(mesh, spec))
        return out

    return shard_batch


def run(mesh_shape, steps, failure_hook=None, ckpt_every=5):
    mesh = auto_mesh(mesh_shape, ("data", "model"))
    cfg, model = make_model()
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    loop_cfg = TrainLoopConfig(
        steps=steps,
        ckpt_dir=CKPT,
        ckpt_every=ckpt_every,
        microbatches=2,
        peak_lr=1e-3,
        warmup=4,
        log_every=1,
    )
    with axis_rules(mesh):
        return train_loop(
            model,
            data_cfg,
            loop_cfg,
            shard_batch=batch_sharder(mesh),
            failure_hook=failure_hook,
        )


def main() -> None:
    assert jax.device_count() == 8, (
        "run under XLA_FLAGS=--xla_force_host_platform_device_count=8"
    )

    # --- clean run -----------------------------------------------------
    shutil.rmtree(CKPT, ignore_errors=True)
    res_clean = run((4, 2), steps=12)
    assert res_clean["final_step"] == 12
    losses = [h["loss"] for h in res_clean["history"]]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    clean_final = res_clean["final_loss"]
    print(f"OK clean run: loss {losses[0]:.3f} -> {losses[-1]:.3f}")

    # --- fault tolerance: inject a failure at step 8, first attempt -----
    shutil.rmtree(CKPT, ignore_errors=True)
    state = {"fired": False}

    def boom(step):
        if step == 8 and not state["fired"]:
            state["fired"] = True
            raise RuntimeError("injected node failure")

    res_ft = run((4, 2), steps=12, failure_hook=boom)
    assert state["fired"]
    assert res_ft["failures"] == 1
    assert res_ft["final_step"] == 12
    # deterministic replay: identical final loss despite the crash
    np.testing.assert_allclose(res_ft["final_loss"], clean_final, rtol=1e-5)
    print(f"OK fault-tolerant run matches clean final loss {clean_final:.4f}")

    # --- elastic restart on a different mesh ----------------------------
    # keep the checkpoints from the ft run (latest = step 12 ckpt at 10);
    # continue to 15 steps on a (2, 4) mesh.
    res_el = run((2, 4), steps=15)
    assert res_el["final_step"] == 15
    assert np.isfinite(res_el["final_loss"])
    print(f"OK elastic restart on (2,4) mesh: final loss {res_el['final_loss']:.4f}")

    # --- controller loop over SCHEDULED dispatch ------------------------
    # Close the loop on a real EP mesh: the runtime primes the schedule,
    # drift injected into the observed routing forces a re-plan, and the
    # re-planned ScheduleTable swaps into the SAME executable — the whole
    # run must perform ZERO schedule-driven recompiles.
    from repro.core import ControllerConfig, DriftScenario, ScheduleRuntime

    shutil.rmtree(CKPT, ignore_errors=True)
    mesh = auto_mesh((4, 2), ("data", "model"))
    cfg, _ = make_model()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="scheduled")
    )
    model = Model(cfg)
    n_ep = 2  # model-axis size
    runtime = ScheduleRuntime(
        ControllerConfig(
            n_ranks=n_ep,
            n_experts=cfg.moe.n_experts,
            ema=1.0,
            cooldown=2,
            group_by="model",
        ),
        model.n_moe_layers,
    )
    tokens = 8 * 16 * cfg.moe.top_k
    runtime.prime(np.full((n_ep, n_ep), tokens / n_ep**2))
    scenario = DriftScenario(
        "shift", cfg.moe.n_experts, shift_step=6, seed=0
    )
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    loop_cfg = TrainLoopConfig(
        steps=12, ckpt_dir=CKPT, ckpt_every=5, peak_lr=1e-3, warmup=4,
        log_every=2,
    )
    with axis_rules(mesh):
        res_ctl = train_loop(
            model,
            data_cfg,
            loop_cfg,
            shard_batch=batch_sharder(mesh),
            runtime=runtime,
            stats_hook=scenario.stats_hook,
        )
    ctl = res_ctl["controller"]
    assert res_ctl["final_step"] == 12
    assert np.isfinite(res_ctl["final_loss"])
    assert ctl["replan_events"] >= 1
    assert ctl["decompose_calls"] == ctl["replan_events"]
    assert ctl["swaps"] >= 1
    # traced tables: swaps never compile — the ONE permitted exception is
    # an accounted phase-envelope growth (the shift concentrates traffic
    # past the day-one envelope's slack here, so expect exactly that)
    assert ctl["compiles"] == ctl["envelope_growths"], ctl
    assert ctl["envelope_growths"] <= 1, ctl
    print(
        f"OK controller over scheduled dispatch: {ctl['replan_events']} "
        f"re-plans, {ctl['swaps']} swaps, {ctl['compiles']} recompiles "
        f"(= {ctl['envelope_growths']} envelope growths), "
        f"final loss {res_ctl['final_loss']:.4f}"
    )

    print("ALL TRAIN CHECKS PASSED")


if __name__ == "__main__":
    main()
