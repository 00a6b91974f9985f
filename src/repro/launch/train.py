"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-235b-a22b \
        --steps 200 --smoke          # reduced config, local devices
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke \
        --dispatch scheduled         # the paper's dispatch mode

Builds the mesh over all local devices, applies the train sharding rules,
plans the MoE A2A schedule when requested, and runs the fault-tolerant
loop (checkpoint/resume, deterministic data).  On a real TPU slice this
is the per-host entry point (jax.distributed handles multi-host).
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.rules import train_rules
from repro.models import Model
from repro.parallel import auto_mesh, axis_rules
from repro.train import TrainLoopConfig, train_loop

log = logging.getLogger("repro.launch.train")


def build_mesh():
    n = jax.device_count()
    model_ax = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0 and cand <= n:
            model_ax = cand
            break
    return auto_mesh((n // model_ax, model_ax), ("data", "model"))


def batch_sharder(mesh):
    """Host batch -> device arrays split over the mesh's 'data' axis."""

    def shard_batch(b):
        return {
            k: jax.device_put(
                v, NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
            )
            for k, v in b.items()
        }

    return shard_batch


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    from repro.parallel.fabric import fabric_names

    ap.add_argument(
        "--dispatch", default=None,
        choices=[None, *fabric_names(), "scheduled"],
    )
    from repro.parallel.fabric import codec_names

    ap.add_argument(
        "--wire-dtype", default=None, choices=[None, *codec_names()],
        help="wire codec for dispatch payloads (fp8/int8 quantize "
        "cross-rank slots with per-slot scales)",
    )
    ap.add_argument(
        "--pod-size", type=int, default=None,
        help="ranks per pod for --dispatch=hierarchical (must divide the "
        "model-axis size; pod-local traffic rides the electrical intra "
        "fabric, the remainder the circuit-scheduled inter fabric)",
    )
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", default=None, choices=[None, "ef8"])
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.moe is not None and args.dispatch:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=args.dispatch)
        )
    if cfg.moe is not None and args.wire_dtype:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, wire_dtype=args.wire_dtype)
        )
    if cfg.moe is not None and args.pod_size:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, pod_size=args.pod_size)
        )
    mesh = build_mesh()
    log.info("mesh %s, arch %s (%.1fM params)", dict(mesh.shape), cfg.name,
             cfg.param_count() / 1e6)

    from repro.parallel.fabric import as_fabric_schedule, consumes_schedule

    schedule = None
    if cfg.moe is not None and consumes_schedule(cfg.moe.dispatch):
        from repro.launch.dryrun import build_hierarchical_table, build_schedule

        n_model = mesh.shape["model"]
        t_rank = max(args.batch // mesh.shape["data"] * args.seq // n_model, 1)
        if cfg.moe.dispatch == "hierarchical":
            # two-level plan from the same expected traffic: the composed
            # fabric takes a HierarchicalTable, not an adapted flat plan
            schedule = build_hierarchical_table(
                cfg, n_model, t_rank, Model(cfg).n_moe_layers,
                plan="lossless",
            )
            log.info(
                "planned hierarchical schedule (pod_size %d): "
                "%d intra + %d inter phase slots",
                cfg.moe.pod_size, int(schedule.intra.k_max),
                int(schedule.inter.k_max),
            )
        else:
            schedule = build_schedule(cfg, n_model, t_rank, plan="lossless")
            log.info("planned %d-phase %s schedule", schedule.num_phases,
                     cfg.moe.schedule_strategy)
            # row-consuming fabrics (phase_pipelined / ragged_a2a) take a
            # traced per-layer table instead of the static plan
            schedule = as_fabric_schedule(
                cfg.moe.dispatch, schedule, Model(cfg).n_moe_layers
            )

    model = Model(cfg, schedule)
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size,
        seq_len=args.seq,
        global_batch=args.batch,
        frontend_tokens=cfg.frontend_tokens if cfg.frontend != "none" else 0,
        d_model=cfg.d_model,
    )
    loop_cfg = TrainLoopConfig(
        steps=args.steps,
        ckpt_dir=args.ckpt,
        ckpt_every=max(args.steps // 4, 10),
        microbatches=args.microbatches,
        grad_compress=args.grad_compress,
        log_every=10,
    )

    with axis_rules(mesh, train_rules()):
        res = train_loop(
            model, data_cfg, loop_cfg, shard_batch=batch_sharder(mesh)
        )
    log.info("done: step %d loss %.4f (%d failures recovered)",
             res["final_step"], res["final_loss"], res["failures"])


if __name__ == "__main__":
    main()
