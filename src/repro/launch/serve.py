"""Batched serving driver: prefill + decode with continuous token-level
metrics.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \
        --batch 4 --prompt-len 64 --new-tokens 64

Serving layout (launch.rules.serve_rules): weights 2D (data x model),
KV caches sharded per DESIGN.md §5b.  Requests arrive as fixed batches
(static shapes); a production front-end would bucket by length — the
bucketing scheduler is host-side and orthogonal to the compiled steps.

``--controller`` closes the scheduler loop at serving granularity for
MoE archs: a ``ScheduleRuntime`` observes per-round routing demand (the
front-end's estimate, here synthesized with an injectable ``--drift``
scenario) and re-plans between request rounds.  Schedules are traced
``ScheduleTable`` input to the prefill/decode executables, so a swap is
just new table arrays into the SAME jits — prefill and decode pick up
re-planned (even per-layer) schedules with zero recompiles.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.rules import dtype_policy, serve_rules
from repro.models import Model
from repro.parallel import auto_mesh, axis_rules

log = logging.getLogger("repro.launch.serve")


def _make_controller(cfg, args, n_ranks: int):
    """(runtime, scenario) for MoE archs via the shared ``core.runtime``
    factory, (None, None) otherwise."""
    from repro.core import make_serving_controller

    runtime, scenario = make_serving_controller(
        cfg,
        n_ranks=n_ranks,
        drift=args.drift,
        rounds=args.rounds,
    )
    if runtime is None and args.controller:
        log.info(
            "controller disabled: arch %s has no EP-compatible MoE",
            cfg.name,
        )
    return runtime, scenario


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=2, help="request batches")
    ap.add_argument(
        "--controller",
        action="store_true",
        help="re-plan MoE schedules between rounds from demand estimates",
    )
    ap.add_argument(
        "--drift",
        default="shift",
        choices=("none", "shift", "hotspot", "skew"),
        help="demand drift injected across rounds (with --controller)",
    )
    ap.add_argument(
        "--virtual-ranks", type=int, default=8,
        help="controller fabric size when no EP mesh is active",
    )
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if jax.device_count() > 1:
        n = jax.device_count()
        mesh = auto_mesh((max(n // 4, 1), min(n, 4)), ("data", "model"))

    runtime = scenario = None
    if args.controller:
        n_ranks = (
            mesh.shape["model"] if mesh is not None else args.virtual_ranks
        )
        runtime, scenario = _make_controller(cfg, args, n_ranks)

    model = Model(cfg)
    max_len = args.prompt_len + args.new_tokens
    policy = dtype_policy(cfg)
    # thread the controller's table only into fabrics that consume
    # traced rows — 'ppermute' bakes plans in and would reject a row at
    # trace time (the controller still observes/logs for it)
    from repro.parallel.fabric import (
        consumes_schedule as fabric_needs_schedule,
        consumes_table as fabric_consumes,
    )

    consumes_schedule = cfg.moe is not None and fabric_consumes(
        cfg.moe.dispatch
    )
    if (
        cfg.moe is not None
        and mesh is not None
        and fabric_needs_schedule(cfg.moe.dispatch)
        and not fabric_consumes(cfg.moe.dispatch)
    ):
        # static-plan fabric (ppermute) on a mesh: plan ONE uniform
        # schedule and bake it into the model — the backend cannot take
        # the controller's traced rows, and schedule-less it would
        # trace-fail inside the jit
        from repro.core import decompose, plan_schedule

        n_model = mesh.shape["model"]
        tokens = args.batch * args.prompt_len * cfg.moe.top_k
        uniform = np.full((n_model, n_model), tokens / n_model**2)
        model = Model(
            cfg,
            plan_schedule(
                decompose(uniform, cfg.moe.schedule_strategy), slack=1.5
            ),
        )
        log.info(
            "baked a static %s plan (%d ranks) — %s cannot swap plans "
            "at runtime",
            cfg.moe.schedule_strategy, n_model, cfg.moe.dispatch,
        )

    def serve_round(params, prompts, prefill, decode, schedule):
        caches = model.init_cache(args.batch, max_len, policy["cache_dtype"])
        t0 = time.perf_counter()
        logits, caches = prefill(params, prompts, caches, schedule=schedule)
        jax.block_until_ready(logits)
        t_pre = time.perf_counter() - t0
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.perf_counter()
        for i in range(args.new_tokens):
            logits, caches = decode(
                params, token, caches, jnp.int32(args.prompt_len + i),
                schedule=schedule,
            )
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(token)
        return t_pre, time.perf_counter() - t0

    def observe_round(r: int):
        """Feed round r's demand estimate; returns the (possibly
        re-planned) schedule table — new arrays, never new executables."""
        if runtime is None:
            return None
        tokens = float(args.batch * args.prompt_len * cfg.moe.top_k)
        stats = np.broadcast_to(
            tokens * scenario.expert_probs(r)[None, None, :],
            (runtime.n_layers, 1, cfg.moe.n_experts),
        )
        decision = runtime.observe(stats)
        if decision.changed:
            log.info(
                "round %d: controller swap (%s)",
                r,
                "library miss" if decision.replanned else "library hit",
            )
        return runtime.table() if consumes_schedule else None

    def run():
        params = model.init(jax.random.PRNGKey(0))
        # jit ONCE: the schedule is a traced argument, so between-round
        # re-planning swaps tables into these same two executables
        prefill = jax.jit(model.prefill, donate_argnums=(2,))
        decode = jax.jit(model.decode_step, donate_argnums=(2,))
        schedule = observe_round(0)  # plan the round-0 schedule
        for r in range(args.rounds):
            if r > 0:
                schedule = observe_round(r)
            prompts = jax.random.randint(
                jax.random.PRNGKey(r), (args.batch, args.prompt_len), 0, cfg.vocab_size
            )
            t_pre, t_dec = serve_round(params, prompts, prefill, decode, schedule)
            toks = args.new_tokens * args.batch
            log.info(
                "round %d: prefill %.1f ms (%.0f tok/s) | decode %.1f ms "
                "(%.0f tok/s)",
                r,
                t_pre * 1e3,
                args.batch * args.prompt_len / t_pre,
                t_dec * 1e3,
                toks / t_dec,
            )
        if runtime is not None:
            s = runtime.summary()
            log.info(
                "controller: %d re-plan events, %d warm / %d cold plans, "
                "%d recompiles, observe %.0fus/round",
                s["replan_events"],
                s["warm_hits"],
                s["cold_plans"],
                max(0, getattr(prefill, "_cache_size", lambda: 1)() - 1)
                + max(0, getattr(decode, "_cache_size", lambda: 1)() - 1),
                s["observe_us_per_step"],
            )

    if mesh is not None:
        with axis_rules(mesh, serve_rules()):
            run()
    else:
        run()


if __name__ == "__main__":
    main()
