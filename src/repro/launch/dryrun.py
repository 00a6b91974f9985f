"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent at
production scale (512 placeholder devices) and extracts the artifacts the
roofline analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline)
reads:

  * compiled.memory_analysis()  — per-device bytes: proves it fits HBM
  * compiled.cost_analysis()    — HLO FLOPs / bytes accessed
  * parse_collectives(compiled.as_text()) — per-type collective bytes

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-moe-235b-a22b \
      --cells train_4k --multi-pod --dispatch scheduled
Artifacts land in reports/dryrun/<mesh>/<arch>.<cell>[.<dispatch>].json.
"""

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.core import decompose, plan_schedule, traffic_matrix
from repro.core.traffic import RouterConfig
from repro.launch.hlo import analyze_module
from repro.launch.mesh import make_production_mesh
from repro.launch.rules import dtype_policy, serve_rules, train_rules
from repro.launch.shapes import CELLS, Cell, cell_applicable, input_specs
from repro.models import Model
from repro.models.attention import _cache_seq_axes
from repro.optim import AdamW
from repro.parallel import axis_rules
from repro.parallel.sharding import logical_to_spec
from repro.train import make_train_step, param_specs

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun")


# --------------------------------------------------------------- utilities
def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def cast_tree(sds_tree, from_dtype, to_dtype):
    def one(s):
        dt = to_dtype if s.dtype == from_dtype else s.dtype
        return jax.ShapeDtypeStruct(s.shape, dt)

    return jax.tree.map(one, sds_tree)


def cache_pspecs(cfg, caches_sds, batch: int):
    """PartitionSpecs for a stacked cache tree (leading period dim)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_sds)
    specs = []
    for path, leaf in flat:
        j = int(str(getattr(path[0], "key", "pos0"))[3:])
        kind = cfg.layer_kind(j)
        if kind == "attn":
            name = str(getattr(path[1], "key"))
            axes = _cache_seq_axes(batch, cfg.n_kv_heads)
            if name in ("k", "v"):
                logical = (None, *axes)
            else:  # pos [P, B, slots]
                logical = (None, axes[0], axes[1])
        elif kind == "mamba":
            idx = getattr(path[1], "idx", 0)
            logical = (
                (None, "batch", "inner", None)
                if idx == 0
                else (None, "batch", None, "inner")
            )
        else:  # rwkv6: (x_tm [P,B,d], S [P,B,H,D,D], x_cm [P,B,d])
            idx = getattr(path[1], "idx", 0)
            logical = (
                (None, "batch", "heads", None, None)
                if idx == 1
                else (None, "batch", None)
            )
        specs.append(logical_to_spec(logical, leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, specs)


def expected_traffic(cfg, n: int, tokens_per_rank: int) -> np.ndarray:
    """The launchers' day-one traffic estimate: one skewed draw from the
    arch's router profile (the controller replaces it with realized
    traffic as soon as it observes)."""
    router = RouterConfig(cfg.name, cfg.moe.n_experts, cfg.moe.top_k)
    rng = np.random.default_rng(0)
    return traffic_matrix(
        rng,
        router,
        np.full(n, max(tokens_per_rank, 1)),
        n_ranks=n,
        skew_alpha=0.3,
    )


def build_schedule(
    cfg, n: int, tokens_per_rank: int, strategy: str = "maxweight", plan: str = "literal"
):
    """Plan the scheduled-dispatch A2A from an expected (skewed) traffic
    matrix — the OCS-controller analogue (DESIGN.md §2.2).

    plan='literal': the paper's circuit semantics (phase cap = max pair,
      generous slack).  plan='v2': §Perf iteration — min-fill deferral in
      the decomposition, p90 quantile caps, tighter slack.
    """
    mat = expected_traffic(cfg, n, tokens_per_rank)
    if plan == "v2":
        d = decompose(mat, strategy, min_fill=0.1)
        return plan_schedule(d, slack=1.1, quantum=8, cap_quantile=0.9)
    if plan == "lossless":
        # zero planned drops at minimum padding (§Perf: compare against
        # a2a at the capacity factor that also reaches zero drops)
        d = decompose(mat, strategy, min_fill=0.1)
        return plan_schedule(d, slack=1.0, quantum=8)
    if plan == "bvn":
        # the paper's BASELINE strategy made executable: Sinkhorn + BvN
        # framed slots, pairs recurring across phases at static offsets
        from repro.core.schedule import plan_schedule_bvn

        return plan_schedule_bvn(decompose(mat, "bvn"), quantum=8)
    return plan_schedule(decompose(mat, strategy), slack=1.3, quantum=8)


def build_hierarchical_table(
    cfg,
    n: int,
    tokens_per_rank: int,
    n_moe_layers: int,
    strategy: str = "maxweight",
    plan: str = "literal",
):
    """Two-level analogue of ``build_schedule`` for the ``hierarchical``
    fabric: the SAME expected-traffic draw, split at ``cfg.moe.pod_size``
    and planned per level with the plan preset's knobs.  Returns a
    ``HierarchicalTable`` with one row per MoE layer."""
    from repro.core import hierarchical_plan

    mat = expected_traffic(cfg, n, tokens_per_rank)
    presets = {
        "literal": dict(slack=1.3, quantum=8),
        "lossless": dict(
            decompose_kwargs={"min_fill": 0.1}, slack=1.0, quantum=8
        ),
        "v2": dict(
            decompose_kwargs={"min_fill": 0.1},
            slack=1.1,
            quantum=8,
            cap_quantile=0.9,
        ),
    }
    if plan not in presets:
        raise ValueError(
            f"hierarchical dispatch has no {plan!r} plan preset; "
            f"pick one of {sorted(presets)}"
        )
    return hierarchical_plan(
        mat,
        cfg.moe.pod_size,
        n_layers=n_moe_layers,
        strategy=strategy,
        **presets[plan],
    )


# --------------------------------------------------------------- cell runs
def lower_cell(
    arch: str, cell: Cell, mesh, *, dispatch: str | None = None, cf_override=None
):
    """Returns (lowered, meta) for one (arch, cell, mesh)."""
    cfg = get_config(arch)
    policy = dtype_policy(cfg)
    is_train = cell.mode == "train"
    rules = train_rules() if is_train else serve_rules()

    plan = "literal"
    expert_2d = False
    if dispatch == "scheduled_v2":
        dispatch, plan = "scheduled", "v2"
    elif dispatch == "scheduled_lossless":
        dispatch, plan = "scheduled", "lossless"
    elif dispatch == "a2a_2d":
        dispatch, expert_2d = "a2a", True
    elif dispatch == "scheduled_2d":
        dispatch, plan, expert_2d = "scheduled", "lossless", True
    elif dispatch == "scheduled_bvn":
        dispatch, plan = "scheduled", "bvn"
    if is_train:
        rules = train_rules(expert_2d=expert_2d)
    if cfg.moe is not None:
        mode = dispatch or ("a2a" if is_train or cell.mode == "prefill" else "dense")
        moe = dataclasses.replace(cfg.moe, dispatch=mode, expert_2d=expert_2d)
        if cf_override is not None:
            moe = dataclasses.replace(moe, capacity_factor=cf_override)
        cfg = dataclasses.replace(cfg, moe=moe)
    else:
        mode = "n/a"

    with axis_rules(mesh, rules) as ar:
        n_model = ar.axis_size(("model",))
        schedule = None
        microbatches = 8 if is_train else 1
        from repro.parallel.fabric import as_fabric_schedule, consumes_schedule

        planned = None  # the static plan, pre-wrap (meta reads phases off it)
        if cfg.moe is not None and consumes_schedule(cfg.moe.dispatch):
            bs = ar.axis_size(tuple(a for a in ("pod", "data") if a in mesh.axis_names))
            if not is_train:
                bs = ar.axis_size(tuple(a for a in ("pod",) if a in mesh.axis_names)) or 1
            # tokens per EP rank per CALL: account for the microbatch split
            t_block = (cell.global_batch // microbatches // max(bs, 1)) * cell.seq_len
            if cfg.moe.dispatch == "hierarchical":
                # the composed fabric plans both levels from the traffic
                # itself — a flat plan can't be adapted after the fact
                schedule = build_hierarchical_table(
                    cfg, n_model, t_block // n_model,
                    Model(cfg).n_moe_layers, plan=plan,
                )
                planned = schedule.inter  # meta reads phases off the circuit level
            else:
                planned = build_schedule(cfg, n_model, t_block // n_model, plan=plan)
                # row-consuming fabrics take a traced per-layer table
                schedule = as_fabric_schedule(
                    cfg.moe.dispatch, planned, Model(cfg).n_moe_layers
                )
        model = Model(cfg, schedule)

        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_sds = jax.eval_shape(model.init, key_sds)
        pd = policy["param_dtype"] if is_train else policy["serve_param_dtype"]
        params_sds = cast_tree(params_sds, jnp.float32, pd)
        p_specs = param_specs(params_sds)
        p_ns = _ns(mesh, p_specs)

        ins = input_specs(cfg, cell)

        if is_train:
            opt = AdamW(moment_dtype=policy["moment_dtype"])
            opt_sds = jax.eval_shape(opt.init, params_sds)
            opt_ns = {"step": NamedSharding(mesh, P()), "mu": p_ns, "nu": p_ns}
            batch_ns = {
                k: NamedSharding(
                    mesh,
                    P(
                        tuple(a for a in ("pod", "data") if a in mesh.axis_names),
                        *([None] * (len(v.shape) - 1)),
                    ),
                )
                for k, v in ins.items()
            }
            # 8 microbatches: standard activation-memory lever at this
            # scale (global batch 256 -> 8 x 32)
            step_fn = make_train_step(model, opt, microbatches=microbatches)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_ns, opt_ns, None, batch_ns),
                out_shardings=(p_ns, opt_ns, None, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_sds, opt_sds, {}, ins)
        elif cell.mode == "prefill":
            caches_sds = jax.eval_shape(
                lambda: model.init_cache(
                    cell.global_batch, cell.seq_len, policy["cache_dtype"]
                )
            )
            c_ns = _ns(mesh, cache_pspecs(cfg, caches_sds, cell.global_batch))
            bspec = P(tuple(a for a in ("pod",) if a in mesh.axis_names) or None)
            tok_ns = NamedSharding(mesh, P(bspec[0], None))
            args = [params_sds, ins["tokens"], caches_sds]
            shardings = [p_ns, tok_ns, c_ns]
            if "ext_embeds" in ins:
                args.append(ins["ext_embeds"])
                shardings.append(NamedSharding(mesh, P(bspec[0], None, None)))
            jitted = jax.jit(
                model.prefill,
                in_shardings=tuple(shardings),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(*args)
        else:  # decode
            caches_sds = jax.eval_shape(
                lambda: model.init_cache(
                    cell.global_batch, cell.seq_len, policy["cache_dtype"]
                )
            )
            c_ns = _ns(mesh, cache_pspecs(cfg, caches_sds, cell.global_batch))
            bspec = tuple(a for a in ("pod",) if a in mesh.axis_names) or None
            pod_size = mesh.devices.shape[0] if bspec else 1
            if cell.global_batch % max(pod_size, 1):
                bspec = None  # batch=1 long-context: replicate over pods
            tok_ns = NamedSharding(mesh, P(bspec[0] if bspec else None))
            jitted = jax.jit(
                model.decode_step,
                in_shardings=(p_ns, tok_ns, c_ns, NamedSharding(mesh, P())),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                params_sds, ins["token"], caches_sds, ins["step"]
            )
    meta = {
        "arch": arch,
        "cell": cell.name,
        "dispatch": mode,
        "param_count": get_config(arch).param_count(),
        "active_param_count": get_config(arch).active_param_count(),
        "param_dtype": str(pd),
        "schedule_phases": None
        if planned is None
        else (
            planned.num_phases  # static A2ASchedule
            if hasattr(planned, "num_phases")
            else int(planned.k_max)  # hierarchical: the circuit level's table
        ),
        "plan": plan if planned is not None else None,
    }
    return lowered, meta


def run_cell(
    arch: str, cell: Cell, mesh, *, dispatch=None, hlo_out=None, cf_override=None
) -> dict:
    n_dev = mesh.devices.size
    t0 = time.time()
    lowered, meta = lower_cell(
        arch, cell, mesh, dispatch=dispatch, cf_override=cf_override
    )
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    t3 = time.time()
    analysis = analyze_module(hlo, n_devices=n_dev)
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)

    coll = dict(analysis["collectives"])
    coll["total"] = analysis["collective_total"]
    coll["wire"] = analysis["wire"]
    coll["wire_total"] = analysis["wire_total"]
    coll["count"] = analysis["collective_counts"]
    if "permute_pair_fraction" in analysis:
        coll["permute_pair_fraction"] = analysis["permute_pair_fraction"]
    result = {
        **meta,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "n_devices": int(n_dev),
        "ok": True,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "analyze_s": round(time.time() - t3, 2),
        # loop-aware (while-body x trip-count) costs from the HLO analyzer
        "flops_per_device": analysis["flops"],
        "bytes_per_device": analysis["hbm_bytes"],
        # XLA's own numbers for reference (while bodies counted once)
        "xla_flops_per_device": cost.get("flops", float("nan")),
        "xla_bytes_per_device": cost.get("bytes accessed", float("nan")),
        "collectives": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    return result


def main(argv=None) -> int:
    # the 512 placeholder devices: takes effect only if no jax backend
    # has started in this process yet (make_production_mesh says so)
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS"),
                      "--xla_force_host_platform_device_count=512"])
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--cells", default=None, help="comma list (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument(
        "--dispatch",
        default=None,
        choices=[None, "dense", "a2a", "ppermute", "phase_pipelined",
                 "ragged_a2a", "scheduled", "scheduled_v2",
                 "scheduled_lossless", "a2a_2d", "scheduled_2d",
                 "scheduled_bvn"],
    )
    ap.add_argument("--cf", type=float, default=None,
                    help="override MoE capacity factor (a2a lossless point)")
    ap.add_argument("--flash", action="store_true",
                    help="prefill attention via the Pallas flash kernel")
    ap.add_argument("--out", default=REPORT_DIR)
    ap.add_argument("--hlo", action="store_true", help="also dump HLO text")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ASSIGNED)
    cells = (
        [CELLS[c] for c in args.cells.split(",")] if args.cells else list(CELLS.values())
    )
    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    if args.flash:
        import repro.models.attention as _attn

        _attn.USE_PALLAS_FLASH = True
    failures = []
    for mesh in meshes:
        mesh_name = "x".join(map(str, mesh.devices.shape))
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            cfg = get_config(arch)
            for cell in cells:
                ok, why = cell_applicable(cfg, cell)
                tag = f"{mesh_name} {arch:24s} {cell.name:12s}"
                if not ok:
                    print(f"SKIP {tag} ({why})")
                    continue
                suffix = f".{args.dispatch}" if args.dispatch else ""
                if args.cf is not None:
                    suffix += f"-cf{args.cf:g}"
                if args.flash:
                    suffix += ".flash"
                path = os.path.join(outdir, f"{arch}.{cell.name}{suffix}.json")
                hlo_out = path.replace(".json", ".hlo.txt") if args.hlo else None
                try:
                    res = run_cell(
                        arch, cell, mesh, dispatch=args.dispatch,
                        hlo_out=hlo_out, cf_override=args.cf,
                    )
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    print(
                        f"OK   {tag} compile={res['compile_s']:7.1f}s "
                        f"flops/dev={res['flops_per_device']:.3e} "
                        f"coll={res['collectives'].get('total', 0)/1e6:10.1f}MB"
                    )
                except Exception as e:  # record, keep going
                    failures.append((arch, cell.name, mesh_name, repr(e)))
                    with open(path, "w") as f:
                        json.dump(
                            {"arch": arch, "cell": cell.name, "ok": False,
                             "error": traceback.format_exc()},
                            f,
                            indent=1,
                        )
                    print(f"FAIL {tag} {e!r}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f4 in failures:
            print("  ", *f4)
        return 1
    print("\nall requested dry-run cells compiled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
