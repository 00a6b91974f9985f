"""Compile the training cell's programs for a described TPU v5e 2x2,
without the chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/rehearse_train.py [--reference]

Compiles the cell's train step (its mesh, rules, shardings and the phase
table's envelope from the configuration), the forward that routes the pool, and with
``--reference`` the step of the reference the configuration names
(its ``leaves``, ``per_leaf``, ``sharding_of`` and ``step_fn``) in float32
and in float8 on the same four chips, and prints ``memory_analysis()`` of each and the
collectives in the train step.  Nothing runs: no time is measured.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402

WORKLOAD = "mixtral.train.ep4.skewed"


def report(name, compiled, t0) -> None:
    m = compiled.memory_analysis()
    gb = lambda x: f"{x / 1e9:.2f} GB"  # noqa: E731
    print(
        f"{name}: arguments {gb(m.argument_size_in_bytes)}, outputs {gb(m.output_size_in_bytes)}, "
        f"temp {gb(m.temp_size_in_bytes)}, aliased {gb(m.alias_size_in_bytes)}; "
        f"compiled in {time.perf_counter() - t0:.1f} s",
        flush=True,
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args()
    H.program_path()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.schedule import A2ASchedule, ScheduleTable
    from repro.launch.rules import train_rules
    from repro.models import Model
    from repro.optim import AdamW, cosine_schedule
    from repro.parallel import auto_mesh, axis_rules
    from repro.train.train_step import make_train_step, param_specs
    from runners.common import model_config
    from traffic.packed import Packed

    cell = H.cell(WORKLOAD)
    conf = cell["config"]
    cfg, hf, ref = model_config(conf, tiny=False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = auto_mesh((conf["mesh"]["data"], conf["mesh"]["model"]), ("data", "model"),
                     devices=topo.devices)
    n, m = conf["mesh"]["model"], cfg.moe
    gen = Packed(cell["traffic"], cfg.vocab_size)
    slots, env = conf["table"]["phase_slots"], conf["table"]["envelope"]
    perms = np.array([(np.arange(n) + 1 + k % (n - 1)) % n for k in range(slots)], np.int32)
    table = ScheduleTable.from_schedules(
        [A2ASchedule(perms=perms, caps=np.asarray(env, np.int32), valid=perms != np.arange(n))],
        envelope=env,
    )
    o = conf["optimizer"]
    opt = AdamW(lr=cosine_schedule(o["peak_lr"], o["warmup_steps"], o["total_steps"]),
                eps=o["eps"])
    model = Model(cfg)
    rep = NamedSharding(mesh, P())

    def sds(tree, sharding):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                            tree, sharding)

    with axis_rules(mesh, train_rules()):
        def init_state():
            p = model.init(jax.random.PRNGKey(0))
            return {"params": p, "opt": opt.init(p), "ef": {}}

        shapes = jax.eval_shape(init_state)
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(shapes),
                             is_leaf=lambda x: isinstance(x, P))
        state = sds(shapes, shard)
        bsh = NamedSharding(mesh, P("data", None))
        batch = {k: jax.ShapeDtypeStruct((gen.batch, gen.sequence), jnp.int32, sharding=bsh)
                 for k in ("tokens", "targets")}
        tab = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), table)
        t0 = time.perf_counter()
        step = jax.jit(make_train_step(model, opt, collect_routing=True),
                       donate_argnums=(0, 1, 2)).lower(
            state["params"], state["opt"], state["ef"], batch, tab).compile()
        report("train step", step, t0)
        kinds = collections.Counter(re.findall(r" ([a-z\-]+)\(", step.as_text()))
        print("train step collectives: " + ", ".join(
            f"{k} {v}" for k, v in sorted(kinds.items())
            if k.split("-start")[0] in ("all-to-all", "all-reduce", "all-gather",
                                        "collective-permute", "reduce-scatter")), flush=True)
        route_model = Model(dataclasses.replace(cfg, moe=dataclasses.replace(m, dispatch="a2a")))
        t0 = time.perf_counter()
        route = jax.jit(lambda p, b: route_model.loss_and_stats(p, b)[1]["routing"]).lower(
            state["params"], batch).compile()
        report("routing forward", route, t0)
    if args.reference:
        dm = ref.dims(hf)
        rmesh = Mesh(np.asarray(topo.devices), ("x",))
        spec = ref.leaves(dm)
        params = ref.per_leaf(dm, lambda k: jax.ShapeDtypeStruct(
            spec[k][1], jnp.float32, sharding=ref.sharding_of(k, rmesh)))
        tok = jax.ShapeDtypeStruct((gen.batch, gen.sequence), jnp.int32,
                                   sharding=NamedSharding(rmesh, P()))
        num = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(rmesh, P()))
        for name, kw in (("reference", {}), ("reference float8", {"low": True}),
                         ("reference no exchange", {"fault": "no_exchange"})):
            t0 = time.perf_counter()
            fn = ref.step_fn(dm, o, n_ranks=n, **kw)
            report(name, fn.lower(params, params, params, tok, tok, num).compile(), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
