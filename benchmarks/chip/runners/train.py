"""Training cell: the program's train step, expert-parallel over the
cell's chips, on packed batches cycled from a pool.

Set-up builds what ``repro.launch.train`` builds (``build_mesh``,
``train_rules``, ``batch_sharder``, ``Model``, the train loop's sharded
init of parameters and moments, here from the seed's weights), and
plans the MoE schedule table from the traffic: the pool is routed once
through the program (``Model.loss_and_stats``), each batch's realized
rank-to-rank demand is taken from its routing counts, and the program's
own planner (``decompose``, ``plan_schedule``) turns the demand, each
pair at its largest over the pool, into one table for the window, with
the planned caps.  Its envelope, the static bound of each phase slot
that sizes the phase buffers, is the one the configuration states (a
bound over the planned caps of many seeds), so every seed runs one
executable; a plan over that bound would have its choices cut, and the
check counts them.  No controller runs.

The step is compiled once, and the same compiled step and state run
the first three steps on the pool's first three batches, then the
window.  Those three steps are what ``correct`` judges: once the window
has closed and the program's state is freed, the plain reference
follows the same three steps, and each step's loss, each leaf's norm of
the first gradient as the optimizer took it (from its first moment)
and each leaf's norm of the change over the three steps are compared;
every routed choice of those steps must have had its slot.

In the window the steps are launched back to back; the host waits only
for the loss of the step before the one just launched, as the program's
train loop does, and notes when each step completed.  With
``--control`` the reference in float8 takes the program's place in the
comparison.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
import types

import numpy as np

import harness as H
from runners.common import model_config

# CPU rehearsal: smoke widths, short sequences and documents, a small
# pool; the table's envelope from its own plan
TINY_TRAFFIC = {"sequence": 64, "document": {"mean": 12, "sigma": 1.0}, "pool": 4}
TINY_IDS_PER_TOPIC = 16
CHECK_STEPS = 3
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# a leaf whose first gradient in the reference is under this share of the
# median leaf's moves under Adam by round-off alone: it is not compared
NEGLIGIBLE_GRAD = 1e-3


@dataclasses.dataclass
class Record:
    """What the metric readers read."""

    seconds: float
    setup_s: float
    window: tuple  # host clock: first launch .. last completion
    steps: list  # (launch, completion) host times of the window's steps
    tokens_per_step: int
    flops_per_step: int
    phases: int  # phase slots per MoE layer in the executed table
    chips: int  # devices the step runs on
    peak: dict | None
    table: dict | None  # the step program's instructions: opcode and scope
    trace: dict | None = None


# ----------------------------------------------------------------- leaves
def leaf_norms(tree) -> dict:
    """{name: L2 norm} of a parameter-shaped tree, named as the
    reference names them: layer-stacked leaves per layer (``@l``), the
    expert weights per expert (``#e``)."""
    import jax
    import jax.numpy as jnp
    import weights as W

    def norm(x, axes=None):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = W.path_name(path)
        if not name.startswith("stack/"):
            out[name] = norm(v)
            continue
        for l in range(v.shape[0]):
            if name.rsplit("/", 1)[-1] in EXPERT_LEAVES:
                n = norm(v[l], tuple(range(1, v.ndim - 1)))
                out.update({f"{name}@{l}#{e}": n[e] for e in range(v.shape[1])})
            else:
                out[f"{name}@{l}"] = norm(v[l])
    return out


def compare(prog: dict, ref: dict) -> tuple[dict, list]:
    """The compared numbers, and the leaves left out (their reference
    gradient is negligible).  A gap is between the program's norm and
    the reference's, over the reference's norm of the leaf or of the
    median leaf, whichever is larger; the worst leaf counts."""
    med_g = float(np.median(list(ref["grad"].values())))
    med_c = float(np.median(list(ref["change"].values())))
    out_leaves = sorted(k for k, v in ref["grad"].items() if v < NEGLIGIBLE_GRAD * med_g)
    keep = [k for k in ref["grad"] if k not in out_leaves]

    def worst(p, r, med):
        gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in keep}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    grad_gap, grad_leaf = worst(prog["grad"], ref["grad"], med_g)
    change_gap, change_leaf = worst(prog["change"], ref["change"], med_c)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    numbers = {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap}
    print(f"worst leaves: first gradient {grad_leaf}, change {change_leaf}", file=sys.stderr)
    return numbers, out_leaves


# ------------------------------------------------------------------ table
def plan(counts, n: int, e_local: int, tc: dict) -> list:
    """The program's plan, one schedule per MoE layer, from the pool's
    routing counts (a list, one [L, n, E] array per batch): each pair's
    demand at its largest over the pool, in the table's units
    (``e_local`` times the pair's largest count of one expert)."""
    from repro.core.decompose import decompose
    from repro.core.schedule import plan_schedule

    c = np.max(np.stack(counts), axis=0)  # [L, n, E]
    return [
        plan_schedule(decompose(e_local * c[l].reshape(n, n, e_local).max(axis=2),
                                tc["strategy"]), slack=tc["slack"], quantum=tc["quantum"])
        for l in range(c.shape[0])
    ]


def plan_table(scheds: list, tc: dict, envelope):
    """The table of the planned schedules in ``phase_slots`` slots under
    ``envelope`` (the configuration's bound, or ``"auto"``: this plan's
    own, ``phase_envelope``).  A plan with more phases than slots
    raises; a planned cap over the envelope is clamped by admission,
    and the choices past it are cut."""
    from repro.core.schedule import ScheduleTable

    return ScheduleTable.from_schedules(scheds, k_max=int(tc["phase_slots"]), envelope=envelope)


def admitted_slots(table, e_local: int, c_local: int) -> np.ndarray:
    """[L, n, n]: slots per expert that the table gives each pair (the
    rank's own experts: the local bucket)."""
    perms = np.asarray(table.perms)
    valid = np.asarray(table.valid)
    n_ph = np.asarray(table.n_phases)
    per_phase = np.asarray(table.phase_slot_caps(e_local))  # [L, K]
    n_layers, _, n = perms.shape
    out = np.zeros((n_layers, n, n), np.int64)
    for l in range(n_layers):
        for k in range(int(n_ph[l])):
            for i in range(n):
                if valid[l, k, i]:
                    out[l, i, perms[l, k, i]] += per_phase[l, k]
        np.fill_diagonal(out[l], c_local)
    return out


def cut_choices(routing: np.ndarray, slots: np.ndarray, e_local: int) -> int:
    """Routed choices with no slot: each (source rank, expert) count
    past the slots its pair holds per expert.  routing [L, n, E]."""
    per = np.repeat(slots, e_local, axis=2)  # [L, n, E]
    return int(np.maximum(routing - per, 0).sum())


def _print_demand(counts, scheds, table, e_local, t_local, k, c_local):
    """The realized skew, the rank-to-rank choices, the plan, and the
    share of buffer slots that carry no routed choice, over the pool."""
    from repro.core.schedule import phase_envelope

    total = np.sum(np.stack(counts), axis=0)  # [L, n, E]
    n = total.shape[1]
    rows = table.envelope_slots(e_local)  # per expert, per phase slot
    phase_slots = e_local * sum(rows)  # per rank
    for l in range(total.shape[0]):
        load = total[l].sum(axis=0)
        rr = total[l].reshape(n, n, e_local).sum(axis=2)  # [source, destination]
        print(f"skew layer {l}: expert load max/mean {load.max() / load.mean():.3f}; load "
              + " ".join(str(int(v)) for v in load), file=sys.stderr)
        print(f"rank-to-rank choices layer {l} (rows: source): "
              + "; ".join(" ".join(str(int(v)) for v in row) for row in rr)
              + " | row sums " + " ".join(str(int(v)) for v in rr.sum(axis=1))
              + " | column sums " + " ".join(str(int(v)) for v in rr.sum(axis=0)),
              file=sys.stderr)
        s = scheds[l]
        own = phase_envelope([s], table.k_max)
        print(f"plan layer {l}: {s.num_phases} phases, perms "
              + "; ".join(" ".join(str(int(v)) for v in p) for p in s.perms)
              + ", planned caps " + " ".join(str(int(v)) for v in s.caps)
              + " (its own envelope " + " ".join(str(int(v)) for v in own)
              + "); executed envelope " + " ".join(str(v) for v in table.envelope)
              + " per pair, " + " ".join(str(v) for v in rows) + " rows per expert",
              file=sys.stderr)
        remote = rr.sum() - np.trace(rr)
        batches = len(counts)
        print(f"buffer slots with no routed choice, layer {l}: "
              f"{100.0 * (1.0 - remote / (n * batches * phase_slots)):.2f}% of the phase slots, "
              f"{100.0 * (1.0 - t_local * k / (phase_slots + e_local * c_local)):.2f}% "
              "with the local bucket", file=sys.stderr)


# ------------------------------------------------------------------ set-up
@dataclasses.dataclass
class Setup:
    hf: dict
    ref: object  # the reference module the configuration names
    opt_conf: dict
    mesh: object
    step: object  # the compiled step
    state: dict
    pool: list  # host batches
    pool_dev: list
    table: object
    phases: int
    slots: np.ndarray
    e_local: int
    info: dict
    peak: dict | None
    scopes: dict
    readings: dict  # the program's: losses, first gradient, change
    cut: int  # routed choices with no slot, first steps
    dropped: int  # the program's own count, first steps
    tokens_per_step: int
    flops_per_step: int


def _check_steps(step, state, pool_dev, table, opt, key, shapes):
    """The first steps, through the window's own call and feed, on the
    pool's first batches; the program's readings of them."""
    import jax
    import jax.numpy as jnp
    import weights as W

    first_grad = jax.jit(lambda mu: leaf_norms(jax.tree.map(lambda m: m / (1 - opt.b1), mu)))
    change = jax.jit(lambda p, k: leaf_norms(
        jax.tree.map(jnp.subtract, p, W.build_tree(shapes, k, jnp.float32))))
    losses, stats, grad = [], [], None
    for i in range(CHECK_STEPS):
        params, opt_state, ef, metrics = step(state["params"], state["opt"], state["ef"],
                                              pool_dev[i], table)
        state = {"params": params, "opt": opt_state, "ef": ef}
        losses.append(float(metrics["loss"]))
        stats.append(jax.tree.map(np.asarray, metrics["moe_stats"]))
        if i == 0:
            grad = {k: float(v) for k, v in first_grad(state["opt"]["mu"]).items()}
    moved = {k: float(v) for k, v in change(state["params"], key).items()}
    return state, {"loss": losses, "grad": grad, "change": moved}, stats


def program(cell, tiny: bool) -> types.SimpleNamespace:
    """What ``repro.launch.train`` builds for the cell: mesh, model, the
    optimizer, the sharded seeded init of the state, and the forward
    that routes a batch (the ``a2a`` dispatch, which needs no table)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.rules import dtype_policy, train_rules
    from repro.launch.train import batch_sharder, build_mesh
    from repro.models import Model
    from repro.optim import AdamW, cosine_schedule
    from repro.parallel import axis_rules
    from repro.train.train_step import param_specs
    from traffic.packed import Packed
    import weights as W

    conf, traffic = cell["config"], dict(cell["traffic"])
    if tiny:
        traffic.update(TINY_TRAFFIC)
        traffic["topics"] = dict(traffic["topics"], ids_per_topic=TINY_IDS_PER_TOPIC)
    cfg, hf, ref = model_config(conf, tiny)
    dtypes = dtype_policy(cfg)
    if dtypes["param_dtype"] != jnp.float32 or dtypes["moment_dtype"] != jnp.float32:
        raise SystemExit(f"the program keeps {dtypes}, the configuration states float32")
    mesh = build_mesh()
    if dict(mesh.shape) != conf["mesh"]:
        raise SystemExit(f"mesh {dict(mesh.shape)}, the configuration states {conf['mesh']} "
                         "(a CPU rehearsal needs XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    n, m = mesh.shape["model"], cfg.moe
    gen = Packed(traffic, cfg.vocab_size)
    t_local = gen.tokens_per_batch // (n * mesh.shape["data"])
    e_local = m.n_experts // n
    o = conf["optimizer"]
    opt = AdamW(
        lr=cosine_schedule(o["peak_lr"], o["warmup_steps"], o["total_steps"], o["final_frac"]),
        b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
        clip_norm=o["clip_norm"],
    )
    model = Model(cfg)
    route_model = Model(dataclasses.replace(cfg, moe=dataclasses.replace(m, dispatch="a2a")))
    rules = train_rules()
    with axis_rules(mesh, rules):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

        def init_state(k):
            params = W.build_tree(shapes, k, jnp.float32)
            return {"params": params, "opt": opt.init(params), "ef": {}}

        specs = param_specs(jax.eval_shape(init_state, W.seed_key(0)))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return types.SimpleNamespace(
        conf=conf, cfg=cfg, hf=hf, ref=ref, mesh=mesh, rules=rules, n=n, e_local=e_local,
        t_local=t_local, top_k=m.top_k,
        # the local bucket: every choice of the rank's own experts, at the
        # configuration's capacity factor
        c_local=8 * math.ceil(math.ceil(t_local * m.top_k / (n * e_local) * m.capacity_factor) / 8),
        gen=gen, opt=opt, model=model, shapes=shapes, shard=batch_sharder(mesh),
        init=jax.jit(init_state, out_shardings=shardings),
        route=jax.jit(lambda p, b: route_model.loss_and_stats(p, b)[1]["routing"]),
    )


def routed(pr, seed: int):
    """The seed's state and pool, and the pool routed through the
    program: (state, host pool, device pool, routing counts per batch)."""
    import jax
    import weights as W
    from repro.parallel import axis_rules

    pool = pr.gen.pool(seed)
    with axis_rules(pr.mesh, pr.rules):
        state = pr.init(W.seed_key(seed))
        pool_dev = [pr.shard({k: b[k] for k in ("tokens", "targets")}) for b in pool]
        counts = [np.asarray(pr.route(state["params"], b)) for b in pool_dev]
    jax.block_until_ready(state)
    return state, pool, pool_dev, counts


def setup(args, cell) -> Setup:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel import axis_rules
    from repro.train.train_step import make_train_step
    import flops as F
    import train_scopes as S
    import weights as W

    info = H.device_info()
    peak = F.peaks(info["kind"]) if info["platform"] == "tpu" else None
    marks = [("start", time.perf_counter())]
    pr = program(cell, args.tiny)
    conf, mesh, e_local, c_local = pr.conf, pr.mesh, pr.e_local, pr.c_local
    state, pool, pool_dev, counts = routed(pr, args.seed)
    marks.append(("weights, pool and routing", time.perf_counter()))
    scheds = plan(counts, pr.n, e_local, conf["table"])
    table = plan_table(scheds, conf["table"], "auto" if args.tiny else conf["table"]["envelope"])
    _print_demand(counts, scheds, table, e_local, pr.t_local, pr.top_k, c_local)
    table = jax.device_put(table, NamedSharding(mesh, P()))
    marks.append(("plan", time.perf_counter()))
    with axis_rules(mesh, pr.rules):
        step_fn = make_train_step(pr.model, pr.opt, collect_routing=True)
        step = jax.jit(step_fn, donate_argnums=(0, 1, 2)).lower(
            state["params"], state["opt"], state["ef"], pool_dev[0], table
        ).compile()
        marks.append(("step compile", time.perf_counter()))
        state, readings, stats = _check_steps(step, state, pool_dev, table, pr.opt,
                                              W.seed_key(args.seed), pr.shapes)
        marks.append(("first steps", time.perf_counter()))
    print("set-up: " + ", ".join(f"{n} {b - a:.1f} s" for (_, a), (n, b) in zip(marks, marks[1:])),
          file=sys.stderr)
    _print_memory(step, jax.devices())
    slots = admitted_slots(table, e_local, c_local)
    cut = sum(cut_choices(s["routing"], slots, e_local) for s in stats)
    dropped = int(sum(s["dropped"].sum() for s in stats))
    scopes = S.table_of(step.as_text()) if args.trace else {}
    dm = pr.ref.dims(pr.hf)
    return Setup(
        hf=pr.hf, ref=pr.ref, opt_conf=conf["optimizer"], mesh=mesh, step=step, state=state,
        pool=pool, pool_dev=pool_dev, table=table, phases=int(np.max(np.asarray(table.n_phases))),
        slots=slots, e_local=e_local, info=info, peak=peak, scopes=scopes,
        readings=readings, cut=cut, dropped=dropped,
        tokens_per_step=pr.gen.tokens_per_batch,
        flops_per_step=F.train_flops(dm, pr.gen.batch, pr.gen.sequence),
    )


def _print_memory(step, devices) -> None:
    """The compiled step's own account of its memory beside the
    allocator's, read after the first steps."""
    m = step.memory_analysis()
    gb = lambda x: f"{x / 1e9:.3f} GB"  # noqa: E731
    if m is not None:
        print(f"step memory_analysis: arguments {gb(m.argument_size_in_bytes)}, outputs "
              f"{gb(m.output_size_in_bytes)}, aliased {gb(m.alias_size_in_bytes)}, temp "
              f"{gb(m.temp_size_in_bytes)}, code {gb(m.generated_code_size_in_bytes)}",
              file=sys.stderr)
    for d in devices:
        st = d.memory_stats()
        if st:
            print(f"memory_stats {d.id}: " + ", ".join(f"{k} {v}" for k, v in sorted(st.items())),
                  file=sys.stderr)


# ------------------------------------------------------------------- run
def reference_readings(reference, seed, hf, opt_conf, pool, devices, n_ranks, low=False,
                       fault=None):
    """The readings of the first steps by the ``reference`` module
    (``low``, ``fault``: see its ``train_readings``)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return reference.train_readings(
            seed, hf, opt_conf, pool[:CHECK_STEPS], devices=devices,
            n_ranks=n_ranks, low=low, fault=fault,
        )


def run(args, cell, t_process: float) -> tuple[dict, list]:
    import jax

    import trace_reduce as T

    su = setup(args, cell)
    step, state, pool_dev, table = su.step, su.state, su.pool_dev, su.table
    spans = H.Spans(traced=bool(args.trace))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None

    # ------------------------------------------------------------ window
    if args.trace:
        jax.profiler.start_trace(trace_dir)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    launched, done, stats = [], [], []
    pending, i = None, CHECK_STEPS
    losses = []
    w0 = time.perf_counter()
    while True:
        launched.append(time.perf_counter())
        with spans.span("bench.launch"):
            params, opt_state, ef, out = step(
                state["params"], state["opt"], state["ef"], pool_dev[i % len(pool_dev)], table
            )
        state = {"params": params, "opt": opt_state, "ef": ef}
        stats.append(out["moe_stats"])
        i += 1
        if pending is not None:
            with spans.span("bench.fetch"):
                losses.append(float(pending))
            done.append(time.perf_counter())
            if done[-1] >= w0 + args.seconds:
                break
        pending = out["loss"]
    with spans.span("bench.fetch"):
        losses.append(float(pending))
    done.append(time.perf_counter())
    w1 = done[-1]
    if args.trace:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    setup_s = w0 - t_process

    devs = jax.devices()
    mem_peak = H.memory_peak_bytes(devs)
    stats = [jax.tree.map(np.asarray, s) for s in stats]
    window_cut = sum(cut_choices(s["routing"], su.slots, su.e_local) for s in stats)
    window_dropped = int(sum(s["dropped"].sum() for s in stats))
    failed = sum(1 for x in losses if not math.isfinite(x))
    rec = Record(
        seconds=args.seconds, setup_s=setup_s, window=(w0, w1),
        steps=list(zip(launched, done)), tokens_per_step=su.tokens_per_step,
        flops_per_step=su.flops_per_step, phases=su.phases, chips=su.mesh.size, peak=su.peak,
        table=su.scopes,
    )
    breakdown, busy = None, None
    if args.trace:
        t0 = time.perf_counter()
        rec.trace = T.load(trace_dir)
        lo, hi = T.window(rec.trace)
        print(f"trace: {sum(len(v) for v in rec.trace['ops'].values())} device ops, read in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        breakdown = T.breakdown(rec.trace, lo, hi)
        busy = np.mean([T.length(T.busy(rec.trace, d, lo, hi)) for d in T.devices(rec.trace)]) * 1e-9
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = H.read_metrics(H.metric_specs(cell["bench"], args.workload, bool(args.trace)), rec)
    print(f"window: {len(rec.steps)} steps, "
          f"{len(rec.steps) * rec.tokens_per_step / (w1 - w0):.2f} tokens/s, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"choices with no slot {window_cut}, the program's dropped count {window_dropped}",
          file=sys.stderr)
    gaps = np.diff(done) * 1e3
    q = max(1, len(gaps) // 4)
    print(f"ms between completions: first quarter {gaps[:q].mean():.2f}, "
          f"last quarter {gaps[-q:].mean():.2f}", file=sys.stderr)

    # ------------------------------------------------------- correctness
    prog, pool, hf, o, info, reference = (su.readings, su.pool, su.hf, su.opt_conf, su.info,
                                          su.ref)
    n_ranks, cut, dropped = su.mesh.shape["model"], su.cut, su.dropped
    del su, step, state, pool_dev, table, params, opt_state, ef, out, rec
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_readings(reference, args.seed, hf, o, pool, devs, n_ranks)
    print(f"reference: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    if args.control:
        print("control: the float8 reference stands in for the program", file=sys.stderr)
        print(f"program losses {prog['loss']}", file=sys.stderr)
        prog = reference_readings(reference, args.seed, hf, o, pool, devs, n_ranks, low=True)
    numbers, left_out = compare(prog, ref)
    print(f"losses: program {prog['loss']}, reference {ref['loss']}; leaves left out "
          f"(first gradient under {NEGLIGIBLE_GRAD} of the median leaf's): {left_out}",
          file=sys.stderr)
    lim = H.limits(args.workload)
    checks = [(k, v, lim[k]["limit"]) for k, v in numbers.items()]
    checks += [("cut_choices", cut, 0), ("dropped", dropped, 0)]
    correct = all(v <= l for _, v, l in checks)
    device = dict(info, memory_peak_bytes=mem_peak)
    if args.trace:
        device.update(busy_s=float(busy), window_s=float((hi - lo) * 1e-9))
    result = {
        "correct": bool(correct),
        "attempted": len(losses),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks
