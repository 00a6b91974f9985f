"""Bring-up check on a TPU: the main path at Mixtral-8x7B widths.

    python chip_smoke.py              # one chip: serve, reference, kernel
    python chip_smoke.py --chips 4    # four chips: expert-parallel training
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal (exits 1)

One chip (the default) runs three phases:

* serve  -- 16 requests through ``repro.serve.ServeEngine`` at Mixtral-8x7B
  widths, depth cut to ``SERVE_DEPTH`` layers, ``phase_pipelined`` dispatch
  with the device controller attached; every request must complete.
* ref    -- one served request's prefill and decode logits, through the KV
  cache, against ``Model.forward`` over the same tokens.
* kernel -- the grouped ``moe_gemm`` kernel at Mixtral widths, forward and
  VJP with ``row_valid``, against ``moe_gemm_ref``; the compiled programs
  must hold the Mosaic kernel, so an einsum fallback fails the run.

``--chips 4`` runs one phase, expert-parallel training: three steps
through ``launch.train``'s mesh and rules and ``train.train_loop``, once
per fabric (``dense``, ``phase_pipelined``, ``ragged_a2a``) from one seed
and one batch.  Losses must agree, no step may fail, the compiled EP steps
must hold their collective, and the expert weights must be split over all
four chips.

Any failed phase raises.  The last line of stdout is one JSON object,
printed only after every phase passed on a TPU.  ``--tiny`` only shrinks
the widths and moves the device check to the end: the phases then run on
the CPU and the script exits 1 without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

import numpy as np

# Depths from compiling the full-width steps for a described v5e chip.
# Serving 4 layers holds 12.13 GB of bf16 weights, and building them under
# one jit needs 3.76 GB more while it runs: 15.89 GB, less than 2 GB short
# of the 16 GB chip.  2 layers: 6.33 + 3.76 GB.  The f32 reference check
# (``reference_phase``) also holds the weights in f32: 12.7 GB at 2
# layers, twice that at 4.
SERVE_DEPTH = 2
# Training, per chip of four: 1 layer holds 5.14 GB of f32 weights and
# Adam moments plus a 3.58 GB step temp; 2 layers need 9.49 + 8.48 GB.
TRAIN_DEPTH = 1

# Logits through the cache vs the full forward, relative L2 error per
# logit row.  The two paths sum in different orders (whole-sequence vs
# one-token matmuls and attention), and at the served bf16 a one-ulp
# (2^-8) difference in the hidden state can flip a near-tied top-2 expert
# choice, which changes that row's logits by order 1 (the first chip run
# saw 0.16 over all rows).  So the exact check reruns both paths on the
# same weights in f32 at full matmul precision, where rounding is ~1e-7
# and no tie flips: every row within 1e-3.  A cache that lost positions
# or a wrong decode offset moves every row by order 1 (decoding at a
# wrong position moved the logits by 2.1 of their 3.6 range on the CPU).
LOGIT_TOL_F32 = 1e-3
# The served bf16 numerics: a flip moves single rows, a cache fault moves
# every row, so the median row is held to a few bf16 ulps.
LOGIT_TOL_BF16 = 3e-2
# moe_gemm vs the f32-accumulated einsum oracle: max error relative to the
# largest reference value.  Both round h and the outputs to bf16 (2^-9
# relative); accumulation order differs.
KERNEL_TOL = 2e-2
# Per-step loss across fabrics, absolute (losses are ~ln(32000) = 10.4).
# No fabric drops a token (dropless capacity, worst-case phase plan), so
# the fabrics compute the same sums in different orders; bf16 rounding of
# the expert outputs moves the mean loss by ~1e-3 at most.
LOSS_TOL = 5e-3


@dataclasses.dataclass(frozen=True)
class Sizes:
    serve_depth: int
    buckets: tuple
    max_len: int
    new_tokens: int
    kernel: tuple  # (E, C, d, f)
    train_depth: int
    train_batch: int
    train_seq: int


FULL = Sizes(
    serve_depth=SERVE_DEPTH,
    buckets=(128, 256, 512),
    max_len=576,
    new_tokens=32,
    kernel=(8, 1024, 4096, 14336),
    train_depth=TRAIN_DEPTH,
    train_batch=2,
    train_seq=1024,
)
TINY = Sizes(
    serve_depth=2,
    buckets=(16, 32, 64),
    max_len=128,
    new_tokens=32,
    kernel=(8, 256, 128, 256),
    train_depth=1,
    train_batch=2,
    train_seq=64,
)


def check(ok, message: str) -> None:
    """Fail the run (unlike ``assert``, never compiled away)."""
    if not ok:
        raise AssertionError(message)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {info['platform']})")
    if info["count"] < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} chips, "
            f"JAX found {info['count']}"
        )


def mixtral(depth: int, tiny: bool, **moe):
    from repro.configs import get_config, smoke_config

    cfg = smoke_config("mixtral-8x7b") if tiny else get_config("mixtral-8x7b")
    return dataclasses.replace(
        cfg,
        n_layers=depth,
        moe=dataclasses.replace(cfg.moe, **moe),
    )


def dropless(cfg):
    """Capacity for every routed choice: E / top_k times the mean load."""
    m = cfg.moe
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(m, capacity_factor=m.n_experts / m.top_k)
    )


def peak_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return (
        f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB peak of "
        f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB"
    )


# ------------------------------------------------------------------ serve
def make_requests(sizes: Sizes, vocab: int, seed: int):
    """16 requests, prompt lengths spread over every bucket, arriving two
    decode steps apart."""
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lo = (8,) + sizes.buckets[:-1]
    reqs = []
    for i in range(16):
        b = i % len(sizes.buckets)
        plen = int(rng.integers(lo[b] + 2, sizes.buckets[b] + 2))
        reqs.append(
            Request(
                prompt=rng.integers(0, vocab, plen),
                max_new_tokens=sizes.new_tokens,
                arrival=2 * i,
            )
        )
    return reqs


def serve_phase(sizes: Sizes, tiny: bool, seed: int):
    import jax

    from repro.serve import ServeEngine, init_serve_params

    cfg = mixtral(sizes.serve_depth, tiny, dispatch="phase_pipelined")
    params = init_serve_params(cfg, seed)
    engine = ServeEngine(
        cfg,
        params,
        decode_slots=8,
        max_len=sizes.max_len,
        buckets=sizes.buckets,
        seed=seed,
    )
    check(engine.has_controller, "serving controller did not attach")
    reqs = make_requests(sizes, cfg.vocab_size, seed)
    t0 = time.perf_counter()
    m = engine.run(reqs)
    wall = time.perf_counter() - t0
    r = m["serve"]["requests"]
    check(r["rejected"] == 0, f"rejected requests: {r}")
    check(r["completed"] == len(reqs), f"unfinished requests: {r}")
    check(
        all(len(q.tokens) == q.max_new_tokens for q in reqs),
        "a request stopped short of its token budget",
    )
    used = {engine.queue.bucket_of(q.prefill_len) for q in reqs}
    want = {
        "decode_executables": 1,
        "prefill_executables": len(used),
        "admit_executables": 1,
    }
    check(m["compile"] == want, f"executables {m['compile']} != {want}")
    ctrl = m["controller"]
    print(
        f"serve: {r['completed']}/{len(reqs)} requests done, "
        f"{m['serve']['generated_tokens']} tokens, wall {wall:.3f} s "
        f"(compilation included), {m['serve']['decode_steps']} decode steps; "
        f"executables {m['compile']}; controller device re-plans "
        f"{ctrl['device_replans']}, host re-plans {ctrl['host_replans']}; "
        f"cfg {cfg.name} depth {cfg.n_layers} d_model {cfg.d_model}; "
        f"device memory {peak_memory(jax.devices()[0])}",
        flush=True,
    )
    return cfg, params, reqs[len(reqs) // 2]


def _cached_vs_forward(model, params, prompt, seq, max_len: int, dtype):
    """Per-row relative L2 error of the logits through the cache (prefill
    of the prompt less its last token, then one decode step per token)
    against ``Model.forward`` over the whole sequence."""
    import jax
    import jax.numpy as jnp

    n_p = prompt.size
    want = jax.jit(model.forward)(params, jnp.asarray(seq[None]))[0, n_p - 2 :]
    caches = model.init_cache(1, max_len, dtype)
    first, caches = jax.jit(model.prefill)(
        params, jnp.asarray(prompt[None, :-1]), caches
    )
    decode = jax.jit(model.decode_step)
    got = [first[0]]
    for i, tok in enumerate(seq[n_p - 1 :]):
        logits, caches = decode(
            params, jnp.asarray([tok], jnp.int32), caches, jnp.int32(n_p - 1 + i)
        )
        got.append(logits[0])
    got = jnp.stack(got).astype(jnp.float32)
    want = want.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)
    return np.asarray(err)


def _upcast(params):
    """f32 copies of the served weights, one leaf at a time, each bf16
    leaf freed once its copy exists: both sets at once would not fit."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree.flatten(params)
    out = []
    for a in leaves:
        out.append(a.astype(jnp.float32).block_until_ready())
        a.delete()
    return jax.tree.unflatten(tree, out)


def reference_phase(cfg, params, req, max_len: int) -> None:
    """Consumes ``params``: the f32 check needs the chip's memory."""
    import jax
    import jax.numpy as jnp

    from repro.models import Model, layers

    # the same weights under a dropless capacity: a capacity-factor drop
    # depends on how many tokens share a call, which differs between one
    # forward pass and token-at-a-time decoding
    model = Model(dropless(cfg))
    prompt = req.prompt
    gen = np.asarray(req.tokens, np.int32)
    seq = np.concatenate([prompt, gen[:-1]]).astype(np.int32)
    served = _cached_vs_forward(model, params, prompt, seq, max_len, jnp.bfloat16)
    params = _upcast(params)
    bf16 = layers.COMPUTE_DTYPE
    layers.COMPUTE_DTYPE = jnp.float32  # read when the model is traced
    try:
        with jax.default_matmul_precision("highest"):
            exact = _cached_vs_forward(
                model, params, prompt, seq, max_len, jnp.float32
            )
    finally:
        layers.COMPUTE_DTYPE = bf16
    del params
    print(
        f"ref: request prompt {prompt.size} + {gen.size} generated tokens, "
        f"{exact.size} logit rows via prefill+decode vs Model.forward, "
        f"relative L2 error per row: f32 max {exact.max():.3e} (tolerance "
        f"{LOGIT_TOL_F32:.0e}); served bf16 median {np.median(served):.3e} "
        f"(tolerance {LOGIT_TOL_BF16:.0e}), max {served.max():.3e}, rows over "
        f"{LOGIT_TOL_BF16:.0e}: {int((served > LOGIT_TOL_BF16).sum())}",
        flush=True,
    )
    check(
        exact.max() <= LOGIT_TOL_F32,
        f"cached logits differ from forward in f32: {exact}",
    )
    check(
        np.median(served) <= LOGIT_TOL_BF16,
        f"served cached logits differ from forward: {served}",
    )


# ----------------------------------------------------------------- kernel
def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _count_kernels(compiled_text: str) -> int:
    return compiled_text.count('custom_call_target="tpu_custom_call"')


def kernel_phase(sizes: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.moe_gemm import (
        moe_gemm,
        moe_gemm_ref,
        select_backward_block_f,
        select_block_sizes,
    )

    e, c, d, f = sizes.kernel
    bf16 = jnp.bfloat16
    kx, kg, ku, kd, kc = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (e, c, d), bf16)
    wg = (jax.random.normal(kg, (e, d, f), jnp.float32) * d**-0.5).astype(bf16)
    wu = (jax.random.normal(ku, (e, d, f), jnp.float32) * d**-0.5).astype(bf16)
    wd = (jax.random.normal(kd, (e, f, d), jnp.float32) * f**-0.5).astype(bf16)
    # filled rows per expert: full, partial, empty, and fills that leave
    # whole row blocks dark (the grouped launch skips those)
    fill = np.array([c, c * 3 // 4 - 5, c // 2, 0, c // 4 + 3, c, 17, c // 2 + 1])
    row_valid = jnp.asarray(np.arange(c)[None, :] < fill[:e, None])
    cot = jax.random.normal(kc, (e, c, d), bf16) * row_valid[..., None]

    def kern(*w):
        return moe_gemm(*w, row_valid=row_valid)

    fwd = jax.jit(kern)
    bwd = jax.jit(lambda g, *w: jax.vjp(kern, *w)[1](g))
    args = (x, wg, wu, wd)
    blocks = select_block_sizes(c, d, f)
    bwd_f = select_backward_block_f(c, d, f, blocks[0])
    launches = "interpret mode"
    if jax.default_backend() == "tpu":
        n_fwd = _count_kernels(fwd.lower(*args).compile().as_text())
        n_bwd = _count_kernels(bwd.lower(cot, *args).compile().as_text())
        # forward: one launch; VJP: dgrad + wgrad (the forward's launch
        # may be kept or dropped by the compiler)
        check(
            n_fwd >= 1 and n_bwd >= 2,
            f"Mosaic kernel missing from the compiled programs "
            f"(forward {n_fwd}, VJP {n_bwd} tpu_custom_call)",
        )
        launches = f"compiled: {n_fwd} forward / {n_bwd} VJP tpu_custom_call"
    out = fwd(*args)
    grads = bwd(cot, *args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(moe_gemm_ref)(*args)
        ref_grads = jax.jit(lambda g, *w: jax.vjp(moe_gemm_ref, *w)[1](g))(
            cot, *args
        )
    mask = row_valid[..., None]
    errs = {"out": _rel_err(out * mask, ref * mask)}
    for name, g, rg in zip(("dx", "dw_gate", "dw_up", "dw_down"), grads, ref_grads):
        errs[name] = _rel_err(g, rg)
    print(
        f"kernel: moe_gemm E={e} C={c} d={d} f={f} blocks {blocks} "
        f"backward block_f {bwd_f} ({launches}); max error / max |ref|: "
        + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
        + f" (tolerance {KERNEL_TOL:.0e})",
        flush=True,
    )
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    check(not bad, f"moe_gemm differs from moe_gemm_ref: {bad}")


# ------------------------------------------------------------------ train
_COLLECTIVE = re.compile(
    r"\s(ragged-all-to-all|all-to-all|all-reduce|all-gather|reduce-scatter"
    r"|collective-permute)(?:-start)?\("
)


def train_phase(sizes: Sizes, tiny: bool, seed: int) -> None:
    import jax

    from repro.core import decompose, plan_schedule
    from repro.data import DataConfig, SyntheticStream
    from repro.launch.rules import train_rules
    from repro.launch.train import batch_sharder, build_mesh
    from repro.models import Model
    from repro.parallel import axis_rules
    from repro.parallel.fabric import (
        as_fabric_schedule,
        consumes_schedule,
        ragged_available,
    )
    from repro.train import TrainLoopConfig, train_loop

    mesh = build_mesh()
    n = mesh.shape["model"]
    check(dict(mesh.shape) == {"data": 1, "model": 4}, f"mesh {dict(mesh.shape)}")
    base = dropless(mixtral(sizes.train_depth, tiny))
    m = base.moe
    data_cfg = DataConfig(
        vocab_size=base.vocab_size,
        seq_len=sizes.train_seq,
        global_batch=sizes.train_batch,
        seed=seed,
    )
    loop_cfg = TrainLoopConfig(steps=3, ckpt_dir=None, log_every=1)
    shard_batch = batch_sharder(mesh)
    # worst-case plan: every pair may carry all of its source's tokens to
    # each of the destination's experts, so no fabric admits fewer tokens
    # than the dropless dense path computes
    e_local = m.n_experts // n
    t_rank = sizes.train_batch * sizes.train_seq // n
    worst = np.full((n, n), float(e_local * t_rank))
    np.fill_diagonal(worst, 0.0)
    plan = plan_schedule(decompose(worst, "maxweight"), quantum=8)
    n_moe = Model(base).n_moe_layers
    losses = {}
    for dispatch in ("dense", "phase_pipelined", "ragged_a2a"):
        cfg = dataclasses.replace(base, moe=dataclasses.replace(m, dispatch=dispatch))
        schedule = (
            as_fabric_schedule(dispatch, plan, n_moe)
            if consumes_schedule(dispatch)
            else None
        )
        t0 = time.perf_counter()
        with axis_rules(mesh, train_rules()):
            res = train_loop(
                Model(cfg, schedule), data_cfg, loop_cfg, shard_batch=shard_batch
            )
            wall = time.perf_counter() - t0
            state = res["state"]
            batch = shard_batch(SyntheticStream(data_cfg).batch(0))
            text = (
                res["step_fn"]
                .lower(state["params"], state["opt"], state["ef"], batch)
                .compile()
                .as_text()
            )
        check(res["failures"] == 0, f"{dispatch}: {res['failures']} failed steps")
        check(res["final_step"] == loop_cfg.steps, f"stopped at {res['final_step']}")
        losses[dispatch] = [h["loss"] for h in res["history"]]
        check(np.all(np.isfinite(losses[dispatch])), f"losses {losses[dispatch]}")
        ops = {}
        for op in _COLLECTIVE.findall(text):
            ops[op] = ops.get(op, 0) + 1
        # off the TPU ragged_a2a runs its documented all-to-all emulation
        need = {
            "phase_pipelined": "all-to-all",
            "ragged_a2a": "ragged-all-to-all" if ragged_available() else "all-to-all",
        }
        if dispatch in need:
            check(
                ops.get(need[dispatch], 0) > 0,
                f"{dispatch}: compiled step has no {need[dispatch]} "
                f"(collectives {ops}); the dense fallback was taken",
            )
        w = state["params"]["stack"]["pos0"]["ffn"]["w_gate"]
        shards = w.addressable_shards
        placed = sorted(s.device.id for s in shards)
        check(
            placed == sorted(d.id for d in mesh.devices.flat)
            and all(s.data.shape[1] == m.n_experts // n for s in shards),
            f"w_gate shards {[(s.device.id, s.data.shape) for s in shards]}",
        )
        print(
            f"train {dispatch}: losses {losses[dispatch]}, failures "
            f"{res['failures']}, wall {wall:.3f} s (compilation included); "
            f"compiled collectives {ops}; w_gate shards {shards[0].data.shape} "
            f"on devices {placed}",
            flush=True,
        )
        del res, state, text
    ref = np.asarray(losses["dense"])
    diff = {
        k: float(np.max(np.abs(np.asarray(v) - ref)))
        for k, v in losses.items()
        if k != "dense"
    }
    print(
        f"train: {base.name} depth {base.n_layers} d_model {base.d_model}, "
        f"batch {sizes.train_batch}x{sizes.train_seq}; max |loss - dense| "
        f"{diff} (tolerance {LOSS_TOL:.0e}); device memory "
        + "; ".join(peak_memory(d) for d in mesh.devices.flat),
        flush=True,
    )
    bad = {k: v for k, v in diff.items() if not v <= LOSS_TOL}
    check(not bad, f"fabric losses differ from dense: {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--tiny",
        action="store_true",
        help="shrink the widths and check the device last (CPU rehearsal)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not args.tiny:
        require_tpu(device_info(), args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    sizes = TINY if args.tiny else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        train_phase(sizes, args.tiny, args.seed)
    else:
        cfg, params, req = serve_phase(sizes, args.tiny, args.seed)
        reference_phase(cfg, params, req, sizes.max_len)
        del params
        gc.collect()  # the kernel phase needs the chip's memory
        kernel_phase(sizes, args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s", flush=True)
    info = device_info()
    require_tpu(info, args.chips)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
