"""Time one MoE layer's expert GEMMs on one TPU chip.

    python3 benchmarks/expert_gemm_sweep.py          # on the chip
    JAX_PLATFORMS=cpu python3 benchmarks/expert_gemm_sweep.py --tiny

Two parts, at Mixtral-8x7B widths (d 4096, f 14336, 8 experts, top-2):

* ``kernels``: the SwiGLU's three GEMMs over one 2048-token prefill's
  4096 routed rows, with per-expert loads of max/mean 1.53 (the serving
  cell's skew), by each implementation: the padded einsum over
  ``[E, cap = 2048, d]`` (every slot multiplied), the sorted rows through
  ``jax.lax.ragged_dot`` and through megablox ``gmm`` at several tilings
  (one layer's weights, and "in_stack": a two-layer stack's, read in
  place as the prefill does), and the padded buffer through
  ``kernels/moe_gemm`` skipping dark row blocks.
* ``sweep``: rows per expert 32 to 2048 (``t`` tokens at capacity factor
  E/k, so ``cap = t``): the whole expert layer (router, pack, GEMMs,
  combine) on the padded pipeline and on the model's sorted one
  (``moe.GMM_TILING``).  Where the sorted one starts to win fixes
  ``moe.ROWS_COMPUTE_BOUND``.

One JSON line per measurement: median device milliseconds of ``--reps``
calls, each ended by ``block_until_ready``, and the TFLOP/s of the
routed rows.  Without a TPU it runs only with ``--tiny`` (interpret-mode
kernels, small widths) and its times mean nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time


def _time(fn, *args, reps: int) -> float:
    """Median milliseconds of ``reps`` calls, after a compiling call."""
    import jax

    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts)


def _loads(rows: int, n_experts: int) -> list[int]:
    """Per-expert rows summing to ``rows``, max/mean 1.53, one expert
    light: the serving cell's measured skew."""
    share = [1.53, 1.25, 1.09, 1.0, 0.94, 0.86, 0.74, 0.59][:n_experts]
    raw = [s * rows / sum(share) for s in share]
    out = [int(r) for r in raw]
    out[-1] += rows - sum(out)
    return out


def _stack_weights(e, d, f, dtype):
    """Two layers' expert weights, ``[2, E, ...]`` each, as a stack holds
    them; the rows under test are layer 1's."""
    import jax

    key = jax.random.split(jax.random.PRNGKey(0), 3)
    wg, wu = (jax.random.normal(kk, (2, e, d, f), dtype) * d**-0.5 for kk in key[:2])
    wd = jax.random.normal(key[2], (2, e, f, d), dtype) * f**-0.5
    return wg, wu, wd


def kernels(d, f, e, t, k, reps, emit) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.kernels.moe_gemm import moe_gemm
    from repro.models import moe

    bf = jnp.bfloat16
    stack = _stack_weights(e, d, f, bf)
    wg, wu, wd = (w[1] for w in stack)  # one layer's, on their own
    rows = t * k
    loads = _loads(rows, e)
    sizes = jnp.asarray(loads, jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(3), (rows, d), bf)
    # the same rows in the padded buckets: expert i's first loads[i] slots
    cap = t
    buf = jnp.zeros((e, cap, d), bf)
    live = jnp.zeros((e, cap), bool)
    start = 0
    for i, n in enumerate(loads):
        buf = buf.at[i, :n].set(xs[start:start + n])
        live = live.at[i, :n].set(True)
        start += n
    flop = 6 * rows * d * f
    base = {"part": "kernels", "rows": rows, "cap": cap, "loads": loads}
    interpret = jax.default_backend() != "tpu"

    def run(name, fn, *args, **extra):
        try:
            ms = _time(jax.jit(fn), *args, reps=reps)
        except Exception as exc:  # a tiling the chip's compiler refuses
            emit({**base, "impl": name, **extra, "error": f"{type(exc).__name__}: {exc}"[:300]})
            return
        emit({**base, "impl": name, **extra, "ms": ms, "routed_tflops": flop / ms * 1e-9})

    def swiglu(matmul, x, w3):
        g, u = matmul(x, w3[0]), matmul(x, w3[1])
        return matmul(jax.nn.silu(g.astype(jnp.float32)).astype(bf) * u, w3[2])

    def ragged_ffn(x, s, w3):
        return swiglu(lambda a, w: jax.lax.ragged_dot(a, w, s), x, w3)

    def gmm_ffn(x, s, w3, tl):
        def mm(a, w):  # the down projection swaps the contraction and output tiles
            t = tl if w.shape[1] == d else (tl[0], tl[2], tl[1])
            return gmm(a, w, s, bf, t, interpret=interpret)

        return swiglu(mm, x, w3)

    def flat(w3):  # [2, E, ...] -> [2E, ...]: a bitcast inside the jit
        return tuple(w.reshape(2 * e, *w.shape[2:]) for w in w3)

    # every array is an argument: a closed-over one could become a constant
    one = (wg, wu, wd)
    # "in stack": a two-layer stack's weights read in place, layer 1's
    # groups after layer 0's empty ones, as the model's prefill runs them
    in_stack = jnp.concatenate([jnp.zeros_like(sizes), sizes])
    run("padded_einsum", lambda b, w: moe._expert_ffn(None, b, e_slice=w), buf, one)
    run("ragged_dot", ragged_ffn, xs, sizes, one)
    run("ragged_dot_in_stack", lambda x, s, w: ragged_ffn(x, s, flat(w)), xs, in_stack, stack)
    tilings = [(128, 128, 128)] if interpret else [
        (512, 1024, 1024), (256, 1024, 1024), (128, 1024, 1024), (512, 512, 1024),
        (256, 512, 512), (128, 128, 128),
    ]
    for tl in tilings:
        tl = tuple(min(a, b) for a, b in zip(tl, (rows, d, f)))
        run(
            "megablox_gmm", lambda x, s, w, tl=tl: gmm_ffn(x, s, w, tl), xs, sizes, one,
            tiling=list(tl),
        )
        run(
            "megablox_gmm_in_stack", lambda x, s, w, tl=tl: gmm_ffn(x, s, flat(w), tl),
            xs, in_stack, stack, tiling=list(tl),
        )
    run(
        "moe_gemm_block_skip",
        lambda b, v, w: moe_gemm(b, *w, row_valid=v), buf, live, one,
    )


def sweep(cfg, sizes, reps, emit) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import moe
    from repro.parallel.fabric import get_fabric
    from repro.parallel.fabric.base import FabricContext

    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    bf = jnp.bfloat16
    stack = _stack_weights(e, d, f, bf)
    one = tuple(w[1] for w in stack)
    wr = (jax.random.normal(jax.random.PRNGKey(1), (d, e), jnp.float32) * 0.02).astype(bf)
    dense = get_fabric("dense")
    for t in sizes:
        x = jax.random.normal(jax.random.PRNGKey(t), (t, d), bf)
        ctx = FabricContext(
            cfg=cfg, n=1, e_local=e, axis=None, me=None, schedule=None, two_d=False, t_local=t
        )
        cap = moe._geom.bucket_capacity(t, m)
        flop = 6 * t * m.top_k * d * f

        def padded(x, r, w):
            return moe._pipeline_body(dense, ctx, x, r, *w, return_stats=False, ep=False)

        def sorted_(x, r, w, layer):  # as the prefill runs it: in place in the stack
            return moe._sorted_body(ctx, x, r, *w, return_stats=False, layer=layer)

        for path, fn, args in (
            ("padded", padded, (x, wr, one)), ("sorted", sorted_, (x, wr, stack, jnp.int32(1)))
        ):
            ms = _time(jax.jit(fn), *args, reps=reps)
            emit({"part": "sweep", "path": path, "t": t, "rows_per_expert": cap, "ms": ms,
                  "routed_tflops": flop / ms * 1e-9})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true", help="small widths, for a CPU rehearsal")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--part", choices=("kernels", "sweep", "both"), default="both")
    ap.add_argument("--sizes", type=int, nargs="*", default=[32, 64, 128, 256, 512, 1024, 2048],
                    help="rows per expert of the sweep")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config

    info = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}
    if info["platform"] != "tpu" and not args.tiny:
        print(f"no TPU ({info['platform']}): nothing timed; --tiny rehearses", file=sys.stderr)
        return 1

    def emit(rec):
        print(json.dumps({**rec, "device": info}), flush=True)

    cfg = get_config("mixtral-8x7b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    if args.tiny:
        cfg = dataclasses.replace(
            cfg, d_model=128, moe=dataclasses.replace(cfg.moe, d_ff_expert=256)
        )
        kernels(128, 256, 8, 128, 2, 2, emit)
        sweep(cfg, (32, 64), 2, emit)
        return 0
    if args.part != "sweep":
        kernels(cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.n_experts, 2048, cfg.moe.top_k,
                args.reps, emit)
    if args.part != "kernels":
        sweep(cfg, tuple(args.sizes), args.reps, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
