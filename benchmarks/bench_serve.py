"""Serving benchmark: continuous batching vs the fixed-round baseline
under offered load (``repro.serve.ServeEngine``).

At each offered-load point (arrival rate in requests per decode step) the
same request trace is served twice:

* **continuous** — finished sequences vacate their slot and the next
  queued request backfills mid-flight (the engine's default);
* **fixed_round** — admission only when the batch has fully drained
  (``run(..., continuous=False)``): the pre-engine round-based demo
  behavior, kept as the baseline.

Reported per mode: request-throughput percentiles (p50/p99 tok/s, wall
clock), queue-wait percentiles (virtual decode-step units — deterministic
under any host speed), and ``tokens_per_step`` (generated tokens per
decode step — the deterministic utilization figure the batching gain is
asserted on).  Continuous batching must beat the round barrier at every
load point (``_MIN_GAIN``); CI re-asserts the gate from the written
history so a regression fails even if someone edits the gate here.

Writes ``BENCH_serve.json`` next to ``BENCH_scheduler.json``: latest run
at the top level, append-only ``history`` validated against
``benchmarks.bench_schema`` (v6) before anything touches the file.

Usage: PYTHONPATH=src python -m benchmarks.bench_serve
"""

from __future__ import annotations

import json
import os
import subprocess
import time

import numpy as np

OUT_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json",
)

# requests per decode step at each measured point: well under capacity
# (queues stay short) and past saturation (the backfill win is largest)
LOADS = (0.25, 1.0)
N_REQUESTS = 48
DECODE_SLOTS = 8
MAX_NEW = (6, 12)  # ragged budgets: rounds drain at the slowest request
_MIN_GAIN = 1.05  # continuous tokens/step must beat fixed-round by 5%


def _git_sha() -> str | None:
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip()
            or None
        )
    except (OSError, subprocess.SubprocessError):
        return None


def _serve_cfg():
    from repro.configs.base import ModelConfig, MoECfg

    return ModelConfig(
        name="bench-serve", family="moe", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
        moe=MoECfg(
            n_experts=8, top_k=2, d_ff_expert=32, dispatch="scheduled"
        ),
        remat="none",
    )


def _trace(rng, load: float):
    """One request trace at ``load`` req/step: ragged prompts and decode
    budgets, Poisson-ish arrivals in virtual decode-step units."""
    from repro.serve import Request

    gaps = rng.exponential(1.0 / load, N_REQUESTS)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    return [
        Request(
            prompt=rng.integers(0, 128, int(rng.integers(3, 8))),
            max_new_tokens=int(rng.integers(MAX_NEW[0], MAX_NEW[1] + 1)),
            arrival=float(a),
        )
        for a in arrivals
    ]


def _serve_one(load: float, continuous: bool) -> dict:
    from repro.serve import ServeEngine

    eng = ServeEngine(
        _serve_cfg(), decode_slots=DECODE_SLOTS, max_len=32, buckets=(8,),
        n_ranks=4, host_observe_every=32, seed=0,
    )
    out = eng.run(
        _trace(np.random.default_rng(7), load), continuous=continuous
    )
    s = out["serve"]
    assert s["requests"]["completed"] == N_REQUESTS, s["requests"]
    assert out["compile"]["decode_executables"] == 1, out["compile"]
    return {
        "p50_tok_s": round(s["request_tok_s"]["p50"], 1),
        "p99_tok_s": round(s["request_tok_s"]["p99"], 1),
        "queue_wait_p50_steps": round(s["queue_wait_steps"]["p50"], 1),
        "queue_wait_p99_steps": round(s["queue_wait_steps"]["p99"], 1),
        "tokens_per_step": round(
            s["generated_tokens"] / max(s["decode_steps"], 1), 3
        ),
        "decode_steps": s["decode_steps"],
        "occupancy": round(s["occupancy"], 3),
        "completed": s["requests"]["completed"],
    }


def bench_serve() -> dict:
    points = []
    for load in LOADS:
        cont = _serve_one(load, continuous=True)
        fixed = _serve_one(load, continuous=False)
        gain = round(
            cont["tokens_per_step"] / max(fixed["tokens_per_step"], 1e-9), 3
        )
        if gain < _MIN_GAIN:
            raise RuntimeError(
                f"continuous batching gain {gain} < {_MIN_GAIN} at load "
                f"{load} req/step: the backfill path lost its payoff"
            )
        points.append(
            {
                "offered_load_req_per_step": load,
                "continuous": cont,
                "fixed_round": fixed,
                "batching_gain_tokens_per_step": gain,
            }
        )
    return {
        "decode_slots": DECODE_SLOTS,
        "n_requests": N_REQUESTS,
        "load_points": points,
    }


def run() -> dict:
    from benchmarks.bench_schema import (
        SCHEMA_VERSION,
        validate_serve_document,
        validate_serve_entry,
    )

    serving = bench_serve()
    meta = {
        "unit_note": "tok/s percentiles are wall clock; queue waits and "
        "tokens_per_step are virtual decode-step units (deterministic)",
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "git_sha": _git_sha(),
    }
    prior = []
    if os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH) as f:
                prior = json.load(f).get("history", [])
        except (json.JSONDecodeError, OSError):
            prior = []
    entry = {
        "timestamp": meta["timestamp"],
        "schema_version": SCHEMA_VERSION,
        "git_sha": meta["git_sha"],
        "serving": serving,
    }
    # schema-gate the append BEFORE touching the file (same contract as
    # bench_scheduler): malformed entries fail the bench, not the file
    errors = validate_serve_entry(entry, "new entry", require_current=True)
    history = prior + [entry]
    errors += validate_serve_document({"history": history})
    if errors:
        raise RuntimeError(
            "refusing to append malformed serve-bench history:\n  "
            + "\n  ".join(errors)
        )
    results = {"serving": serving, "meta": meta, "history": history}
    with open(OUT_PATH, "w") as f:
        json.dump(results, f, indent=2)
    for p in serving["load_points"]:
        c, fx = p["continuous"], p["fixed_round"]
        print(
            f"load {p['offered_load_req_per_step']} req/step: continuous "
            f"{c['tokens_per_step']} tok/step (p50 {c['p50_tok_s']} tok/s, "
            f"queue p99 {c['queue_wait_p99_steps']} steps) vs fixed-round "
            f"{fx['tokens_per_step']} tok/step (queue p99 "
            f"{fx['queue_wait_p99_steps']} steps) -> gain "
            f"{p['batching_gain_tokens_per_step']}x"
        )
    print(f"wrote {os.path.abspath(OUT_PATH)} ({len(history)} history entries)")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
