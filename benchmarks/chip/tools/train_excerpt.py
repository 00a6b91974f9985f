"""One traced run of the training cell, as ``run.py --trace 1`` makes it,
that also writes an excerpt of its trace: the data of the reader tests.

    python3 benchmarks/chip/tools/train_excerpt.py --seed 7 --seconds 10 \\
        --excerpt reports/train_excerpt.json

The excerpt holds, for every device, the second step program run of the
window with every op and program that starts inside it, the host spans
around it, a ``bench.window`` span that covers it, and the step
program's table (opcode and scope) for the ops it holds.  Beside it,
the numbers each training reader gives on the excerpt, counted by plain
loops here, for the tests to hold the readers to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402

WORKLOAD = "mixtral.train.ep4.skewed"
PROGRAM = "jit_train_step"


def excerpt(rec) -> dict:
    tr = rec.trace
    lo, hi = next((s, s + d) for n, s, d in tr["spans"] if n == "bench.window")
    ops, modules, a_all, b_all = {}, {}, [], []
    for dev, rows in tr["modules"].items():
        runs = sorted((s, s + d) for n, s, d in rows
                      if n.split("(")[0].startswith(PROGRAM) and s >= lo and s + d <= hi)
        a, b = runs[1]
        a_all.append(a), b_all.append(b)
        modules[dev] = [r for r in rows if a <= r[1] < b]
        ops[dev] = [[n, s, d, ""] for n, s, d, _ in tr["ops"].get(dev, []) if a <= s < b]
    a, b = min(a_all), max(b_all)
    spans = [sp for sp in tr["spans"] if sp[1] < b and sp[1] + sp[2] > a and sp[0] != "bench.window"]
    names = {r[0] for rows in ops.values() for r in rows}
    table = {k: {n: v for n, v in rec.table[k].items() if n in names} for k in ("kind", "scope")}
    return {"ops": ops, "modules": modules,
            "spans": [["bench.window", a - 1000, b - a + 2000]] + spans, "table": table,
            "flops_per_step": rec.flops_per_step, "phases": rec.phases, "chips": rec.chips,
            "peak": rec.peak}


def hand_count(ex: dict) -> dict:
    """The readers' numbers by plain loops over the excerpt (one step per
    device)."""
    kinds, scopes = ex["table"]["kind"], ex["table"]["scope"]

    def merged(iv):
        out = []
        for s, e in sorted(iv):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def total(iv):
        return sum(e - s for s, e in merged(iv))

    ffn, a2a, exposed, step = [], [], [], []
    for dev, rows in ex["ops"].items():
        (m,) = [r for r in ex["modules"][dev] if r[0].split("(")[0].startswith(PROGRAM)]
        end = m[1] + m[2]
        iv = [(n, s, min(s + d, end)) for n, s, d, _ in rows]
        ffn.append(total([(s, e) for n, s, e in iv if scopes.get(n) == "moe/expert_ffn"]))
        x = [(s, e) for n, s, e in iv if kinds.get(n, "").split("-start")[0].split("-done")[0]
             in ("all-to-all", "ragged-all-to-all", "collective-permute")
             and scopes.get(n) in ("moe/dispatch", "moe/combine")]
        c = [(s, e) for n, s, e in iv if not any(
            kinds.get(n, "").startswith(k) for k in
            ("all-to-all", "ragged-all-to-all", "collective-permute", "all-reduce", "all-gather",
             "reduce-scatter", "collective-broadcast"))]
        a2a.append(total(x))
        # exposed: the exchange's time less its overlap with compute
        overlap = sum(max(0, min(e, ce) - max(s, cs)) for s, e in merged(x) for cs, ce in merged(c))
        exposed.append(total(x) - overlap)
        step.append(m[2])
    n = len(step)
    return {
        "expert_ffn_ms.train": 1e-6 * sum(ffn) / n,
        "a2a_ms.train": 1e-6 * sum(a2a) / n,
        "a2a_exposed_ms.train": 1e-6 * sum(exposed) / n,
        "step_mfu.train": 100.0 * ex["flops_per_step"] / (sum(step) / n * 1e-9 * ex["chips"]
                                                         * ex["peak"]["bf16_flop_s"]),
        "matchings.train": ex["phases"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", required=True)
    args = ap.parse_args()
    cell = H.cell(WORKLOAD)
    H.program_path()
    H.require_chips(H.device_info(), cell["workload"]["chips"])
    print(f"compile cache: {H.enable_compile_cache()}", file=sys.stderr, flush=True)
    from runners import train as TR

    read = H.read_metrics

    def keep(specs, rec):
        if rec.trace is not None:
            ex = excerpt(rec)
            ex["hand_count"] = hand_count(ex)
            os.makedirs(os.path.dirname(os.path.abspath(args.excerpt)), exist_ok=True)
            with open(args.excerpt, "w") as f:
                json.dump(ex, f)
            print(f"excerpt: {args.excerpt}; hand count {ex['hand_count']}", file=sys.stderr)
        return read(specs, rec)

    H.read_metrics = keep
    run_args = argparse.Namespace(workload=WORKLOAD, seed=args.seed, seconds=args.seconds,
                                  trace=1, tiny=False, control=False)
    result, checks = TR.run(run_args, cell, T_PROCESS)
    H.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
