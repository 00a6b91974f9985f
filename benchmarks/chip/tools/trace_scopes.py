"""One traced run of a serving cell, its decode step split by the
program's named scopes, and its host gaps split by the engine's spans.

    python3 benchmarks/chip/tools/trace_scopes.py --workload mixtral.serve.steady \\
        --seed 7 --seconds 45 [--excerpt reports/decode_excerpt.json]

Runs the cell as ``run.py --trace 1`` does, and reads, besides the
cell's declared per-layer metrics, every reader under ``metrics/`` that
``--extra`` names (a reader that finds nothing reads null here).  It
prints one JSON line: the metrics, the decode program's device time per
step by scope (``decode_scopes.by_scope``, with the table's coverage),
and the idle time between plain decode steps (no program and no
admission between them) by the innermost host span it fell in.
``--excerpt`` writes four consecutive decode steps of the trace, an
admission among them, with the decode program's table for their ops: the
data of the reader tests.  The run's garbage collections are annotated
too (``bench.gc``), and the longest idle gaps under no host span are
listed with the spans that end before and start after each.
"""

from __future__ import annotations

import argparse
import bisect
import gc
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import decode_scopes as S  # noqa: E402
import harness as H  # noqa: E402
import trace_reduce as T  # noqa: E402

EXTRA = ["decode_expert_ffn_ms.serve", "decode_attention_ms.serve", "decode_scan_copy_ms.serve",
         "decode_host_gap_ms.serve"]


def gaps_by_span(trace) -> dict:
    """Idle ns between plain consecutive decode runs (device 0), by the
    innermost host span covering each idle moment, per gap."""
    lo, hi = T.window(trace)
    dev = T.devices(trace)[0]
    runs = sorted(T.module_runs(trace, dev, S.DECODE, lo, hi))
    others = [(s, s + d) for n, s, d in trace["modules"].get(dev, [])
              if not n.split("(")[0].startswith(S.DECODE)]
    others += [(s, s + d) for n, s, d in trace["spans"] if n == "serve.engine.admit"]
    busy = T.busy(trace, dev, lo, hi)
    spans = [sp for sp in trace["spans"] if sp[0] != "bench.window"]
    starts = [s for _, s, _ in spans]
    out: dict[str, float] = {}
    n = 0
    for (_, a), (b, _) in zip(runs, runs[1:]):
        if any(s < b and e > a for s, e in others):
            continue
        n += 1
        idle = T.subtract([(a, b)], T.clip(busy, a, b))
        cuts = sorted({a, b, *(x for _, s, d in spans[max(bisect.bisect_left(starts, a) - 64, 0):
                                                   bisect.bisect_left(starts, b)]
                              for x in (s, s + d) if a < x < b)})
        for x, y in zip(cuts, cuts[1:]):
            held = T.length(T.clip(idle, x, y))
            if held > 0:
                name = _innermost(spans, starts, (x + y) / 2)
                out[name] = out.get(name, 0.0) + held
    return {k: v / max(n, 1) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def _innermost(spans, starts, t) -> str:
    for i in range(bisect.bisect_right(starts, t) - 1, max(bisect.bisect_right(starts, t) - 65, -1), -1):
        name, s, d = spans[i]
        if s <= t <= s + d:
            return name
    return "no host span"


def unnamed_gaps(trace, top: int = 10) -> list:
    """The longest idle gaps of device 0 (of 0.1 ms or more) that no host
    span covers, each with the span that ended last before it and the
    next to start."""
    lo, hi = T.window(trace)
    dev = T.devices(trace)[0]
    gaps = T.subtract([(lo, hi)], T.busy(trace, dev, lo, hi))
    spans = [sp for sp in trace["spans"] if sp[0] != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1]):
        if b - a < 1e5:  # 0.1 ms
            break
        if T.span_at(trace, (a + b) / 2) != "no host span":
            continue
        before = max((sp for sp in spans if sp[1] + sp[2] <= a), key=lambda sp: sp[1] + sp[2], default=None)
        after = min((sp for sp in spans if sp[1] >= a), key=lambda sp: sp[1], default=None)
        out.append({
            "ms": (b - a) * 1e-6,
            "after_span": before and [before[0], (a - before[1] - before[2]) * 1e-6],
            "before_span": after and [after[0], (after[1] - a) * 1e-6],
        })
        if len(out) == top:
            break
    return out


def annotate_gc() -> None:
    """A ``bench.gc`` span around every garbage collection."""
    import jax

    open_spans = []

    def note(phase, info):
        if phase == "start":
            open_spans.append(jax.profiler.TraceAnnotation(f"bench.gc.gen{info['generation']}"))
            open_spans[-1].__enter__()
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(note)


def excerpt(trace, table) -> dict | None:
    """Four consecutive decode runs of device 0 with an admission
    between the second and the third, their ops, the programs and spans
    in between, and the table for their ops."""
    lo, hi = T.window(trace)
    dev = T.devices(trace)[0]
    runs = sorted(T.module_runs(trace, dev, S.DECODE, lo, hi))
    admits = [s for n, s, _ in trace["spans"] if n == "serve.engine.admit"]
    mid = len(runs) // 2
    for i in list(range(mid, len(runs) - 2)) + list(range(1, mid)):
        if any(runs[i][1] < t < runs[i + 1][0] for t in admits):
            a, b = runs[i - 1][0] - 1000, runs[i + 2][1] + 1000
            break
    else:
        return None

    def inside(s, d):
        return s >= a and s + d <= b

    ops = [r for r in trace["ops"][dev] if inside(r[1], r[2])]
    return {
        "ops": {"0": ops},
        "modules": {"0": [r for r in trace["modules"][dev] if inside(r[1], r[2])]},
        "spans": [["bench.window", a, b - a]] + [r for r in trace["spans"] if inside(r[1], r[2])],
        "table": {r[0]: table[r[0]] for r in ops if r[0] in table},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mixtral.serve.steady")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--extra", nargs="*", default=EXTRA)
    ap.add_argument("--excerpt", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    args.trace, args.rate, args.control = 1, None, False
    cell = H.cell(args.workload)
    H.program_path()
    if not args.tiny:
        H.require_chips(H.device_info(), cell["workload"]["chips"])
    H.enable_compile_cache()
    from runners import serve as R

    seen = {}

    def read_all(specs, run):
        names = [m["name"] for m in specs] + [n for n in args.extra if n not in {m["name"] for m in specs}]
        t0 = time.perf_counter()
        table = S.program_table()
        seen["table_s"] = time.perf_counter() - t0
        seen["metrics"] = {n: H.load_module("metrics", n + ".py").read(run) for n in names}
        if run.trace is not None and table is not None:
            split = S.by_scope(run.trace, {**table})
            lo, hi = T.window(run.trace)
            seen["decode_runs"] = len(T.module_runs(run.trace, T.devices(run.trace)[0], S.DECODE, lo, hi)) \
                if T.devices(run.trace) else 0
            seen["by_scope_ms"] = None if split is None else {str(k): v * 1e-6 for k, v in split.items()}
            if T.devices(run.trace):
                seen["host_gap_by_span_ms"] = {k: v * 1e-6 for k, v in gaps_by_span(run.trace).items()}
                seen["unnamed_gaps"] = unnamed_gaps(run.trace)
                if args.excerpt:
                    ex = excerpt(run.trace, table)
                    if ex is not None:
                        os.makedirs(os.path.dirname(os.path.abspath(args.excerpt)), exist_ok=True)
                        with open(args.excerpt, "w") as f:
                            json.dump(ex, f)
        return {n: {"value": v, "unit": ""} for n, v in seen["metrics"].items() if v is not None}

    H.read_metrics = read_all
    annotate_gc()
    result, checks = R.run(args, cell, T_PROCESS)
    seen["correct"] = all(v <= lim for _, v, lim in checks)
    seen["breakdown"] = result.get("breakdown")
    seen["device"] = result.get("device")
    print(json.dumps(seen), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
