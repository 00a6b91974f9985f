"""Find the serving cell's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/tools/knee_sweep.py --workload mixtral.serve.steady \\
        --rates 3 4 5 6 7 --seconds 20 --seed 1

One process, one engine; for each rate an open-loop stream of the cell's
traffic at that rate, a ramp, then a window.  Per rate it prints the
95th-percentile time to first token from due, the tokens/s completed,
and the queue left when the window closes: past the knee the queue
grows all through the window and the time to first token with it.
The rows are also written to reports/knee_sweep.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mixtral.serve.steady")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    args.trace, args.rate = 0, None
    cell = H.cell(args.workload)
    H.program_path()
    if not args.tiny:
        H.require_chips(H.device_info(), cell["workload"]["chips"])
    H.enable_compile_cache()
    from runners import serve as S
    from traffic.open_loop import OpenLoop

    su = S.setup(args, cell)
    rows = []
    for i, rate in enumerate(args.rates):
        gen = OpenLoop(su.traffic, su.cfg.vocab_size, rate=rate)
        ramp = float(su.traffic["ramp_s"])
        start = time.perf_counter()
        w0, w1 = start + ramp, start + ramp + args.seconds
        reqs = S._requests(gen.stream(args.seed + i, ramp, args.seconds), start)
        loop = su.loop
        loop.tokens.clear()
        queue_at_close = []
        loop.serve(reqs, until=w1, stop=lambda: True,
                   hooks=[(w1, lambda: queue_at_close.append(len(su.engine.queue)))])
        loop.serve([])  # finish what is in flight before the next rate
        due = [r for r in reqs if w0 <= r.arrival < w1]
        ttft = [(loop.tokens[r.rid][0] - r.arrival) * 1e3 for r in due if loop.tokens.get(r.rid)]
        toks = sum(1 for t in loop.tokens.values() for x in t if w0 <= x < w1)
        row = {
            "rate": rate,
            "due": len(due),
            "ttft_p50_ms": H.percentile(ttft, 50),
            "ttft_p95_ms": H.percentile(ttft, 95),
            "tok_s": toks / args.seconds,
            "queue_at_close": queue_at_close[0] if queue_at_close else None,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(H.ROOT, "reports")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "knee_sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
