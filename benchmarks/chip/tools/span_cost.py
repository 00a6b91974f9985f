"""What the serving engine's spans cost the host per decode step, with
the profiler off and on.

    python3 benchmarks/chip/tools/span_cost.py [--steps 20000]

Times the spans one plain decode step opens (``serve.engine.decode.
inputs``, ``.launch``, ``.fetch`` with their ``step``, and
``serve.engine.advance``), with nothing inside them, first with no
profiler running and then under ``jax.profiler.start_trace``; and one
admission's span with its three fields.  Prints microseconds each.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import jax
from jax.profiler import TraceAnnotation


def decode_step_spans(n: int) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        with TraceAnnotation("serve.engine.decode.inputs", step=i):
            pass
        with TraceAnnotation("serve.engine.decode.launch", step=i):
            pass
        with TraceAnnotation("serve.engine.decode.fetch", step=i):
            pass
        with TraceAnnotation("serve.engine.advance"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def admit_spans(n: int) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        with TraceAnnotation("serve.engine.admit") as span:
            span.set_metadata(rid=i, bucket=1024, slot=i % 32)
    return (time.perf_counter() - t0) / n * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=20000)
    args = ap.parse_args()
    decode_step_spans(1000), admit_spans(1000)  # warm
    off = {"decode_step_us": decode_step_spans(args.steps), "admit_us": admit_spans(args.steps)}
    jax.profiler.start_trace(tempfile.mkdtemp(prefix="span_cost_"))
    try:
        on = {"decode_step_us": decode_step_spans(args.steps), "admit_us": admit_spans(args.steps)}
    finally:
        jax.profiler.stop_trace()
    print(json.dumps({"profiler_off": off, "profiler_on": on, "steps": args.steps,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
