"""Model FLOPs of the window's decode steps (``flops.decode_flops``) over
the decode program's device time in the trace, over the chip's peak."""

import flops as F
import trace_reduce as T


def read(run):
    if run.trace is None or run.peak is None:
        return None
    lo, hi = T.window(run.trace)
    steps = run.window_steps()
    t = sum(T.length(T.module_runs(run.trace, d, "jit__decode", lo, hi)) for d in T.devices(run.trace))
    if not steps or t <= 0:
        return None
    work = sum(F.decode_flops(run.dm, ctx) for _, _, ctx, _, _ in steps)
    return 100.0 * work / (t * 1e-9) / run.peak["bf16_flop_s"]
