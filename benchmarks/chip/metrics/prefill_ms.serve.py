"""Device milliseconds per admitted request of the prefill and admit
programs, over the traced window (the trace's program executions)."""

import trace_reduce as T


def read(run):
    if run.trace is None:
        return None
    lo, hi = T.window(run.trace)
    wlo, whi = run.window
    n = sum(1 for t in run.admitted.values() if wlo <= t < whi)
    ms = []
    for dev in T.devices(run.trace):
        runs = T.module_runs(run.trace, dev, "jit_prefill", lo, hi)
        runs += T.module_runs(run.trace, dev, "jit__admit", lo, hi)
        ms.append(T.length(runs) * 1e-6)
    if not n or not any(ms):
        return None
    return sum(ms) / len(ms) / n
