"""Mean device-idle milliseconds between consecutive decode programs
with nothing else between them: no other program on the device and no
admission (``serve.engine.admit`` span) on the host.  The host's share
of a plain decode step: copies in, the launch, the token copy back,
the batcher."""

import bisect

import trace_reduce as T

DECODE = "jit__decode"


def read(run):
    if run.trace is None:
        return None
    lo, hi = T.window(run.trace)
    admits = [(s, s + d) for n, s, d in run.trace["spans"] if n == "serve.engine.admit"]
    gaps = []
    for dev in T.devices(run.trace):
        runs = sorted(T.module_runs(run.trace, dev, DECODE, lo, hi))
        others = sorted(
            (s, s + d) for n, s, d in run.trace["modules"].get(dev, [])
            if not n.split("(")[0].startswith(DECODE)
        )
        between = sorted(others + admits)
        starts = [s for s, _ in between]
        ends = sorted(e for _, e in between)
        busy = T.busy(run.trace, dev, lo, hi)
        busy_starts = [s for s, _ in busy]
        for (_, a), (b, _) in zip(runs, runs[1:]):
            # anything that starts before b and ends after a lies between
            if bisect.bisect_left(starts, b) > bisect.bisect_right(ends, a):
                continue
            k = max(bisect.bisect_right(busy_starts, a) - 1, 0)
            held = T.length(T.clip(busy[k : bisect.bisect_left(busy_starts, b)], a, b))
            gaps.append(b - a - held)
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
