"""95th percentile of every gap between consecutive output tokens of any
request, pooled, over the gaps that end in the window."""

import harness as H


def read(run):
    lo, hi = run.window
    gaps = []
    for times in run.tokens.values():
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:]) if lo <= b < hi)
    return H.percentile(gaps, 95)
