"""Output tokens produced in the window, over the window."""


def read(run):
    lo, hi = run.window
    n = sum(1 for times in run.tokens.values() for t in times if lo <= t < hi)
    return n / (hi - lo) if n else None
