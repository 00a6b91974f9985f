"""Tokens trained per second: the tokens of every step run in the
window, over the window (from the first step's launch to the last
step's completion), with tracing off."""


def read(run):
    if not run.steps:
        return None
    lo, hi = run.window
    return run.tokens_per_step * len(run.steps) / (hi - lo)
