"""Model FLOPs of a training step (``flops.train_flops``) over the step
program's device time per step (averaged over the chips in the trace),
over the peak of the chips the step runs on: the share of the peak that
the whole step reaches while it runs."""

import numpy as np

import train_scopes as S
import trace_reduce as T


def read(run):
    if run.trace is None or run.peak is None:
        return None
    lo, hi = T.window(run.trace)
    per_step = []
    for dev in T.devices(run.trace):
        runs = T.module_runs(run.trace, dev, S.PROGRAM, lo, hi)
        if runs:
            per_step.append(T.length(runs) * 1e-9 / len(runs))
    if not per_step:
        return None
    return 100.0 * run.flops_per_step / (float(np.mean(per_step)) * run.chips * run.peak["bf16_flop_s"])
