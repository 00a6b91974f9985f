"""Device milliseconds per training step in which the MoE exchange
(the intervals ``a2a_ms.train`` reads) runs and no compute op runs on
the same chip: the part of the exchange that compute does not hide."""

import numpy as np

import train_scopes as S
import trace_reduce as T


def read(run):
    devs = S.exchange(run)
    if devs is None:
        return None
    return 1e-6 * float(np.mean([
        T.length(S.uncovered(a2a, compute)) / steps for a2a, compute, steps in devs
    ]))
