"""Device milliseconds per training step in the MoE exchange: the
collectives (all-to-all, ragged all-to-all, collective permute) under
the program's ``moe/dispatch`` and ``moe/combine`` scopes, forward and
backward, averaged over the chips."""

import numpy as np

import train_scopes as S
import trace_reduce as T


def read(run):
    devs = S.exchange(run)
    if devs is None:
        return None
    return 1e-6 * float(np.mean([T.length(a2a) / steps for a2a, _, steps in devs]))
