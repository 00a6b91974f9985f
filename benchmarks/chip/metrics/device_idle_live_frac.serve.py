"""Idle share of the device while at least one request is live: the
traced window less the host's waits for arrivals (``serve.wait``
spans), and of that the part in which no op ran on the device."""

import numpy as np

import trace_reduce as T


def read(run):
    if run.trace is None:
        return None
    lo, hi = T.window(run.trace)
    waits = [(s, s + d) for n, s, d in run.trace["spans"] if n == "serve.wait"]
    live = T.subtract([(lo, hi)], waits)
    total = T.length(live)
    devs = T.devices(run.trace)
    if total <= 0 or not devs:
        return None
    busy = np.mean([
        sum(T.length(T.clip(T.busy(run.trace, d, lo, hi), a, b)) for a, b in live) for d in devs
    ])
    return 100.0 * (1.0 - busy / total)
