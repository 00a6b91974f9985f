"""Device time of the decode program by the program's named scopes.

The program notes each program it runs, and compiles the decode step
again on request to read which scope each of its instructions belongs
to (``repro.serve.metrics.op_scopes``: instruction name -> innermost
path of ``repro.scopes.SCOPES``).  A trace names device ops by
instruction, so that table puts each op's time down to a scope.  Every
number here is per decode step: over the ``jit__decode`` runs in the
window, divided by their count.
"""

from __future__ import annotations

import numpy as np

import trace_reduce as T

DECODE = "jit__decode"
# least share of the decode program's op time that the table must name;
# below it the table and the executable disagree, and nothing is read
COVERAGE = 0.99


def program_table() -> dict | None:
    """The decode program's table, from the program in this process
    (None where the program keeps no such table, or ran no decode)."""
    try:
        from repro.serve.metrics import op_scopes
    except ImportError:
        return None
    try:
        return op_scopes(DECODE)
    except (KeyError, ValueError):
        return None


def _decode_ops(trace, dev, lo, hi):
    """The decode runs in the window, and (name, ns) of each op that
    starts inside one, clipped to its run's end."""
    runs = sorted(T.module_runs(trace, dev, DECODE, lo, hi))
    rows = trace["ops"].get(dev, [])
    if not runs or not rows:
        return runs, []
    a = np.array([r[0] for r in runs])
    b = np.array([r[1] for r in runs])
    s = np.array([r[1] for r in rows], np.float64)
    e = s + np.array([r[2] for r in rows], np.float64)
    i = np.searchsorted(a, s, side="right") - 1
    inside = (i >= 0) & (s < b[np.maximum(i, 0)])
    ns = np.minimum(e, b[np.maximum(i, 0)]) - s
    return runs, [(rows[k][0], float(ns[k])) for k in np.flatnonzero(inside)]


def by_scope(trace, table: dict) -> dict | None:
    """{scope: device ns per decode step} over the window (None for
    ops the table does not name), or None where the table names less
    than ``COVERAGE`` of the decode program's op time, or no decode ran.
    Devices are averaged."""
    lo, hi = T.window(trace)
    per_dev = []
    for dev in T.devices(trace):
        runs, rows = _decode_ops(trace, dev, lo, hi)
        if not runs:
            continue
        out: dict = {}
        for n, ns in rows:
            k = table.get(n)
            out[k] = out.get(k, 0.0) + ns
        total = sum(out.values())
        if total <= 0 or total - out.get(None, 0.0) < COVERAGE * total:
            return None
        per_dev.append({k: v / len(runs) for k, v in out.items()})
    if not per_dev:
        return None
    keys = set().union(*per_dev)
    return {k: sum(d.get(k, 0.0) for d in per_dev) / len(per_dev) for k in keys}


def scope_ms(run, scope: str, table: dict | None = None) -> float | None:
    """Device ms per decode step in ops under exactly ``scope``."""
    if run.trace is None:
        return None
    table = program_table() if table is None else table
    if table is None:
        return None
    split = by_scope(run.trace, table)
    return None if split is None else split.get(scope, 0.0) * 1e-6
