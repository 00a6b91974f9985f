"""Device time of the train-step program, by the program's named scopes
and by kind of op.

The runner compiles the step itself and keeps, from the compiled text,
a table of the instructions a trace shows (``repro.launch.hlo``): each
one's opcode, and the innermost path of ``repro.scopes.SCOPES`` it runs
under (``op_scopes``; the optimizer and the loss's own arithmetic run
under none).  A trace names device ops by instruction, so the table
puts each op's time down to a scope and a kind.  Every number here is
per step: over the step program's runs in the window, divided by their
count, and averaged over the devices.
"""

from __future__ import annotations

import numpy as np

import trace_reduce as T

PROGRAM = "jit_train_step"
# least share of the step program's op time whose instructions the table
# lists; below it the table and the executable disagree, and nothing is read
COVERAGE = 0.99
# the collectives that carry the MoE exchange
EXCHANGE = ("all-to-all", "ragged-all-to-all", "collective-permute")
COLLECTIVES = EXCHANGE + ("all-reduce", "all-gather", "reduce-scatter", "collective-broadcast")
EXCHANGE_SCOPES = ("moe/dispatch", "moe/combine")


def table_of(hlo_text: str) -> dict:
    """{"kind": {instruction: opcode}, "scope": {instruction: scope}}
    over the instructions of the compiled step that a trace shows.

    The expert-parallel MoE layer runs inside a ``shard_map``, which
    puts a ``shard_map`` part into its ops' names between ``moe`` and
    the stage (``moe/shard_map/expert_ffn/...``); the program's
    ``scope_of`` reads a scope path only where its parts are adjacent,
    so the table is read with that part taken out."""
    from repro.launch import hlo

    comps = hlo.parse_module(hlo_text)
    shown = set(hlo.trace_ops(hlo_text))
    kind = {op.name: op.kind for c in comps.values() for op in c.ops if op.name in shown}
    own = hlo.scope_of
    hlo.scope_of = lambda name: own("/".join(p for p in name.split("/") if p != "shard_map"))
    try:
        scope = {k: v for k, v in hlo.op_scopes(hlo_text).items() if k in shown}
    finally:
        hlo.scope_of = own
    return {"kind": kind, "scope": scope}


def base_kind(kind: str) -> str:
    for suffix in ("-start", "-done", "-update"):
        if kind.endswith(suffix):
            return kind[: -len(suffix)]
    return kind


def step_ops(trace, dev, lo, hi):
    """The step runs in the window, and (name, start, end) of each op
    that starts inside one, clipped to its run's end."""
    runs = sorted(T.module_runs(trace, dev, PROGRAM, lo, hi))
    rows = trace["ops"].get(dev, [])
    if not runs or not rows:
        return runs, []
    a = np.array([r[0] for r in runs])
    b = np.array([r[1] for r in runs])
    s = np.array([r[1] for r in rows], np.float64)
    e = s + np.array([r[2] for r in rows], np.float64)
    i = np.maximum(np.searchsorted(a, s, side="right") - 1, 0)
    inside = (s >= a[i]) & (s < b[i])
    end = np.minimum(e, b[i])
    return runs, [(rows[k][0], float(s[k]), float(end[k])) for k in np.flatnonzero(inside)]


def _memo(fn):
    """Keep ``fn(run)`` on the run: every reader of one run needs the
    same step ops, found again otherwise in a trace of millions of ops."""

    def wrapped(run):
        memo = vars(run).setdefault("_train_scopes", {})
        if fn.__name__ not in memo:
            memo[fn.__name__] = fn(run)
        return memo[fn.__name__]

    return wrapped


@_memo
def per_device(run):
    """[(runs, ops)] per device, or None where there is no trace, no
    table, no step in the window, or the table lists less than
    ``COVERAGE`` of a device's step op time."""
    if run.trace is None or not run.table:
        return None
    lo, hi = T.window(run.trace)
    kinds = run.table["kind"]
    out = []
    for dev in T.devices(run.trace):
        runs, ops = step_ops(run.trace, dev, lo, hi)
        if not runs:
            continue
        total = sum(e - s for _, s, e in ops)
        known = sum(e - s for n, s, e in ops if n in kinds)
        if total <= 0 or known < COVERAGE * total:
            return None
        out.append((runs, ops))
    return out or None


def scope_ms(run, scope: str) -> float | None:
    """Device ms per step in ops under exactly ``scope``."""
    devs = per_device(run)
    if devs is None:
        return None
    scopes = run.table["scope"]
    ms = [
        T.length(T.union([(s, e) for n, s, e in ops if scopes.get(n) == scope])) / len(runs)
        for runs, ops in devs
    ]
    return 1e-6 * float(np.mean(ms))


@_memo
def exchange(run):
    """Per device: (the intervals of the exchange collectives under
    ``EXCHANGE_SCOPES``, the intervals of every op that is no
    collective, the number of step runs)."""
    devs = per_device(run)
    if devs is None:
        return None
    kinds, scopes = run.table["kind"], run.table["scope"]
    out = []
    for runs, ops in devs:
        a2a, compute = [], []
        for n, s, e in ops:
            k = base_kind(kinds.get(n, ""))
            if k in EXCHANGE and scopes.get(n) in EXCHANGE_SCOPES:
                a2a.append((s, e))
            elif k not in COLLECTIVES:
                compute.append((s, e))
        out.append((T.union(a2a), T.union(compute), len(runs)))
    return out


def uncovered(intervals, holes) -> list:
    """Parts of ``intervals`` that no interval of ``holes`` covers, as
    ``trace_reduce.subtract`` gives them, in one pass over both (each a
    sorted union, as ``exchange`` gives them)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        cur, k = a, j
        while k < len(holes) and holes[k][0] < b:
            ha, hb = holes[k]
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out
