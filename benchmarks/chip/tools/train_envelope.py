"""The training cell's phase envelope: the bound over many seeds' planned
caps that the configuration states and every run executes.  Run by
``tools/train_readings.py --survey-seeds ... [--write-envelope]``.

For each seed the cell's set-up is followed as far as the plan: the
seed's weights, its pool routed through the program, and the program's
planner (``runners.train.plan``).  One JSON line per seed gives the
phases, the planned caps, the expert load's max over mean and the
rank-to-rank column sums.  The last line gives the bound: per phase
slot the largest planned cap, or the mean plus ``SPREADS`` standard
deviations where that is larger, rounded up to the quantum, and the
largest of these for every slot (max-weight does not order a plan's
phases by cap, so a fresh seed's largest cap may come in any slot); it
can be written into the configuration file's ``table.envelope``.
"""

from __future__ import annotations

import json
import math
import os
import sys

import harness as H

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKLOAD = "mixtral.train.ep4.skewed"
# a seed's caps are a maximum over its pool; past the mean by this many
# of their standard deviations lies a small share of fresh seeds
SPREADS = 6


def survey(cell, seeds, tiny=False) -> list:
    """[(seed, per-layer planned caps)], one JSON line per seed."""
    import numpy as np

    from runners import train as TR

    pr = TR.program(cell, tiny)
    rows = []
    for seed in seeds:
        _, _, _, counts = TR.routed(pr, seed)
        scheds = TR.plan(counts, pr.n, pr.e_local, pr.conf["table"])
        total = np.sum(np.stack(counts), axis=0)
        line = {
            "seed": seed, "phases": [s.num_phases for s in scheds],
            "caps": [[int(c) for c in s.caps] for s in scheds],
            "load_max_over_mean": [float(t.sum(0).max() / t.sum(0).mean()) for t in total],
            "column_sums": [[int(v) for v in t.reshape(pr.n, pr.n, pr.e_local).sum(axis=(0, 2))]
                            for t in total],
        }
        print(json.dumps(line), flush=True)
        rows.append((seed, scheds))
    return rows


def bound(rows, slots: int, quantum: int) -> list:
    """One bound for every phase slot: the largest over the slots of
    max(largest cap, mean + SPREADS sd), rounded up."""
    import numpy as np

    caps = np.array([[s.caps[k] if k < s.num_phases else 0 for k in range(slots)]
                     for _, scheds in rows for s in scheds], np.float64)
    need = np.maximum(caps.max(axis=0), caps.mean(axis=0) + SPREADS * caps.std(axis=0))
    return [int(quantum * math.ceil(need.max() / quantum))] * slots


def write(envelope: list) -> str:
    path = os.path.join(HERE, "configs", H.cell(WORKLOAD)["workload"]["config"] + ".json")
    with open(path) as f:
        conf = json.load(f)
    conf["table"]["envelope"] = envelope
    with open(path, "w") as f:
        json.dump(conf, f, indent=2)
        f.write("\n")
    return path


def settle(cell, seeds, tiny: bool, to_file: bool) -> list:
    """Survey the seeds, print the bound, and put it into ``cell`` (and
    with ``to_file`` into the configuration file)."""
    tc = cell["config"]["table"]
    rows = survey(cell, seeds, tiny)
    env = bound(rows, int(tc["phase_slots"]), int(tc["quantum"]))
    print(json.dumps({"envelope": env, "seeds": len(rows),
                      "most_phases": max(max(s.num_phases for s in sc) for _, sc in rows)}),
          flush=True)
    tc["envelope"] = env
    if to_file:
        print(f"wrote {write(env)}", file=sys.stderr, flush=True)
    return env
