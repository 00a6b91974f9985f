"""The training readers on one step of a chip trace.

``data/train_excerpt.json`` holds, from a traced run of
``mixtral.train.ep4.skewed`` on a TPU v5e 2x2 (``tools/train_excerpt.py``;
recorded with each phase slot at a pair's whole bucket, the same ops
at larger shapes than the committed envelope's):
for two of the four chips, one run of the train-step program with
every op that starts inside it, the host spans around it, and the step
program's table (opcode and scope) for those ops.  The expected numbers
(``hand_count``) were counted by plain loops over the same file when it
was written: per step and chip, the op time under ``moe/expert_ffn``,
the time of the exchange's collectives under ``moe/dispatch`` and
``moe/combine`` and the part of it outside every compute op, and the
step's model FLOPs over its device time and the chips' peak.
"""

import json
import os
import types

import pytest

import harness as H
import train_scopes as S

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAMES = ["expert_ffn_ms.train", "a2a_ms.train", "a2a_exposed_ms.train", "step_mfu.train",
         "matchings.train"]


@pytest.fixture
def excerpt():
    with open(os.path.join(DATA, "train_excerpt.json")) as f:
        d = json.load(f)
    trace = {"ops": {int(k): v for k, v in d["ops"].items()},
             "modules": {int(k): v for k, v in d["modules"].items()}, "spans": d["spans"]}
    run = types.SimpleNamespace(trace=trace, table=d["table"], flops_per_step=d["flops_per_step"],
                                phases=d["phases"], chips=d["chips"], peak=d["peak"])
    return run, d["hand_count"]


def _read(name, run):
    return H.load_module("metrics", name + ".py").read(run)


@pytest.mark.parametrize("name", NAMES)
def test_readers_match_the_hand_count(excerpt, name):
    run, hand = excerpt
    assert _read(name, run) == pytest.approx(hand[name], rel=1e-9)


def test_the_numbers_are_sound(excerpt):
    run, _ = excerpt
    ffn, a2a, exposed = (_read(n, run) for n in NAMES[:3])
    assert 0 < exposed <= a2a and ffn > 0
    assert 0 < _read("step_mfu.train", run) <= 100
    assert run.phases >= 1


def test_the_table_covers_the_step(excerpt):
    run, _ = excerpt
    assert S.per_device(run) is not None
    for dev, rows in run.trace["ops"].items():
        total = sum(d for _, _, d, _ in rows)
        named = sum(d for n, _, d, _ in rows if n in run.table["kind"])
        assert named >= S.COVERAGE * total


def test_scope_readers_read_nothing_below_coverage(excerpt):
    run, _ = excerpt
    # a table that misses the expert FFN's ops does not match the
    # executable: nothing is read, rather than a low number
    ffn = {n for n, s in run.table["scope"].items() if s == "moe/expert_ffn"}
    run.table = {k: {n: v for n, v in t.items() if n not in ffn} for k, t in run.table.items()}
    for name in NAMES[:3]:
        assert _read(name, run) is None
    run.table = {}  # a step compiled without its table
    assert _read("expert_ffn_ms.train", run) is None


def test_untraced_readers_read_nothing():
    run = types.SimpleNamespace(trace=None, table={}, peak=None, phases=3)
    for name in NAMES[:4]:
        assert _read(name, run) is None


def test_train_tok_s():
    # three steps of 100 tokens between 10.0 s and 10.5 s on the host clock
    run = types.SimpleNamespace(steps=[(10.0, 10.2), (10.1, 10.35), (10.2, 10.5)],
                                window=(10.0, 10.5), tokens_per_step=100)
    assert _read("train_tok_s", run) == pytest.approx(600.0)
    assert _read("train_tok_s", types.SimpleNamespace(steps=[], window=(0, 1))) is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_uncovered_is_subtract(seed):
    import numpy as np

    import trace_reduce as T

    rng = np.random.default_rng(seed)

    def spans(n):
        s = np.sort(rng.uniform(0, 1000, n))
        return T.union([(float(a), float(a + d)) for a, d in zip(s, rng.exponential(3.0, n))])

    a, holes = spans(200), spans(400)
    assert S.uncovered(a, holes) == T.subtract(a, holes)
