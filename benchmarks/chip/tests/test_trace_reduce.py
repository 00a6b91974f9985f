"""The reduction from a trace to numbers, on a small recorded trace."""

import json
import os

import numpy as np
import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        t = json.load(f)
    t["ops"] = {int(k): v for k, v in t["ops"].items()}
    t["modules"] = {int(k): v for k, v in t["modules"].items()}
    return t


def test_busy_and_idle_share(trace):
    lo, hi = T.window(trace)
    # device 0: [0, 20] + [30, 40] + [60, 80] = 50 of 100
    assert T.length(T.busy(trace, 0, lo, hi)) == 50
    # device 1: [0, 60]
    assert T.length(T.busy(trace, 1, lo, hi)) == 60


def test_collective_exposure(trace):
    lo, hi = T.window(trace)
    a2a = T.op_intervals(trace, 0, lo, hi, names={"all-to-all.3"})
    other = [(s, s + d) for n, s, d, _ in trace["ops"][0] if n != "all-to-all.3"]
    # [30, 40] less the compute op at [35, 38]
    assert T.length(T.subtract(a2a, other)) == 7


def test_module_runs_and_ops_inside(trace):
    lo, hi = T.window(trace)
    runs = T.module_runs(trace, 0, "jit_train_step", lo, hi)
    assert runs == [(0, 80)]
    assert T.ops_in(trace, 0, runs, {"convolution.5"}) == 20


def test_breakdown_names_gaps_by_host_span(trace):
    lo, hi = T.window(trace)
    b = T.breakdown(trace, lo, hi)
    assert b["device_ops"][0][0] == "fusion.1"
    gaps = dict((n, s) for n, s in b["idle_gaps"])
    # device 0 idle in [20, 30] (train.step) and [40, 60], [80, 100] (train.wait)
    assert gaps["train.step"] == pytest.approx(10e-9)
    assert b["idle_gaps"][0][1] == pytest.approx(20e-9)


def test_union_merges_overlaps():
    assert np.isclose(T.length(T.union([(0, 2), (1, 3), (5, 6)])), 4)
