"""Program against the plain training reference at smoke widths on four
emulated CPU devices: the program's first three steps read within the
cell's limits of the gradient and the change, and each control (the
reference in float8, and with the exchange or half of the batch left
out, put in the program's place) reads past at least one of the cell's
limits.  The program's loss gap is held below each fault's on the same
seed instead of the cell's limit: at d_model 64 bfloat16 rounds the
smoke model's loss about ten times more coarsely than at the cell's
widths (smoke program up to 1.35e-3, chip program up to 9.8e-5), as
coarsely as the float8 control does there (1.15e-3 to 1.57e-3), so at
this size the loss tells the program from the faults but not from the
control.  The chip readings, from which the limits were set, are in
PERF.md."""

import json
import os
import subprocess
import sys

import pytest

import harness as H

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SEEDS = [3, 2**31 + 3]


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "train_readings.py"), "--tiny",
         "--seeds", *map(str, SEEDS), "--control-seeds", *map(str, SEEDS)],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    rows = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    assert len(rows) == 4 * len(SEEDS), proc.stderr[-3000:]
    return rows


LIMITED = ("loss_gap", "grad_gap", "change_gap")
FAULTS = ("no_exchange", "half_batch")


def _limits():
    return {k: v["limit"] for k, v in H.limits("mixtral.train.ep4.skewed").items()}


def test_program_within_limits(readings):
    lim = _limits()
    for r in readings:
        if r["kind"] == "program":
            assert r["grad_gap"] <= lim["grad_gap"] and r["change_gap"] <= lim["change_gap"], r
            others = [c["loss_gap"] for c in readings
                      if c["seed"] == r["seed"] and c["kind"] in FAULTS]
            assert r["loss_gap"] < min(others), r
            assert r["cut_choices"] == 0 and r["dropped"] == 0, r


@pytest.mark.parametrize("kind", ["control", "no_exchange", "half_batch"])
def test_each_control_fails_a_limit(readings, kind):
    lim = _limits()
    rows = [r for r in readings if r["kind"] == kind]
    assert rows
    for r in rows:
        assert any(r[k] > lim[k] for k in LIMITED), r
