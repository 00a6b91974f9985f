"""The control -- the plain reference computed in float8, the precision
below the configuration's bfloat16, put in the program's place -- comes
out as not correct through the harness's own checks and limits, on a
CPU rehearsal at smoke widths; the program on the same seeds reads
below the control.  The chip readings at the cell's size, from which
the limits were set, are in PERF.md."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def control(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "mixtral.serve.steady",
         "--seconds", "3", "--seed", str(seed), "--tiny", "--control"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    lines = [l for l in proc.stderr.splitlines() if l.startswith("rehearsal result: ")]
    assert lines, proc.stderr[-3000:]
    prog = re.search(r"^program gap_mean: (\S+),", proc.stderr, re.M)
    ctl = re.search(r"^check gap_mean: (\S+) ", proc.stderr, re.M)
    assert prog and ctl, proc.stderr[-3000:]
    result = json.loads(lines[-1][len("rehearsal result: "):])
    return result, float(prog.group(1)), float(ctl.group(1)), proc.stderr


@pytest.mark.parametrize("seed", [11, 21, 2**31 + 5])
def test_control_is_not_correct(seed):
    result, prog, ctl, err = control(seed)
    assert result["correct"] is False, err[-2000:]
    assert ctl > prog, (ctl, prog)
