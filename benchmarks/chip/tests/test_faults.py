"""Each fault a serving cell can have, planted under the timed path of a
CPU rehearsal, makes ``correct`` come out false against the cell's
limits."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = ["--workload", "mixtral.serve.steady", "--seconds", "3"]


def rehearse(fault, cell_args, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), fault, *cell_args,
         "--seed", str(seed), "--tiny"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    lines = [l for l in proc.stderr.splitlines() if l.startswith("rehearsal result: ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][len("rehearsal result: "):]), proc.stderr


@pytest.mark.parametrize("fault", ["altered_token", "stale_cache"])
def test_serve_faults(fault):
    result, err = rehearse(fault, SERVE, 2**31 + 17)
    assert result["correct"] is False, err[-2000:]
