"""Each fault a training cell can have, planted under the timed path of
a CPU rehearsal on four emulated devices, makes ``correct`` come out
false against the cell's limits; the unbroken program comes out true;
and the control (the reference in float8 put in the program's place)
comes out false."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TRAIN = ["--workload", "mixtral.train.ep4.skewed", "--seconds", "2"]


def rehearse(script, args, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, script, *args, *TRAIN, "--seed", str(seed), "--tiny"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    lines = [l for l in proc.stderr.splitlines() if l.startswith("rehearsal result: ")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1][len("rehearsal result: "):]), proc.stderr


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "no_exchange"])
def test_train_faults(fault):
    result, err = rehearse(os.path.join(HERE, "fault_run.py"), [fault], 2**31 + 29)
    assert result["correct"] is False, err[-2000:]


def test_train_sound_run():
    result, err = rehearse(os.path.join(HERE, "fault_run.py"), ["none"], 2**31 + 29)
    assert result["correct"] is True, err[-2000:]
    assert result["metrics"]["train_tok_s"]["value"] > 0


def test_train_control_is_not_correct():
    result, err = rehearse(os.path.join(BENCH, "run.py"), ["--control"], 2**31 + 29)
    assert result["correct"] is False, err[-2000:]
