"""A model the harness has never seen goes through the serving runner
with new files only: a reference module that publishes its own key names
and holds a share of the experts, and a configuration file that names
it.  The harness calls that module's ``implements``, ``published``,
``dims`` and ``serve_logits``, and its FLOP count follows the share held."""

import json
import os
import re

import flops as F
import harness as H
import run
from runners import serve as S
from runners.common import model_config

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
HELD = 2  # of the 8 experts

STUB = '''
"""Mixtral's reference under Qwen-style key names, holding HELD experts."""

from reference import mixtral as M

CALLS = []
RENAMED = {"num_experts": "num_local_experts", "moe_intermediate_size": "intermediate_size"}
KEYS = tuple({v: k for k, v in RENAMED.items()}.get(k, k) for k in M.KEYS)


def _mixtral(hf):
    return {RENAMED.get(k, k): v for k, v in hf.items()}


def published(cfg):
    CALLS.append("published")
    back = {v: k for k, v in RENAMED.items()}
    return {back.get(k, k): v for k, v in M.published(cfg).items()}


def implements(cfg):
    CALLS.append("implements")
    return M.implements(cfg)


def dims(hf):
    CALLS.append("dims")
    return dict(M.dims(_mixtral(hf)), e_held=HELD)


def serve_logits(seed, hf, *args, **kw):
    CALLS.append("serve_logits")
    return M.serve_logits(seed, _mixtral(hf), *args, **kw)
'''


def stub_files(tmp_path):
    """The stub reference under ``tmp_path/reference`` and a serving
    configuration that names it, with Mixtral's values under the stub's
    keys."""
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "qwen_style_stub.py").write_text(STUB.replace("HELD", str(HELD)))
    with open(os.path.join(BENCH, "configs", "mixtral-8x7b.serve.json")) as f:
        conf = json.load(f)
    conf["num_experts"] = conf.pop("num_local_experts")
    conf["moe_intermediate_size"] = conf.pop("intermediate_size")
    conf.update(name="qwen-style-stub.serve", reference="qwen_style_stub")
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "qwen-style-stub.serve.json"
    path.write_text(json.dumps(conf))
    return path


def test_stub_reference_runs_through_the_serving_runner(tmp_path, monkeypatch, capsys):
    path = stub_files(tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    conf = json.loads(path.read_text())

    # the program's published widths read back under the stub's keys
    cfg, hf, ref = model_config(conf, tiny=False)
    assert ref.__name__ == "reference.qwen_style_stub"
    assert hf["num_experts"] == 8 and "num_local_experts" not in hf
    assert ref.CALLS == ["published", "implements"]
    ref.CALLS.clear()

    real_cell = H.cell

    def cell(name):
        found = real_cell(name)
        found["config"] = json.loads(path.read_text())
        return found

    monkeypatch.setattr(H, "cell", cell)
    seen = []
    real_setup = S.setup

    def setup(args, c):
        su = real_setup(args, c)
        seen.append((su.ref, su.dm))
        return su

    monkeypatch.setattr(S, "setup", setup)
    rc = run.main(["--workload", "mixtral.serve.steady", "--seed", str(2**31 + 41),
                   "--seconds", "2", "--tiny"])
    err = capsys.readouterr().err
    assert rc == 1, err[-3000:]  # a rehearsal exits 1 after its result
    line = re.search(r"^rehearsal result: (.*)$", err, re.M)
    assert line, err[-3000:]
    result = json.loads(line.group(1))
    assert result["correct"] is True, err[-3000:]
    assert result["attempted"] > 0

    assert ref.CALLS == ["published", "implements", "dims", "serve_logits"], ref.CALLS
    ((used, dm),) = seen
    assert used is ref and dm["e"] == 8 and dm["e_held"] == HELD

    # the decode step's count follows the held share: of each token's 2
    # choices, 2 * HELD / 8 are counted, over the smoke model's layers
    full = dict(dm, e_held=dm["e"])
    expert = dm["layers"] * dm["k"] * 3 * dm["d"] * dm["f"]
    for ctx in ([5], [17, 40, 3]):
        assert F.decode_flops(full, ctx) - F.decode_flops(dm, ctx) == (
            2 * len(ctx) * expert * (8 - HELD) // 8)
