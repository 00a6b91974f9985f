"""Every configuration names its plain reference, every named reference
meets the harness's contract (``runners/common.py``), and no part of the
harness outside the named module imports a reference of its own."""

import ast
import glob
import importlib
import json
import os

import pytest

from runners.common import model_config

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))
PER_RUNNER = {"serve": "serve_logits", "train": "train_readings"}
# the harness's own modules: they take the reference from the configuration
GENERAL = sorted(
    glob.glob(os.path.join(BENCH, "runners", "*.py"))
    + glob.glob(os.path.join(BENCH, "metrics", "*.py"))
    + [os.path.join(BENCH, "flops.py"), os.path.join(BENCH, "harness.py")]
)


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_configuration_names_a_reference_that_meets_the_contract(path):
    conf = load(path)
    ref = importlib.import_module("reference." + conf["reference"])
    for name in ("published", "implements", "dims", PER_RUNNER[conf["runner"]]):
        assert callable(getattr(ref, name, None)), (conf["reference"], name)
    assert ref.KEYS and set(ref.KEYS) <= set(conf), set(ref.KEYS) - set(conf)
    dm = ref.dims(conf)
    assert 0 < dm["e_held"] <= dm["e"]


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_program_config_is_the_files_under_its_references_keys(path):
    conf = load(path)
    cfg, hf, ref = model_config(conf, tiny=False)
    assert ref.__name__ == "reference." + conf["reference"]
    assert {k: hf[k] for k in ref.KEYS} == {k: conf[k] for k in ref.KEYS}
    assert ref.implements(cfg) is None


def imported_modules(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("path", GENERAL, ids=lambda p: os.path.relpath(p, BENCH))
def test_harness_imports_no_reference(path):
    named = [m for m in imported_modules(path) if m == "reference" or m.startswith("reference.")]
    assert not named, named


def test_a_file_without_a_reference_is_an_error_that_names_it():
    conf = load(os.path.join(BENCH, "configs", "mixtral-8x7b.serve.json"))
    del conf["reference"]
    with pytest.raises(SystemExit, match=r"configs/mixtral-8x7b\.serve\.json: names no reference"):
        model_config(conf, tiny=True)


def test_a_reference_that_is_not_there_is_an_error_that_names_the_file():
    conf = dict(load(os.path.join(BENCH, "configs", "mixtral-8x7b.serve.json")),
                name="absent", reference="no_such_reference")
    with pytest.raises(SystemExit, match=r"configs/absent\.json: no reference module"):
        model_config(conf, tiny=True)


REFUSES = '''
from reference.mixtral import KEYS, dims, published, serve_logits  # noqa: F401


def implements(cfg):
    return "no QK-norm before the rotary positions"
'''


def test_a_reference_that_refuses_the_block_exits_naming_file_and_reason(tmp_path, monkeypatch):
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "refuses_block.py").write_text(REFUSES)
    monkeypatch.syspath_prepend(str(tmp_path))
    conf = dict(load(os.path.join(BENCH, "configs", "mixtral-8x7b.serve.json")),
                name="stub-refuses", reference="refuses_block")
    with pytest.raises(SystemExit) as e:
        model_config(conf, tiny=True)
    msg = str(e.value)
    assert "configs/stub-refuses.json" in msg and "no QK-norm before the rotary positions" in msg
