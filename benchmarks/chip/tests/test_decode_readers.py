"""The decode-step readers on four decode steps of a chip trace.

``data/decode_excerpt.json`` holds, from a traced run of
``mixtral.serve.steady`` on one TPU v5e: four consecutive ``jit__decode``
runs with two admissions (a prefill, two conversions and an admit each)
between the second and the fourth, every op and host span inside them,
and the decode program's op-to-scope table for those ops.  The expected
numbers were summed by hand from the file: per decode step, the op time
under each scope; and the one gap between decode runs with nothing
between them (the first pair).
"""

import json
import os
import types

import pytest

import decode_scopes as S
import harness as H

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def excerpt():
    with open(os.path.join(DATA, "decode_excerpt.json")) as f:
        d = json.load(f)
    table = d.pop("table")
    return types.SimpleNamespace(trace=d), table


def _read(name, run):
    return H.load_module("metrics", name + ".py").read(run)


@pytest.mark.parametrize(
    "name, want_ms",
    [
        ("decode_expert_ffn_ms.serve", 7.50436325),
        ("decode_attention_ms.serve", 0.90741075),
        ("decode_scan_copy_ms.serve", 4.048643),
    ],
)
def test_scope_readers(excerpt, monkeypatch, name, want_ms):
    run, table = excerpt
    monkeypatch.setattr(S, "program_table", lambda: table)
    assert _read(name, run) == pytest.approx(want_ms, rel=1e-9)


def test_split_covers_the_decode_program(excerpt):
    run, table = excerpt
    split = S.by_scope(run.trace, table)
    # op time per step, within the runs' mean of 12.89961 ms; ops the
    # table does not name (the executable came from a compile cache entry
    # of another build, numbered otherwise in four reshapes): 1.73 us
    assert sum(split.values()) * 1e-6 == pytest.approx(12.899024, rel=1e-9)
    assert sum(split.values()) * 1e-6 <= 12.89961
    assert split[None] * 1e-6 == pytest.approx(0.00173275, rel=1e-9)


def test_scope_readers_read_nothing_below_coverage(excerpt, monkeypatch):
    run, table = excerpt
    # a table that misses the attention ops (7% of the step) does not match
    # the executable: nothing is read, rather than a low number
    partial = {k: v for k, v in table.items() if v != "attention"}
    monkeypatch.setattr(S, "program_table", lambda: partial)
    assert _read("decode_expert_ffn_ms.serve", run) is None
    monkeypatch.setattr(S, "program_table", lambda: None)  # a program with no table
    assert _read("decode_scan_copy_ms.serve", run) is None


def test_host_gap_skips_steps_with_admissions(excerpt):
    run, _ = excerpt
    # only the first pair of decode runs has no program and no admission
    # between them: idle from 12.900 to 15.612 ms into the excerpt
    assert _read("decode_host_gap_ms.serve", run) == pytest.approx(2.711483, rel=1e-9)
    no_admit = dict(run.trace, spans=[s for s in run.trace["spans"] if s[0] != "serve.engine.admit"])
    # the programs between the later pairs still keep them out
    assert _read("decode_host_gap_ms.serve", types.SimpleNamespace(trace=no_admit)) == pytest.approx(
        2.711483, rel=1e-9)
