"""Named scopes, the op-to-scope table, and the serving engine's spans.

* ``hlo.op_scopes`` puts a scanned toy's instructions down to the right
  scopes: the scan's own slice and write-back under ``stack`` alone,
  a dot under ``moe/expert_ffn``;
* the smoke-width Mixtral engine's decode table names every scope the
  architecture runs and covers the instructions a trace shows; the
  scopes change no instruction of the compiled program;
* under ``jax.profiler`` the engine's ``serve.engine.*`` spans come in
  loop order, with the request on each admission;
* the table of programs keeps no engine alive.
"""

import dataclasses
import gc
import glob
import os
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.hlo import op_scopes, scope_of, trace_ops
from repro.models import SCOPES
from repro.scopes import scope, scopes_off
from repro.serve import Request, ServeEngine
from repro.serve import metrics as serve_metrics


def _engine(controller="off"):
    cfg = smoke_config("mixtral-8x7b")
    if controller == "auto":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, dispatch="scheduled"))
    return ServeEngine(cfg, decode_slots=4, max_len=64, buckets=(8, 16), controller=controller)


def _serve(engine, seed=0, lengths=(5, 12), new=3):
    rng = np.random.default_rng(seed)
    vocab = engine.cfg.vocab_size
    reqs = [Request(prompt=rng.integers(0, vocab, n), max_new_tokens=new) for n in lengths]
    engine.run(reqs)
    return reqs


def _decode_text(engine):
    _serve(engine, lengths=(3,), new=1)  # notes its programs again
    prog = serve_metrics._PROGRAMS[("jit__decode", None)]
    assert prog.fn() is engine._decode_jit
    return engine._decode_jit.lower(*prog.args, **prog.kwargs).compile().as_text()


def _canonical(hlo_text):
    """The computations with metadata stripped and instructions numbered
    in order of appearance (lowering under a name stack may number a few
    instructions inside fusions differently)."""
    keep = [ln for ln in hlo_text.splitlines() if ln.startswith(("%", "ENTRY", "  ", "}", "HloModule"))]
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(keep))
    names: dict[str, str] = {}
    return re.sub(r"%([\w.\-]+)", lambda m: "%" + names.setdefault(m.group(1), f"i{len(names)}"), text)


@pytest.fixture(scope="module")
def served():
    engine = _engine()
    _serve(engine)
    return engine


# ------------------------------------------------------------------ table
class TestOpScopes:
    def test_toy_scan_copies_fall_under_stack_alone(self):
        def f(ws, x, cache):
            def body(c, inp):
                w, kv = inp
                with scope("attention"):
                    y = jnp.tanh(c @ w) + kv.sum()
                with scope("moe"), scope("moe/expert_ffn"):
                    y = y @ w
                return y, kv * 2.0

            with scope("stack"):
                return jax.lax.scan(body, x, (ws, cache))

        args = (jnp.ones((4, 64, 64)), jnp.ones((8, 64)), jnp.ones((4, 16, 64)))
        text = jax.jit(f).lower(*args).compile().as_text()
        table = op_scopes(text)
        kinds = {}
        for ln in text.splitlines():
            m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = .*? ([\w\-]+)\(", ln)
            if m and m.group(1) in table:
                kinds[m.group(1)] = (m.group(2), ln)
        slices = [n for n, (_, ln) in kinds.items() if "/while/body/dynamic_slice" in ln]
        updates = [n for n, (_, ln) in kinds.items() if "/while/body/dynamic_update_slice" in ln]
        assert slices and updates
        assert {table[n] for n in slices + updates} == {"stack"}
        dots = [n for n, (k, ln) in kinds.items() if k == "dot" and "expert_ffn" in ln]
        assert dots and {table[n] for n in dots} == {"moe/expert_ffn"}
        assert "attention" in table.values()

    @pytest.mark.parametrize(
        "op_name, want",
        [
            ("jit(_decode)/stack/while/body/closed_call/moe/expert_ffn/dot_general", "moe/expert_ffn"),
            ("jit(_decode)/stack/while/body/dynamic_slice", "stack"),
            ("jit(f)/transpose(jvp(stack))/while/body/transpose(jvp(attention))/mul", "attention"),
            ("jit(_decode)/router/add", None),  # a stage outside its layer
            ("jit(_decode)/argmax", None),
        ],
    )
    def test_scope_of_innermost_path(self, op_name, want):
        assert scope_of(op_name) == want


# ------------------------------------------------------------ engine table
class TestEngineTable:
    @pytest.mark.parametrize("controller", ["off", "auto"])
    def test_decode_table_names_every_scope_and_covers_trace_ops(self, controller, served):
        engine = served if controller == "off" else _engine("auto")
        text = _decode_text(engine)
        ops = trace_ops(text)
        if controller == "auto":
            # XLA's copies of the controller state that the step returns
            # unchanged have no op_name, and nothing to take a scope from
            named = {m.group(1) for m in re.finditer(r"%([\w.\-]+) = [^\n]*op_name=", text)}
            ops = [o for o in ops if o in named]
        table = serve_metrics.op_scopes("jit__decode")
        covered = [o for o in ops if o in table]
        assert len(covered) >= 0.99 * len(ops), sorted(set(ops) - set(covered))
        used = {"embed", "attention", "moe", "moe/router", "moe/pack", "moe/expert_ffn",
                "moe/combine", "logits", "stack"}
        if controller == "auto":  # the in-graph controller and its re-plan
            used |= {"controller", "lap"}
        assert used <= set(table.values()) <= set(SCOPES)

    def test_scopes_change_no_instruction(self, served):
        on = _decode_text(served)
        with scopes_off():
            plain = _engine()
            _serve(plain)
            off = _decode_text(plain)
        assert "/stack/" in on and "/stack/" not in off
        assert trace_ops(on) == trace_ops(off)
        assert _canonical(on) == _canonical(off)

    def test_programs_noted_per_bucket(self, served):
        _serve(served)
        mods = set(serve_metrics.programs())
        assert {("jit__decode", None), ("jit__admit", None), ("jit_prefill", 8),
                ("jit_prefill", 16)} <= mods
        with pytest.raises(ValueError, match="variant"):
            serve_metrics.op_scopes("jit_prefill")
        assert "attention" in serve_metrics.op_scopes("jit_prefill", 16).values()

    def test_table_keeps_no_engine_alive(self):
        engine = _engine()
        _serve(engine, seed=1)
        serve_metrics.op_scopes("jit__decode")
        ref = weakref.ref(engine)
        del engine
        gc.collect()
        assert ref() is None
        assert ("jit__decode", None) not in serve_metrics.programs()
        with pytest.raises(KeyError):
            serve_metrics.op_scopes("jit__decode")


# ------------------------------------------------------------------ spans
def test_engine_spans_in_loop_order(tmp_path):
    from jax.profiler import ProfileData

    engine = _engine()
    _serve(engine, seed=2)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = _serve(engine, seed=3, new=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    events = sorted(
        (ev.start_ns, ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("serve.engine.")
    )
    names = [n for _, n, _ in events]
    step = ["serve.engine.decode.inputs", "serve.engine.decode.launch",
            "serve.engine.decode.fetch", "serve.engine.advance"]
    assert names[:2] == ["serve.engine.admit"] * 2
    assert names[2:] == step * (len(names[2:]) // 4) and len(names[2:]) == 4 * 2
    assert [s["rid"] for _, n, s in events[:2]] == [r.rid for r in reqs]
    assert {s["slot"] for _, _, s in events[:2]} == {0, 1}
    assert [s["bucket"] for _, _, s in events[:2]] == [8, 16]
    steps = [s["step"] for _, n, s in events if n.startswith("serve.engine.decode.")]
    assert steps == sorted(steps) and len(set(steps)) == 2
