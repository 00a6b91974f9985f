"""Named scopes of the program's device work, as one table.

Every layer of the model, the layer scan, and the schedule controller
runs under one of ``SCOPES``.  ``jax.named_scope`` carries the name into
each compiled instruction's ``metadata={op_name=...}``, and
``repro.launch.hlo.op_scopes`` reads it back: a profiler trace names
ops by instruction, so that table joins the trace's device time to the
layers.  Readers and tests import ``SCOPES`` from here, so the names
cannot drift.

A path names its parents: ``moe/router`` is entered as ``router``
inside the ``moe`` scope.  Scopes change metadata only; ``scopes_off``
turns them off, so a test can compare the compiled programs.
"""

from __future__ import annotations

import contextlib

import jax

SCOPES = (
    "embed",
    "attention",
    "mamba",
    "rwkv",
    "ffn",
    "moe",
    "moe/router",
    "moe/pack",
    "moe/dispatch",
    "moe/expert_ffn",
    "moe/combine",
    "logits",
    "stack",
    "controller",
    "lap",
)

_on = True


def scope(name: str):
    """``jax.named_scope`` for the entry ``name`` of ``SCOPES`` (its last
    component; the caller is inside the others)."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in SCOPES")
    return jax.named_scope(name.rsplit("/", 1)[-1]) if _on else contextlib.nullcontext()


@contextlib.contextmanager
def scopes_off():
    """Trace without scopes (for comparing compiled programs; a jit
    traced before keeps what it traced)."""
    global _on
    was, _on = _on, False
    try:
        yield
    finally:
        _on = was
