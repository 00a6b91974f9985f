"""Serving telemetry: admission counters, queue waits, and latency /
throughput percentiles; and the table of the engine's device programs,
from which a profiler trace's ops are put down to the model's scopes.

``ServeMetrics`` is host-side bookkeeping over completed lifecycle
events; nothing touches the device.  Queue waits are recorded in
*virtual* decode-step units (deterministic under any host speed);
per-request throughput uses real wall timestamps.

The engine notes each program it runs (``register_program``: the jitted
function, weakly, and its arguments' shapes, dtypes and shardings, no
buffer).  ``op_scopes(module)`` compiles the program for those
arguments when first asked, and reads its instructions' scopes
(``repro.launch.hlo.op_scopes``); an untraced run never asks.
"""

from __future__ import annotations

import weakref

import jax
import numpy as np

from repro.serve.queue import Request

__all__ = ["ServeMetrics", "op_scopes", "percentiles", "programs", "register_program"]


def percentiles(xs, ps=(50, 99)) -> dict:
    """{"p50": ..., "p99": ..., "mean": ...} over ``xs`` (0s if empty)."""
    a = np.asarray(list(xs), np.float64)
    if a.size == 0:
        return {**{f"p{p}": 0.0 for p in ps}, "mean": 0.0}
    out = {f"p{p}": float(np.percentile(a, p)) for p in ps}
    out["mean"] = float(a.mean())
    return out


class ServeMetrics:
    """Accumulates one engine run's serving telemetry."""

    def __init__(self):
        self.offered = 0
        self.admitted = 0
        self.rejected = 0  # never schedulable: too long for buckets/KV
        self.completed = 0
        self.queue_wait_steps: list[float] = []
        self.request_tok_s: list[float] = []
        self.request_latency_s: list[float] = []
        self.generated_tokens = 0
        self.decode_steps = 0
        self.idle_steps = 0
        self.live_slot_steps = 0  # sum of live counts over decode steps
        self.n_slots = 0
        self.wall_s = 0.0
        # prefill expert GEMM rows, over the MoE layers of each admission
        self.expert_rows_computed = 0
        self.expert_rows_routed = 0

    # ------------------------------------------------------------- events
    def record_offered(self, n: int = 1) -> None:
        self.offered += n

    def record_rejected(self, req: Request, reason: str) -> None:
        del req, reason  # reasons are uniform for now; counter suffices
        self.rejected += 1

    def record_admitted(self, req: Request, step_no: int) -> None:
        self.admitted += 1
        self.queue_wait_steps.append(float(step_no - req.arrival))

    def record_expert_rows(self, computed: int, routed: int) -> None:
        self.expert_rows_computed += int(computed)
        self.expert_rows_routed += int(routed)

    def record_decode_step(self, n_live: int) -> None:
        self.decode_steps += 1
        self.live_slot_steps += int(n_live)

    def record_idle_step(self) -> None:
        self.idle_steps += 1

    def record_finished(self, req: Request) -> None:
        self.completed += 1
        self.generated_tokens += len(req.tokens)
        if req.admit_wall is not None and req.finish_wall is not None:
            dt = max(req.finish_wall - req.admit_wall, 1e-9)
            self.request_latency_s.append(dt)
            self.request_tok_s.append(len(req.tokens) / dt)

    # ------------------------------------------------------------ summary
    def summary(self) -> dict:
        return {
            "requests": {
                "offered": self.offered,
                "admitted": self.admitted,
                "rejected": self.rejected,
                "completed": self.completed,
            },
            "queue_wait_steps": percentiles(self.queue_wait_steps),
            "request_tok_s": percentiles(self.request_tok_s),
            "request_latency_s": percentiles(self.request_latency_s),
            "throughput_tok_s": self.generated_tokens / max(self.wall_s, 1e-9),
            "generated_tokens": self.generated_tokens,
            "decode_steps": self.decode_steps,
            "idle_steps": self.idle_steps,
            "occupancy": self.live_slot_steps
            / max(self.decode_steps * max(self.n_slots, 1), 1),
            "expert_rows_computed": self.expert_rows_computed,
            "expert_rows_routed": self.expert_rows_routed,
            # routed rows over rows the prefills' expert GEMMs computed
            "expert_row_fill": self.expert_rows_routed
            / max(self.expert_rows_computed, 1),
        }


# ------------------------------------------------------------- programs
class _Program:
    __slots__ = ("fn", "args", "kwargs", "table")

    def __init__(self, fn, args, kwargs):
        self.fn = weakref.ref(fn)
        self.args, self.kwargs = args, kwargs
        self.table: dict[str, str] | None = None


# (module name, variant) -> the program last run under that name
_PROGRAMS: dict[tuple[str, object], _Program] = {}


def _spec(a):
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding, weak_type=a.weak_type)
    if isinstance(a, np.ndarray):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


def register_program(fn, *args, variant=None, **kwargs) -> None:
    """Note that the jitted ``fn`` runs with these arguments, under the
    module name its trace events carry (``jit_<name>``).  ``variant``
    tells apart executables of one function (a prefill bucket).  Costs a
    dict lookup once noted."""
    key = (f"jit_{fn.__name__}", variant)
    prog = _PROGRAMS.get(key)
    if prog is not None and prog.fn() is fn:
        return
    args, kwargs = jax.tree.map(_spec, (args, kwargs))
    _PROGRAMS[key] = _Program(fn, args, kwargs)


def programs() -> list[tuple[str, object]]:
    """(module, variant) of every noted program whose function lives."""
    return [k for k, p in _PROGRAMS.items() if p.fn() is not None]


def op_scopes(module: str, variant=None) -> dict[str, str]:
    """{instruction: scope} of the live program noted under ``module``
    (``repro.launch.hlo.op_scopes`` of its compiled text), built on the
    first call.  Raises KeyError if there is none, ValueError if
    ``variant`` is needed to choose among several."""
    keys = [k for k in programs() if k[0] == module and (variant is None or k[1] == variant)]
    if not keys:
        raise KeyError(f"no live program {module!r} (variant {variant!r})")
    if len(keys) > 1:
        raise ValueError(f"{module!r} names {len(keys)} executables; give a variant: {keys}")
    prog = _PROGRAMS[keys[0]]
    if prog.table is None:
        from repro.launch.hlo import op_scopes as hlo_op_scopes

        text = prog.fn().lower(*prog.args, **prog.kwargs).compile().as_text()
        head = text.split(",", 1)[0].split()
        if head[:2] != ["HloModule", module]:
            raise ValueError(f"compiled {head[1:2]}, not {module!r}")
        prog.table = hlo_op_scopes(text)
    return prog.table
