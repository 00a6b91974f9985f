"""Continuous-batching decode service with schedule-regime warm-swap.

``ServeEngine`` turns the repo's round-based serving demos into a
service: an async admission queue feeds a slot-based decode batch, and
the scheduler loop closes over *realized* routing statistics instead of
synthetic demand estimates.

Executable inventory — the whole engine compiles exactly three step
functions, and none of them retrace as requests come and go:

* **prefill** — one jit, one cache entry per prompt-length bucket.
  Prefill is disaggregated from decode (its own executable, its own
  ``ScheduleTable``: the host runtime's plan, re-planned on a cadence
  from aggregated realized decode routing).  Each request prefills at
  batch 1 padded to its bucket; padding KV is masked (``pos = -1``)
  before the row enters the decode cache.
* **decode** — ONE fused executable over the fixed ``decode_slots``
  batch: per-slot position vectors (ragged depths), liveness-masked
  routing stats, greedy sampling, and the device controller's
  observe → score → re-plan transition, all in-graph.  Its schedule is
  the *device* state's table (``DeviceController.table_of``) — distinct
  from the prefill table, re-planned at decode granularity.
* **admit** — one jit that masks a prefilled row's padding positions
  and scatters it into the decode batch's cache at a traced slot index.

Admission is KV-aware: a request whose peak position exceeds the
decode cache is rejected at enqueue (surfaced in metrics), and one that
fits but finds no free slot waits in the length-bucketed queue.

**Spans.**  The host's part of serving runs under profiler annotations
on the trace's own clock (free while no profiler runs), so a trace puts
each device-idle gap down to host work: ``serve.engine.admit`` per
request (``rid``, ``bucket``, ``slot``), and per decode step (``step``)
``serve.engine.decode.inputs`` (copies in), ``.launch`` (the jit call),
``.fetch`` (the wait for the next tokens), ``serve.engine.observe`` (the
host planner) and ``serve.engine.advance`` (the batcher).  Each program
the engine runs is noted in ``repro.serve.metrics`` (shapes only), so a
trace's ops can be put down to the model's scopes.

**Schedule-regime warm-swap.**  With ``regime_slots > 0`` the device
controller state carries a library of pre-planned tables keyed by
normalized traffic shape.  ``capture_regime`` snapshots the *current*
plan + EMA'd realized traffic into the library (the plan was cold-solved
for exactly that regime); ``load_regimes`` pre-plans tables for known
reference regimes.  When routing drifts back into a recognized shape,
the in-graph re-plan warm-swaps the stored plan (a gather) instead of
re-running the batched LAP — and, the regime's circuits being
pre-established, pays no reconfiguration dark window
(``replan_penalty`` exempts warm swaps).
"""

from __future__ import annotations

import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.launch.rules import dtype_policy
from repro.models import Model
from repro.models.moe import expert_path, expert_rows
from repro.scopes import scope
from repro.serve.batcher import ContinuousBatcher
from repro.serve.metrics import ServeMetrics, register_program
from repro.serve.queue import Request, RequestQueue

__all__ = ["ServeEngine", "init_serve_params"]


def init_serve_params(cfg, seed: int = 0) -> dict:
    """Seeded weights in the serving dtype (bf16), initialized and cast
    under one jit, so the f32 initial values never fill the device."""
    dt = dtype_policy(cfg)["serve_param_dtype"]
    model = Model(cfg)
    return jax.jit(
        lambda key: jax.tree.map(lambda a: a.astype(dt), model.init(key))
    )(jax.random.PRNGKey(seed))


class ServeEngine:
    """One model's serving loop (see module docstring).

    ``controller="auto"`` closes the scheduler loop when the config has
    a table-consuming MoE fabric whose expert count divides ``n_ranks``;
    ``"off"`` serves without one (dense archs, static-plan fabrics).
    The regime/penalty knobs reach the device controller config; the
    host-side prefill planner re-plans from realized decode routing
    aggregated every ``host_observe_every`` steps.
    """

    def __init__(
        self,
        cfg,
        params=None,
        *,
        decode_slots: int = 4,
        max_len: int = 64,
        buckets=(8, 16, 32),
        n_ranks: int = 8,
        controller: str = "auto",
        regime_slots: int = 0,
        regime_threshold: float = 0.25,
        replan_penalty: float = 0.0,
        drop_tolerance: float = 0.05,
        hysteresis_steps: int = 1,
        cooldown: int = 2,
        ema: float = 0.5,
        host_observe_every: int = 16,
        plan_overrides: dict | None = None,
        cache_dtype=jnp.bfloat16,
        seed: int = 0,
    ):
        if controller not in ("auto", "off"):
            raise ValueError(f"controller must be 'auto' or 'off', got {controller!r}")
        if max(buckets) > max_len:
            raise ValueError(
                f"largest bucket {max(buckets)} exceeds max_len {max_len}"
            )
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = (
            self.model.init(jax.random.PRNGKey(seed)) if params is None else params
        )
        self.max_len = int(max_len)
        self.host_observe_every = int(host_observe_every)
        self.queue = RequestQueue(buckets)
        self.batcher = ContinuousBatcher(decode_slots, max_len)
        self._metrics = ServeMetrics()
        self._metrics.n_slots = decode_slots
        self._host_swaps = 0
        self._routing_acc: list[np.ndarray] = []
        self._decode_steps = 0
        self._bank_tables: list = []
        self._bank_refs: list[np.ndarray] = []

        # ---------------------------------------------------- controller
        self._runtime = None
        self._ctrl = None
        self._state = None
        self._prefill_table = None
        if controller == "auto" and cfg.moe is not None:
            from repro.parallel.fabric import consumes_table

            if consumes_table(cfg.moe.dispatch):
                self._build_controller(
                    n_ranks=n_ranks,
                    regime_slots=regime_slots,
                    regime_threshold=regime_threshold,
                    replan_penalty=replan_penalty,
                    drop_tolerance=drop_tolerance,
                    hysteresis_steps=hysteresis_steps,
                    cooldown=cooldown,
                    ema=ema,
                    plan_overrides=plan_overrides or {},
                )

        # --------------------------------------------------- executables
        model = self.model
        ctrl = self._ctrl
        self._prefill = jax.jit(model.prefill)

        if ctrl is not None:

            def _decode(params, token, caches, steps, live, state):
                table = ctrl.table_of(state)
                logits, caches, stats = model.decode_step(
                    params, token, caches, steps, schedule=table,
                    collect_stats=True, live=live,
                )
                with scope("logits"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                state = ctrl.step(state, stats["routing"], stats["dropped"])
                return nxt, caches, state, stats["routing"]

        else:

            def _decode(params, token, caches, steps, live):
                del live  # liveness only weights stats; none collected
                logits, caches = model.decode_step(params, token, caches, steps)
                with scope("logits"):
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

        # the runner of a benchmark may wrap ``_decode``; the table of
        # programs needs the jitted function itself
        self._decode = self._decode_jit = jax.jit(_decode)

        def _admit(caches, row, slot, plen):
            # padding KV written by the bucketed prefill carries positions
            # >= plen: mark them empty so decode attention never sees
            # them.  The attention 'pos' leaves are the only integer
            # cache leaves (mamba/rwkv states are float).
            def fix(a):
                if jnp.issubdtype(a.dtype, jnp.integer):
                    return jnp.where(a >= plen, jnp.int32(-1), a)
                return a

            row = jax.tree.map(fix, row)
            return jax.tree.map(
                lambda big, one: jax.lax.dynamic_update_slice_in_dim(
                    big, one.astype(big.dtype), slot, axis=1
                ),
                caches, row,
            )

        self._admit_jit = jax.jit(_admit)
        self._row_template = model.init_cache(1, max_len, cache_dtype)
        self._n_moe_layers = sum(
            cfg.ffn_kind(i) == "moe" for i in range(cfg.n_layers)
        )
        self._caches = model.init_cache(decode_slots, max_len, cache_dtype)

    # ----------------------------------------------------------- controller
    def _build_controller(
        self, *, n_ranks, regime_slots, regime_threshold, replan_penalty,
        drop_tolerance, hysteresis_steps, cooldown, ema, plan_overrides,
    ) -> None:
        from repro.core import (
            DeviceController,
            HierarchicalDeviceController,
            HierarchicalRuntime,
            make_serving_controller,
        )

        # plan_overrides must reach the HOST planner too: the initial
        # device capmat comes from the runtime's first table, so a
        # coarse host plan (training-scale quantum/min_cap) would grant
        # every pair more capacity than smoke-scale decode traffic can
        # ever overflow — and the device controller would never fire
        runtime, _ = make_serving_controller(
            self.cfg, n_ranks=n_ranks, drift="none", ema=ema,
            cooldown=cooldown, replan_penalty=replan_penalty,
            plan_kwargs=plan_overrides or None,
        )
        if runtime is None:  # experts don't divide the rank count
            return
        cfg = self.cfg
        # prime the host planner with a uniform estimate; realized decode
        # routing replaces it on the first observe cadence
        stats0 = np.full(
            (runtime.n_layers, 1, cfg.moe.n_experts),
            float(self.batcher.n_slots * cfg.moe.top_k) / cfg.moe.n_experts,
            np.float32,
        )
        runtime.observe(stats0)
        if isinstance(runtime, HierarchicalRuntime):
            # the composed fabric's two-level controller: regime library
            # and penalty knobs are flat-controller features for now
            ctrl, state = HierarchicalDeviceController.from_runtime(runtime)
        else:
            # plan_overrides tunes the solver's cap granularity
            # (quantum/min_cap/slack): smoke-scale traffic needs finer
            # caps than the training-scale defaults to see drift at all
            ctrl, state = DeviceController.from_runtime(
                runtime,
                drop_tolerance=drop_tolerance,
                hysteresis_steps=hysteresis_steps,
                regime_slots=regime_slots,
                regime_threshold=regime_threshold,
                replan_penalty=replan_penalty,
                **plan_overrides,
            )
        self._runtime = runtime
        self._ctrl = ctrl
        self._state = state
        self._prefill_table = runtime.table()

    @property
    def has_controller(self) -> bool:
        return self._ctrl is not None

    @property
    def regime_capacity(self) -> int:
        cfg = getattr(self._ctrl, "cfg", None)
        return int(getattr(cfg, "regime_slots", 0) or 0)

    def _require_regime_library(self):
        if self._ctrl is None or self.regime_capacity == 0:
            raise ValueError(
                "no regime library: construct the engine with a "
                "table-consuming MoE config and regime_slots > 0"
            )

    def capture_regime(self) -> int:
        """Snapshot the CURRENT plan + EMA'd realized traffic shape into
        the regime library — the plan was cold-solved for exactly this
        regime, so a later warm swap replays it verbatim.  Returns the
        library index."""
        self._require_regime_library()
        tab = self._ctrl.table_of(self._state)
        ref = np.asarray(self._state.smoothed, np.float32).mean(axis=0)
        self._bank_tables.append(
            jax.tree.map(np.asarray, tab)
        )
        self._bank_refs.append(ref)
        self._state = self._ctrl.load_regimes(
            self._state, self._bank_tables, self._bank_refs
        )
        return len(self._bank_tables) - 1

    def load_regimes(self, references) -> None:
        """Pre-plan tables for known reference regimes (``[n, n]``
        traffic matrices in per-step token units, e.g. from historical
        telemetry) and fill the library with them."""
        self._require_regime_library()
        for ref in references:
            self._bank_tables.append(self._plan_table(np.asarray(ref)))
            self._bank_refs.append(np.asarray(ref, np.float32))
        self._state = self._ctrl.load_regimes(
            self._state, self._bank_tables, self._bank_refs
        )

    def _plan_table(self, ref: np.ndarray):
        """Host-plan one regime table with the device controller's exact
        solver knobs, so warm-swapped plans are bit-identical to what the
        cold branch would have produced for the reference traffic."""
        from repro.core import ScheduleTable, greedy_phases_jax

        dcfg = self._ctrl.cfg
        n = dcfg.n_ranks
        if ref.shape != (n, n):
            raise ValueError(f"reference shape {ref.shape} != {(n, n)}")
        traffic = np.broadcast_to(
            ref[None], (self._runtime.n_layers, n, n)
        ).astype(np.float32)
        plan = greedy_phases_jax(
            jnp.asarray(traffic),
            k_max=dcfg.k_max,
            quantum=dcfg.quantum,
            min_cap=dcfg.min_cap,
            slack=dcfg.slack,
            mask=jnp.ones((n, n), bool),
            max_rounds=dcfg.max_rounds,
        )
        return ScheduleTable(
            perms=np.asarray(plan["perms"]),
            caps=np.asarray(plan["caps"]),
            valid=np.asarray(plan["valid"]),
            offsets=np.zeros_like(np.asarray(plan["perms"])),
            n_phases=np.asarray(plan["n_phases"]),
            envelope=dcfg.envelope,
        )

    # -------------------------------------------------------------- serving
    def _prefill_row(self, req: Request, bucket: int):
        """Prefill one request at its bucket length, batch 1."""
        plen = req.prefill_len
        row = self._row_template
        if plen > 0:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt[:-1]
            args = (self.params, jnp.asarray(padded), row)
            register_program(
                self._prefill, *args, variant=bucket, schedule=self._prefill_table
            )
            _, row = self._prefill(*args, schedule=self._prefill_table)
            if self._n_moe_layers:
                computed, routed = expert_rows(self.cfg, bucket, self._prefill_table)
                self._metrics.record_expert_rows(
                    computed * self._n_moe_layers, routed * self._n_moe_layers
                )
        return row, plen

    def _admit_ready(self, step_no: int, wall: float) -> None:
        """Admit queued requests into free slots (KV already checked at
        enqueue: anything in the queue fits a slot's cache)."""
        while len(self.queue):
            slot = self.batcher.free_slot()
            if slot is None:
                return
            with TraceAnnotation("serve.engine.admit") as span:
                req, bucket = self.queue.pop()
                meta = {"rid": req.rid, "bucket": bucket, "slot": slot}
                if self._n_moe_layers:
                    meta["expert_path"] = expert_path(
                        self.cfg, bucket, self._prefill_table
                    )
                span.set_metadata(**meta)
                row, plen = self._prefill_row(req, bucket)
                args = (self._caches, row, jnp.int32(slot), jnp.int32(plen))
                register_program(self._admit_jit, *args)
                self._caches = self._admit_jit(*args)
                del args  # the old cache, before the next prefill
                self.batcher.admit(slot, req)
            req.admit_step = step_no
            req.admit_wall = wall
            self._metrics.record_admitted(req, step_no)

    def _decode_once(self) -> np.ndarray:
        """One fused decode step over the slot batch; returns the next
        token per slot (garbage on vacant slots — never read)."""
        n = self._decode_steps
        self._decode_steps += 1
        with TraceAnnotation("serve.engine.decode.inputs", step=n):
            token = jnp.asarray(self.batcher.token)
            steps = jnp.asarray(self.batcher.step)
            live = jnp.asarray(self.batcher.live)
        state = () if self._ctrl is None else (self._state,)
        register_program(
            self._decode_jit, self.params, token, self._caches, steps, live, *state
        )
        with TraceAnnotation("serve.engine.decode.launch", step=n):
            out = self._decode(self.params, token, self._caches, steps, live, *state)
        if self._ctrl is not None:
            nxt, self._caches, self._state, routing = out
            with TraceAnnotation("serve.engine.decode.fetch", step=n):
                self._routing_acc.append(np.asarray(routing))
                nxt = np.asarray(nxt)
            if len(self._routing_acc) >= self.host_observe_every:
                with TraceAnnotation("serve.engine.observe", step=n):
                    self._host_observe()
            return nxt
        nxt, self._caches = out
        with TraceAnnotation("serve.engine.decode.fetch", step=n):
            return np.asarray(nxt)

    def _host_observe(self) -> None:
        """Feed aggregated realized decode routing to the host planner —
        the prefill table's re-plan loop (real stats, not estimates)."""
        avg = np.mean(np.stack(self._routing_acc), axis=0)
        self._routing_acc.clear()
        decision = self._runtime.observe(avg)
        if decision.changed:
            self._prefill_table = self._runtime.table()
            self._host_swaps += 1

    def run(self, requests, *, continuous: bool = True, max_steps: int = 100_000):
        """Serve ``requests`` (arrival in decode-step units) to completion.

        ``continuous=False`` is the fixed-round baseline: admission only
        when the batch is EMPTY, so every round drains fully before the
        next one seats — the pre-engine ``examples/serve_decode.py``
        behavior, kept as the benchmark's comparison point.
        Returns the metrics summary (also available via ``metrics()``).
        """
        m = self._metrics
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        m.record_offered(len(pending))
        step_no = 0
        t0 = time.perf_counter()
        while pending or len(self.queue) or self.batcher.n_live:
            if step_no >= max_steps:
                raise RuntimeError(f"serve loop exceeded {max_steps} steps")
            while pending and pending[0].arrival <= step_no:
                req = pending.popleft()
                if req.kv_tokens > self.max_len or not self.queue.add(req):
                    m.record_rejected(req, "capacity")
            if continuous or self.batcher.n_live == 0:
                self._admit_ready(step_no, time.perf_counter())
            if self.batcher.n_live == 0:
                m.record_idle_step()  # waiting on future arrivals
                step_no += 1
                continue
            m.record_decode_step(self.batcher.n_live)
            nxt = self._decode_once()
            for req in self.batcher.advance(nxt, time.perf_counter()):
                m.record_finished(req)
            step_no += 1
        m.wall_s = time.perf_counter() - t0
        return self.metrics()

    # -------------------------------------------------------------- metrics
    def metrics(self) -> dict:
        def cache_size(fn):
            return int(getattr(fn, "_cache_size", lambda: 1)())

        out = {
            "serve": self._metrics.summary(),
            "compile": {
                "decode_executables": cache_size(self._decode),
                "prefill_executables": cache_size(self._prefill),
                "admit_executables": cache_size(self._admit_jit),
            },
        }
        if self._ctrl is not None:
            out["controller"] = {
                **self._ctrl.metrics(self._state),
                "host_replans": self._runtime.summary()["replan_events"],
                "host_prefill_swaps": self._host_swaps,
            }
        return out
