"""Fault-tolerant training loop.

Production behaviors exercised here (and tested in multidev_train.py):
* state placed on the active mesh from the start: parameters, moments
  and error-feedback buffers are initialized under one jit straight into
  their ``param_specs`` shardings, so no device ever holds the whole
  unsharded state,
* resume-from-latest on start (elastic: restore works across mesh shapes
  because checkpoints are stored unsharded; the new mesh's shardings are
  applied at device_put); ``ckpt_dir=None`` runs without checkpoints,
* periodic async checkpointing off the critical path,
* retry-on-failure: a step that raises (injected in tests; an XLA/ICI
  error in production) rolls back to the last checkpoint and continues,
* deterministic data: batch(step) is pure, so replayed steps see
  identical data,
* a non-finite loss consumes the same failure budget as a crashed step
  (``NonFiniteLossError`` -> rollback); donated optimizer state would
  otherwise carry the NaN forward forever,
* degraded-fabric fallback: with a runtime whose ``fallback_chain`` is
  set, hard fabric faults (``FabricFaultError``) quarantine the active
  backend, re-plan around the fault's link mask, and the loop rebuilds
  its step on the next fabric in the chain (a deliberate, counted
  recompile — ``controller.fabric_switches``), probing back to the
  preferred backend once the runtime's health FSM recovers
  (docs/robustness.md),
* straggler note: SPMD steps are globally synchronous, so per-step
  stragglers surface as slow steps, not divergence; mitigation at this
  layer = checkpoint + restart excluding the slow host (elastic restore),
  plus the async checkpointer never blocking the step.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core.faults import FabricFaultError, NonFiniteLossError
from repro.data import DataConfig, SyntheticStream
from repro.optim import AdamW, cosine_schedule, ef_int8_init
from repro.parallel.fabric import (
    consumes_schedule as _fabric_consumes,
    consumes_table as _fabric_consumes_table,
)
from repro.parallel.sharding import current_rules
from repro.train.train_step import make_train_step, param_specs

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_dir: str | None = "/tmp/repro_ckpt"  # None: no checkpoints
    ckpt_every: int = 50
    keep: int = 3
    microbatches: int = 1
    peak_lr: float = 3e-4
    warmup: int = 20
    grad_compress: str | None = None
    # failure budget: consecutive failed attempts.  The counter resets as
    # soon as the run progresses past the step that failed (NOT on any
    # replayed pre-failure step — a persistently failing step must still
    # exhaust the budget), so transient faults spread across a long run
    # never kill it; only a genuinely stuck step does.
    max_failures: int = 3
    log_every: int = 10


def train_loop(
    model,
    data_cfg: DataConfig,
    loop_cfg: TrainLoopConfig,
    *,
    shard_batch: Callable | None = None,
    failure_hook: Callable[[int], None] | None = None,
    runtime=None,
    stats_hook: Callable | None = None,
    device_controller=None,
    device_ctrl_state=None,
) -> dict:
    """Run (or resume) training.  Returns final metrics/history, the
    final state and the jitted step.

    shard_batch: optional fn(dict of np arrays) -> device arrays with the
      mesh's batch sharding (identity when single-device).
    failure_hook: test hook called before each step; may raise to inject
      a failure.
    runtime: optional ``core.ScheduleRuntime`` closing the controller
      loop: the step function emits per-layer realized routing counts,
      the loop host-fetches the *previous* step's counts (never blocking
      on in-flight work) and feeds them to ``runtime.observe``; when the
      decision swaps schedules, the loop fetches the re-planned
      ``ScheduleTable`` and passes it to the SAME jitted step — the
      schedule is traced input, so drift swaps perform zero recompiles
      (asserted via the executable cache size in ``controller.compiles``).
    stats_hook: optional fn(step, stats) -> stats applied to the observed
      routing counts before ``runtime.observe`` (drift injection in tests
      and the drift-scenario examples).
    device_controller + device_ctrl_state: a ``core.DeviceController``
      and its initial ``DeviceControllerState`` select the device-resident
      controller instead of ``runtime``: the observe -> score -> re-plan
      loop runs *inside* the jitted step (``lax.cond`` fires the batched
      JAX LAP re-plan on traced drift), so routing stats never cross to
      the host on steady-state steps.  The host reads controller
      telemetry (``DeviceController.metrics``) only on the logging
      cadence.  Mutually exclusive with ``runtime``/``stats_hook`` (the
      host-driven path stays available as the parity oracle).
    """
    if device_controller is not None:
        if runtime is not None:
            raise ValueError(
                "device_controller and runtime are mutually exclusive: "
                "the device controller replaces the host observe loop "
                "(keep the runtime path as a separate parity run)"
            )
        if stats_hook is not None:
            raise ValueError(
                "stats_hook needs host-fetched routing stats; the device "
                "controller never surfaces them — inject drift through "
                "the data stream instead"
            )
        if device_ctrl_state is None:
            raise ValueError(
                "device_controller needs an initial state: build one via "
                "DeviceController.init_state or .from_runtime"
            )
    stream = SyntheticStream(data_cfg)
    opt = AdamW(
        lr=cosine_schedule(loop_cfg.peak_lr, loop_cfg.warmup, loop_cfg.steps)
    )

    def build_step(m):
        return jax.jit(
            make_train_step(
                m,
                opt,
                microbatches=loop_cfg.microbatches,
                grad_compress=loop_cfg.grad_compress,
                collect_routing=runtime is not None,
                controller=device_controller,
            ),
            donate_argnums=(0, 1, 2),
        )

    # does the configured fabric execute a planned schedule?  Resolved
    # through the fabric registry (unknown dispatch names fail fast here,
    # listing the registered backends, instead of max_failures+1 times
    # inside the jitted step).
    moe_cfg = getattr(model.cfg, "moe", None)
    consumes_schedule = moe_cfg is not None and _fabric_consumes(
        moe_cfg.dispatch
    )
    schedule = None
    if device_controller is not None:
        if not consumes_schedule or not _fabric_consumes_table(
            moe_cfg.dispatch if moe_cfg is not None else ""
        ):
            raise ValueError(
                "device_controller needs a table-consuming fabric "
                "('phase_pipelined' or 'ragged_a2a'): the in-graph re-plan "
                "writes new schedule arrays into the SAME executable"
            )
    elif runtime is not None and consumes_schedule:
        # fail fast: config errors, not transient faults — left to the
        # step function they would trace-fail max_failures+1 times.
        if not _fabric_consumes_table(moe_cfg.dispatch):
            raise ValueError(
                f"{moe_cfg.dispatch!r} bakes its schedule into the "
                "executable — a controller runtime cannot swap its plans "
                "without recompiling; use the 'phase_pipelined' or "
                "'ragged_a2a' fabric for runtime-driven swaps, or drop "
                "the runtime and pass a static schedule via Model"
            )
        # The runtime MUST be primed here even if the model carries a
        # static schedule: the step compiles against the table's pytree
        # structure from step 0, so a later None -> table transition
        # would retrace — the recompile the traced path exists to avoid.
        if runtime.schedules is None:
            raise ValueError(
                f"{moe_cfg.dispatch!r} dispatch with a runtime needs a "
                "primed runtime before the first step "
                "(ScheduleRuntime.prime), so drift swaps stay "
                "compile-free from step 0"
            )
        schedule = runtime.table()
    elif consumes_schedule and model.schedule is None:
        raise ValueError(
            f"{moe_cfg.dispatch!r} dispatch needs a schedule before the "
            "first step: prime the runtime (ScheduleRuntime.prime) or "
            "pass a Model with an initial schedule"
        )
    # degraded-fabric fallback: validate the declared chain up front —
    # config errors, not transient faults (same fail-fast rationale as
    # the dispatch checks above)
    chain = runtime.cfg.fallback_chain if runtime is not None else ()
    if chain:
        if moe_cfg is None:
            raise ValueError(
                "fallback_chain needs an MoE model (no moe config found)"
            )
        if chain[0] != moe_cfg.dispatch:
            raise ValueError(
                f"fallback_chain must start at the configured dispatch: "
                f"chain {chain} vs dispatch {moe_cfg.dispatch!r}"
            )
        for fname in chain:
            if _fabric_consumes(fname) and not _fabric_consumes_table(fname):
                raise ValueError(
                    f"fallback_chain entry {fname!r} bakes its schedule "
                    "into the executable — the FSM cannot swap onto it "
                    "mid-run; chain table-consuming or schedule-free "
                    "fabrics only"
                )
    current_dispatch = moe_cfg.dispatch if moe_cfg is not None else None
    # ONE executable for the whole run: the schedule is traced input
    # (ScheduleTable), so controller swaps pass new arrays into the same
    # compiled step.  There is no per-assignment compile cache anymore.
    # (Degradation-chain fabric switches are the exception: each rebuilds
    # the step on a different backend — a deliberate, counted recompile.)
    step_fn = build_step(model)
    manager = (
        CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep)
        if loop_cfg.ckpt_dir
        else None
    )

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        ef_state = (
            ef_int8_init(params) if loop_cfg.grad_compress == "ef8" else {}
        )
        return {"params": params, "opt": opt_state, "ef": ef_state}

    # On a mesh every state leaf takes its parameter's sharding (moments
    # and error feedback mirror the parameter paths; the step counter is
    # replicated).  Off-mesh: the default device.
    ar = current_rules()
    shardings = None
    if ar is not None and ar.mesh is not None:
        shardings = jax.tree.map(
            lambda spec: NamedSharding(ar.mesh, spec),
            param_specs(jax.eval_shape(init_state)),
            is_leaf=lambda x: isinstance(x, P),
        )
    fresh_state = jax.jit(init_state, out_shardings=shardings)

    def restore_latest(template):
        if manager is None:
            return None, None
        ck_step, restored = manager.restore_latest(template)
        if restored is not None and shardings is not None:
            restored = jax.device_put(restored, shardings)
        return ck_step, restored

    state = fresh_state()
    start_step, restored = restore_latest(state)
    if restored is not None:
        state = restored
        log.info("resumed from step %d", start_step)
    else:
        start_step = 0

    if shard_batch is None:
        shard_batch = lambda b: b

    history = []
    failures = 0  # total over the run (reported)
    consecutive_failures = 0  # the retry budget (resets on progress)
    last_failure_step = -1
    step = start_step
    swaps = 0
    fabric_switches = 0  # degradation-chain step rebuilds (recompiles)
    cache_fn = getattr(step_fn, "_cache_size", lambda: 1)
    # executable count at the first swap: any growth beyond it is a
    # swap-attributable recompile.  (The first couple of steps may compile
    # twice anyway while donated-param shardings converge on a mesh —
    # that's jit warmup, not the controller's doing.)
    pre_swap_cache = None
    pending_routing = None  # previous step's routing counts (device)
    pending_loss = None  # previous step's loss scalar (device)
    last_loss = None  # previous step's loss, host-fetched (FSM input)
    # device-controller mode: executable count after jit warmup — any
    # growth past it would mean an in-graph re-plan retraced (contract: 0)
    device_cache_base = None

    def switch_fabric(want: str) -> None:
        """Rebuild the step on another fabric of the degradation chain.

        The model facade is immutable, so the switch is a rebuilt facade
        + a fresh jit — the ONE kind of mid-run recompile this loop
        performs on purpose (counted in ``fabric_switches``; the
        zero-recompile contract of schedule swaps is tracked per
        executable, so the cache baseline resets here too)."""
        nonlocal model, step_fn, cache_fn, pre_swap_cache
        nonlocal consumes_schedule, schedule, current_dispatch, fabric_switches
        new_cfg = dataclasses.replace(
            model.cfg, moe=dataclasses.replace(model.cfg.moe, dispatch=want)
        )
        model = type(model)(new_cfg, model.schedule)
        step_fn = build_step(model)
        cache_fn = getattr(step_fn, "_cache_size", lambda: 1)
        pre_swap_cache = None
        consumes_schedule = _fabric_consumes(want)
        schedule = (
            runtime.table()
            if (consumes_schedule and _fabric_consumes_table(want))
            else (model.schedule if consumes_schedule else None)
        )
        current_dispatch = want
        fabric_switches += 1

    t_last = time.perf_counter()
    steps_since_log = 0
    while step < loop_cfg.steps:
        try:
            if failure_hook is not None:
                failure_hook(step)
            if pending_loss is not None:
                # same off-critical-path contract as pending_routing: the
                # previous step's device work already finished, so this
                # fetch never blocks.  A NaN/Inf here consumes the
                # failure budget like a crash — donated state means the
                # poisoned params are already gone; rollback is the only
                # way back.
                last_loss = float(np.asarray(pending_loss))
                pending_loss = None
                if not np.isfinite(last_loss):
                    raise NonFiniteLossError(
                        f"step {step - 1} produced non-finite loss "
                        f"{last_loss}; rolling back to the last checkpoint"
                    )
            if runtime is not None and pending_routing is not None:
                # Observe the PREVIOUS step's realized routing: its device
                # computation already finished, so the host fetch never
                # blocks on in-flight work (off the critical path).
                stats = pending_routing["routing"]
                dropped = pending_routing["dropped"]
                pending_routing = None
                if stats_hook is not None:
                    # the hook's contract is numpy in / numpy out — fetch
                    # here (fetch_us then reads ~0 inside observe)
                    stats = stats_hook(
                        step, np.asarray(stats, dtype=np.float64)
                    )
                # device arrays pass through: runtime.observe does the
                # host fetch itself and times it as fetch_us_per_step,
                # keeping the host-vs-device observe cost attributable
                decision = runtime.observe(
                    stats, dropped=dropped, loss=last_loss
                )
                if decision.changed:
                    swaps += 1
                    if consumes_schedule:
                        if pre_swap_cache is None:
                            pre_swap_cache = cache_fn()
                        # new table arrays, same shapes, same executable
                        schedule = runtime.table()
                    log.info(
                        "step %d: controller swap (%s; %s)",
                        step,
                        "library miss" if decision.replanned else "library hit",
                        ",".join(decision.actions),
                    )
            if runtime is not None and chain:
                # the health FSM may have moved along the degradation
                # chain (quarantine, or a backoff probe restoring the
                # preferred backend)
                want = runtime.active_fabric()
                if want is not None and want != current_dispatch:
                    log.info(
                        "step %d: degradation chain %s -> %s (%s)",
                        step,
                        current_dispatch,
                        want,
                        runtime.health_state,
                    )
                    switch_fabric(want)
            batch = shard_batch(stream.batch(step))
            if device_controller is not None:
                # fused step: schedule derivation, the observe -> score ->
                # re-plan loop, and the drift-conditional LAP all run
                # in-graph — no routing stats reach the host here
                params, opt_state, ef_state, device_ctrl_state, metrics = (
                    step_fn(
                        state["params"],
                        state["opt"],
                        state["ef"],
                        batch,
                        device_ctrl_state,
                    )
                )
            else:
                params, opt_state, ef_state, metrics = step_fn(
                    state["params"], state["opt"], state["ef"], batch, schedule
                )
            state = {"params": params, "opt": opt_state, "ef": ef_state}
            if device_controller is not None and device_cache_base is None:
                device_cache_base = cache_fn()
            if runtime is not None:
                pending_routing = metrics.pop("moe_stats")
            pending_loss = metrics["loss"]
            if step == loop_cfg.steps - 1:
                # the deferred check would miss the final step: fetch it
                # synchronously (we're at the end; nothing left to overlap)
                last_loss = float(np.asarray(pending_loss))
                pending_loss = None
                if not np.isfinite(last_loss):
                    raise NonFiniteLossError(
                        f"step {step} produced non-finite loss {last_loss}; "
                        "rolling back to the last checkpoint"
                    )
            if step >= last_failure_step:
                # progressed past the failing step: the fault was transient
                consecutive_failures = 0
        except Exception as err:  # roll back to last checkpoint, retry
            failures += 1
            consecutive_failures += 1
            last_failure_step = step
            if consecutive_failures > loop_cfg.max_failures:
                raise
            log.warning("step %d failed (%s); restoring last checkpoint", step, err)
            if runtime is not None and isinstance(err, FabricFaultError):
                # a hard fabric fault: quarantine the backend and re-plan
                # around the fault's link mask before the retry (the
                # rolled-back step then executes a plan the fabric can
                # honor — bounded by the same failure budget)
                runtime.record_fault(err)
            if manager is not None:
                manager.wait()
            template = fresh_state()
            ck_step, restored = restore_latest(template)
            if restored is not None:
                state, step = restored, ck_step
            else:
                state, step = template, 0
            # replayed steps re-log: drop history at/after the restored
            # step so the returned history has no duplicate step numbers
            history = [h for h in history if h["step"] < step]
            pending_routing = None
            pending_loss = None
            last_loss = None
            if runtime is not None and chain:
                want = runtime.active_fabric()
                if want is not None and want != current_dispatch:
                    log.info(
                        "step %d: degradation chain %s -> %s (%s)",
                        step,
                        current_dispatch,
                        want,
                        runtime.health_state,
                    )
                    switch_fabric(want)
                elif consumes_schedule and _fabric_consumes_table(
                    current_dispatch
                ):
                    # no fabric change, but record_fault may have swapped
                    # in a masked plan — refresh the traced table
                    schedule = runtime.table()
            t_last = time.perf_counter()
            steps_since_log = 0
            continue

        steps_since_log += 1
        if step % loop_cfg.log_every == 0 or step == loop_cfg.steps - 1:
            loss = float(metrics["loss"])
            now = time.perf_counter()
            dt_step = (now - t_last) / steps_since_log
            t_last = now
            steps_since_log = 0
            entry = {"step": step, "loss": loss, "dt_s": dt_step}
            if device_controller is not None:
                # the ONE place routing telemetry crosses to the host in
                # device-controller mode: the explicit logging cadence
                dm = device_controller.metrics(device_ctrl_state)
                entry["device_replans"] = dm["device_replans"]
                entry["drop_fraction"] = dm["drop_fraction"]
            history.append(entry)
            log.info("step %d loss %.4f (%.3fs/step)", step, loss, dt_step)
        step += 1
        if manager is not None and (
            step % loop_cfg.ckpt_every == 0 or step == loop_cfg.steps
        ):
            manager.save_async(step, state)
    if manager is not None:
        manager.wait()
    out = {
        "history": history,
        "final_step": step,
        "failures": failures,
        "final_loss": history[-1]["loss"] if history else float("nan"),
        # the trained state and the jitted step that produced it (its
        # compiled program: ``step_fn.lower(...).compile()`` hits the
        # executable cache)
        "state": state,
        "step_fn": step_fn,
    }
    if runtime is not None:
        # honest compile count, read off the jit executable cache:
        # growth after the first swap is a swap-driven recompile.  With
        # traced schedule tables this must stay 0 (regression-tested).
        compiles = (
            max(0, cache_fn() - pre_swap_cache)
            if pre_swap_cache is not None
            else 0
        )
        out["controller"] = {
            **runtime.metrics(),
            "swaps": swaps,
            "compiles": compiles,
            "fabric_switches": fabric_switches,
            "final_dispatch": current_dispatch,
        }
    elif device_controller is not None:
        # same honesty for the fused path: executable-cache growth after
        # warmup would mean an in-graph re-plan retraced — contract is 0
        compiles = (
            max(0, cache_fn() - device_cache_base)
            if device_cache_base is not None
            else 0
        )
        out["controller"] = {
            **device_controller.metrics(device_ctrl_state),
            "mode": "device",
            "compiles": compiles,
            "final_dispatch": current_dispatch,
        }
        out["device_ctrl_state"] = device_ctrl_state
    return out
