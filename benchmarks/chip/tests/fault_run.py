"""Run a cell's CPU rehearsal with a fault planted under the timed path.

    python3 fault_run.py <fault> --workload ... --seed ... --seconds ... --tiny

The fault is patched into the program before the run; the run's
rehearsal result (stderr) then says whether ``correct`` caught it.  The
training cell's faults need four devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""

from __future__ import annotations

import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def altered_token():
    """Each decode step's tokens are replaced by the next id."""
    from repro.serve import engine

    orig = engine.ServeEngine._decode_once

    def decode_once(self):
        return (orig(self) + 1) % self.cfg.vocab_size

    engine.ServeEngine._decode_once = decode_once


def stale_cache():
    """The decode step returns the cache it was given, unwritten."""
    from repro.models import model

    orig = model.Model.decode_step

    @functools.wraps(orig)
    def decode_step(self, params, token, caches, step, **kw):
        out = orig(self, params, token, caches, step, **kw)
        return (out[0], caches, *out[2:])

    model.Model.decode_step = decode_step


def unchanged_state():
    """The step returns the parameters and optimizer state it was given."""
    from repro.train import train_step as ts

    orig = ts.make_train_step

    @functools.wraps(orig)
    def make(*a, **kw):
        step = orig(*a, **kw)

        def same(params, opt_state, ef_state, batch, schedule=None):
            out = step(params, opt_state, ef_state, batch, schedule)
            return (params, opt_state, ef_state, *out[3:])

        return same

    ts.make_train_step = make


def half_batch():
    """The loss and its gradient over the first half of the sequences."""
    from repro.models import model

    orig = model.Model.loss_and_stats

    def loss_and_stats(self, params, batch, **kw):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, params, half, **kw)

    model.Model.loss_and_stats = loss_and_stats


def no_exchange():
    """The phases carry nothing between chips: each rank's tokens reach
    only its own experts."""
    import jax.numpy as jnp

    from repro.parallel.fabric.phase_pipelined import PhasePipelinedFabric as F

    def transfer(self, ctx, row, k, region, vregion, meta):
        return jnp.zeros_like(region), jnp.zeros(vregion.shape, bool)

    def transfer_back(self, ctx, row, k, y_k, meta):
        return jnp.zeros_like(y_k)

    F._transfer, F._transfer_back = transfer, transfer_back


FAULTS = {f.__name__: f for f in (altered_token, stale_cache, unchanged_state, half_batch,
                                   no_exchange)}

if __name__ == "__main__":
    name = sys.argv.pop(1)
    if name != "none":
        FAULTS[name]()
    import run

    sys.exit(run.main(sys.argv[1:]))
