"""Run a cell's CPU rehearsal with a fault planted under the timed path.

    python3 fault_run.py <fault> --workload ... --seed ... --seconds ... --tiny

The fault is patched into the program before the run; the run's
rehearsal result (stderr) then says whether ``correct`` caught it.
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


FAULTS = {f.__name__: f for f in (altered_token, stale_cache)}

if __name__ == "__main__":
    name = sys.argv.pop(1)
    if name != "none":
        FAULTS[name]()
    import run

    sys.exit(run.main(sys.argv[1:]))
