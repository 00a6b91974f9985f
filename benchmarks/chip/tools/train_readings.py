"""The training check's readings over many seeds, in one process: what
the limits of ``checks/mixtral.train.ep4.skewed.json`` are set from.

    python3 benchmarks/chip/tools/train_readings.py --seeds 11 12 13 \\
        --control-seeds 11 12 13 [--out reports/train_readings.jsonl] \\
        [--survey-seeds 1 2 3 ... [--write-envelope]]

For each seed of ``--seeds``: the program's first steps as the cell's
set-up runs them, and the plain reference's; for each seed of
``--control-seeds`` also the reference put in the program's place in
float8 (the control) and with each fault of ``reference.mixtral_train``
planted.  Every reading is compared with the reference by the runner's
own ``compare``, and printed as one JSON line per seed and kind.  No
window runs.  ``--survey-seeds`` first sets the table's envelope as
``tools/train_envelope.py`` does, in the same process.  ``--tiny``: the
CPU rehearsal (four devices emulated).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402

WORKLOAD = "mixtral.train.ep4.skewed"
KINDS = (("control", {"low": True}), ("no_exchange", {"fault": "no_exchange"}),
         ("half_batch", {"fault": "half_batch"}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--survey-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--write-envelope", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    H.program_path()
    cell = H.cell(WORKLOAD)
    if not args.tiny:
        H.require_chips(H.device_info(), cell["workload"]["chips"])
    print(f"compile cache: {H.enable_compile_cache()}", file=sys.stderr, flush=True)
    import jax

    from runners import train as TR
    from tools import train_envelope

    if args.survey_seeds:
        train_envelope.settle(cell, args.survey_seeds, args.tiny, args.write_envelope)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a") if args.out else None
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        t0 = time.perf_counter()
        run_args = argparse.Namespace(seed=seed, tiny=args.tiny, trace=0)
        su = TR.setup(run_args, cell)
        prog, pool, hf, o, reference = su.readings, su.pool, su.hf, su.opt_conf, su.ref
        n_ranks, cut, dropped = su.mesh.shape["model"], su.cut, su.dropped
        del su
        gc.collect()
        devs = jax.devices()
        ref = TR.reference_readings(reference, seed, hf, o, pool, devs, n_ranks)
        rows = [("program", prog)]
        if seed in args.control_seeds:
            rows += [(k, TR.reference_readings(reference, seed, hf, o, pool, devs, n_ranks, **kw))
                     for k, kw in KINDS]
        for kind, got in rows:
            numbers, left_out = TR.compare(got, ref)
            line = {"seed": seed, "kind": kind, **numbers, "left_out": left_out,
                    "losses": got["loss"], "reference_losses": ref["loss"]}
            if kind == "program":
                line.update(cut_choices=cut, dropped=dropped)
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
