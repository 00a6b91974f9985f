"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload mixtral.serve.steady \\
        --seed 7 --seconds 20 --trace 0

The cell (configuration, traffic mix, chips) is looked up by name in
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the window.  The last line of stdout is the result;
the numbers that decided ``correct`` are the last lines of stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--tiny`` is the CPU rehearsal: the
same path at smoke widths (``JAX_PLATFORMS=cpu``), which prints the
result to stderr and exits 1.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at smoke widths")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered requests/s instead of the traffic file's (knee sweep)")
    ap.add_argument("--control", action="store_true",
                    help="judge the float8 reference's choices in place of the served "
                         "tokens (for setting limits; never part of a benchmark run)")
    args = ap.parse_args(argv)

    cell = H.cell(args.workload)
    H.program_path()
    info = H.device_info()
    if not args.tiny:
        H.require_chips(info, cell["workload"]["chips"])
    print(f"compile cache: {H.enable_compile_cache()}", file=sys.stderr, flush=True)
    runner = importlib.import_module("runners." + cell["config"]["runner"])
    result, checks = runner.run(args, cell, T_PROCESS)
    if args.tiny:
        for name, value, limit in checks:
            print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
        print("rehearsal result: " + json.dumps(result), file=sys.stderr, flush=True)
        return 1
    H.require_chips(H.device_info(), cell["workload"]["chips"])
    H.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
