"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Modules that need heavy
compile steps (roofline over the 512-device mesh) are run separately via
``python -m benchmarks.roofline``; the default run stays laptop-friendly.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig1_compute_knee,
        fig2_matchings,
        fig3_small_batch,
        fig4_large_batch,
    )

    from benchmarks import a2a_hlo, bench_scheduler, overlap_model

    modules = [
        ("fig1", fig1_compute_knee.run),
        ("fig2", fig2_matchings.run),
        ("fig3", fig3_small_batch.run),
        ("fig4", fig4_large_batch.run),
        ("overlap_model", overlap_model.run),
        ("a2a_hlo", a2a_hlo.run),
        ("bench_scheduler", bench_scheduler.run),
    ]

    failed = []
    for name, fn in modules:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception:  # keep the harness going; report at the end
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
