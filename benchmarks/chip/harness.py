"""What every runner shares: the cell's files, the device, the compile
cache, host spans, and the result line.

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root; its configuration and traffic mix are files of their own under
``configs/`` and ``traffic/``, and each metric is read by
``metrics/<name>.py``.  Adding a cell or a metric adds files and entries
and edits none of these.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return {
                "workload": w,
                "config": load_json(HERE, "configs", w["config"] + ".json"),
                "traffic": load_json(HERE, "traffic", w["traffic"] + ".json"),
                "bench": bench,
            }
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def limits(name: str) -> dict:
    return load_json(HERE, "checks", name + ".json")


def metric_specs(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The cell's metrics: end-to-end ones untraced, per-layer traced."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(specs: list[dict], run) -> dict:
    """Every metric ``BENCHMARK.json`` declares for the cell, each read by
    its own reader.  A reader returns None where it finds nothing to
    read; for a declared metric that is an error, and no result is
    reported."""
    out, missing = {}, []
    for m in specs:
        value = load_module("metrics", m["name"] + ".py").read(run)
        if value is None:
            missing.append(m["name"])
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if missing:
        raise SystemExit(f"declared metrics found nothing to read: {missing}")
    return out


# ------------------------------------------------------------------ device
def program_path() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def require_chips(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info['platform']}; no result is reported")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {info['count']}")


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """The program's own cache directory (``JAX_COMPILATION_CACHE_DIR``
    where set, else fixed inside the checkout), and every program cached,
    however quick its compile, so a warm run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


# ------------------------------------------------------------------- spans
@dataclasses.dataclass
class Spans:
    """Host spans on the host clock; in a traced run each also becomes a
    profiler annotation, so idle gaps in the trace can be attributed."""

    traced: bool = False
    events: list = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.traced:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.events.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = -math.inf, hi: float = math.inf) -> list[float]:
        return [b - a for n, a, b in self.events if n == name and a >= lo and b <= hi]


# ------------------------------------------------------------------ result
def emit(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Print the compared numbers (stderr, last lines) and the result
    line (stdout, last line), with the checks under a key that comes
    last."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(result), flush=True)


def percentile(xs, q: float) -> float | None:
    import numpy as np

    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, q)) if xs.size else None

