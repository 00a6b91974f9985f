"""From a profiler trace to device intervals, and from those to numbers.

``load`` reads the newest ``.xplane.pb`` under a directory into plain
lists: per device, its ops (less loops and calls, whose events span
their bodies' ops) and its program (module) executions; and the host
spans that the benchmark annotated.  Every time is in nanoseconds
on the trace's one clock.  The rest works on those lists, so the tests
can feed it a small recorded trace kept as JSON.
"""

from __future__ import annotations

import glob
import json
import os
import re

HOST_PREFIXES = ("bench.", "serve.")
# ops whose trace events span the ops of their bodies, listed on their own
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w\-]*)\(")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = {"ops": {}, "modules": {}, "spans": []}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    rows = ([*op_name(e.name), e.start_ns, e.duration_ns] for e in line.events)
                    out["ops"][dev] = [
                        [n, s, d, label] for n, op, label, s, d in rows if op not in CONTAINERS
                    ]
                elif line.name == "XLA Modules":
                    out["modules"][dev] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            elif plane.name.startswith("/host"):
                out["spans"].extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES)
                )
    out["spans"].sort(key=lambda s: s[1])
    return out


def op_name(text: str) -> tuple[str, str, str]:
    """(name, opcode, label) of a trace op, whose event name on a TPU is
    the instruction's text: ``%fusion.12 = bf16[...] fusion(...), ...``.
    The label is the name, the opcode and the result's first array."""
    if " = " not in text:
        return text.lstrip("%"), "", text
    name, rhs = text.split(" = ", 1)
    name = name.strip().lstrip("%")
    m = _OPCODE.search(rhs)
    op = m.group(1) if m else ""
    shape = re.search(r"\w+\[[\d,]*\]", rhs)
    return name, op, f"{name} {op} {shape.group(0) if shape else ''}".strip()


def save(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


# -------------------------------------------------------------- intervals
def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """Parts of ``intervals`` that no interval of ``holes`` covers."""
    holes = union(holes)
    out = []
    for a, b in union(intervals):
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
        if cur < b:
            out.append((cur, b))
    return out


def window(trace: dict, name: str = "bench.window"):
    for n, s, d in trace["spans"]:
        if n == name:
            return s, s + d
    raise KeyError(f"no {name} span in the trace")


def op_intervals(trace: dict, dev, lo, hi, names=None):
    rows = trace["ops"].get(dev, [])
    return clip([(s, s + d) for n, s, d, _ in rows if names is None or n in names], lo, hi)


def module_runs(trace: dict, dev, prefix: str, lo, hi):
    """[(start, end)] of the executions of programs named ``prefix...``."""
    return clip(
        [(s, s + d) for n, s, d in trace["modules"].get(dev, []) if _module_is(n, prefix)],
        lo,
        hi,
    )


def _module_is(name, prefix) -> bool:
    return name is not None and name.split("(")[0].startswith(prefix)


def ops_in(trace: dict, dev, runs, names) -> float:
    """Device time in which an op named in ``names`` runs, inside the
    ``runs``."""
    rows = trace["ops"].get(dev, [])
    total = 0.0
    for a, b in union(runs):
        total += length(union(clip([(s, s + d) for n, s, d, _ in rows if n in names], a, b)))
    return total


def busy(trace: dict, dev, lo, hi):
    return union(op_intervals(trace, dev, lo, hi))


def devices(trace: dict) -> list:
    return sorted(trace["ops"])


# -------------------------------------------------------------- breakdown
def breakdown(trace: dict, lo, hi, top: int = 10) -> dict:
    """The device ops that took most time (summed by name over the
    devices, seconds per device) and the longest idle gaps of device 0,
    each named by the innermost host span it fell in."""
    devs = devices(trace)
    per_op: dict[str, float] = {}
    for dev in devs:
        for _, s, d, label in trace["ops"][dev]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                per_op[label] = per_op.get(label, 0.0) + (b - a)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if devs:
        busy0 = busy(trace, devs[0], lo, hi)
        gaps = subtract([(lo, hi)], busy0)
    named = sorted(
        ((span_at(trace, (a + b) / 2), (b - a)) for a, b in gaps), key=lambda g: -g[1]
    )[:top]
    n = max(len(devs), 1)
    return {
        "device_ops": [[k, v / n * 1e-9] for k, v in ops],
        "idle_gaps": [[k, v * 1e-9] for k, v in named],
    }


def span_at(trace: dict, t) -> str:
    """The latest-started benchmark span that covers ``t``."""
    best = "no host span"
    for n, s, d in trace["spans"]:
        if s <= t <= s + d and n != "bench.window":
            best = n
        if s > t:
            break
    return best
