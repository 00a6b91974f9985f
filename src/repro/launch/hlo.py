"""HLO module analyzer: loop-aware FLOPs / HBM-bytes / collective-bytes.

XLA's ``compiled.cost_analysis()`` visits each ``while`` body **once**, so
anything under a ``lax.scan`` (our layer stacks, time recurrences, MoE
collectives) is undercounted by the trip count.  This analyzer parses the
post-SPMD, post-fusion HLO text, builds the computation call graph with
while-loop trip counts (recovered from the canonical scan condition
``compare(iter, constant)``), and accumulates per-computation costs times
their execution multiplier:

  * **flops** — 2*M*N*K for every ``dot`` (including dots inside fused
    computations), batch dims included.  Dots dominate these models.
  * **hbm bytes** — sum of (operand + result) bytes over *top-level* ops
    of non-fused computations.  Post-fusion, each op boundary is real HBM
    traffic (fusion internals stay on-chip), so this is a principled
    traffic model (no cache-reuse credit).
  * **collective bytes** — per type; ``operand`` follows the assignment's
    "sum operand sizes" definition, ``wire`` is the ring-model bytes the
    links actually carry (used for the roofline collective term).
    collective-permute wire bytes are scaled by the source-target pair
    fraction (sparse scheduled phases keep idle pairs dark).

``op_scopes`` reads each instruction's ``metadata={op_name=...}`` back
into the program's named scopes (``repro.scopes.SCOPES``): a profiler
trace names device ops by instruction, so its table joins a trace to
the model's layers.
"""

from __future__ import annotations

import re
from collections import defaultdict

from repro.scopes import SCOPES

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_REF_RE = re.compile(r"%([\w\.\-]+)")
_ATTR_COMP_RE = re.compile(
    r"(?:condition|body|to_apply|calls)=%?([\w\.\-]+)"
)
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_DIMS_RE = {
    "lb": re.compile(r"lhs_batch_dims=\{([0-9,]*)\}"),
    "lc": re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}"),
}
_CONST_RE = re.compile(r"\bconstant\((\d+)\)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shapes_bytes(text: str) -> int:
    total = 0
    for d, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for x in dims.split(","):
                n *= int(x)
        total += n * _DTYPE_BYTES[d]
    return total


def _shape_dims(text: str) -> list[list[int]]:
    out = []
    for _, dims in _SHAPE_RE.findall(text):
        out.append([int(x) for x in dims.split(",")] if dims else [])
    return out


class Op:
    __slots__ = ("name", "kind", "result", "line", "operands", "comps", "op_name")

    def __init__(self, name, kind, result, line):
        self.name = name
        self.kind = kind
        self.result = result  # result type text
        self.line = line  # attrs text (post-operands, pre-metadata)
        self.operands: list[str] = []
        self.comps: list[str] = []
        self.op_name: str | None = None  # metadata op_name (None: XLA made it)


class Computation:
    def __init__(self, name: str, is_entry: bool):
        self.name = name
        self.is_entry = is_entry
        self.ops: list[Op] = []
        self.defs: dict[str, str] = {}  # op name -> result type text


def parse_module(hlo_text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in hlo_text.splitlines():
        line = raw.split(", metadata=")[0]
        if cur is None:
            if not raw or raw[0] in " }\t" or " -> " not in raw:
                continue
            m = _COMP_HEADER_RE.match(line.strip())
            if m and raw.rstrip().endswith("{"):
                cur = Computation(m.group(2), bool(m.group(1)))
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m is None:
            # computation parameters in header style or stray lines
            continue
        name, result, kind = m.group(1), m.group(2), m.group(3)
        if kind == "while":
            line = raw  # keep backend_config for known_trip_count
        rest = line[m.end() :]
        # operands: refs inside the first paren group (up to matching ')')
        depth = 1
        i = 0
        while i < len(rest) and depth > 0:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        opnds = rest[: i - 1] if i else ""
        attrs = rest[i:]
        op = Op(name, kind, result, attrs)
        op.operands = _REF_RE.findall(opnds)
        op.comps = _ATTR_COMP_RE.findall(attrs)
        bm = _BRANCHES_RE.search(attrs)
        if bm:
            op.comps += _REF_RE.findall(bm.group(1))
        nm = _OP_NAME_RE.search(raw)
        if nm:
            op.op_name = nm.group(1)
        cur.defs[name] = result
        cur.ops.append(op)
    return comps


def _trip_count(op: Op, comps: dict[str, Computation]) -> int:
    """Trip count of a while op: XLA's known_trip_count annotation, else
    the canonical scan condition constant (compare(iter, constant(N)))."""
    m = _TRIP_RE.search(op.line)
    if m:
        return max(int(m.group(1)), 1)
    cm = re.search(r"condition=%?([\w\.\-]+)", op.line)
    cond = comps.get(cm.group(1)) if cm else None
    consts = []
    if cond is not None:
        for o in cond.ops:
            consts += [int(x) for x in _CONST_RE.findall(o.result + o.line)]
    return max([1] + consts)


def _multipliers(comps: dict[str, Computation]) -> dict[str, float]:
    """Execution count per computation: topological accumulation over the
    (acyclic) computation call graph from ENTRY."""
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {k: 1.0 for k in comps}
    # edges: parent -> [(child, weight)]
    edges: dict[str, list[tuple[str, float]]] = {}
    for comp in comps.values():
        out = []
        for op in comp.ops:
            if op.kind == "while":
                trips = _trip_count(op, comps)
                cm = re.search(r"condition=%?([\w\.\-]+)", op.line)
                bm = re.search(r"body=%?([\w\.\-]+)", op.line)
                if bm and bm.group(1) in comps:
                    out.append((bm.group(1), float(trips)))
                if cm and cm.group(1) in comps:
                    out.append((cm.group(1), float(trips + 1)))
            else:
                for c in op.comps:
                    if c in comps:
                        out.append((c, 1.0))
        edges[comp.name] = out
    # topological order via DFS
    order: list[str] = []
    state: dict[str, int] = {}

    def dfs(n: str):
        if state.get(n):
            return
        state[n] = 1
        for c, _ in edges.get(n, ()):  # children first
            dfs(c)
        state[n] = 2
        order.append(n)

    dfs(entry.name)
    mult: dict[str, float] = defaultdict(float)
    mult[entry.name] = 1.0
    for name in reversed(order):  # parents before children
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for child, w in edges.get(name, ()):  # propagate
            mult[child] += m * w
    return dict(mult)


def _dot_flops(op: Op, comp: Computation) -> float:
    result_dims = _shape_dims(op.result)
    if not result_dims:
        return 0.0
    out_elems = 1
    for d in result_dims[0]:
        out_elems *= d
    # contracting size from lhs operand shape
    k = 1
    if op.operands:
        lhs_type = comp.defs.get(op.operands[0])
        if lhs_type:
            lhs_dims = _shape_dims(lhs_type)
            lc = _DIMS_RE["lc"].search(op.line)
            if lhs_dims and lc and lc.group(1):
                for i in lc.group(1).split(","):
                    idx = int(i)
                    if idx < len(lhs_dims[0]):
                        k *= lhs_dims[0][idx]
    return 2.0 * out_elems * k


def _group_size(attrs: str) -> int:
    m = _GROUPS_BRACKET_RE.search(attrs)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_BRACE_RE.search(attrs)
    if m:
        return max(len([x for x in m.group(1).split(",") if x.strip()]), 1)
    return 1


_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "bitcast-convert",
}


def analyze_module(hlo_text: str, *, n_devices: int | None = None) -> dict:
    comps = parse_module(hlo_text)
    mult = _multipliers(comps)

    flops = 0.0
    hbm_bytes = 0.0
    operand: dict = defaultdict(float)
    wire: dict = defaultdict(float)
    counts: dict = defaultdict(float)
    pair_fracs: list[float] = []

    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m <= 0:
            continue
        fused = comp.name.startswith("fused_") or ".fused" in comp.name
        for op in comp.ops:
            if op.kind in ("dot", "convolution"):
                flops += m * _dot_flops(op, comp)
            if fused:
                continue  # bytes/collectives only at top-level op boundaries
            if op.kind in _SKIP_BYTES_OPS or op.kind == "while":
                continue
            result_b = _shapes_bytes(op.result)
            # Slice-aware traffic: dynamic-(update-)slice — whether plain or
            # anywhere inside a fusion — reads/writes only the slice, not
            # the (scan-carried, often stacked) buffer it indexes into.
            inner_kinds = {op.kind}
            if op.kind == "fusion":
                for c in op.comps:
                    if c in comps:
                        inner_kinds |= {o.kind for o in comps[c].ops}
            if "dynamic-update-slice" in inner_kinds:
                # traffic = the updated slice(s), read+write, both ends.
                upd = 0
                if op.kind == "dynamic-update-slice":
                    if len(op.operands) >= 2:
                        upd = _shapes_bytes(comp.defs.get(op.operands[1], ""))
                else:  # fusion: read the DUS update shapes inside
                    for c in op.comps:
                        if c not in comps:
                            continue
                        for o2 in comps[c].ops:
                            if o2.kind == "dynamic-update-slice" and len(o2.operands) >= 2:
                                upd += _shapes_bytes(
                                    comps[c].defs.get(o2.operands[1], "")
                                )
                if upd == 0:
                    upd = result_b  # conservative fallback
                hbm_bytes += m * 2 * upd
                continue
            if "dynamic-slice" in inner_kinds:
                hbm_bytes += m * (
                    2 * result_b
                    + sum(
                        min(_shapes_bytes(comp.defs.get(o, "")), result_b)
                        for o in op.operands
                    )
                )
                continue
            move_only = {
                "convert", "copy", "transpose", "bitcast", "reshape",
                "broadcast", "parameter", "constant", "get-tuple-element",
                "tuple", "slice", "concatenate", "select",
            }
            if inner_kinds <= move_only:
                # pure data movement: count the write once.  On TPU these
                # mostly vanish (native bf16 dots; fusion into consumers) —
                # XLA-CPU materializes f32 converts of bf16 buffers.
                hbm_bytes += m * result_b
                continue
            opnd_b = sum(
                _shapes_bytes(comp.defs.get(o, "")) for o in op.operands
            )
            hbm_bytes += m * (result_b + opnd_b)
            kind = op.kind.removesuffix("-start")
            if kind in COLLECTIVE_KINDS:  # noqa: redefinition is intended
                s = _group_size(op.line)
                counts[kind] += m
                if kind == "all-gather":
                    operand[kind] += m * result_b / max(s, 1)
                    wire[kind] += m * result_b * (s - 1) / max(s, 1)
                elif kind == "reduce-scatter":
                    operand[kind] += m * result_b * s
                    wire[kind] += m * result_b * (s - 1)
                elif kind == "all-reduce":
                    rb = result_b
                    if "promoted" in op.line:
                        # XLA-CPU promotes bf16 reductions to f32; the
                        # logical (TPU) tensor is half as wide
                        rb //= 2
                    operand[kind] += m * rb
                    wire[kind] += m * 2 * rb * (s - 1) / max(s, 1)
                elif kind == "all-to-all":
                    operand[kind] += m * result_b
                    wire[kind] += m * result_b * (s - 1) / max(s, 1)
                else:  # collective-permute
                    frac = 1.0
                    pm = _PAIRS_RE.search(op.line)
                    if pm and n_devices:
                        frac = pm.group(1).count("{") / n_devices
                        pair_fracs.append(frac)
                    operand[kind] += m * result_b
                    wire[kind] += m * result_b * frac

    out = {
        "flops": flops,
        "hbm_bytes": hbm_bytes,
        "collectives": {k: int(v) for k, v in operand.items()},
        "collective_total": int(sum(operand.values())),
        "wire": {k: int(v) for k, v in wire.items()},
        "wire_total": int(sum(wire.values())),
        "collective_counts": {k: round(v, 1) for k, v in counts.items()},
        "n_computations": len(comps),
    }
    if pair_fracs:
        out["permute_pair_fraction"] = sum(pair_fracs) / len(pair_fracs)
    return out


def parse_collectives(hlo_text: str, *, n_devices: int | None = None) -> dict:
    """Back-compat wrapper: loop-aware collective bytes."""
    a = analyze_module(hlo_text, n_devices=n_devices)
    out = dict(a["collectives"])
    out["total"] = a["collective_total"]
    out["wire"] = a["wire"]
    out["wire_total"] = a["wire_total"]
    out["count"] = a["collective_counts"]
    if "permute_pair_fraction" in a:
        out["permute_pair_fraction"] = a["permute_pair_fraction"]
    return out


# ----------------------------------------------------------- named scopes
_SCOPE_PARTS = [(path, path.split("/")) for path in SCOPES]
_WRAPPED_RE = re.compile(r"^(?:[\w\-]+\()+([^()]*)\)+$")  # transpose(jvp(moe)) -> moe
# ops that run the instructions of other computations
_CONTROL_OPS = ("while", "conditional", "call")
# ops that a trace does not show as device work
TRIVIAL_OPS = frozenset(
    {"parameter", "constant", "get-tuple-element", "tuple", "bitcast", *_CONTROL_OPS}
)
_PASS_THROUGH = ("tuple", "get-tuple-element", "bitcast", "copy")
_UNNAMED = object()  # an instruction XLA made: no op_name


def scope_of(op_name: str) -> str | None:
    """The innermost ``SCOPES`` path in an ``op_name`` (None if none)."""
    parts = [_WRAPPED_RE.sub(r"\1", p) for p in op_name.split("/")]
    best, best_end = None, -1
    for path, want in _SCOPE_PARTS:
        k = len(want)
        for i in range(len(parts) - k, -1, -1):
            if parts[i : i + k] == want:
                if i + k > best_end:
                    best, best_end = path, i + k
                break
    return best


def _run_by_control_flow(comps: dict[str, Computation]) -> dict[str, Op | None]:
    """The computations whose instructions run as device ops of their
    own: the entry, and those that control flow runs (loop bodies and
    conditions, branches, calls), not the insides of fusions or
    reducers; each with the instruction that runs it, parents first."""
    entry = next((c for c in comps.values() if c.is_entry), None)
    order: dict[str, Op | None] = {}
    todo = [(entry.name, None)] if entry is not None else []
    while todo:
        name, caller = todo.pop(0)
        if name in order or name not in comps:
            continue
        order[name] = caller
        for op in comps[name].ops:
            if op.kind in _CONTROL_OPS or op.kind.endswith("-start"):
                todo.extend((c, op) for c in op.comps)
    return order


def trace_ops(hlo_text: str) -> list[str]:
    """Names of the instructions a profiler trace shows as device work."""
    comps = parse_module(hlo_text)
    return [
        op.name
        for c in _run_by_control_flow(comps)
        for op in comps[c].ops
        if op.kind not in TRIVIAL_OPS
    ]


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: innermost ``SCOPES`` path} over the
    instructions of the computations a trace shows (see
    ``trace_ops``).  An instruction whose ``op_name`` names no scope is
    left out.  One that XLA made (no ``op_name``) takes the scope of what
    is inside it (a fusion), else of its nearest user or operand
    (through tuples and copies), else of the instruction that runs its
    computation."""
    comps = parse_module(hlo_text)
    out: dict[str, str] = {}
    for name, caller in _run_by_control_flow(comps).items():
        outer = out.get(caller.name) if caller is not None else None
        comp = comps[name]
        own = {op.name: _own_scope(op, comps) for op in comp.ops}
        ops = {op.name: op for op in comp.ops}
        users: dict[str, list[str]] = defaultdict(list)
        for op in comp.ops:
            for a in op.operands:
                users[a].append(op.name)
        for op in comp.ops:
            sc = own[op.name]
            if sc is _UNNAMED:
                sc = (
                    _nearest(op.name, lambda n: users.get(n, ()), ops, own)
                    or _nearest(op.name, lambda n: ops[n].operands if n in ops else (), ops, own)
                    or outer
                )
            if sc is not None:
                out[op.name] = sc
    return out


def _own_scope(op: Op, comps: dict[str, Computation]):
    if op.op_name is not None:
        return scope_of(op.op_name)
    if op.kind == "fusion":
        for c in op.comps:
            for inner in reversed(comps[c].ops) if c in comps else ():
                if inner.op_name is not None:
                    return scope_of(inner.op_name)
    return _UNNAMED


def _nearest(name: str, step, ops: dict, own: dict) -> str | None:
    """The scope of the nearest instruction along ``step`` (users or
    operands) that has one, through tuples and copies, within 3 steps."""
    frontier, seen = [name], {name}
    for _ in range(3):
        nxt = []
        for n in frontier:
            for m in step(n):
                if m in seen:
                    continue
                seen.add(m)
                sc = own.get(m)
                if isinstance(sc, str):
                    return sc
                if m in ops and ops[m].kind in _PASS_THROUGH:
                    nxt.append(m)
        frontier = nxt
    return None
