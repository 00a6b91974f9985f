"""Serving cell: the program's ``ServeEngine`` driven by wall-clock,
open-loop arrivals.

``ServeEngine.run`` counts arrivals in decode steps, so this runner owns
the loop and calls the engine's own parts in the order ``run`` does:
``queue.add``, ``_admit_ready`` (bucketed prefill and admit scatter),
``_decode_once`` and ``batcher.advance``.  Every event is stamped on
the host clock.  Requests due before the window are served and not
counted; arrivals stop when the window closes, and the run goes on
until every request due in the window has its first token.

``correct`` compares, once the window has closed and the engine is
freed, a sample of the finished requests (the longest among them)
against the plain reference: for every served token, the gap between
the reference's best logit and the logit of the token served.  With
``--control`` the reference in float8 takes the program's place: at
each of those positions its first choice is judged instead of the
served token, by the same checks and limits.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from collections import deque

import numpy as np

import harness as H
from reference import mixtral as R
from runners.common import model_config

# CPU rehearsal: smoke widths, short prompts and outputs
TINY_ENGINE = {"decode_slots": 8, "max_len": 128, "buckets": [16, 32, 64]}
TINY_TRAFFIC = {
    "prompt": {"median": 24, "sigma": 0.8, "min": 4, "max": 63},
    "output": {"median": 8, "sigma": 0.6, "min": 4, "max": 16},
    "ids_per_topic": 16,
    "rate_rps": 6.4,
    "ramp_s": 5.0,
}


@dataclasses.dataclass
class Record:
    """What the metric readers read."""

    seconds: float
    setup_s: float
    window: tuple  # host clock
    due: dict  # rid -> host-clock due time
    in_window: set  # rids due in the window
    tokens: dict  # rid -> host times of its output tokens
    admitted: dict  # rid -> host time its admission started
    rejected: set
    steps: list  # (t0, t1, contexts, routing [L, E] or None, after an admission?) per decode step
    spans: H.Spans
    replans: int  # device controller re-plans in the run (0 without a controller)
    dm: dict
    peak: dict | None
    trace: dict | None = None

    def window_steps(self):
        lo, hi = self.window
        return [s for s in self.steps if s[0] >= lo and s[1] <= hi]


def _requests(specs, t0):
    from repro.serve import Request

    out = []
    for s in specs:
        out.append(Request(prompt=s.prompt, max_new_tokens=s.max_new, arrival=t0 + s.due))
    return out


class Loop:
    """The engine's serving loop on the host clock."""

    def __init__(self, engine, spans: H.Spans):
        self.e = engine
        self.spans = spans
        self.tokens: dict[int, list[float]] = {}
        self.admitted: dict[int, float] = {}
        self.rejected: set[int] = set()
        self.steps: list = []
        self.step_no = 0
        self._routing = None
        if engine.has_controller:  # its decode also returns the routing counts
            decode = engine._decode

            def recording(*args):
                out = decode(*args)
                self._routing = out[-1]
                return out

            engine._decode = recording

    def serve(self, reqs, *, until=None, stop=None, hooks=()):
        """Serve ``reqs`` (``arrival`` = host due time).  Ends when every
        request is done, or when ``stop()`` is true after ``until``."""
        e = self.e
        pending = deque(sorted(reqs, key=lambda r: r.arrival))
        hooks = sorted(hooks)
        while True:
            now = time.perf_counter()
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1]()
            while pending and pending[0].arrival <= now:
                r = pending.popleft()
                if r.kv_tokens > e.max_len or not e.queue.add(r):
                    self.rejected.add(r.rid)
            admitted = False
            if len(e.queue) and e.batcher.free_slot() is not None:
                with self.spans.span("serve.admit"):
                    waiting = _queued(e.queue)
                    e._admit_ready(self.step_no, now)
                for r in waiting:
                    if r.admit_wall is not None and r.rid not in self.admitted:
                        self.admitted[r.rid] = r.admit_wall
                        admitted = True
            if e.batcher.n_live:
                live = [r for r in e.batcher.requests if r is not None]
                ctx = [int(s) + 1 for s in e.batcher.step[e.batcher.live]]
                t0 = time.perf_counter()
                with self.spans.span("serve.decode"):
                    nxt = e._decode_once()
                t1 = time.perf_counter()
                for r in live:
                    self.tokens.setdefault(r.rid, []).append(t1)
                e.batcher.advance(nxt, t1)
                self.steps.append((t0, t1, ctx, self._routing, admitted))
                self.step_no += 1
            elif not pending and not len(e.queue):
                if until is None or now >= until:
                    return
            if until is not None and now >= until and stop is not None and stop():
                return
            if not e.batcher.n_live and not len(e.queue):
                nxt_due = pending[0].arrival if pending else now + 1e-3
                nxt_hook = hooks[0][0] if hooks else nxt_due
                with self.spans.span("serve.wait"):
                    time.sleep(max(0.0, min(nxt_due, nxt_hook, now + 2e-3) - time.perf_counter()))


def _queued(queue):
    return [r for dq in queue._q.values() for r in dq]  # the queue has no public iterator


@dataclasses.dataclass
class Setup:
    engine: object
    params: object
    loop: Loop
    gen: object
    eng_conf: dict
    traffic: dict
    cfg: object
    hf: dict
    dm: dict
    info: dict
    peak: dict | None
    compiled: dict


def setup(args, cell) -> Setup:
    """Weights from the seed, the engine, and a warm-up that compiles
    every prefill bucket, the admit and the decode step."""
    import jax

    from repro.launch.rules import dtype_policy
    from repro.models import Model
    from repro.serve import ServeEngine
    from traffic.open_loop import OpenLoop
    import flops as F
    import weights as W

    conf, traffic = cell["config"], dict(cell["traffic"])
    eng_conf = dict(conf["engine"])
    if args.tiny:
        eng_conf.update(TINY_ENGINE)
        traffic.update({k: TINY_TRAFFIC[k] for k in ("prompt", "output", "rate_rps", "ramp_s")})
        traffic["topics"] = dict(traffic["topics"], ids_per_topic=TINY_TRAFFIC["ids_per_topic"])
    if getattr(args, "rate", None) is not None:
        traffic["rate_rps"] = args.rate
    cfg, hf = model_config(conf, args.tiny)
    info = H.device_info()
    peak = F.peaks(info["kind"]) if info["platform"] == "tpu" else None
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    dtype = dtype_policy(cfg)["serve_param_dtype"]
    params = jax.jit(lambda k: W.build_tree(shapes, k, dtype))(W.seed_key(args.seed))
    engine = ServeEngine(
        cfg,
        params,
        decode_slots=eng_conf["decode_slots"],
        max_len=eng_conf["max_len"],
        buckets=tuple(eng_conf["buckets"]),
        n_ranks=eng_conf.get("n_ranks", 8),
        controller=eng_conf["controller"],
        plan_overrides=eng_conf.get("plan_overrides"),
    )
    if engine.has_controller != (eng_conf["controller"] == "auto"):
        raise SystemExit(f"controller {eng_conf['controller']!r}, yet attached: {engine.has_controller}")
    gen = OpenLoop(traffic, cfg.vocab_size, rate=traffic["rate_rps"])
    loop = Loop(engine, H.Spans(traced=bool(getattr(args, "trace", 0))))
    loop.serve(_requests(gen.warmup(args.seed, eng_conf["buckets"]), time.perf_counter()))
    compiled = dict(engine.metrics()["compile"])
    want = {"decode_executables": 1, "prefill_executables": len(eng_conf["buckets"]),
            "admit_executables": 1}
    if compiled != want:
        raise SystemExit(f"warm-up compiled {compiled}, expected {want}")
    loop.tokens.clear(), loop.admitted.clear(), loop.steps.clear()
    return Setup(engine, params, loop, gen, eng_conf, traffic, cfg, hf, R.dims(hf), info,
                 peak, compiled)


def run(args, cell, t_process: float) -> tuple[dict, list]:
    import jax

    import trace_reduce as T

    su = setup(args, cell)
    engine, params, loop, gen = su.engine, su.params, su.loop, su.gen
    eng_conf, traffic, hf, dm, info, peak = su.eng_conf, su.traffic, su.hf, su.dm, su.info, su.peak
    compiled, spans = su.compiled, su.loop.spans
    del su
    replans0 = _replans(engine)

    # ------------------------------------------------------------ window
    ramp = float(traffic["ramp_s"])
    start = time.perf_counter()
    w0, w1 = start + ramp, start + ramp + args.seconds
    specs = gen.stream(args.seed, ramp, args.seconds)
    reqs = _requests(specs, start)
    due = {r.rid: r.arrival for r in reqs}
    in_window = {r.rid for r in reqs if w0 <= r.arrival < w1}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    window_span = []

    def open_window():
        if args.trace:
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
            window_span.append(span)

    def close_window():
        if window_span:
            window_span.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()

    hooks = [(w0, open_window), (w1, close_window)]
    if args.trace:
        hooks.append((max(start, w0 - 1.0), lambda: jax.profiler.start_trace(trace_dir)))
    drain = w1 + float(traffic["drain_s"])

    def all_first_tokens():
        return time.perf_counter() >= drain or all(
            loop.tokens.get(rid) or rid in loop.rejected for rid in in_window
        )

    loop.serve(reqs, until=w1, stop=all_first_tokens, hooks=hooks)
    close_window()
    setup_s = w0 - t_process
    devs = jax.devices()
    mem_peak = H.memory_peak_bytes(devs)
    replans = _replans(engine) - replans0
    late = dict(engine.metrics()["compile"])
    if late != compiled:
        print(f"warning: executables compiled during the window: {compiled} -> {late}",
              file=sys.stderr)
    steps = [(a, b, c, None if r is None else np.asarray(r), p) for a, b, c, r, p in loop.steps]

    rec = Record(
        seconds=args.seconds, setup_s=setup_s, window=(w0, w1), due=due, in_window=in_window,
        tokens=loop.tokens, admitted=loop.admitted, rejected=loop.rejected, steps=steps,
        spans=spans, replans=replans, dm=dm, peak=peak,
    )
    breakdown, busy = None, None
    if args.trace:
        rec.trace = T.load(trace_dir)
        lo, hi = T.window(rec.trace)
        breakdown = T.breakdown(rec.trace, lo, hi)
        busy = np.mean([T.length(T.busy(rec.trace, d, lo, hi)) for d in T.devices(rec.trace)]) * 1e-9
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = H.read_metrics(H.metric_specs(cell["bench"], args.workload, bool(args.trace)), rec)

    # ------------------------------------------------------- correctness
    finished = [r for r in reqs if r.done and len(r.tokens) == r.max_new_tokens]
    unserved = sum(1 for rid in in_window if not loop.tokens.get(rid))
    short = sum(1 for r in reqs if r.done and len(r.tokens) != r.max_new_tokens)
    sample = _sample(finished, int(traffic["check_sample"]), args.seed)
    seqs, rows, served, lengths = _check_rows(sample, eng_conf["max_len"])
    del engine, params, loop, reqs, finished
    gc.collect()
    ref, load = R.serve_logits(args.seed, hf, seqs, rows, lengths=lengths)
    _print_skew(rec, load)
    best = ref.max(axis=-1)
    gaps = best - ref[np.arange(len(served)), served]
    if args.control:
        print(f"program gap_mean: {float(gaps.mean())!r}, gap_max: {float(gaps.max())!r}",
              file=sys.stderr)
        low = R.serve_logits(args.seed, hf, seqs, rows, low=True).argmax(axis=-1)
        gaps = best - ref[np.arange(len(served)), low]
        print("control: the float8 reference's first choices stand in for the served tokens",
              file=sys.stderr)
    lim = H.limits(args.workload)
    numbers = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    for k, v in numbers.items():
        if k not in lim:  # read and shown, not compared (see PERF.md)
            print(f"reading {k}: {v!r}", file=sys.stderr)
    checks = [(k, v, lim[k]) for k, v in numbers.items() if k in lim]
    checks += [("unserved", unserved, 0), ("short", short, 0)]
    print(
        f"check: {len(sample)} requests, {len(served)} served tokens against the reference "
        f"(longest request {max(len(s.tokens) + s.prompt_len for s in sample)} tokens)",
        file=sys.stderr,
    )
    correct = all(v <= l for _, v, l in checks)
    device = dict(info, memory_peak_bytes=mem_peak)
    if args.trace:
        device.update(busy_s=float(busy), window_s=float((hi - lo) * 1e-9))
    result = {
        "correct": bool(correct),
        "attempted": len(in_window),
        "failed": unserved + len(rec.rejected & in_window),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks


def _replans(engine) -> int:
    return engine.metrics().get("controller", {}).get("device_replans", 0)


def _sample(finished, n, seed):
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 4])
    longest = max(finished, key=lambda r: r.prompt_len + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    pick = rng.choice(len(rest), size=min(n, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def _check_rows(sample, max_len):
    """Sequences padded to ``max_len`` (prompt, then served tokens but
    the last), the (sequence, position) rows whose logits chose each
    served token, those tokens, and each sequence's length."""
    seqs = np.zeros((len(sample), max_len), np.int32)
    rows, served, lengths = [], [], []
    for i, r in enumerate(sample):
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, toks[:-1]])
        seqs[i, : seq.size] = seq
        lengths.append(seq.size)
        for j, t in enumerate(toks):
            rows.append((i, r.prompt_len - 1 + j))
            served.append(int(t))
    return (seqs, np.asarray(rows, np.int64).reshape(-1, 2), np.asarray(served, np.int64),
            np.asarray(lengths))


def _print_skew(rec: Record, load: np.ndarray) -> None:
    """Realized routing skew: max/mean expert load per layer over the
    checked requests' tokens as the reference routes them, and over the
    window's decode steps where the program reports its routing."""
    ratio = load.max(axis=1) / np.maximum(load.mean(axis=1), 1e-9)
    print("skew: checked tokens' expert load max/mean per layer "
          + ", ".join(f"{x:.3f}" for x in ratio)
          + "; load " + "; ".join(" ".join(str(int(v)) for v in row) for row in load),
          file=sys.stderr)
    counts = [s[3] for s in rec.window_steps() if s[3] is not None]
    if counts:
        dec = np.sum(counts, axis=0).reshape(len(counts[0]), -1)  # [L, E]
        ratio = dec.max(axis=1) / np.maximum(dec.mean(axis=1), 1e-9)
        print("skew: decode expert load max/mean per layer "
              + ", ".join(f"{x:.3f}" for x in ratio), file=sys.stderr)
