"""Open-loop request stream: independent users arriving as a Poisson
process at a fixed mean rate.

A stretch of ``s`` seconds (the ramp, then the window) holds
``round(rate * s)`` arrivals: a Poisson process conditioned on its
count, whose ``n + 1`` spacings are ``s`` times exponentials over their
sum.  The spacings and the requests' prompt and output lengths
(lognormal, clipped) are drawn once per stretch from the traffic file's
``pool_seed``; the run's seed deals them out in an order of its own and
draws each request's topic and tokens.  So every seed offers the same
work, bursts and lulls included, in another order: a stretch of
arrivals is as bursty as Poisson arrivals are, while runs of different
seeds differ only as two orders of one sample do.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from traffic.topics import Topics


@dataclasses.dataclass
class Spec:
    due: float  # seconds after the stream starts
    prompt: np.ndarray
    max_new: int
    topic: int


def _lognormal(rng: np.random.Generator, p: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(p["median"]), p["sigma"], size=n)
    return np.clip(np.round(x), p["min"], p["max"]).astype(np.int64)


class OpenLoop:
    def __init__(self, params: dict, vocab: int, rate: float | None = None):
        self.p = params
        self.rate = float(rate if rate is not None else params["rate_rps"])
        self.topics = Topics(params["topics"], vocab)

    def pool(self, stretch: int, seconds: float):
        """The fixed sample of one stretch (0: the ramp, 1: the window):
        spacings [n + 1] and prompt and output lengths [n]."""
        n = int(round(self.rate * seconds))
        rng = np.random.default_rng([int(self.p["pool_seed"]), stretch, n])
        e = rng.exponential(size=n + 1)
        return (e / e.sum() * seconds, _lognormal(rng, self.p["prompt"], n),
                _lognormal(rng, self.p["output"], n))

    def stream(self, seed: int, ramp_s: float, window_s: float) -> list[Spec]:
        """Requests due in [0, ramp_s) and then in [ramp_s, ramp_s + window_s)."""
        rng = np.random.default_rng([int(seed), 1])
        out: list[Spec] = []
        for stretch, (t0, seconds) in enumerate(((0.0, ramp_s), (ramp_s, window_s))):
            if seconds <= 0:
                continue
            spacing, prompt, output = self.pool(stretch, seconds)
            due = t0 + np.cumsum(spacing[rng.permutation(spacing.size)])[:-1]
            for t, i in zip(due, rng.permutation(prompt.size)):
                topic = self.topics.topic(rng)
                out.append(Spec(
                    due=float(t),
                    prompt=self.topics.tokens(rng, topic, int(prompt[i])),
                    max_new=int(output[i]),
                    topic=topic,
                ))
        return out

    def warmup(self, seed: int, buckets) -> list[Spec]:
        """One request per prefill bucket, filling it exactly, two tokens
        each: compiles every prefill bucket, the admit and the decode."""
        rng = np.random.default_rng([int(seed), 2])
        return [
            Spec(0.0, self.topics.tokens(rng, 0, int(b) + 1), 2, 0) for b in buckets
        ]
