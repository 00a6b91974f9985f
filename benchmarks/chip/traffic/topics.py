"""Topic-skewed token draws, shared by the traffic generators.

Each topic owns a disjoint slice of ``ids_per_topic`` vocabulary ids,
taken from one fixed permutation of the vocabulary.  A topic is drawn
Zipf(``zipf``) over ``n`` topics; within its slice, ids are drawn
Zipf(``id_zipf``) over their rank, and with probability ``repeat`` a
position instead takes the slice id after the previous draw (the
Zipf/Markov stream of ``repro.data.SyntheticStream``, confined to the
slice).  A token's routing depends on its id, so a request or document
from one topic loads the experts unevenly, as real MoE routing does.
"""

from __future__ import annotations

import numpy as np


def zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return p / p.sum()


class Topics:
    def __init__(self, params: dict, vocab: int):
        self.n = int(params["n"])
        self.width = int(params["ids_per_topic"])
        if self.n * self.width > vocab:
            raise ValueError(f"{self.n} topics of {self.width} ids exceed vocab {vocab}")
        perm = np.random.default_rng(int(params["vocab_seed"])).permutation(vocab)
        self.slices = perm[: self.n * self.width].reshape(self.n, self.width)
        self.topic_p = zipf_probs(self.n, float(params["zipf"]))
        self.id_p = zipf_probs(self.width, float(params["id_zipf"]))
        self.repeat = float(params["repeat"])

    def topic(self, rng: np.random.Generator) -> int:
        return int(rng.choice(self.n, p=self.topic_p))

    def tokens(self, rng: np.random.Generator, topic: int, length: int) -> np.ndarray:
        base = rng.choice(self.width, size=length, p=self.id_p)
        keep = rng.random(length) < self.repeat
        idx = np.where(keep, (np.roll(base, 1) + 1) % self.width, base)
        return self.slices[topic][idx].astype(np.int32)
