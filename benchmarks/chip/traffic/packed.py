"""Packed training batches: documents of one topic each, packed end to
end into fixed-length sequences.

A batch holds ``batch`` sequences of ``sequence`` tokens.  It is filled
from a stream of documents: each document's length is drawn lognormal
with the traffic file's ``document`` mean and sigma, its topic and its
ids from the topics of the traffic file (``traffic.topics``), and an
end-of-document token follows it.  The stream is cut into the batch's
sequences as it comes, so a document may straddle two expert-parallel
ranks or two sequences, and the last one is cut where the batch ends.
Each rank holds a contiguous slice of positions, and so the topics of
the few documents that fall into it: the rank-to-rank demand is skewed
and not doubly stochastic.

A run cycles a pool of ``pool`` batches drawn from its seed.  The seed
draws the lengths, the topics and the ids; every batch has the same
shape.  Targets are the next token of the sequence, and -1 (no loss) at
its last position.
"""

from __future__ import annotations

import math

import numpy as np

from traffic.topics import Topics


class Packed:
    def __init__(self, params: dict, vocab: int):
        self.p = params
        self.batch = int(params["batch"])
        self.sequence = int(params["sequence"])
        doc = params["document"]
        self.sigma = float(doc["sigma"])
        # lognormal: the mean is exp(mu + sigma^2 / 2)
        self.mu = math.log(float(doc["mean"])) - self.sigma**2 / 2
        self.eos = int(params["eos"])
        self.topics = Topics(params["topics"], vocab)

    @property
    def tokens_per_batch(self) -> int:
        return self.batch * self.sequence

    def batch_at(self, seed: int, i: int) -> dict:
        """Batch ``i`` of the seed's pool: ``tokens`` and ``targets``
        [batch, sequence] int32; ``document`` [batch, sequence], the
        index of the document each position belongs to (its
        end-of-document token included); ``topics``, the topic of every
        document."""
        rng = np.random.default_rng([int(seed), 3, int(i)])
        total = self.tokens_per_batch
        parts, docs, topics, n = [], [], [], 0
        while n < total:
            length = max(1, int(round(rng.lognormal(self.mu, self.sigma))))
            topic = self.topics.topic(rng)
            parts += [self.topics.tokens(rng, topic, length), np.array([self.eos], np.int32)]
            docs.append(np.full(length + 1, len(topics), np.int32))
            topics.append(topic)
            n += length + 1
        shape = (self.batch, self.sequence)
        tokens = np.concatenate(parts)[:total].reshape(shape).astype(np.int32)
        targets = np.concatenate(
            [tokens[:, 1:], np.full((self.batch, 1), -1, np.int32)], axis=1
        )
        return {"tokens": tokens, "targets": targets,
                "document": np.concatenate(docs)[:total].reshape(shape),
                "topics": np.array(topics)}

    def pool(self, seed: int) -> list[dict]:
        return [self.batch_at(seed, i) for i in range(int(self.p["pool"]))]
