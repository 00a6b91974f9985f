"""The packed training traffic: sizes, packing, determinism from the
seed, and the skew it puts on the expert-parallel ranks."""

import json
import os

import numpy as np
import pytest

from traffic.packed import Packed

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 32000
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def gen():
    with open(os.path.join(BENCH, "traffic", "packed_skewed.json")) as f:
        return Packed(json.load(f), VOCAB)


def test_sizes_and_targets(gen):
    b = gen.batch_at(SEED, 0)
    assert b["tokens"].shape == b["targets"].shape == b["document"].shape == (2, 8192)
    assert b["tokens"].dtype == np.int32
    assert gen.tokens_per_batch == 16384
    # the next token of the sequence, and no target at the last position
    np.testing.assert_array_equal(b["targets"][:, :-1], b["tokens"][:, 1:])
    assert (b["targets"][:, -1] == -1).all()
    assert len(gen.pool(SEED)) == 32


def test_documents_packed_end_to_end(gen):
    """Each document is one topic's ids and ends in the end-of-document
    token; documents follow each other across ranks and sequences."""
    b = gen.batch_at(SEED, 3)
    doc, tok = b["document"].ravel(), b["tokens"].ravel()
    assert (np.diff(doc) >= 0).all() and (np.diff(doc) <= 1).all()
    ends = np.flatnonzero(np.diff(doc)) + 1  # first position of each later document
    assert (tok[ends - 1] == gen.eos).all()
    slices = gen.topics.slices
    for d in np.unique(doc):
        body = np.flatnonzero(doc == d)[:-1]  # without its last token, the end or a cut
        assert set(tok[body].tolist()) <= set(slices[b["topics"][d]].tolist())
    # a document straddles a rank's slice (2048 positions) somewhere in the pool
    pool = gen.pool(SEED)
    assert any(
        (p["document"][:, r * 2048 - 1] == p["document"][:, r * 2048]).any()
        for p in pool for r in (1, 2, 3)
    )


def test_document_lengths_follow_the_mean(gen):
    lengths = np.concatenate([np.bincount(gen.batch_at(SEED, i)["document"].ravel())[:-1]
                              for i in range(32)])  # the last document of a batch is cut
    # the mean length with its end token: 427 + 1, within a few standard errors
    assert abs(lengths.mean() - 428) < 5 * lengths.std() / np.sqrt(len(lengths))


def test_same_seed_same_batches(gen):
    a, b = gen.batch_at(SEED, 5), gen.batch_at(SEED, 5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = gen.batch_at(SEED + 1, 5)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], gen.batch_at(SEED, 6)["tokens"])


def test_rank_to_rank_demand_is_skewed(gen):
    """Under a router that sends every id to fixed experts (2 per rank,
    4 ranks, each rank holding a quarter of every sequence), each rank
    sends as many choices as any other, but the ranks receive unevenly:
    the matrix is not doubly stochastic."""
    rng = np.random.default_rng(0)
    experts = np.stack([rng.choice(8, 2, replace=False) for _ in range(VOCAB)])
    worst = 0.0
    for i in range(8):
        tok = gen.batch_at(SEED, i)["tokens"]
        m = np.zeros((4, 4))
        for src in range(4):
            ids = tok[:, src * 2048:(src + 1) * 2048].ravel()
            np.add.at(m[src], (experts[ids] // 2).ravel(), 1)
        rows, cols = m.sum(axis=1), m.sum(axis=0)
        np.testing.assert_array_equal(rows, np.full(4, 2 * 2 * 2048))
        worst = max(worst, cols.max() / cols.mean())
    assert worst > 1.05
