"""The traffic generator is a pure function of the seed."""

import json
import os

import numpy as np

from traffic.open_loop import OpenLoop

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def test_open_loop_same_seed_same_stream():
    gen = OpenLoop(load("steady"), 32000)
    a, b = gen.stream(2**31 + 12345, 6.0, 30.0), gen.stream(2**31 + 12345, 6.0, 30.0)
    assert len(a) == len(b) > 100
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_seeds_share_the_work():
    """Every seed offers the same arrivals in each stretch, the same
    lengths and the same spacings, in another order."""
    p = load("steady")
    gen = OpenLoop(p, 32000)
    ramp, window = 6.0, 30.0
    a, b = gen.stream(1, ramp, window), gen.stream(2**31 + 99, ramp, window)
    wa = [s for s in a if s.due >= ramp]
    wb = [s for s in b if s.due >= ramp]
    assert len(wa) == len(wb) == round(p["rate_rps"] * window)
    assert len(a) - len(wa) == round(p["rate_rps"] * ramp)
    assert sorted(len(s.prompt) for s in wa) == sorted(len(s.prompt) for s in wb)
    assert sorted(s.max_new for s in wa) == sorted(s.max_new for s in wb)
    assert [s.due for s in wa] != [s.due for s in wb]

    def spacings(w):
        return np.sort(np.diff([ramp] + [s.due for s in w] + [ramp + window]))

    np.testing.assert_allclose(spacings(wa), spacings(wb), atol=1e-9)
    assert all(ramp <= s.due < ramp + window for s in wa)
    assert all(p["prompt"]["min"] <= len(s.prompt) <= p["prompt"]["max"] for s in a)
    assert all(p["output"]["min"] <= s.max_new <= p["output"]["max"] for s in a)


def test_open_loop_arrivals_are_poisson_like():
    """Spacings of a long stretch look exponential: coefficient of
    variation near 1, and bursts (spacings under a tenth of the mean)
    as often as an exponential has them."""
    gen = OpenLoop(load("steady"), 32000, rate=5.0)
    spacing, _, _ = gen.pool(1, 400.0)
    mean = spacing.mean()
    assert abs(spacing.std() / mean - 1.0) < 0.1
    assert abs((spacing < 0.1 * mean).mean() - (1 - np.exp(-0.1))) < 0.03


def test_prompt_lengths_follow_the_cited_median():
    p = load("steady")
    _, prompt, output = OpenLoop(p, 32000, rate=5.0).pool(1, 400.0)
    assert abs(np.median(prompt) / p["prompt"]["median"] - 1) < 0.1
    assert abs(np.median(output) / p["output"]["median"] - 1) < 0.1


def test_topics_stay_in_their_slice():
    gen = OpenLoop(load("steady"), 32000)
    for s in gen.stream(7, 2.0, 10.0):
        assert set(s.prompt.tolist()) <= set(gen.topics.slices[s.topic].tolist())
