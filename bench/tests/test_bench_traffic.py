"""The traffic generator: seeded, inside its distributions, and the same
work on every seed: one realisation of the mix, drawn from its
``traffic_seed``."""

import numpy as np
import pytest

from bench import traffic
from bench.spec import BENCH, load_json

# the cells' mixes, and a small one with a shared prefix
MIXES = ["chat", "longdoc", "tiny_agent"]
VOCAB = 1000


def mix(name):
    where = "tests/data" if name.startswith("tiny_") else "traffic"
    return load_json(BENCH / where / f"{name}.json")


def draw(m, seed):
    if m["loop"] == "open":
        return traffic.open_loop(m, 30.0, VOCAB, seed)
    return traffic.closed_loop(m, VOCAB, seed)


@pytest.mark.parametrize("name", MIXES)
def test_inside_distributions(name):
    m = mix(name)
    shared = m.get("shared_prefix", 0)
    for r in draw(m, 2**31 + 17):
        body = len(r.prompt) - shared
        assert m["prompt"]["min"] <= body <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new_tokens <= m["output"]["max"]
        assert r.prefix_len == shared
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_repeats(name):
    m = mix(name)
    a, b = draw(m, 5), draw(m, 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due_s) == (y.max_new_tokens, y.due_s)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    m = mix(name)
    a, b = draw(m, 1), draw(m, 2**31 + 3)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    # the run's seed draws the token ids
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_traffic_seed_draws_the_realisation(name):
    m = mix(name)
    other = dict(m, traffic_seed=m["traffic_seed"] + 1)
    a, b = draw(m, 5), draw(other, 5)
    assert ([r.max_new_tokens for r in a][:20]
            != [r.max_new_tokens for r in b][:20])


def test_open_loop_is_poisson_from_the_warm_in():
    m = dict(mix("chat"), rate_per_s=2.0, warm_in_s=30)
    reqs = traffic.open_loop(m, 2000.0, VOCAB, 9)
    due = np.array([r.due_s for r in reqs])
    assert np.all(np.diff(due) > 0)
    assert -30 <= due[0] < -25 and 1990 < due[-1] < 2000
    # as many arrivals as the rate offers, to a few standard deviations
    assert abs(len(reqs) - 2.0 * 2030) < 4 * (2.0 * 2030) ** 0.5
    gaps = np.diff(due)
    assert 0.95 < gaps.std() / gaps.mean() < 1.05       # exponential
    # independent gaps: short gaps come in runs as often as chance has it
    short = gaps < np.median(gaps)
    assert 0.2 < np.mean(short[1:] & short[:-1]) < 0.3


def test_longer_window_extends_the_same_arrivals():
    m = mix("chat")
    short = traffic.open_loop(m, 20.0, VOCAB, 4)
    long = traffic.open_loop(m, 51.0, VOCAB, 4)
    assert len(long) > len(short)
    for x, y in zip(short, long):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_open_loop_offers_the_rate_inside_the_window():
    m = mix("chat")
    reqs = traffic.open_loop(m, 51.0, VOCAB, 9)
    due = [r.due_s for r in reqs]
    assert due == sorted(due)
    assert due[0] < 0 and due[-1] < 51.0
    inside = [d for d in due if d >= 0]
    expect = m["rate_per_s"] * 51.0
    assert abs(len(inside) - expect) < 4 * expect ** 0.5


def test_lognormal_median_and_clip():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
         "max": 2048}
    x = traffic.lengths(d, 40001, np.random.default_rng(3))
    assert 500 <= np.median(x) <= 524
    assert x.min() == 64 and x.max() == 2048
    # the clipped shares: P(z < ln(64/512)/0.8) = 0.47%,
    # P(z > ln(2048/512)/0.8) = 4.15%
    assert 0.003 < np.mean(x == 64) < 0.0065
    assert 0.037 < np.mean(x == 2048) < 0.046


def test_uniform_lengths_cover_their_range():
    d = {"dist": "uniform", "min": 1024, "max": 2048}
    x = traffic.lengths(d, 20000, np.random.default_rng(3))
    assert x.min() == 1024 and x.max() == 2048
    assert abs(x.mean() - 1536) < 10


def test_shared_prefix_is_one_block_for_every_request():
    m = mix("tiny_agent")
    reqs = traffic.closed_loop(m, VOCAB, 3)
    head = traffic.shared_prefix(m, VOCAB, 3)
    assert len(head) == m["shared_prefix"]
    assert all(np.array_equal(r.prompt[:len(head)], head) for r in reqs[:50])
