"""The traffic generator: seeded, the stated distributions, the same work
for every seed, and arrivals drawn from the seed as a Poisson process."""
import collections
import statistics

import numpy as np
import pytest

from chipbench import generator, spec

MIXES = {"prefill_sat": None, "chat": 2.0}


def _take(mix_name, seed, n, rate=None):
    mix = spec.load_traffic(mix_name)
    s = generator.Stream(mix, vocab=50304, seed=seed, rate=rate)
    return [next(s) for _ in range(n)]


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_deterministic_for_a_seed(mix_name):
    a = _take(mix_name, 2**31 + 5, 40, MIXES[mix_name])
    b = _take(mix_name, 2**31 + 5, 40, MIXES[mix_name])
    c = _take(mix_name, 7, 40, MIXES[mix_name])
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert any(len(x.prompt) != len(z.prompt) or
               not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_every_seed_gets_the_same_work_in_the_same_order(mix_name):
    a = _take(mix_name, 1, 300, MIXES[mix_name])
    b = _take(mix_name, 3_000_000_019, 300, MIXES[mix_name])
    assert [(len(x.prompt), x.max_new) for x in a] == \
        [(len(x.prompt), x.max_new) for x in b]


def test_open_loop_arrivals_are_drawn_from_the_seed():
    a = [r.due_s for r in _take("chat", 1, 64, rate=2.0)]
    b = [r.due_s for r in _take("chat", 3_000_000_019, 64, rate=2.0)]
    assert a != b
    assert a == [r.due_s for r in _take("chat", 1, 64, rate=2.0)]


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_every_seed_gets_the_same_sizes(mix_name):
    def sizes(seed):
        reqs = _take(mix_name, seed, generator.POOL, MIXES[mix_name])
        return (collections.Counter(len(r.prompt) for r in reqs),
                collections.Counter(r.max_new for r in reqs))
    assert sizes(1) == sizes(2) == sizes(3_000_000_019)


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_lengths_match_the_stated_distribution(mix_name):
    mix = spec.load_traffic(mix_name)
    reqs = _take(mix_name, 11, generator.POOL, MIXES[mix_name])
    for key, vals in (("prompt", [len(r.prompt) for r in reqs]),
                      ("output", [r.max_new for r in reqs])):
        d = mix[key]
        if d["dist"] == "fixed":
            assert set(vals) == {d["value"]}
            continue
        assert min(vals) >= d["min"] and max(vals) <= d["max"]
        assert abs(statistics.median(vals) - d["median"]) <= 0.02 * d["median"]
        # the log-spread between the quartiles is 2 * 0.6745 sigma
        q1, _, q3 = statistics.quantiles(vals, n=4)
        assert np.log(q3 / q1) == pytest.approx(2 * 0.6745 * d["sigma"],
                                                rel=0.05)
    for r in reqs:
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 50304


def test_open_loop_arrivals_are_poisson_at_the_rate():
    reqs = _take("chat", 5, 16384, rate=4.0)
    due = np.array([r.due_s for r in reqs])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert (gaps > 0).all()
    assert gaps.mean() == pytest.approx(0.25, rel=0.03)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(0.25, rel=0.05)
    # independent gaps: neighbours do not correlate, and the time that
    # 16 arrivals take spreads as a sum of 16 exponentials (sd 4 / rate)
    assert abs(np.corrcoef(gaps[:-1], gaps[1:])[0, 1]) < 0.03
    spans = gaps.reshape(-1, 16).sum(axis=1)
    assert spans.std() == pytest.approx(1.0, rel=0.1)


def test_each_block_holds_one_value_of_every_stratum():
    vals = generator.quantile_values(
        {"dist": "lognormal", "median": 768, "sigma": 0.6, "min": 256,
         "max": 2048}, 256)
    order = generator.stratified_order(vals, np.random.default_rng(3))
    strata = np.sort(vals).reshape(generator.STRATA, -1)
    for b in range(0, 256, generator.STRATA):
        block = sorted(order[b:b + generator.STRATA])
        for s, v in zip(strata, block):
            assert s.min() <= v <= s.max()


def test_closed_loop_has_no_due_times_and_open_needs_a_rate():
    assert all(r.due_s is None for r in _take("prefill_sat", 1, 5))
    with pytest.raises(ValueError):
        generator.Stream(spec.load_traffic("chat"), vocab=10, seed=1)
