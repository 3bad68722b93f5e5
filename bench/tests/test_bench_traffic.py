"""The traffic generator: deterministic per seed, inside its ranges, and
the same sizes at the same due times for every seed."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import registry  # noqa: E402
from traffic import Traffic, gap_pool, length_pool  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(registry.BENCH,
                                                      "traffic"))
               if f.endswith(".json"))


def lengths(tr, n):
    return [(len(tr.request(i)[0]), tr.request(i)[1]) for i in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = registry.traffic(mix)
    a, b = Traffic(m, 2**31 + 17, 1000), Traffic(m, 2**31 + 17, 1000)
    for i in (0, 5, m["pool"] + 3):
        pa, ga = a.request(i)
        pb, gb = b.request(i)
        assert ga == gb and np.array_equal(pa, pb)
        assert a.arrival(i) == b.arrival(i)
    # another seed: the same sizes at the same due times, other tokens
    c = Traffic(m, 2**31 + 18, 1000)
    assert lengths(a, m["pool"]) == lengths(c, m["pool"])
    assert [a.arrival(i) for i in range(m["pool"])] \
        == [c.arrival(i) for i in range(m["pool"])]
    assert not np.array_equal(a.request(0)[0], c.request(0)[0])
    # blocks differ from each other in order
    assert lengths(a, 2 * m["pool"])[:m["pool"]] \
        != lengths(a, 2 * m["pool"])[m["pool"]:]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_draws_the_same_sizes_within_range(mix):
    m = registry.traffic(mix)
    n = 2 * m["pool"]
    seen = []
    for seed in (1, 2**31 + 5, 2**40 + 3):
        tr = Traffic(m, seed, 50)
        ls = lengths(tr, n)
        for p, g in ls:
            assert m["prompt"]["min"] <= p <= m["prompt"]["max"]
            assert m["output"]["min"] <= g <= m["output"]["max"]
        seen.append((sorted(p for p, _ in ls), sorted(g for _, g in ls)))
        assert all((tr.request(i)[0] < 50).all() for i in range(4))
        assert tr.max_len <= m["prompt"]["max"] + m["output"]["max"]
    assert seen[0] == seen[1] == seen[2]


def test_length_pool_quantiles():
    pool = length_pool({"dist": "lognormal", "median": 512, "sigma": 0.8,
                        "min": 128, "max": 2048}, 64)
    assert int(np.median(pool)) in range(480, 545)
    assert pool.min() >= 128 and pool.max() <= 2048
    uni = length_pool({"dist": "uniform", "min": 32, "max": 128}, 32)
    assert uni.min() >= 32 and uni.max() <= 128
    assert abs(uni.mean() - 80) < 2


@pytest.mark.parametrize("mix", MIXES)
def test_arrivals_keep_the_mix_rate_for_every_seed(mix):
    m = registry.traffic(mix)
    n = 3 * m["pool"]
    spans = []
    for seed in (1, 2**31 + 5, 2**40 + 3):
        tr = Traffic(m, seed, 50)
        due = [tr.arrival(i) for i in range(n + 1)]
        assert due[0] == 0.0 and all(np.diff(due) > 0)
        spans.append(due[n])
    # whole blocks of the pool: the same gaps in another order
    assert spans == pytest.approx([n / m["rate"]] * 3)


def test_gap_pool_is_exponential_at_the_rate():
    g = gap_pool(4.0, 1000)
    assert g.mean() == pytest.approx(0.25)
    assert np.median(g) == pytest.approx(0.25 * np.log(2), rel=0.01)
