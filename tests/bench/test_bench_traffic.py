"""Traffic: deterministic by seed, within its declared ranges, and the
same requests in the same order for every seed."""
import os

import numpy as np
import pytest

from harness import spec, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                       "traffic")))


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_and_in_range(name):
    mix = spec.traffic_file(name)
    a = traffic.items(mix, 2**33 + 1, 1000)
    b = traffic.items(mix, 2**33 + 1, 1000)
    assert len(a) == mix["clients"] * mix["per_client"]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    for it in a:
        p, o = mix["prompt"], mix["output"]
        assert p["min"] <= len(it.prompt) <= p["max"]
        assert len(it.prompt) % p.get("multiple", 1) == 0
        assert o["min"] <= it.max_new <= o["max"]
        assert it.prompt.min() >= 0 and it.prompt.max() < 1000


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_gaps(name):
    """Two seeds send the same sizes in the same order; only the token
    ids differ."""
    mix = spec.traffic_file(name)
    a = traffic.items(mix, 1, 1000)
    b = traffic.items(mix, 2, 1000)
    sizes = lambda xs: [(len(x.prompt), x.max_new) for x in xs]
    assert sizes(a) == sizes(b)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 1,
         "max": 10**6}
    x = traffic.lengths(d, 2001)
    assert abs(np.median(x) - 256) <= 1
    y = traffic.lengths(dict(d, min=64, max=512, multiple=64), 200)
    assert y.min() == 64 and y.max() == 512 and not np.any(y % 64)
    with pytest.raises(ValueError):
        traffic.lengths(dict(d, dist="uniform"), 3)
