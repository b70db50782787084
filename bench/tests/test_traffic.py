"""The seeded traffic generator."""
import itertools

import numpy as np

from bench import traffic
from bench.tests.conftest import ROOT, fixture_json


def take(stream, n):
    return list(itertools.islice(stream, n))


def test_same_seed_same_requests():
    t = fixture_json("smoke-closed.json")
    a = take(traffic.requests(t, 2**33 + 5, 256), 40)
    b = take(traffic.requests(t, 2**33 + 5, 256), 40)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes_per_block():
    t = fixture_json("smoke-closed.json")
    n = 3 * t["block"]
    size = lambda s: sorted((len(p), o) for p, o in
                            take(traffic.requests(t, s, 256), n))
    lens = lambda s: (sorted(len(p) for p, _ in
                             take(traffic.requests(t, s, 256), n)),
                      sorted(o for _, o in
                             take(traffic.requests(t, s, 256), n)))
    assert lens(1) == lens(2 ** 40 + 7)
    assert size(1) != size(2)          # in another order, paired anew


def test_lengths_follow_the_mix_and_its_clipping():
    t = fixture_json("smoke-closed.json")
    v = traffic.lognormal_sizes(t["prompt"], 64)
    assert v.min() >= t["prompt"]["min"] and v.max() <= t["prompt"]["max"]
    assert abs(np.median(v) - t["prompt"]["median"]) <= 1


def test_poisson_arrivals_repeat_and_keep_the_rate():
    t = fixture_json("smoke-poisson.json")
    a = traffic.arrival_offsets(t, 11, 50.0)
    assert a == traffic.arrival_offsets(t, 11, 50.0)
    assert a != traffic.arrival_offsets(t, 12, 50.0)
    assert all(x < y for x, y in zip(a, a[1:])) and a[-1] <= 50.0
    assert abs(len(a) / 50.0 - t["rate_rps"]) / t["rate_rps"] < 0.1


def test_every_benchmark_traffic_file_is_well_formed():
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        import json
        t = json.loads(path.read_text())
        assert t["loop"] in ("closed", "poisson")
        e = t["engine"]
        assert e["token_budget"] >= e["max_slots"]
        assert t["prompt"]["max"] + t["output"]["max"] <= e["max_len"]


def test_fixed_order_gives_every_seed_the_same_sizes_in_order():
    t = dict(fixture_json("smoke-closed.json"), fixed_order=True)
    a = take(traffic.requests(t, 3, 256), 24)
    b = take(traffic.requests(t, 2 ** 40 + 9, 256), 24)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, b))
