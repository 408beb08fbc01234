"""The open loop's schedule, the pool picks and the percentile arithmetic."""
import numpy as np

from bench.loadgen import arrival_offsets, percentiles_ms, query_picks


def test_every_seed_offers_the_same_gaps_in_another_order():
    tr = {"rate_qps": 300.0}
    a = arrival_offsets(tr, 10.0, seed=1)
    b = arrival_offsets(tr, 10.0, seed=2**33 + 7)
    assert len(a) == len(b) and 2700 < len(a) < 3300
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert a[-1] < 10.0 and np.all(np.diff(a) >= 0)
    np.testing.assert_array_equal(a, arrival_offsets(tr, 10.0, seed=1))


def test_percentiles_interpolate_as_the_router_does():
    lat = np.random.default_rng(0).exponential(0.05, size=1001)
    p = percentiles_ms(lat)
    assert p["count"] == 1001
    assert p["p50_ms"] == np.percentile(lat * 1e3, 50)
    assert p["p95_ms"] == np.percentile(lat * 1e3, 95)
    assert percentiles_ms([])["p95_ms"] is None
    assert percentiles_ms([0.001, 0.003])["p50_ms"] == 2.0


def test_query_picks_are_uniform_and_seeded():
    a = query_picks(4096, 50000, seed=3)
    np.testing.assert_array_equal(a, query_picks(4096, 50000, seed=3))
    assert a.min() >= 0 and a.max() < 4096
    counts = np.bincount(a, minlength=4096)
    assert counts.min() > 0
