"""The traffic generator and the benchmark's copy of the wire format."""

import numpy as np
import pytest

from benchmark import traffic, wire
from tests.benchmark.helpers import keyspace


@pytest.mark.parametrize("rank", [1, 2, 7, 128, 99999, 10_000_000])
def test_hand_assembled_item_is_the_library_encoding(rank):
    ks = keyspace("parity", population=10_000_000)
    enc = traffic.ItemEncoder(ks)
    want = wire.item_bytes(ks.name, ks.unique_key(rank), 1, ks.limit(rank),
                           ks.duration_ms, ks.algo(rank))
    assert enc.item(rank) == want
    msg = wire.GetRateLimitsReq.FromString(enc.rpc([rank, rank]))
    assert len(msg.requests) == 2
    r = msg.requests[1]
    assert (r.unique_key, r.hits, r.limit, r.duration, r.algorithm) == (
        ks.unique_key(rank), 1, ks.limit(rank), ks.duration_ms, ks.algo(rank))


def test_parity_rule_and_limits():
    ks = keyspace("parity")
    assert ks.algo(1) == traffic.LEAKY and ks.algo(2) == traffic.TOKEN
    assert {ks.limit(r) for r in range(1, 200)} == {10, 100, 1000, 10000}
    assert keyspace("leaky").algo(2) == traffic.LEAKY
    ranks = np.arange(1, 500)
    for rule in ("parity", "leaky", "token"):
        ks = keyspace(rule)
        assert ks.limits_of(ranks).tolist() == [ks.limit(int(r)) for r in ranks]
        assert ks.algos_of(ranks).tolist() == [ks.algo(int(r)) for r in ranks]


def test_zipf_is_the_bounded_distribution():
    z = traffic.Zipf(1000, 1.1)
    w = np.arange(1, 1001) ** -1.1
    assert z.share(1) == pytest.approx(1 / w.sum())
    draws = z.draw(np.random.default_rng(0), 200000)
    assert draws.min() >= 1 and draws.max() <= 1000
    assert (draws == 1).mean() == pytest.approx(z.share(1), rel=0.05)


MIX = {"base_seed": 5, "pool_rpcs_per_proc": 64, "items_per_rpc": 10}


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_123)])
def test_every_seed_offers_the_same_work_in_another_order(seeds):
    ks = keyspace("parity", population=5000)
    a = traffic.rpc_pool(ks, MIX, seeds[0], 0, 2)
    b = traffic.rpc_pool(ks, MIX, seeds[1], 0, 2)
    assert not np.array_equal(a, b)
    assert sorted(map(tuple, a)) == sorted(map(tuple, b))
    ga = np.diff(traffic.arrival_offsets(MIX, 100, seeds[0], 0, 2, 10), prepend=0)
    gb = np.diff(traffic.arrival_offsets(MIX, 100, seeds[1], 0, 2, 10), prepend=0)
    assert len(ga) == len(gb) == 500
    assert np.sort(ga) == pytest.approx(np.sort(gb))
    assert not np.allclose(ga, gb)


def test_arrivals_fill_the_window_at_the_stated_rate():
    t = traffic.arrival_offsets(MIX, 400, 1, 1, 4, 40)
    assert len(t) == 4000 and 0 < t[0] and t[-1] < 40
    assert np.all(np.diff(t) > 0)


def test_followed_ranks_are_the_hot_ones_and_a_seeded_share():
    check = {"sample_mod": 16, "hot_ranks": [1, 2, 3]}
    ranks = np.arange(1, 100001)
    m = traffic.sampled_ranks_mask(ranks, check, 42)
    assert m[:3].all()
    assert m.mean() == pytest.approx(1 / 16, rel=0.1)
    other = traffic.sampled_ranks_mask(ranks, check, 43)
    assert not np.array_equal(m, other)
    assert traffic.sampled_ranks_mask(ranks, {"sample_mod": 1}, 1).all()
