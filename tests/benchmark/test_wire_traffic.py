"""The traffic generator and the benchmark's copy of the wire format."""

import hashlib

import numpy as np
import pytest

from benchmark import harness, traffic, wire
from tests.benchmark.helpers import keyspace


# the last three are keys of the `global` family: ranks above the population
@pytest.mark.parametrize("rank", [1, 2, 7, 128, 99999, 10_000_000,
                                  10_000_001, 10_000_002, 10_000_064])
def test_hand_assembled_item_is_the_library_encoding(rank):
    ks = keyspace("parity", population=10_000_000, family="parity")
    enc = traffic.ItemEncoder(ks)
    fam, k = ks.family(rank)
    glob = rank > ks.population
    assert (fam is ks.glob) == glob == ks.is_global(rank)
    want = wire.item_bytes(fam.name, ks.unique_key(rank), 1, ks.limit(rank),
                           ks.duration(rank), ks.algo(rank),
                           traffic.GLOBAL if glob else 0)
    assert enc.item(rank) == want
    msg = wire.GetRateLimitsReq.FromString(enc.rpc([rank, rank]))
    assert len(msg.requests) == 2
    r = msg.requests[1]
    assert (r.name, r.unique_key, r.hits, r.limit, r.duration, r.algorithm,
            r.behavior) == (
        "g" if glob else "n", f"t:{k}" if glob else f"k:{rank}", 1,
        ks.limit(rank), ks.duration_ms, ks.algo(rank), 2 if glob else 0)
    # a keyspace without the family gives a BATCHING key the same bytes,
    # and never field 7
    if not glob:
        plain = traffic.ItemEncoder(keyspace("parity", population=10_000_000))
        assert plain.item(rank) == want and b"\x38" not in want[-2:]


# sha256 (first 16 hex digits) over the four generator processes of each
# accepted cell, computed on the parent of the PR that brought the `global`
# family (commit 04092c5): the cells offer byte-identical traffic after it
PARENT_DIGESTS = {
    "mixed-10m-1chip.bulk-1000|7": {
        "pool": "21fafa66f31ab6b5",
        "bytes": "804a432461bfeac5",
        "arrivals": "7dc2d763e2918840",
        "followed": "9175bbae2c5ab172"
    },
    "mixed-10m-1chip.bulk-1000|3000000123": {
        "pool": "38643d27365249a3",
        "bytes": "a5a89713ed353406",
        "arrivals": "da59b8340c5bd115",
        "followed": "1e1ff421973bf272"
    },
    "mixed-10m-1chip.edge-2item|7": {
        "pool": "179be2063b4464c5",
        "bytes": "b5cbccd6f55c8f15",
        "arrivals": "aa27b8e78946781a",
        "followed": "9b5cc23a94faa43d"
    },
    "mixed-10m-1chip.edge-2item|3000000123": {
        "pool": "b22abc5008a07677",
        "bytes": "06b706b6f30c9e21",
        "arrivals": "a1613d64562b7cf5",
        "followed": "4b27598f1ea987bf"
    },
    "leaky-1m-1chip.edge-2item|7": {
        "pool": "b56a7e7a416058c4",
        "bytes": "b1fbc336f00fecc6",
        "arrivals": "46020a5f0559d9b0",
        "followed": "42dd6d905f388477"
    },
    "leaky-1m-1chip.edge-2item|3000000123": {
        "pool": "af3eb4667e8063f4",
        "bytes": "fd2729d71e5826ed",
        "arrivals": "fb74bcce6b5f577f",
        "followed": "eaa0903810e4ecb8"
    }
}


def traffic_digests(cell_name, seed):
    cell = harness.Bench().cell(cell_name)
    mix = cell["mix"]
    ks = traffic.KeySpace(cell["config"]["keyspace"])
    n = int(mix["generator_procs"])
    h = {k: hashlib.sha256() for k in ("pool", "bytes", "arrivals", "followed")}
    enc = traffic.ItemEncoder(ks)
    for proc in range(n):
        pool = traffic.rpc_pool(ks, mix, seed, proc, n)
        h["pool"].update(np.ascontiguousarray(pool, dtype=np.int64).tobytes())
        for row in pool[:64]:
            h["bytes"].update(enc.rpc(row.tolist()))
        h["followed"].update(np.packbits(traffic.sampled_ranks_mask(
            pool, mix["check"], seed, ks.population)).tobytes())
        offs = traffic.arrival_offsets(mix, float(mix.get("rate_rps", 1000)),
                                       seed, proc, n, 40.0)
        h["arrivals"].update(np.ascontiguousarray(offs).tobytes())
    return {k: v.hexdigest()[:16] for k, v in h.items()}


@pytest.mark.parametrize("case", sorted(PARENT_DIGESTS))
def test_the_accepted_cells_offer_the_traffic_they_offered(case):
    cell, seed = case.split("|")
    assert traffic_digests(cell, int(seed)) == PARENT_DIGESTS[case]


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


@pytest.mark.parametrize("share", [0.0, 0.25])
@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_123)])
def test_every_seed_offers_the_same_work_in_another_order(seeds, share):
    ks = keyspace("parity", population=5000, family="token")
    mix = dict(MIX, global_item_share=share) if share else MIX
    a = traffic.rpc_pool(ks, mix, seeds[0], 0, 2)
    b = traffic.rpc_pool(ks, mix, seeds[1], 0, 2)
    assert not np.array_equal(a, b)
    assert sorted(map(tuple, a)) == sorted(map(tuple, b))
    # exactly the stated share of the pool's item positions asks for a key
    # of the family, whatever the seed; without the share, none does
    assert (a > ks.population).sum() == round(share * a.size)
    assert a.max() <= ks.population + ks.glob.keys
    plain = traffic.rpc_pool(keyspace("parity", population=5000), MIX,
                             seeds[0], 0, 2)
    assert np.array_equal(np.where(a > ks.population, plain, a), plain)
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


@pytest.mark.parametrize("mod", [None, 4])
def test_followed_keys_of_the_global_family(mod):
    check = {"sample_mod": 16, "hot_ranks": [1, 2, 3]}
    if mod:
        check["global_sample_mod"] = mod
    ranks = np.concatenate([np.arange(1, 5001), 5000 + np.arange(1, 1025)])
    m = traffic.sampled_ranks_mask(ranks, check, 42, population=5000)
    # the others are followed as they were
    assert np.array_equal(m[:5000], traffic.sampled_ranks_mask(
        ranks[:5000], check, 42))
    if mod is None:
        assert m[5000:].all()                     # every key of the family
    else:
        assert m[5000:].mean() == pytest.approx(1 / mod, rel=0.2)
        other = traffic.sampled_ranks_mask(ranks, check, 43, population=5000)
        assert not np.array_equal(m[5000:], other[5000:])


def test_a_share_without_the_family_is_refused():
    with pytest.raises(ValueError, match="global"):
        traffic.rpc_pool(keyspace("parity", population=5000),
                         dict(MIX, global_item_share=0.1), 1, 0, 2)
    with pytest.raises(ValueError):
        keyspace("parity", population=5000).unique_key(5001)
