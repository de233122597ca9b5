"""The one general traffic generator: keys, their limits, and arrivals.

A configuration's `keyspace` says which keys exist and what each key's limit
is; a traffic mix (benchmark/traffic/<name>.json) says how RPCs are formed
and sent.  Everything here is a pure function of those two files and the
seed.  The multiset of work is the same for every seed: a pool of RPC rows
and a list of arrival gaps are drawn from the mix's `base_seed`, and the
run's `--seed` only permutes them, so two seeds offer the same sizes and
arrivals in another order.
"""

import numpy as np

TOKEN, LEAKY = 0, 1


def mix32(rank):
    """Cheap fixed hash of a rank (works on ints and on numpy arrays)."""
    return ((rank * 2654435761) & 0xFFFFFFFF) >> 16


class KeySpace:
    """Which keys exist and what each one's limit is (from a config file)."""

    def __init__(self, spec):
        self.population = int(spec["population"])
        self.zipf_s = float(spec["zipf_s"])
        self.algorithms = spec["algorithms"]          # parity | leaky | token
        self.limits = [int(x) for x in spec["limits"]]
        self.duration_ms = int(spec["duration_ms"])
        self.name = spec["name"]
        self.key_prefix = spec["key_prefix"]
        if self.algorithms not in ("parity", "leaky", "token"):
            raise ValueError(f"unknown algorithms rule {self.algorithms!r}")

    def algo(self, rank):
        if self.algorithms == "parity":
            return rank & 1                           # odd ranks leak
        return LEAKY if self.algorithms == "leaky" else TOKEN

    def limit(self, rank):
        return self.limits[int(mix32(int(rank))) % len(self.limits)]

    def algos_of(self, ranks):
        """`algo` over a numpy array of ranks."""
        if self.algorithms == "parity":
            return ranks & 1
        return np.full(ranks.shape, self.algo(1), dtype=np.int64)

    def limits_of(self, ranks):
        """`limit` over a numpy array of ranks."""
        return np.asarray(self.limits)[mix32(ranks) % len(self.limits)]

    def unique_key(self, rank):
        return f"{self.key_prefix}{rank}"


class ItemEncoder:
    """rank -> the bytes of its `requests` entry (hits = 1), cached for the
    hot ranks.  Hand-assembled in canonical protobuf form; checked against
    the protobuf library's own encoding in tests/benchmark."""

    CACHE_BELOW = 200_000

    def __init__(self, keyspace, hits=1):
        from benchmark import wire
        ks = self.ks = keyspace
        self._head = b"\x0a" + wire._varint(len(ks.name)) + ks.name.encode()
        self._prefix = ks.key_prefix.encode()
        self._tail = {}
        for algo in (TOKEN, LEAKY):
            for lim in ks.limits:
                t = (b"\x18" + wire._varint(hits) + b"\x20" + wire._varint(lim)
                     + b"\x28" + wire._varint(ks.duration_ms))
                if algo:
                    t += b"\x30" + wire._varint(algo)
                self._tail[(algo, lim)] = t
        self._varint = wire._varint
        self._cache = {}

    def item(self, rank):
        got = self._cache.get(rank)
        if got is not None:
            return got
        ks = self.ks
        key = self._prefix + str(rank).encode()
        body = (self._head + b"\x12" + bytes((len(key),)) + key
                + self._tail[(ks.algo(rank), ks.limit(rank))])
        out = b"\x0a" + self._varint(len(body)) + body
        if rank < self.CACHE_BELOW:
            self._cache[rank] = out
        return out

    def rpc(self, ranks):
        item = self.item
        return b"".join([item(r) for r in ranks])


class Zipf:
    """Exact bounded Zipf(s) over ranks 1..population, by inverse CDF."""

    def __init__(self, population, s):
        w = np.arange(1, population + 1, dtype=np.float64) ** -s
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def draw(self, rng, n):
        u = rng.random(n)
        return (np.searchsorted(self.cdf, u, side="left") + 1).astype(np.int64)

    def share(self, rank):
        lo = self.cdf[rank - 2] if rank > 1 else 0.0
        return float(self.cdf[rank - 1] - lo)


def rpc_pool(keyspace, mix, seed, proc, nprocs):
    """This process's pool of RPC rows, [rows, items] of ranks: the rows are
    drawn from the mix's base seed (so every run seed has the same rows) and
    ordered by the run's seed."""
    rows = int(mix["pool_rpcs_per_proc"])
    items = int(mix["items_per_rpc"])
    base = np.random.default_rng([int(mix["base_seed"]), proc, nprocs, 1])
    z = Zipf(keyspace.population, keyspace.zipf_s)
    pool = z.draw(base, rows * items).reshape(rows, items)
    order = np.random.default_rng([int(seed), proc, nprocs, 2]).permutation(rows)
    return pool[order]


def arrival_offsets(mix, rate_rps, seed, proc, nprocs, seconds):
    """Open loop: this process's due times (seconds from the start), Poisson
    at rate_rps / nprocs.  The gaps come from the base seed and are permuted
    by the run's seed; the whole list is then scaled to the exact rate, so
    every seed offers the same number of RPCs in the same span."""
    n = max(1, int(round(rate_rps / nprocs * seconds)))
    base = np.random.default_rng([int(mix["base_seed"]), proc, nprocs, 3])
    gaps = base.exponential(1.0, n)
    np.random.default_rng([int(seed), proc, nprocs, 4]).shuffle(gaps)
    t = np.cumsum(gaps)
    return t * (seconds / (t[-1] + gaps.mean()))


def sampled_ranks_mask(ranks, check, seed):
    """Which of these ranks the run's comparison follows: the mix's fixed
    hot ranks plus a 1-in-`sample_mod` draw of all ranks keyed by the seed."""
    ranks = np.asarray(ranks)
    mod = int(check.get("sample_mod", 1))
    if mod <= 1:
        return np.ones(ranks.shape, dtype=bool)
    pick = int(seed) % mod
    m = (mix32(ranks + int(seed) % 1009) % mod) == pick
    for h in check.get("hot_ranks", ()):
        m |= ranks == int(h)
    return m
