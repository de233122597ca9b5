"""The one general traffic generator: keys, their limits, and arrivals.

A configuration's `keyspace` says which keys exist and what each key's limit
is; a traffic mix (benchmark/traffic/<name>.json) says how RPCs are formed
and sent.  Everything here is a pure function of those two files and the
seed.  The multiset of work is the same for every seed: a pool of RPC rows
and a list of arrival gaps are drawn from the mix's `base_seed`, and the
run's `--seed` only permutes them, so two seeds offer the same sizes and
arrivals in another order.

A keyspace may hold a second family of keys, `global`: keys that are asked
for under Behavior GLOBAL (field 7 of the request).  They ride the one rank
column everywhere as ranks above `population` (population + 1 is the
family's first key), and a mix's `global_item_share` says what share of the
pool's item positions draw from that family.  A keyspace without the block
and a mix without the share give what they gave before the family existed.
"""

import numpy as np

TOKEN, LEAKY = 0, 1
GLOBAL = 2          # Behavior GLOBAL on the wire (field 7)


def mix32(rank):
    """Cheap fixed hash of a rank (works on ints and on numpy arrays)."""
    return ((rank * 2654435761) & 0xFFFFFFFF) >> 16


class Family:
    """One family of keys: how a key's algorithm, limit and name follow from
    its number within the family (1..keys)."""

    def __init__(self, spec, keys):
        self.keys = int(keys)
        self.zipf_s = float(spec.get("zipf_s", 0.0))     # 0: uniform
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

    def draw(self, rng, n):
        """n keys of the family, 1..keys: Zipf(zipf_s), or uniform at 0."""
        if self.zipf_s > 0:
            return Zipf(self.keys, self.zipf_s).draw(rng, n)
        return rng.integers(1, self.keys + 1, n, dtype=np.int64)


class KeySpace(Family):
    """Which keys exist and what each one's limit is (from a config file).
    The keys asked for under Behavior BATCHING are ranks 1..population, with
    `duration_ms`, `name` and the rules of `Family`; the keys of the `global`
    block, if there is one, are the ranks above (`family`, `number`)."""

    def __init__(self, spec):
        Family.__init__(self, spec, spec["population"])
        self.population = self.keys
        self.glob = None
        if spec.get("global"):
            self.glob = Family(spec["global"], spec["global"]["keys"])

    def is_global(self, rank):
        return rank > self.population

    def family(self, rank):
        """(the family a rank belongs to, its number within that family)."""
        if rank > self.population:
            if self.glob is None or rank > self.population + self.glob.keys:
                raise ValueError(f"rank {rank} is in no family of the keyspace")
            return self.glob, rank - self.population
        return self, rank

    def algo(self, rank):
        if rank > self.population:
            fam, k = self.family(rank)
            return fam.algo(k)
        return Family.algo(self, rank)

    def limit(self, rank):
        if rank > self.population:
            fam, k = self.family(rank)
            return fam.limit(k)
        return Family.limit(self, rank)

    def duration(self, rank):
        return self.family(rank)[0].duration_ms

    def _of(self, ranks, what):
        ranks = np.asarray(ranks)
        out = getattr(Family, what)(self, ranks)
        g = ranks > self.population
        if self.glob is not None and g.any():
            out = np.array(out, dtype=np.int64)
            out[g] = getattr(self.glob, what)(ranks[g] - self.population)
        return out

    def algos_of(self, ranks):
        """`algo` over a numpy array of ranks."""
        return self._of(ranks, "algos_of")

    def limits_of(self, ranks):
        """`limit` over a numpy array of ranks."""
        return self._of(ranks, "limits_of")

    def durations_of(self, ranks):
        ranks = np.asarray(ranks)
        out = np.full(ranks.shape, self.duration_ms, dtype=np.int64)
        if self.glob is not None:
            out[ranks > self.population] = self.glob.duration_ms
        return out

    def unique_key(self, rank):
        fam, k = self.family(rank)
        return Family.unique_key(fam, k)


class ItemEncoder:
    """rank -> the bytes of its `requests` entry (hits = 1), cached for the
    hot ranks.  Hand-assembled in canonical protobuf form; checked against
    the protobuf library's own encoding in tests/benchmark.  A key of the
    `global` family carries field 7, behavior = GLOBAL, after field 6."""

    CACHE_BELOW = 200_000

    def __init__(self, keyspace, hits=1):
        from benchmark import wire
        self.ks = keyspace
        self._varint = wire._varint
        self._parts = {False: self._family_parts(keyspace, hits, b"")}
        if keyspace.glob is not None:
            self._parts[True] = self._family_parts(
                keyspace.glob, hits, b"\x38" + wire._varint(GLOBAL))
        self._cache = {}

    def _family_parts(self, fam, hits, behavior):
        v = self._varint
        head = b"\x0a" + v(len(fam.name)) + fam.name.encode()
        tail = {}
        for algo in (TOKEN, LEAKY):
            for lim in fam.limits:
                t = (b"\x18" + v(hits) + b"\x20" + v(lim)
                     + b"\x28" + v(fam.duration_ms))
                if algo:
                    t += b"\x30" + v(algo)
                tail[(algo, lim)] = t + behavior
        return head, fam.key_prefix.encode(), tail

    def item(self, rank):
        got = self._cache.get(rank)
        if got is not None:
            return got
        ks = self.ks
        if rank > ks.population:
            fam, k = ks.family(rank)
        else:
            fam, k = ks, rank
        head, prefix, tail = self._parts[fam is not ks]
        key = prefix + str(k).encode()
        body = (head + b"\x12" + bytes((len(key),)) + key
                + tail[(Family.algo(fam, k), Family.limit(fam, k))])
        out = b"\x0a" + self._varint(len(body)) + body
        if rank < self.CACHE_BELOW or fam is not ks:
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
    share = float(mix.get("global_item_share", 0.0))
    if share > 0:
        # exactly that share of the pool's item positions, and which key of
        # the family each asks for, from the base seed as the pool itself
        if keyspace.glob is None:
            raise ValueError("global_item_share needs a `global` block in "
                             "the configuration's keyspace")
        fam = np.random.default_rng([int(mix["base_seed"]), proc, nprocs, 5])
        n = int(round(share * rows * items))
        at = fam.permutation(rows * items)[:n]
        pool.reshape(-1)[at] = keyspace.population + keyspace.glob.draw(fam, n)
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


def sampled_ranks_mask(ranks, check, seed, population=None):
    """Which of these ranks the run's comparison follows: the mix's fixed
    hot ranks plus a 1-in-`sample_mod` draw of all ranks keyed by the seed.
    Ranks above `population` are the `global` family's: every one of them,
    or with `global_sample_mod` in the mix's `check` a seeded 1-in-N."""
    ranks = np.asarray(ranks)
    mod = int(check.get("sample_mod", 1))
    if mod <= 1:
        m = np.ones(ranks.shape, dtype=bool)
    else:
        pick = int(seed) % mod
        m = (mix32(ranks + int(seed) % 1009) % mod) == pick
        for h in check.get("hot_ranks", ()):
            m |= ranks == int(h)
    if population is not None:
        g = ranks > population
        if g.any():
            gmod = int(check.get("global_sample_mod", 1))
            m = np.where(g, True if gmod <= 1 else
                         (mix32(ranks - population + int(seed) % 1009) % gmod)
                         == int(seed) % gmod, m)
    return m
