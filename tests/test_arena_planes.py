"""The resident arena's layout: ten uint32 planes (a lo and a hi for each
int64 column of BucketState) and `algo` (ops/kernel.py ArenaPlanes).

What has to hold, whatever the layout:

  * the converters arena planes <-> int64 rows lose no bit;
  * a drain on the planes equals kernel.window_step on int64 rows (the
    oracle), lane for lane and slot for slot: the full-format step on
    values far outside 32 bits, the compact32 serving step and the
    engine's compiled drain inside the compact caps;
  * snapshots keep their formats: what the planes export restores
    bit-identically, and files written by the parent commit (int64 rows
    resident, tests/data/arena_snapshot_parent_*.snap) restore into the
    planes;
  * migration and tier demotion read the same rows as before.

The parent's files were written by `python -m tests.test_arena_planes
<dir>` with this file copied into a checkout of commit 17a7d4b (its
__main__ below uses nothing the parent lacks).
"""

import io
import os
import sys

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.ops import kernel
from gubernator_tpu.parallel.mesh import make_mesh
from gubernator_tpu.state import snapshot as snapmod

T0 = 1_754_000_000_000
DATA = os.path.join(os.path.dirname(__file__), "data")

# every int64 bit pattern that matters to a stored column
PATTERNS = np.array(
    [0, -1, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**32 + 1, 2**63 - 1,
     -2**63, -2**31, -2**32, -2**32 - 1, T0, T0 + 86_400_000,
     1_791_000_000_123], np.int64)


# ------------------------------------------------------------- converters


def _pattern_rows(xp):
    """int64 rows [2, 16]: each column a rotation of PATTERNS, so every
    pattern meets every column; algo an int32 ramp."""
    cols = [np.stack([np.roll(PATTERNS, i), np.roll(PATTERNS[::-1], i)])
            for i in range(5)]
    algo = np.arange(32, dtype=np.int32).reshape(2, 16) % 5
    return kernel.BucketState(*[xp.asarray(c) for c in cols],
                              xp.asarray(algo))


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_converters_round_trip_every_bit_pattern(xp):
    rows = _pattern_rows(xp)
    planes = kernel.arena_from_rows(rows)
    assert planes._fields == kernel.ArenaPlanes._fields
    for name, p in zip(planes._fields[:-1], planes[:-1]):
        assert p.dtype == np.uint32 and p.shape == (2, 16), name
    assert planes.algo.dtype == np.int32
    # the halves are the int64's own little-endian words
    for i, f in enumerate(kernel.BucketState._fields[:5]):
        words = np.asarray(rows[i]).view(np.uint32).reshape(2, 16, 2)
        np.testing.assert_array_equal(np.asarray(planes[2 * i]),
                                      words[..., 0], err_msg=f"{f}_lo")
        np.testing.assert_array_equal(np.asarray(planes[2 * i + 1]),
                                      words[..., 1], err_msg=f"{f}_hi")
    back = kernel.arena_to_rows(planes)
    for f, a, b in zip(rows._fields, rows, back):
        assert np.asarray(b).dtype == np.asarray(a).dtype, f
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)


def test_zeros_is_the_dead_arena():
    planes = kernel.ArenaPlanes.zeros(8)
    assert all(not np.asarray(p).any() for p in planes)
    assert sum(np.asarray(p).nbytes for p in planes) == 8 * 44
    rows = kernel.arena_to_rows(planes)
    assert not np.asarray(rows.expire).any()  # expire == 0: never written


def test_gather_and_commit_take_either_form():
    """The two functions window_prep / window_commit are built on: the same
    registers come out of rows and planes, and a commit lands the same
    values (out-of-range lanes dropped)."""
    rows = kernel.BucketState(*[a[0] for a in _pattern_rows(jnp)])
    planes = kernel.arena_from_rows(rows)
    g = jnp.asarray([0, 3, 3, 15, 7], jnp.int32)
    for a, b in zip(kernel.gather_registers(rows, g),
                    kernel.gather_registers(planes, g)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fin = kernel._Reg(*[jnp.asarray(np.roll(PATTERNS, 5 + i)[:5])
                        for i in range(5)],
                      jnp.asarray([4, 3, 2, 1, 0], jnp.int32))
    wslot = jnp.asarray([1, 16, 2, 9, 16], jnp.int32)   # 16 == C: dropped
    new_rows = kernel.commit_registers(rows, wslot, fin)
    new_planes = kernel.commit_registers(planes, wslot, fin)
    assert isinstance(new_planes, kernel.ArenaPlanes)
    for f, a, b in zip(rows._fields, new_rows,
                       kernel.arena_to_rows(new_planes)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
    assert int(new_rows.limit[1]) == int(fin.limit[0])
    assert int(new_rows.limit[0]) == int(rows.limit[0])


# ------------------------------------------ a drain on planes == the oracle

C, B = 48, 32


def _seed_rows(rng, wide):
    """A live arena: configs and counters above 2^32 when `wide`, negative
    `remaining`, dead slots (expire 0), expired and live ones."""
    top = 2**40 if wide else 2**20
    limit = rng.integers(1, top, C)
    duration = rng.integers(1000, top if wide else 600_000, C)
    remaining = rng.integers(-50 if wide else 0, limit + 1, C)
    tstamp = T0 + rng.integers(-duration, duration, C)
    expire = T0 + rng.integers(-1000, duration, C)
    expire[rng.random(C) < 0.25] = 0
    algo = rng.integers(0, 2, C).astype(np.int32)
    if wide:
        limit[:4] = [2**32, 2**32 - 1, 2**33 + 5, 2**62]
        remaining[:4] = [2**32, -1, -2**31 - 1, 2**61]
        expire[:4] = T0 + 10_000_000
    return kernel.BucketState(*[jnp.asarray(a.astype(np.int64)) for a in
                                (limit, duration, remaining, tstamp, expire)],
                              jnp.asarray(algo))


def _window(rng, wide):
    """Duplicates on hot slots, recycled slots (is_init mid-run: the
    commit_mask's case), pad lanes, reads and over-asks."""
    slot = rng.integers(0, C, B).astype(np.int32)
    hot = rng.integers(0, C, 3)
    dup = rng.random(B) < 0.5
    slot[dup] = hot[rng.integers(0, 3, int(dup.sum()))]
    slot[rng.random(B) < 0.2] = kernel.PAD_SLOT
    top = 2**40 if wide else 900
    hits = rng.choice([0, 1, 1, 2, 5, 2**33 if wide else 7], B)
    limit = rng.integers(1, top, B)
    if not wide:
        # a hot key's duplicates agree on their config, as a client's do
        limit[dup] = 500
    duration = rng.integers(1000, 2**34 if wide else 600_000, B)
    return kernel.WindowBatch(
        slot=jnp.asarray(slot), hits=jnp.asarray(hits.astype(np.int64)),
        limit=jnp.asarray(limit.astype(np.int64)),
        duration=jnp.asarray(duration.astype(np.int64)),
        algo=jnp.asarray(rng.integers(0, 2, B).astype(np.int32)),
        is_init=jnp.asarray(rng.random(B) < 0.25))


def _assert_same(tag, st_rows, st_planes, out_a, out_b):
    for f, a, b in zip(kernel.WindowOutput._fields, out_a, out_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{tag}: lane {f}")
    assert isinstance(st_planes, kernel.ArenaPlanes)
    for f, a, b in zip(st_rows._fields, st_rows,
                       kernel.arena_to_rows(st_planes)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{tag}: slot {f}")


_step = jax.jit(kernel.window_step)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_full_format_step_on_planes_equals_oracle(seed):
    """kernel.window_step itself, rows against planes, on values that need
    all 64 bits (the engine's full-format executables take this path)."""
    rng = np.random.default_rng(seed)
    st_rows = _seed_rows(rng, wide=True)
    st_planes = kernel.arena_from_rows(st_rows)
    for w in range(6):
        bt, now = _window(rng, wide=True), jnp.int64(T0 + 700 * w)
        st_rows, out_r = _step(st_rows, bt, now)
        st_planes, out_p = _step(st_planes, bt, now)
        _assert_same(f"seed {seed} window {w}", st_rows, st_planes,
                     out_r, out_p)
    # the windows left 64-bit values standing in the arena
    assert int(np.asarray(st_rows.limit).max()) >= 2**32


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compact32_serving_step_on_planes_equals_oracle(seed):
    """The serving drain's window step (rebased int32 math) on the
    planes against the int64 oracle on rows, inside the compact caps."""
    rng = np.random.default_rng(100 + seed)
    st_rows = _seed_rows(rng, wide=False)
    st_planes = kernel.arena_from_rows(st_rows)
    step_c32 = jax.jit(kernel.window_step_compact32)
    for w in range(6):
        bt, now = _window(rng, wide=False), jnp.int64(T0 + 700 * w)
        st_rows, out_r = _step(st_rows, bt, now)
        st_planes, out_p = step_c32(st_planes, bt, now)
        _assert_same(f"seed {seed} window {w}", st_rows, st_planes,
                     out_r, out_p)


def _mk_engine(**kw):
    kw.setdefault("use_native", False)
    return RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                           capacity_per_shard=64, batch_per_shard=16,
                           global_capacity=16, global_batch_per_shard=8,
                           max_global_updates=8, **kw)


@pytest.mark.parametrize("seed", [5, 6])
def test_engine_drain_equals_oracle(seed):
    """The compiled serving drain (shard_map, scan, donation: everything
    around the step) against the oracle chained window by window, twice,
    so the donated planes carry from one dispatch to the next."""
    from .test_mesh_drain import _oracle_drain, _random_stack
    rng = np.random.default_rng(seed)
    eng = _mk_engine()
    assert isinstance(eng.state, kernel.ArenaPlanes)
    assert all(p.dtype == np.uint32 for p in eng.state[:-1])
    oracle = [kernel.BucketState.zeros(64)]
    for rnd in range(2):
        stack = _random_stack(rng, 3, 1, 16, 64)
        nows = np.asarray([T0 + rnd * 5_000_000 + 900 * k for k in range(3)],
                          np.int64)
        words, limits, mism = eng.pipeline_dispatch(stack, nows)
        want = _oracle_drain(oracle, stack, nows)
        for name, got, w in zip(("words", "limits", "mism"),
                                (words, limits, mism), want):
            np.testing.assert_array_equal(np.asarray(got), w,
                                          err_msg=f"round {rnd} {name}")
    rows = kernel.arena_to_rows(
        kernel.ArenaPlanes(*[np.asarray(p)[0] for p in eng.state]))
    for f, a, b in zip(rows._fields, rows, oracle[0]):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=f)


# ------------------------------------------------------------- snapshots

# rows no 32-bit column could hold, installed as they are (import_rows)
WIDE_ROWS = [
    {"key": "w_a", "limit": 2**33 + 5, "duration": 2**32, "remaining": -7,
     "tstamp": T0 + 2**32, "expire": T0 + 2**33, "algo": 0},
    {"key": "w_b", "limit": 2**62, "duration": 2**40, "remaining": 2**61,
     "tstamp": T0 - 1, "expire": T0 + 2**40, "algo": 1},
    {"key": "w_c", "limit": 2**32 - 1, "duration": 2**31,
     "remaining": -2**31 - 1, "tstamp": T0, "expire": T0 + 2**31, "algo": 1},
]


def _traffic(eng, seed, rounds=6):
    rng = np.random.default_rng(seed)
    now = T0
    for _ in range(rounds):
        now += int(rng.choice([3, 700, 30_000]))
        eng.process([RateLimitReq(
            name="snap", unique_key=f"k{int(rng.integers(0, 20))}",
            hits=int(rng.integers(0, 4)), limit=int(rng.integers(2, 12)),
            duration=int(rng.choice([50, 2_000, 60_000])),
            algorithm=Algorithm.TOKEN_BUCKET if rng.integers(2) else
            Algorithm.LEAKY_BUCKET) for _ in range(int(rng.integers(1, 10)))],
            now=now)
    return now


def _loaded_engine(layout):
    eng = _mk_engine()
    now = _traffic(eng, 11)
    if layout == "int64":
        eng.import_rows(WIDE_ROWS, now=now)
    return eng, now


def _planes_equal(tag, a, b):
    assert set(a) == set(b) == set(kernel.BucketState._fields), tag
    for f in a:
        assert a[f].dtype == b[f].dtype, f"{tag}: {f}"
        np.testing.assert_array_equal(a[f], b[f], err_msg=f"{tag}: {f}")


@pytest.mark.parametrize("layout", ["int64", "compact32"])
def test_snapshot_from_planes_restores_bit_identically(layout):
    eng, now = _loaded_engine(layout)
    snap = eng.export_state(now=now, layout=layout)
    assert snap.planes["limit"].dtype == np.int64     # the format's rows
    assert snap.planes["algo"].dtype == np.int32
    blob = snapmod.dumps(snap)
    eng2 = _mk_engine()
    eng2.import_state(snapmod.loads(blob))
    for f, a, b in zip(kernel.ArenaPlanes._fields, eng.state, eng2.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
    _planes_equal("re-export", snap.planes,
                  eng2.export_state(now=now, layout=layout).planes)
    if layout == "int64":
        got = {r["key"]: r for r in
               eng2.export_rows([r["key"] for r in WIDE_ROWS])}
        assert got == {r["key"]: r for r in WIDE_ROWS}


def _payload(blob):
    """A snapshot blob's arrays (the npz behind the magic, version, crc)."""
    with np.load(io.BytesIO(blob[len(snapmod.MAGIC) + 8:])) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.parametrize("layout", ["int64", "compact32"])
def test_parent_snapshot_file_restores_into_planes(layout):
    """A file the parent commit wrote from int64-resident planes: it
    restores into the uint32 planes, reads back row for row, and the
    restored engine goes on serving as the file's writer would have."""
    path = os.path.join(DATA, f"arena_snapshot_parent_{layout}.snap")
    with open(path, "rb") as f:
        blob = f.read()
    snap = snapmod.loads(blob)
    assert snap.layout == layout
    eng = _mk_engine()
    eng.import_state(snap)
    again = eng.export_state(now=snap.now, layout=layout)
    _planes_equal("parent file", snap.planes, again.planes)
    _planes_equal("parent file GLOBAL", snap.gplanes, again.gplanes)
    # and the other way round: what the planes write is what the parent
    # wrote, array for array
    a, b = _payload(blob), _payload(snapmod.dumps(again))
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # the writer's state is a replay of _loaded_engine: same rows here
    ref, _ = _loaded_engine(layout)
    for f, x, y in zip(kernel.ArenaPlanes._fields, ref.state, eng.state):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)


# ------------------------------------------- migration and tier demotion


def test_migration_reads_and_writes_the_same_rows():
    src, now = _loaded_engine("int64")
    keys = src.local_keys()
    rows = src.export_rows(keys)
    assert {r["key"] for r in WIDE_ROWS} <= {r["key"] for r in rows}
    snap = src.export_state(now=now, layout="int64")
    slots = dict(zip(snap.tables[0][0], np.asarray(snap.tables[0][1])))
    for r in rows:   # a row is its slot's int64 columns, nothing else
        for f in kernel.BucketState._fields:
            assert r[f] == int(snap.planes[f][0, slots[r["key"]]]), (r, f)
    dst = _mk_engine()
    n, skipped = dst.import_rows(rows, now=now)
    assert (n, skipped) == (len(rows), 0)
    key = lambda r: r["key"]  # noqa: E731
    assert sorted(dst.export_rows(keys), key=key) == sorted(rows, key=key)
    # staleness rule reads the device's expire through the same gather
    assert dst.import_rows(rows, now=now) == (0, len(rows))


def test_tier_demotion_spills_the_rows_the_arena_held():
    from gubernator_tpu.config import TierConfig
    eng = RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                          capacity_per_shard=8, batch_per_shard=8,
                          global_capacity=16, global_batch_per_shard=8,
                          max_global_updates=8, use_native=False)
    tiers = eng.enable_tiers(TierConfig(warm_rows=64, layout="int64"),
                             epoch=T0)
    reqs = [RateLimitReq(name="t", unique_key=f"k{i}", hits=2, limit=10,
                         duration=600_000) for i in range(8)]
    eng.process(reqs, now=T0)
    held = {r["key"]: r for r in eng.export_rows(
        [r.hash_key() for r in reqs])}
    assert len(held) == 8
    # eight new keys evict the first eight: each spills at the fence
    eng.process([RateLimitReq(name="t", unique_key=f"n{i}", hits=1, limit=10,
                              duration=600_000) for i in range(8)],
                now=T0 + 10)
    assert tiers.counters["demotions"] == 8
    wkeys, wcols = tiers.warm.export_rows()
    assert set(wkeys) == set(held)
    for j, k in enumerate(wkeys):
        for f in kernel.BucketState._fields:
            assert int(wcols[f][j]) == held[k][f], (k, f)
    # and a demoted key comes back with its counters
    out = eng.process([reqs[3]], now=T0 + 20)[0]
    assert out.remaining == 10 - 2 - 2


if __name__ == "__main__":
    # writes the two snapshot files a test above restores (see the module
    # docstring); run at the commit whose files are wanted
    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)
    for lay in ("int64", "compact32"):
        e, t = _loaded_engine(lay)
        blob_ = snapmod.dumps(e.export_state(now=t, layout=lay))
        with open(os.path.join(out_dir, f"arena_snapshot_parent_{lay}.snap"),
                  "wb") as fh:
            fh.write(blob_)
        print(lay, len(blob_), "bytes")
