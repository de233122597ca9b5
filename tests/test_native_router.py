"""Native C++ router tests: unit behavior + differential vs the Python path.

The native router replaces SlotTable + crc32 routing (state/arena.py,
core/engine.py shard_of) for regular keys; these tests pin the two backends
to identical responses over randomized workloads, and the router's own LRU /
eviction / overflow semantics.
"""

import os
import random

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu import native
from gubernator_tpu.api.types import Algorithm, RateLimitReq, Second, Status
from gubernator_tpu.core.engine import RateLimitEngine

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native router unavailable")

T0 = 1_700_000_000_000


def _pack_once(r, keys, now=T0, lanes=8, shards=4, duration=1000):
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8)
    ends = np.cumsum([len(k) for k in keys]).astype(np.int64)
    n = len(keys)
    out_slot = np.full((shards, lanes), -1, np.int32)
    o_h = np.zeros((shards, lanes), np.int64)
    o_l = np.zeros((shards, lanes), np.int64)
    o_d = np.zeros((shards, lanes), np.int64)
    o_a = np.zeros((shards, lanes), np.int32)
    o_i = np.zeros((shards, lanes), np.uint8)
    oshard = np.zeros(n, np.int32)
    olane = np.zeros(n, np.int32)
    fill = np.zeros(shards, np.int32)
    packed = r.pack(kb, ends, np.ones(n, np.int64), np.full(n, 5, np.int64),
                    np.full(n, duration, np.int64), np.zeros(n, np.int32),
                    now, lanes, out_slot, o_h, o_l, o_d, o_a, o_i,
                    oshard, olane, fill)
    # these unit tests treat each pack as a dispatched window (the engine
    # commits after every successful dispatch — init-pending protocol)
    r.commit()
    return packed, out_slot, o_i, oshard, olane


def test_lru_eviction_order():
    r = native.NativeRouter(1, 4)
    keys = [f"n_k{i}".encode() for i in range(4)]
    _pack_once(r, keys, shards=1)
    # touch k0 to make it MRU; k1 becomes LRU
    _pack_once(r, [keys[0]], shards=1)
    # two new keys evict k1 then k2
    _, _, _, _, _ = _pack_once(r, [b"n_new1", b"n_new2"], shards=1)
    # k0 and k3 still resident (no is_init), k1/k2 evicted (is_init)
    _, _, init, _, _ = _pack_once(r, [keys[0], keys[3]], shards=1)
    assert init.reshape(-1)[:2].tolist() == [0, 0]
    _, _, init, oshard, olane = _pack_once(r, [keys[1]], shards=1)
    assert init[oshard[0], olane[0]] == 1  # was evicted


def test_lane_overflow_partial_pack():
    r = native.NativeRouter(1, 64)
    keys = [f"n_k{i}".encode() for i in range(10)]
    packed, *_ = _pack_once(r, keys, shards=1, lanes=4)
    assert packed == 4  # stopped at the lane budget


def test_expiry_counts_miss_but_keeps_slot():
    r = native.NativeRouter(1, 8)
    _pack_once(r, [b"n_a"], shards=1, duration=10)
    h0, m0 = r.hits, r.misses
    _pack_once(r, [b"n_a"], shards=1, now=T0 + 100, duration=10)
    assert r.misses == m0 + 1  # expired touch is a miss (lru.go:110-114)
    assert r.hits == h0


def test_differential_native_vs_python():
    """Both engines must produce identical responses on a random workload."""
    mk = lambda nat: RateLimitEngine(
        capacity_per_shard=64, batch_per_shard=32,
        global_capacity=32, global_batch_per_shard=16, max_global_updates=16,
        use_native=nat)
    py_eng, nat_eng = mk(False), mk("on")
    assert nat_eng.native is not None and py_eng.native is None

    rng = random.Random(7)
    keys = [f"dk{i}" for i in range(40)]  # > capacity/shard -> evictions too
    now = T0
    for w in range(25):
        window = [
            RateLimitReq(
                name="diff", unique_key=rng.choice(keys),
                hits=rng.choice([0, 1, 1, 2, 5]),
                limit=rng.choice([2, 5, 10]),
                duration=rng.choice([5, 100, 1000]),
                algorithm=rng.choice([Algorithm.TOKEN_BUCKET,
                                      Algorithm.LEAKY_BUCKET]),
            )
            for _ in range(rng.randint(1, 25))
        ]
        a = py_eng.process(window, now=now)
        b = nat_eng.process(window, now=now)
        for i, (x, y) in enumerate(zip(a, b)):
            assert (x.status, x.limit, x.remaining, x.reset_time) == \
                   (y.status, y.limit, y.remaining, y.reset_time), \
                   f"window {w} item {i}"
        now += rng.choice([0, 1, 7, 120])


def test_native_engine_with_globals_and_flood():
    eng = RateLimitEngine(
        capacity_per_shard=256, batch_per_shard=64,
        global_capacity=32, global_batch_per_shard=16, max_global_updates=16,
        use_native="on")
    from gubernator_tpu.api.types import Behavior
    g = lambda h: RateLimitReq(name="ng", unique_key="g1", hits=h, limit=50,
                               duration=60_000, behavior=Behavior.GLOBAL)
    flood = [RateLimitReq(name="nf", unique_key=f"k{i % 300}", hits=1,
                          limit=5, duration=60_000) for i in range(600)]
    rs = eng.process([g(3)] + flood + [g(2)], now=T0)
    assert rs[0].remaining == 47  # as-if init with hits=3
    assert rs[-1].remaining == 48  # same window: as-if init with its own hits
    assert [r.remaining for r in rs[1:301]] == [4] * 300
    assert [r.remaining for r in rs[301:601]] == [3] * 300
    r2 = eng.process([g(0)], now=T0 + 5)[0]
    assert r2.remaining == 45  # psum applied 3+2


def test_differential_exact_key_guard():
    """The opt-in exact-key guard (EngineConfig.exact_keys /
    GUBER_EXACT_KEYS) stores and compares full keys on every lookup; the
    engine must behave identically to the fingerprint-only router on a
    workload with allocation, reuse, eviction, and expiry (a real 64-bit
    FNV collision cannot be synthesized here, but this drives the storage,
    compare, and free/realloc paths on every probe)."""
    mk = lambda **kw: RateLimitEngine(
        capacity_per_shard=64, batch_per_shard=32,
        global_capacity=32, global_batch_per_shard=16, max_global_updates=16,
        use_native="on", **kw)
    plain, exact = mk(), mk(exact_keys=True)

    rng = random.Random(11)
    keys = [f"xk{i}" for i in range(40)]
    now = T0
    for w in range(20):
        window = [
            RateLimitReq(
                name="exact", unique_key=rng.choice(keys),
                hits=rng.choice([0, 1, 2]),
                limit=rng.choice([3, 8]),
                duration=rng.choice([5, 500]),
                algorithm=rng.choice([Algorithm.TOKEN_BUCKET,
                                      Algorithm.LEAKY_BUCKET]),
            )
            for _ in range(rng.randint(1, 25))
        ]
        a = plain.process(window, now=now)
        b = exact.process(window, now=now)
        for i, (x, y) in enumerate(zip(a, b)):
            assert (x.status, x.limit, x.remaining, x.reset_time) == \
                   (y.status, y.limit, y.remaining, y.reset_time), \
                   f"window {w} item {i}"
        now += rng.choice([0, 1, 40])


def test_native_library_is_keyed_on_source_content(monkeypatch, tmp_path):
    """The .so is named after host_router.cc's sha256, so a copied or
    edited tree can never pair a stale library with a newer source; and a
    toolchain that fails to build the source raises instead of quietly
    handing the engine the Python router."""
    import hashlib
    import shutil

    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert native._so_path().endswith(f"libhost_router-{digest}.so")
    assert native.available() and os.path.exists(native._so_path())

    broken = tmp_path / "host_router.cc"
    broken.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(broken))
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    if shutil.which("g++") is None:
        assert native._load() is None
    else:
        with pytest.raises(RuntimeError, match="native router build failed"):
            native._load()
    assert not list(tmp_path.glob("*.so"))
