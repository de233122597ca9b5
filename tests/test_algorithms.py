"""Algorithm-plane suite: the GCRA / sliding-window / concurrency ladders
against the plain-python serial oracles (algorithms/oracles.py), on every
window body that serves them.

The oracles mirror ops/kernel.py transition() branch for branch but share
no code with it (only format constants), so each differential here compares
two independent derivations of the reference semantics:

  * kernel-vs-oracle per algorithm on both window bodies — the int64
    path and the compact32 serving body — and the serving body again
    through the packed wire;
  * a mixed stream that switches one key across all five algorithm values
    (each switch must re-init, per the device's fresh-lane rule);
  * the engine end-to-end (batcher, router, compact gating, fold) vs the
    same oracles;
  * out-of-range algorithm values degrade to token bucket, pinning the
    reference fallback (algorithms.go:100-104) at both the kernel and
    the engine layer;
  * snapshot forward-compat: restored rows carrying unknown algorithm
    values drop to a cold start (log-and-drop, never misinterpret);
  * the concurrency-lease book lifecycle (algorithms/leases.py) and its
    service hooks: acquire/release accounting, stream-close and
    peer-death reclaim, the per-client cap, GLOBAL behavior rejection.
"""

import asyncio

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from gubernator_tpu.algorithms import oracles
from gubernator_tpu.algorithms.leases import LeaseBook
from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
)
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.ops import kernel
from gubernator_tpu.state import snapshot as snapmod

from .harness import wire_window

pytestmark = pytest.mark.algorithms

T0 = 1_754_000_000_000

_step_int64 = jax.jit(kernel.window_step)
_step_c32 = jax.jit(kernel.window_step_compact32)
_wire_window = jax.jit(wire_window)


def _fresh_state(C):
    z = jnp.zeros(C, jnp.int64)
    return kernel.BucketState(limit=z, duration=z, remaining=z,
                              tstamp=z, expire=z,
                              algo=jnp.zeros(C, jnp.int32))


def _stream(algo, seed, W=6, C=8):
    """W windows of C lanes (slot i = lane i), fixed config per slot,
    hit sizes spanning reads / partial / drain / over-ask (and negative
    releases for concurrency), dts spanning in-window and past-expiry."""
    rng = np.random.default_rng(seed)
    limit = rng.integers(1, 40, C).astype(np.int64)
    duration = rng.choice([50, 2_000, 60_000], C).astype(np.int64)
    now = T0
    windows = []
    for _ in range(W):
        now += int(rng.choice([3, 40, 700, 30_000, 70_000]))
        if algo == kernel.CONCURRENCY:
            hits = rng.integers(-6, 7, C).astype(np.int64)
        else:
            hits = rng.integers(0, limit + 3).astype(np.int64)
        batch = kernel.WindowBatch(
            slot=np.arange(C, dtype=np.int32), hits=hits,
            limit=limit.copy(), duration=duration.copy(),
            algo=np.full(C, algo, np.int32), is_init=np.zeros(C, bool))
        windows.append((batch, now))
    return windows


def _oracle_window(rows, batch, now):
    """Apply one window lane by lane through the python oracles; returns
    a WindowOutput of numpy arrays."""
    C = batch.slot.shape[0]
    st = np.zeros(C, np.int32)
    lm = np.zeros(C, np.int64)
    rm = np.zeros(C, np.int64)
    rt = np.zeros(C, np.int64)
    for i in range(C):
        s = int(batch.slot[i])
        row, (st[i], lm[i], rm[i], rt[i]) = oracles.apply(
            rows.get(s), int(batch.hits[i]), int(batch.limit[i]),
            int(batch.duration[i]), int(batch.algo[i]), now)
        rows[s] = row
    return kernel.WindowOutput(status=st, limit=lm, remaining=rm,
                               reset_time=rt)


def _assert_state_matches_rows(st, rows, tag):
    for s, row in rows.items():
        for f in ("limit", "duration", "remaining", "tstamp", "expire",
                  "algo"):
            assert int(np.asarray(getattr(st, f))[s]) == getattr(row, f), \
                f"{tag}: slot {s} state.{f}"


ALGOS = [kernel.TOKEN_BUCKET, kernel.LEAKY_BUCKET, kernel.GCRA,
         kernel.SLIDING_WINDOW, kernel.CONCURRENCY]
WINDOW_BODIES = {
    "int64": _step_int64,
    "compact32": _step_c32,
}


@pytest.mark.parametrize("lowering", sorted(WINDOW_BODIES))
@pytest.mark.parametrize("algo", ALGOS)
def test_kernel_matches_oracle(algo, lowering):
    step = WINDOW_BODIES[lowering]
    for seed in range(3):
        windows = _stream(algo, 1000 * algo + seed)
        st = _fresh_state(windows[0][0].slot.shape[0])
        rows = {}
        for w, (batch, now) in enumerate(windows):
            st, out = step(st, batch, jnp.int64(now))
            want = _oracle_window(rows, batch, now)
            for f in kernel.WindowOutput._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(out, f)), getattr(want, f),
                    err_msg=f"algo {algo} {lowering} seed {seed} "
                            f"window {w} out.{f}")
        _assert_state_matches_rows(
            st, rows, f"algo {algo} {lowering} seed {seed}")


@pytest.mark.parametrize("algo", ALGOS)
def test_wire_matches_oracle(algo):
    """The same differential through the packed wire: compact-encoded
    requests into the serving body over the resident planes, response
    words out, vs the oracle outputs pushed through the device word
    encoder."""
    for seed in range(2):
        windows = _stream(algo, 2000 * algo + seed)
        st = kernel.ArenaPlanes.zeros(windows[0][0].slot.shape[0])
        rows = {}
        for w, (batch, now) in enumerate(windows):
            packed = jnp.asarray(kernel.encode_batch_host(
                np.asarray(batch.slot), np.asarray(batch.hits),
                np.asarray(batch.limit), np.asarray(batch.duration),
                np.asarray(batch.algo), np.asarray(batch.is_init)))
            st, words, limits, _ = _wire_window(st, packed, jnp.int64(now))
            want = _oracle_window(rows, batch, now)
            want_words = kernel.encode_output_word(
                kernel.WindowOutput(
                    status=jnp.asarray(want.status, jnp.int32),
                    limit=jnp.asarray(want.limit),
                    remaining=jnp.asarray(want.remaining),
                    reset_time=jnp.asarray(want.reset_time)),
                jnp.int64(now))
            np.testing.assert_array_equal(
                np.asarray(words), np.asarray(want_words),
                err_msg=f"algo {algo} seed {seed} window {w} wire words")
            np.testing.assert_array_equal(
                np.asarray(limits), want.limit,
                err_msg=f"algo {algo} seed {seed} window {w} wire limits")
        _assert_state_matches_rows(kernel.arena_to_rows(st), rows,
                                   f"algo {algo} wire s{seed}")


def test_mixed_algorithm_stream_matches_oracle():
    """One slot cycled through every algorithm value across windows: each
    switch must re-init (the stored row's algo no longer matches), on the
    int64 and compact32 lowerings alike."""
    C = 4
    rows = {}
    st64 = _fresh_state(C)
    st32 = _fresh_state(C)
    now = T0
    for w, algo in enumerate([0, 1, 2, 3, 4, 2, 0, 3, 4, 1]):
        now += 500
        batch = kernel.WindowBatch(
            slot=np.arange(C, dtype=np.int32),
            hits=np.asarray([1, 0, 2, -1 if algo == 4 else 3], np.int64),
            limit=np.full(C, 10, np.int64),
            duration=np.full(C, 60_000, np.int64),
            algo=np.full(C, algo, np.int32),
            is_init=np.zeros(C, bool))
        st64, out = _step_int64(st64, batch, jnp.int64(now))
        st32, out32 = _step_c32(st32, batch, jnp.int64(now))
        want = _oracle_window(rows, batch, now)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f)), getattr(want, f),
                err_msg=f"mixed window {w} (algo {algo}) out.{f}")
            np.testing.assert_array_equal(
                np.asarray(getattr(out32, f)), getattr(want, f),
                err_msg=f"mixed window {w} (algo {algo}) compact32 out.{f}")
    _assert_state_matches_rows(st64, rows, "mixed int64")
    _assert_state_matches_rows(st32, rows, "mixed compact32")


def test_out_of_range_algorithm_falls_back_to_token():
    """Regression pin on the reference fallback (algorithms.go:100-104):
    an algorithm value outside the wire alphabet serves EXACTLY like
    token bucket — same responses, same committed balances — while the
    stored algo column keeps the out-of-range value."""
    C = 6
    mk = lambda a: kernel.WindowBatch(  # noqa: E731
        slot=np.arange(C, dtype=np.int32),
        hits=np.asarray([0, 1, 3, 5, 9, 2], np.int64),
        limit=np.full(C, 5, np.int64),
        duration=np.full(C, 60_000, np.int64),
        algo=np.full(C, a, np.int32),
        is_init=np.zeros(C, bool))
    st9, st0 = _fresh_state(C), _fresh_state(C)
    rows = {}
    now = T0
    for w in range(3):
        now += 1_000
        st9, out9 = _step_int64(st9, mk(9), jnp.int64(now))
        st0, out0 = _step_int64(st0, mk(0), jnp.int64(now))
        want = _oracle_window(rows, mk(9), now)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out9, f)), np.asarray(getattr(out0, f)),
                err_msg=f"window {w} algo9-vs-token out.{f}")
            np.testing.assert_array_equal(
                np.asarray(getattr(out9, f)), getattr(want, f),
                err_msg=f"window {w} algo9-vs-oracle out.{f}")
    # balances identical, stored algo keeps the out-of-range value
    np.testing.assert_array_equal(np.asarray(st9.remaining),
                                  np.asarray(st0.remaining))
    assert set(np.asarray(st9.algo).tolist()) == {9}


# ------------------------------------------------------- engine end-to-end


def _mk_engine(use_native=False):
    return RateLimitEngine(capacity_per_shard=64, batch_per_shard=16,
                           global_capacity=16, global_batch_per_shard=8,
                           max_global_updates=8, use_native=use_native)


def _backends():
    from gubernator_tpu import native
    return [False] + (["on"] if native.available() else [])


@pytest.mark.parametrize("use_native", _backends())
def test_engine_process_matches_oracle_all_algorithms(use_native):
    """The full serving stack (router staging, compact gating, fold,
    response synthesis) against the python oracles, all five algorithms
    interleaved over a shared key pool."""
    rng = np.random.default_rng(23)
    eng = _mk_engine(use_native)
    keys = [f"a{i}" for i in range(16)]
    key_algo = {k: int(rng.integers(0, 5)) for k in keys}
    key_limit = {k: int(rng.integers(1, 30)) for k in keys}
    key_dur = {k: int(rng.choice([50, 2_000, 60_000])) for k in keys}
    rows = {}
    now = T0
    for _ in range(12):
        now += int(rng.choice([3, 40, 700, 30_000, 70_000]))
        window = []
        for _ in range(int(rng.integers(1, 10))):
            k = str(rng.choice(keys))
            a = key_algo[k]
            h = (int(rng.integers(-4, 5)) if a == kernel.CONCURRENCY
                 else int(rng.integers(0, key_limit[k] + 2)))
            window.append(RateLimitReq(
                name="alg", unique_key=k, hits=h, limit=key_limit[k],
                duration=key_dur[k], algorithm=a))
        got = eng.process(window, now=now)
        for j, (r, g) in enumerate(zip(window, got)):
            hk = r.hash_key()
            row, (s, lm, rm, rt) = oracles.apply(
                rows.get(hk), r.hits, r.limit, r.duration, r.algorithm,
                now)
            rows[hk] = row
            assert (int(g.status), g.limit, g.remaining, g.reset_time) \
                == (s, lm, rm, rt), \
                f"item {j} at t+{now - T0}: {r} -> {g}"


def test_engine_out_of_range_algorithm_serves_as_token():
    """The engine layer's half of the fallback pin: algo values outside
    the wire alphabet can't ride the 3-bit compact wire, so the engine
    must route them to the full path — where they serve as token."""
    eng = _mk_engine()
    now = T0
    mk = lambda k, a, h: RateLimitReq(  # noqa: E731
        name="oor", unique_key=k, hits=h, limit=5, duration=60_000,
        algorithm=a)
    for w in range(3):
        now += 1_000
        got9 = eng.process([mk("x", 9, 2)], now=now)[0]
        got0 = eng.process([mk("y", 0, 2)], now=now)[0]
        assert (int(got9.status), got9.remaining, got9.reset_time) == \
            (int(got0.status), got0.remaining, got0.reset_time), f"w {w}"


# ------------------------------------------- snapshot forward-compat pin


def test_snapshot_unknown_algorithm_rows_drop_to_cold_start():
    """A snapshot written by a NEWER build can carry algorithm values this
    build cannot interpret; restore must log-and-drop those rows to a cold
    start (never misread their packed columns), keeping every known row."""
    eng = _mk_engine()
    now = T0 + 1_000
    reqs = [RateLimitReq(name="fc", unique_key=k, hits=2, limit=10,
                         duration=600_000) for k in ("keep", "drop")]
    eng.process(reqs, now=now)
    snap = eng.export_state(now=now)

    # forge a newer-build row: find `drop`'s slot and poison its algo
    poisoned = 0
    snap.planes["algo"] = snap.planes["algo"].copy()
    for shard, (keys, slots, _) in enumerate(snap.tables):
        for key, slot in zip(keys, slots):
            if key == "fc_drop":
                snap.planes["algo"][shard, int(slot)] = 7
                poisoned += 1
    assert poisoned == 1

    restored = snapmod.loads(snapmod.dumps(snap))
    eng2 = _mk_engine()
    eng2.import_state(restored)

    later = now + 1_000
    keep, drop = eng2.process(
        [RateLimitReq(name="fc", unique_key=k, hits=1, limit=10,
                      duration=600_000) for k in ("keep", "drop")],
        now=later)
    # `keep` survived the restore (balance continues: 10-2-1)
    assert keep.remaining == 7
    # `drop` cold-started (fresh init consumed 1 of 10)
    assert drop.remaining == 9


def test_snapshot_known_algorithms_round_trip():
    """All five algorithm values survive dumps/loads bit-exactly (the
    forward-compat dropper must not touch rows it understands)."""
    eng = _mk_engine()
    now = T0 + 1_000
    reqs = [RateLimitReq(name="rt", unique_key=f"k{a}", hits=1, limit=10,
                         duration=600_000, algorithm=a) for a in range(5)]
    eng.process(reqs, now=now)
    snap = eng.export_state(now=now)
    restored = snapmod.loads(snapmod.dumps(snap))
    eng2 = _mk_engine()
    eng2.import_state(restored)
    got = eng2.process(
        [RateLimitReq(name="rt", unique_key=f"k{a}", hits=0, limit=10,
                      duration=600_000, algorithm=a) for a in range(5)],
        now=now + 10)
    want = eng.process(
        [RateLimitReq(name="rt", unique_key=f"k{a}", hits=0, limit=10,
                      duration=600_000, algorithm=a) for a in range(5)],
        now=now + 10)
    for a, (g, w) in enumerate(zip(got, want)):
        assert (int(g.status), g.remaining, g.reset_time) == \
            (int(w.status), w.remaining, w.reset_time), f"algo {a}"


# ----------------------------------------------------- lease book lifecycle


def test_lease_book_acquire_release_counts():
    b = LeaseBook()
    b.acquire("k1", "c1", 3, T0 + 100)
    b.acquire("k1", "c1", 2, T0 + 50)   # additive, expiry keeps the max
    b.acquire("k1", "c2", 1, T0 + 200)
    b.acquire("k2", "c1", 4, T0 + 100)
    assert b.held("k1") == 6
    assert b.count("c1", "k1") == 5
    assert b.holds("c1", "k1") and b.holds("c2") and not b.holds("c3")
    assert b.stats() == (2, 2, 10)
    assert b.release("k1", "c1", 2) == 2
    assert b.release("k1", "c1", 99) == 3  # saturates at held
    assert b.release("k1", "c1", 1) == 0   # nothing left
    assert b.count("c1", "k1") == 0
    assert b.held("k1") == 1


def test_lease_book_release_client_and_sweep():
    b = LeaseBook()
    b.acquire("k1", "c1", 2, T0 + 100)
    b.acquire("k2", "c1", 3, T0 + 100)
    b.acquire("k1", "c2", 1, T0 - 10)  # already expired
    assert sorted(b.release_client("c1")) == [("k1", 2), ("k2", 3)]
    assert not b.holds("c1")
    assert b.release_client("c1") == []
    dropped = b.sweep(T0)
    assert dropped == [("k1", "c2", 1)]
    assert b.stats() == (0, 0, 0)


def test_lease_book_export_import_drop():
    b = LeaseBook()
    b.acquire("k1", "c1", 2, T0 + 100)
    b.acquire("k2", "c2", 3, T0 + 200)
    rows = b.export_rows()
    b2 = LeaseBook()
    assert b2.import_rows(rows) == 2
    assert b2.stats() == b.stats()
    assert b2.export_rows(["k2"]) == [("k2", "c2", 3, T0 + 200)]
    b2.drop_keys(["k2"])
    assert not b2.holds("c2")
    assert b2.count("c1", "k1") == 2


# --------------------------------------------------------- service hooks


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def run(loop, coro):
    return loop.run_until_complete(asyncio.wait_for(coro, timeout=120))


def _instance(**lease_kw):
    from gubernator_tpu.config import (
        BehaviorConfig, Config, EngineConfig, LeaseConfig,
    )
    from gubernator_tpu.core.service import Instance
    inst = Instance(Config(
        behaviors=BehaviorConfig(),
        engine=EngineConfig(capacity_per_shard=256, batch_per_shard=32,
                            global_capacity=64, global_batch_per_shard=16,
                            max_global_updates=16, use_native=False),
        leases=LeaseConfig(**lease_kw)))
    # no warmup: the lease tests touch one bucket size — let it compile
    # lazily instead of paying the whole serving ladder on a 1-core box
    return inst


def _conc(key, hits, client=None, limit=5):
    return RateLimitReq(name="lease", unique_key=key, hits=hits,
                        limit=limit, duration=60_000,
                        algorithm=Algorithm.CONCURRENCY)


def test_service_lease_accounting(loop):
    """Granted acquires land in the book attributed to the client;
    explicit releases drain it; the device counter agrees throughout."""
    async def body():
        inst = _instance()
        try:
            r = (await inst.get_rate_limits([_conc("a", 3)],
                                            client_id="10.0.0.1"))[0]
            assert int(r.status) == int(Status.UNDER_LIMIT)
            assert r.remaining == 2
            assert inst.leases.count("10.0.0.1", "lease_a") == 3
            # over-ask rejected: no grant recorded
            r = (await inst.get_rate_limits([_conc("a", 3)],
                                            client_id="10.0.0.2"))[0]
            assert int(r.status) == int(Status.OVER_LIMIT)
            assert not inst.leases.holds("10.0.0.2")
            # explicit release gives slots back on device AND in the book
            r = (await inst.get_rate_limits([_conc("a", -2)],
                                            client_id="10.0.0.1"))[0]
            assert r.remaining == 4
            assert inst.leases.count("10.0.0.1", "lease_a") == 1
        finally:
            inst.close()

    run(loop, body())


def test_service_release_client_leases(loop):
    """Stream-close / peer-death reclaim: every slot a vanished client
    holds is pushed back through the decision path, so the device counter
    recovers without waiting for bucket expiry."""
    async def body():
        inst = _instance()
        try:
            await inst.get_rate_limits([_conc("a", 2), _conc("b", 1)],
                                       client_id="10.9.9.9")
            assert inst.leases.holds("10.9.9.9")
            freed = await inst.release_client_leases("10.9.9.9")
            assert freed == 3
            assert not inst.leases.holds("10.9.9.9")
            # device slots actually came back: a fresh client can take all 5
            r = (await inst.get_rate_limits([_conc("a", 5)],
                                            client_id="10.0.0.3"))[0]
            assert int(r.status) == int(Status.UNDER_LIMIT)
            # peer-death entry point resolves host:port down to the host
            await inst.get_rate_limits([_conc("c", 1)],
                                       client_id="10.7.7.7")
            assert await inst.release_peer_leases("10.7.7.7:8081") == 1
        finally:
            inst.close()

    run(loop, body())


def test_service_lease_cap_per_client(loop):
    """GUBER_LEASE_MAX_PER_CLIENT: an acquire past the cap is answered
    OVER_LIMIT on the host — the device never sees it."""
    async def body():
        inst = _instance(max_per_client=2)
        try:
            r = (await inst.get_rate_limits([_conc("a", 2)],
                                            client_id="10.0.0.1"))[0]
            assert int(r.status) == int(Status.UNDER_LIMIT)
            r = (await inst.get_rate_limits([_conc("a", 1)],
                                            client_id="10.0.0.1"))[0]
            assert int(r.status) == int(Status.OVER_LIMIT)
            # a different client still gets slots (device has 3 free and
            # this client's own count is 0)
            r = (await inst.get_rate_limits([_conc("a", 2)],
                                            client_id="10.0.0.2"))[0]
            assert int(r.status) == int(Status.UNDER_LIMIT)
            assert inst.leases.count("10.0.0.2", "lease_a") == 2
        finally:
            inst.close()

    run(loop, body())


def test_service_rejects_global_with_new_algorithms(loop):
    """GLOBAL behavior stays token/leaky-only: the staged pair-transition
    was deliberately not extended, so the service must refuse rather than
    silently serve wrong math."""
    async def body():
        inst = _instance()
        try:
            for algo in (Algorithm.GCRA, Algorithm.SLIDING_WINDOW,
                         Algorithm.CONCURRENCY):
                r = (await inst.get_rate_limits([RateLimitReq(
                    name="g", unique_key="k", hits=1, limit=5,
                    duration=60_000, algorithm=algo,
                    behavior=Behavior.GLOBAL)]))[0]
                assert "GLOBAL behavior does not support" in r.error
            # token + GLOBAL still serves
            r = (await inst.get_rate_limits([RateLimitReq(
                name="g", unique_key="k", hits=1, limit=5,
                duration=60_000, behavior=Behavior.GLOBAL)]))[0]
            assert r.error == ""
        finally:
            inst.close()

    run(loop, body())
