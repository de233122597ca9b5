"""Restart equivalence: snapshot -> kill -> restore must be invisible.

The state lifecycle's core contract (state/snapshot.py): traffic served
after a restore must be BIT-IDENTICAL to an uninterrupted run — the int64
host oracle (tests/pyref.py) runs straight through while the engine is
snapshotted, destroyed, and restored mid-workload, with the clock resumed
both INSIDE live windows (remaining must survive) and PAST window/expiry
boundaries (lazy TTL must fire exactly as it would have).  Both wire
layouts are covered: the compact32 layout runs through ops/kernel's
pair-rebase helpers, so these tests also pin that codec against the int64
truth.

Corruption: a truncated or bit-flipped snapshot must degrade to a logged
cold start (restore_engine), never a crash or a half-restore.
"""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu import native
from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.state import snapshot as snapmod

from .pyref import PyRefCache

pytestmark = pytest.mark.snapshot

T0 = 1_754_000_000_000

# key pool smaller than capacity: the oracle has no eviction, so
# eviction-free workloads are the comparable domain (same rule as
# test_property_fuzz.py)
KEYS = [f"s{i}" for i in range(24)]


def _mk_engine(use_native=False):
    return RateLimitEngine(capacity_per_shard=64, batch_per_shard=16,
                           global_capacity=16, global_batch_per_shard=8,
                           max_global_updates=8, use_native=use_native)


def _workload(rng, rounds):
    """(dt, window) pairs mixing algorithms, hit sizes and durations so
    windows close, buckets drain, and TTLs lapse across the timeline."""
    out = []
    for _ in range(rounds):
        dt = int(rng.choice([3, 40, 700, 30_000]))
        window = [RateLimitReq(
            name="snap", unique_key=str(rng.choice(KEYS)),
            hits=int(rng.integers(0, 5)),
            limit=int(rng.integers(2, 12)),
            duration=int(rng.choice([50, 2_000, 60_000])),
            algorithm=Algorithm.TOKEN_BUCKET if rng.integers(2) else
            Algorithm.LEAKY_BUCKET,
        ) for _ in range(int(rng.integers(1, 10)))]
        out.append((dt, window))
    return out


def _drive(eng, oracle, workload, now):
    for dt, window in workload:
        now += dt
        got = eng.process(window, now=now)
        want = [oracle.hit(r, now) for r in window]
        for j, (g, w) in enumerate(zip(got, want)):
            assert (int(g.status), g.limit, g.remaining, g.reset_time) == \
                (int(w.status), w.limit, w.remaining, w.reset_time), \
                f"item {j} at t+{now - T0}: {window[j]}"
    return now


def _backends():
    return [False] + (["on"] if native.available() else [])


@pytest.mark.parametrize("layout", ["int64", "compact32"])
@pytest.mark.parametrize("use_native", _backends())
def test_restart_equivalence(layout, use_native):
    """Traffic -> snapshot -> kill -> restore -> more traffic, with resume
    deltas both inside live windows and past duration/TTL boundaries; the
    oracle never restarts, so any drift in the snapshot codec or the
    restore path shows up as a decision mismatch."""
    rng = np.random.default_rng(7)
    oracle = PyRefCache()
    eng = _mk_engine(use_native)
    now = _drive(eng, oracle, _workload(rng, 8), T0)

    blob = snapmod.dumps(eng.export_state(now=now, layout=layout))
    del eng  # the "kill": nothing survives but the blob

    # resume INSIDE open windows (+25ms: 50ms buckets still live), then a
    # second restart resuming PAST most windows/TTLs (+70s)
    for resume_dt in (25, 70_000):
        eng = _mk_engine(use_native)
        eng.import_state(snapmod.loads(blob))
        restored_oracle = _clone_oracle(oracle)
        now2 = now + resume_dt
        _drive(eng, restored_oracle, _workload(rng, 6), now2)


def _clone_oracle(oracle):
    import copy
    c = PyRefCache()
    c.entries = copy.deepcopy(oracle.entries)
    return c


@pytest.mark.parametrize("use_native", _backends())
def test_layouts_restore_bit_identically(use_native):
    """int64 and compact32 must restore the SAME device state: the
    compact32 rebase runs through ops/kernel's pair helpers and may
    not drift from the plain int64 path by even one bit."""
    rng = np.random.default_rng(11)
    eng = _mk_engine(use_native)
    now = T0
    for dt, window in _workload(rng, 8):
        now += dt
        eng.process(window, now=now)
    snap = eng.export_state(now=now)
    engines = {}
    for layout in ("int64", "compact32"):
        snap.layout = layout
        e = _mk_engine(use_native)
        e.import_state(snapmod.loads(snapmod.dumps(snap)))
        engines[layout] = e.export_state(now=now, layout="int64")
    a, b = engines["int64"], engines["compact32"]
    for name in a.planes:
        assert np.array_equal(a.planes[name], b.planes[name]), name
    for name in a.gplanes:
        assert np.array_equal(a.gplanes[name], b.gplanes[name]), name


def test_corrupted_snapshot_falls_back_cold(tmp_path, caplog):
    """A truncated or bit-flipped snapshot file degrades to a logged cold
    start — restore_engine must return None and leave the engine serving,
    never raise."""
    import logging

    eng = _mk_engine()
    reqs = [RateLimitReq(name="c", unique_key=f"k{i}", hits=1, limit=5,
                         duration=60_000,
                         algorithm=Algorithm.TOKEN_BUCKET)
            for i in range(8)]
    eng.process(reqs, now=T0)
    path = str(tmp_path / "arena.snap")
    snapmod.save(eng.export_state(now=T0 + 100), path)

    blob = open(path, "rb").read()
    cases = {
        "truncated": blob[:len(blob) // 3],
        "bitflip": blob[:64] + bytes([blob[64] ^ 0x10]) + blob[65:],
        "garbage": b"not a snapshot at all",
    }
    for name, bad in cases.items():
        bad_path = str(tmp_path / f"{name}.snap")
        open(bad_path, "wb").write(bad)
        fresh = _mk_engine()
        with caplog.at_level(logging.WARNING, "gubernator.snapshot"):
            got = snapmod.restore_engine(fresh, bad_path)
        assert got is None, name
        assert any("starting cold" in r.getMessage()
                   for r in caplog.records), name
        caplog.clear()
        # the cold engine still serves
        out = fresh.process(reqs[:2], now=T0 + 200)
        assert all(not r.error for r in out)
    # a missing file is an INFO cold start, not a warning
    fresh = _mk_engine()
    assert snapmod.restore_engine(fresh, str(tmp_path / "absent.snap")) is None


def test_geometry_mismatch_rejected(tmp_path):
    eng = _mk_engine()
    eng.process([RateLimitReq(name="g", unique_key="x", hits=1, limit=5,
                              duration=1000,
                              algorithm=Algorithm.TOKEN_BUCKET)], now=T0)
    snap = snapmod.loads(snapmod.dumps(eng.export_state(now=T0)))
    other = RateLimitEngine(capacity_per_shard=32, batch_per_shard=16,
                            global_capacity=16, global_batch_per_shard=8,
                            max_global_updates=8, use_native=False)
    with pytest.raises(snapmod.SnapshotError, match="geometry"):
        other.import_state(snap)


def test_rebase_to_preserves_remaining_lifetime():
    """rebase_to shifts every live timestamp by the downtime: a bucket
    snapshotted with 40ms of its 50ms window left still has 40ms left
    after a 10-minute outage, unlike the default absolute-time restore
    where it would have lapsed."""
    eng = _mk_engine()
    r = RateLimitReq(name="rb", unique_key="shorty", hits=2, limit=10,
                     duration=50, algorithm=Algorithm.TOKEN_BUCKET)
    eng.process([r], now=T0)
    blob = snapmod.dumps(eng.export_state(now=T0 + 10))

    outage = 600_000
    resumed = _mk_engine()
    resumed.import_state(snapmod.loads(blob), rebase_to=T0 + 10 + outage)
    got = resumed.process([r], now=T0 + 20 + outage)[0]
    # 10ms into the (shifted) 50ms window: prior 2 hits still deducted
    assert got.remaining == 10 - 2 - 2
    # the default absolute restore lapses the bucket instead
    cold = _mk_engine()
    cold.import_state(snapmod.loads(blob))
    got2 = cold.process([r], now=T0 + 20 + outage)[0]
    assert got2.remaining == 10 - 2  # fresh window


@pytest.mark.skipif(not native.available(), reason="native router unavailable")
def test_python_snapshot_restores_into_native_engine():
    """Backend portability one way: a Python-table snapshot carries key
    strings, so a native-routed engine can rebuild its fingerprint table
    from it (the reverse is impossible and must raise)."""
    rng = np.random.default_rng(3)
    oracle = PyRefCache()
    py = _mk_engine(False)
    now = _drive(py, oracle, _workload(rng, 6), T0)
    blob = snapmod.dumps(py.export_state(now=now))

    nat = _mk_engine("on")
    nat.import_state(snapmod.loads(blob))
    _drive(nat, _clone_oracle(oracle), _workload(rng, 4), now + 40)

    nat2 = _mk_engine("on")
    for dt, window in _workload(rng, 4):
        nat2.process(window, now=now)
    nblob = snapmod.dumps(nat2.export_state(now=now))
    with pytest.raises(snapmod.SnapshotError, match="fingerprint"):
        _mk_engine(False).import_state(snapmod.loads(nblob))


def test_snapshot_file_roundtrip(tmp_path):
    eng = _mk_engine()
    eng.process([RateLimitReq(name="f", unique_key=f"k{i}", hits=1, limit=9,
                              duration=30_000,
                              algorithm=Algorithm.LEAKY_BUCKET)
                 for i in range(10)], now=T0)
    path = snapmod.snapshot_path(str(tmp_path))
    size = snapmod.save(eng.export_state(now=T0 + 5), path)
    assert size == len(open(path, "rb").read())
    fresh = _mk_engine()
    restored = snapmod.restore_engine(fresh, path)
    assert restored is not None and restored.total_keys() == 10
    assert fresh.cache_stats(now=T0 + 10)["live"] == 10


def test_cache_stats_coherent():
    """The single cache_stats accessor must agree with the legacy
    properties and expose occupancy that sums to capacity."""
    eng = _mk_engine()
    reqs = [RateLimitReq(name="st", unique_key=f"k{i}", hits=1, limit=5,
                         duration=100, algorithm=Algorithm.TOKEN_BUCKET)
            for i in range(12)]
    eng.process(reqs, now=T0)
    eng.process(reqs[:6], now=T0 + 10)  # 6 hits
    st = eng.cache_stats(now=T0 + 10)
    assert st["size"] == eng.cache_size == 12
    assert st["hits"] == eng.cache_hits == 6
    assert st["misses"] == eng.cache_misses == 12
    assert st["free"] + st["live"] + st["expired"] == st["capacity"]
    assert st["live"] == 12
    # after the duration lapses they count as expired, not live
    st2 = eng.cache_stats(now=T0 + 1000)
    assert st2["expired"] == 12 and st2["live"] == 0
