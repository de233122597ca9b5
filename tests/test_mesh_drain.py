"""Sharded serving on a forced 8-device CPU mesh (`make test-mesh-drain`).

The lockstep tick's drain is the GLOBAL-composed executable
(engine.pipeline_dispatch_global): every shard runs the compact32 window
body per window over its own plane-arena shard, and the whole drain pays
ONE collective — the GLOBAL reconciliation psum.  This suite pins that path
differentially against the int64 host oracle (ops/kernel), bit for bit,
including the psum traffic, the donated plane carry across consecutive
drains, uneven shard occupancy, and the stats reduction composed into the
same executable (ops/analytics.shard_stats vs its numpy oracle).  Plus the
normalized boolean parsing every GUBER_* on/off reader shares
(config.env_bool).
"""

import asyncio
import logging

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from gubernator_tpu import native
from gubernator_tpu.api.types import Behavior, RateLimitReq
from gubernator_tpu.config import AnalyticsConfig, BehaviorConfig, env_bool
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.observability.metrics import Metrics
from gubernator_tpu.ops import analytics as ops_analytics
from gubernator_tpu.ops import kernel
from gubernator_tpu.parallel.distributed import LockstepClock
from gubernator_tpu.parallel.mesh import make_mesh

from .pyref import PyRefCache

pytestmark = pytest.mark.mesh_drain

T0 = 1_754_000_000_000  # ms epoch, like the engine's serving clocks

# One shape for every engine-level test in this file: the compiled-builder
# caches (engine lru_caches keyed on the mesh) then compile each executable
# exactly once for the whole suite.
S, B, C, Bg, K = 8, 16, 64, 8, 4


def _mk_engine():
    mesh = make_mesh(jax.devices()[:S])
    return RateLimitEngine(mesh=mesh, capacity_per_shard=C,
                           batch_per_shard=B, global_capacity=16,
                           global_batch_per_shard=Bg, max_global_updates=8)


# ---------------------------------------------------------------------------
# boolean GUBER_* parsing: one shared normalized reader


@pytest.mark.parametrize("val,want", [
    ("1", True), ("true", True), ("TRUE", True), ("yes", True), ("on", True),
    (" On ", True),
    ("0", False), ("false", False), ("no", False), ("off", False),
    ("", False),
])
def test_env_bool_normalizes(monkeypatch, val, want):
    monkeypatch.setenv("GUBER_TEST_BOOL", val)
    # default is the opposite of the expected parse, so a fall-through
    # to the default would be caught
    assert env_bool("GUBER_TEST_BOOL", default=not want) is want


def test_env_bool_unset_means_default(monkeypatch):
    monkeypatch.delenv("GUBER_TEST_BOOL_UNSET", raising=False)
    assert env_bool("GUBER_TEST_BOOL_UNSET", default=True) is True
    assert env_bool("GUBER_TEST_BOOL_UNSET", default=False) is False


def test_env_bool_unrecognized_warns_once(monkeypatch, caplog):
    monkeypatch.setenv("GUBER_TEST_BOOL_BAD", "maybe")
    with caplog.at_level(logging.WARNING, logger="gubernator.config"):
        assert env_bool("GUBER_TEST_BOOL_BAD", default=True) is True
        assert env_bool("GUBER_TEST_BOOL_BAD", default=False) is False
    warns = [r for r in caplog.records
             if "GUBER_TEST_BOOL_BAD" in r.getMessage()]
    assert len(warns) == 1  # once per (name, value), not per read


# ---------------------------------------------------------------------------
# helpers: random per-shard compact stacks + the int64 host oracle


def _random_stack(rng, K, S, B, C, pad_frac=0.25, empty_shards=()):
    """i64[K, S, B, 2] compact stack: duplicates, folds, inits, pads.
    Shards in `empty_shards` stage nothing (all-PAD every window)."""
    stack = np.zeros((K, S, B, 2), np.int64)
    for k in range(K):
        for s in range(S):
            if s in empty_shards:
                continue  # zero word decodes as PAD (inert lane)
            slot = rng.integers(0, C, B).astype(np.int32)
            hot = rng.integers(0, C, 3)
            dup = rng.random(B) < 0.4
            slot[dup] = hot[rng.integers(0, 3, int(dup.sum()))]
            slot[rng.random(B) < pad_frac] = kernel.PAD_SLOT
            hits = rng.choice([0, 1, 1, 2, 5], B).astype(np.int64)
            limit = rng.integers(1, 900, B).astype(np.int64)
            duration = rng.integers(1000, 600_000, B).astype(np.int64)
            algo = rng.integers(0, 2, B).astype(np.int32)
            is_init = rng.random(B) < 0.3
            agg = (rng.random(B) < 0.1) & (slot >= 0)
            eslot = np.where(agg, slot | kernel.AGG_SLOT_BIT, slot)
            stack[k, s] = np.asarray(kernel.encode_batch_host(
                eslot, hits, limit, duration, algo, is_init))
    return stack


_oracle_step = jax.jit(kernel.window_step)


def _oracle_drain(states, stack, nows):
    """Chain each shard's windows through the int64 oracle
    (decode_batch -> window_step -> encode_output_word), mutating
    `states` (list of per-shard BucketState) in place."""
    K, S, B = stack.shape[:3]
    words = np.zeros((K, S, B), np.int64)
    limits = np.zeros((K, S, B), np.int64)
    mism = np.zeros((K, S), bool)
    for s in range(S):
        st = states[s]
        for k in range(K):
            bt = kernel.decode_batch(jnp.asarray(stack[k, s]))
            st, out = _oracle_step(st, bt, jnp.int64(int(nows[k])))
            words[k, s] = np.asarray(
                kernel.encode_output_word(out, jnp.int64(int(nows[k]))))
            limits[k, s] = np.asarray(out.limit)
            mism[k, s] = bool(np.any(
                (np.asarray(out.limit) != np.asarray(bt.limit))
                & (np.asarray(bt.slot) >= 0)))
        states[s] = st
    return words, limits, mism


def _assert_outputs_equal(got, oracle, tag):
    for name, g, w in zip(("words", "limits", "mism"), got[:3], oracle):
        np.testing.assert_array_equal(np.asarray(g), w,
                                      err_msg=f"{tag}: {name} vs oracle")


def _assert_states_equal(eng, oracle_states, tag):
    rows = kernel.arena_to_rows(
        kernel.ArenaPlanes(*[np.asarray(p) for p in eng.state]))
    for name, af in zip(kernel.BucketState._fields, rows):
        for s in range(len(oracle_states)):
            np.testing.assert_array_equal(
                af[s], np.asarray(getattr(oracle_states[s], name)),
                err_msg=f"{tag}: shard {s} state.{name} vs oracle")


# ---------------------------------------------------------------------------
# the differential contract on the 8-device mesh


def test_mesh_drain_differential():
    """Two consecutive composed drains (K windows each) over all 8
    shards equal the oracle on every response word, limit lane, mismatch
    flag, and every arena plane — the second drain also proves the
    donated plane carry across dispatches."""
    rng = np.random.default_rng(42)
    eng = _mk_engine()
    oracle_states = [kernel.BucketState.zeros(C) for _ in range(S)]
    for rnd in range(2):
        stack = _random_stack(rng, K, S, B, C)
        nows = np.asarray(
            [T0 + rnd * 10_000_000 + 1000 * k for k in range(K)], np.int64)
        gb, ga, upd = eng.empty_drain_control()
        got = eng.pipeline_dispatch_global(stack, nows, gb, ga, upd)
        want = _oracle_drain(oracle_states, stack, nows)
        _assert_outputs_equal(got, want, f"round {rnd}")
    _assert_states_equal(eng, oracle_states, "final")


def _uneven_stack(rng):
    """Shard 0 saturated, most shards partial, shards 6-7 staging nothing,
    plus one all-PAD window mesh-wide."""
    stack = _random_stack(rng, K, S, B, C, empty_shards=(6, 7))
    stack[0, 0] = np.asarray(kernel.encode_batch_host(
        np.arange(B, dtype=np.int32),            # shard 0 fully occupied
        np.ones(B, np.int64), np.full(B, 9, np.int64),
        np.full(B, 60_000, np.int64), np.zeros(B, np.int32),
        np.ones(B, bool)))
    stack[2] = 0                                  # window 2: all-PAD mesh-wide
    return stack


def test_mesh_drain_uneven_shard_occupancy():
    """Unevenly occupied mesh: the inert shards/windows must not perturb
    the busy ones."""
    eng = _mk_engine()
    stack = _uneven_stack(np.random.default_rng(43))
    nows = np.asarray([T0 + 1000 * k for k in range(K)], np.int64)
    gb, ga, upd = eng.empty_drain_control()
    got = eng.pipeline_dispatch_global(stack, nows, gb, ga, upd)
    oracle_states = [kernel.BucketState.zeros(C) for _ in range(S)]
    want = _oracle_drain(oracle_states, stack, nows)
    _assert_outputs_equal(got, want, "uneven")
    _assert_states_equal(eng, oracle_states, "uneven")
    # the empty shards' arenas stayed untouched
    for name, pf in zip(kernel.ArenaPlanes._fields, eng.state):
        for s in (6, 7):
            np.testing.assert_array_equal(
                np.asarray(pf)[s],
                np.asarray(getattr(kernel.ArenaPlanes.zeros(C), name)),
                err_msg=f"idle shard {s} state.{name}")


def test_mesh_drain_global_psum_traffic():
    """GLOBAL lanes staged on three different shards for one slot: the
    drain's single reconciliation psum must apply the summed hits ONCE
    to the replicated arena, and the per-lane reads must follow the
    miss-then-prior-psum model."""
    eng = _mk_engine()
    eng.register_global_keys([("pg_g", 50, 60_000, 0)], now=T0)
    slot = eng.gtable.peek("pg_g")
    assert slot is not None

    stack = np.zeros((K, S, B, 2), np.int64)  # regular lanes inert
    nows = np.asarray([T0 + 10 + k for k in range(K)], np.int64)
    remaining = {}
    gstate_rem = {}
    for drain in range(2):
        gb, ga, upd = eng.empty_drain_control()
        for s in range(3):
            gb.slot[s, 0] = slot
            gb.hits[s, 0] = 1
            gb.limit[s, 0] = 50
            gb.duration[s, 0] = 60_000
            ga[s, 0] = 1
        _, _, _, gf = eng.pipeline_dispatch_global(stack, nows, gb, ga, upd)
        gf = np.asarray(gf)
        remaining[drain] = [int(gf[s, 0, 2]) for s in range(3)]
        gstate_rem[drain] = int(np.asarray(eng.gstate.remaining)[slot])
    # drain 0: each lane reads the miss path independently (limit - own
    # hits), then the psum lands the TOTAL (3) exactly once: 50 -> 47
    assert remaining[0] == [49, 49, 49]
    assert gstate_rem[0] == 47
    # drain 1: cached reads return the reconciled value, then another psum
    assert remaining[1] == [47, 47, 47]
    assert gstate_rem[1] == 44


@pytest.mark.parametrize("occupancy", ["all-shards", "two-idle-shards"])
def test_mesh_drain_with_analytics_differential(occupancy):
    """The composed drain with the stats reduction composed INTO it
    (shard_stats inside the executable, reading the drain's own words and
    the post-drain expiry plane): responses and arena equal the int64
    oracle as without analytics, and every shard's stats row and carried
    sketch equal the numpy oracle's, over two drains, the second with the
    halving decay."""
    rng = np.random.default_rng(44)
    eng = _mk_engine()
    conf = AnalyticsConfig()
    eng.enable_analytics(conf)
    kw = dict(tenant_slots=conf.tenant_slots, topk=conf.topk,
              over_weight=conf.over_weight)
    oracle_states = [kernel.BucketState.zeros(C) for _ in range(S)]
    sketches = [np.zeros((conf.sketch_depth, conf.sketch_width), np.int64)
                for _ in range(S)]
    for rnd, decay in enumerate((0, 1)):
        stack = (_random_stack(rng, K, S, B, C) if occupancy == "all-shards"
                 else _uneven_stack(rng))
        tenants = rng.integers(0, conf.tenant_slots, (K, S, B)).astype(
            np.int32)
        nows = np.asarray(
            [T0 + rnd * 10_000_000 + 1000 * k for k in range(K)], np.int64)
        gb, ga, upd = eng.empty_drain_control()
        got = eng.pipeline_dispatch_global(
            stack, nows, gb, ga, upd, analytics_args=(tenants, decay))
        want = _oracle_drain(oracle_states, stack, nows)
        _assert_outputs_equal(got, want, f"round {rnd}")
        stats = np.asarray(got[4])
        for s in range(S):
            sketches[s], st = ops_analytics.oracle_stats(
                sketches[s], stack[:, s], want[0][:, s], tenants[:, s],
                np.asarray(oracle_states[s].expire), int(nows[0]), decay,
                **kw)
            np.testing.assert_array_equal(
                stats[s], st, err_msg=f"round {rnd} shard {s} stats")
            np.testing.assert_array_equal(
                np.asarray(eng._an_sketch)[s], sketches[s],
                err_msg=f"round {rnd} shard {s} sketch")
    _assert_states_equal(eng, oracle_states, "final")


# ---------------------------------------------------------------------------
# end to end: the lockstep batcher serving through the composed drain


@pytest.mark.skipif(not native.available(),
                    reason="native router unavailable")
def test_lockstep_serving_end_to_end():
    """An 8-device mesh batcher on the lockstep tick: regular traffic
    matches the reference-semantics oracle, GLOBAL singles ride the
    composed psum window, and the drain counter advances."""
    eng = _mk_engine()
    clock = LockstepClock(T0, 0.02)
    m = Metrics()
    b = WindowBatcher(eng, BehaviorConfig(batch_wait=0.02, lockstep_stack=2),
                      metrics=m, lockstep_clock=clock)
    assert b.pipeline is not None and b.pipeline.lockstep
    eng.register_global_keys([("ee_g", 50, 60_000, 0)], now=T0)
    oracle = PyRefCache()

    async def run():
        b.start_lockstep()
        reqs = [RateLimitReq(name="ee", unique_key=f"k{i % 5}", hits=1,
                             limit=8, duration=60_000) for i in range(12)]
        outs = await asyncio.gather(*(b.submit(r) for r in reqs))
        gouts = []
        for _ in range(3):
            gouts.append(await b.submit(RateLimitReq(
                name="ee", unique_key="g", hits=1, limit=50,
                duration=60_000, behavior=Behavior.GLOBAL)))
        return reqs, outs, gouts

    try:
        reqs, outs, gouts = asyncio.run(run())
    finally:
        b.close()
    want = [oracle.hit(r, T0) for r in reqs]
    for j, (g, w) in enumerate(zip(outs, want)):
        assert (int(g.status), g.limit, g.remaining) == \
            (int(w.status), w.limit, w.remaining), (j, g, w)
    # GLOBAL: miss-path first read, then prior-psum reads (awaited
    # sequentially, so each request lands in its own drain)
    assert [r.remaining for r in gouts] == [49, 49, 48]
    assert all(not r.error for r in gouts)
    assert b.pipeline.decisions_staged >= 15  # 12 regular + 3 GLOBAL
    drains = m.registry.get_sample_value("guber_tpu_windows_total")
    assert drains and drains > 0
