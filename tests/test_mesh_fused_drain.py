"""Sharded fused serving on a forced 8-device CPU mesh (`make
test-mesh-fused`).

The lockstep tick's drain is now the GLOBAL-composed executable
(engine.pipeline_dispatch_global): every shard runs the fused megakernel
per window over its own plane-arena shard, and the whole drain pays ONE
collective — the GLOBAL reconciliation psum.  This suite pins that path
differentially: fused vs the legacy compact32-XLA drain vs the int64
host oracle (ops/kernel), bit for bit, including the psum traffic, the
donated plane carry across consecutive drains, uneven shard occupancy,
and the executed-kernel census that justifies the path (ISSUE
acceptance: >=5x fewer kernels per window than the legacy mesh step).
Plus the normalized GUBER_PALLAS_FUSED parsing every reader shares
(config.env_bool / pallas_kernel.fused_enabled).
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
from gubernator_tpu.config import BehaviorConfig, env_bool
from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.observability.metrics import Metrics
from gubernator_tpu.ops import kernel
from gubernator_tpu.ops import pallas_kernel as pk
from gubernator_tpu.parallel.distributed import LockstepClock
from gubernator_tpu.parallel.mesh import make_mesh

from .pyref import PyRefCache

pytestmark = pytest.mark.mesh_fused

T0 = 1_754_000_000_000  # ms epoch, like the engine's serving clocks

# One shape for every engine-level test in this file: the compiled-builder
# caches (engine lru_caches keyed on (mesh, flags)) then compile each
# variant exactly once for the whole suite.
S, B, C, Bg, K = 8, 16, 64, 8, 4


def _mk_engine():
    mesh = make_mesh(jax.devices()[:S])
    return RateLimitEngine(mesh=mesh, capacity_per_shard=C,
                           batch_per_shard=B, global_capacity=16,
                           global_batch_per_shard=Bg, max_global_updates=8)


# ---------------------------------------------------------------------------
# GUBER_PALLAS_FUSED parsing: one shared normalized reader


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


def test_fused_enabled_shares_normalization(monkeypatch):
    monkeypatch.setenv("GUBER_PALLAS_FUSED", "true")
    assert pk.fused_enabled() is True
    monkeypatch.setenv("GUBER_PALLAS_FUSED", "off")
    assert pk.fused_enabled(True) is False
    monkeypatch.delenv("GUBER_PALLAS_FUSED")
    assert pk.fused_enabled() is False
    assert pk.fused_enabled(True) is True


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


def _dispatch_pair(monkeypatch, ef, ex, stack, nows, gb, ga, upd):
    """The same composed drain through both engines: ef with the fused
    megakernel, ex with the legacy compact32-XLA body."""
    monkeypatch.setenv("GUBER_PALLAS_FUSED", "1")
    f = ef.pipeline_dispatch_global(stack, nows, gb, ga, upd)
    monkeypatch.setenv("GUBER_PALLAS_FUSED", "0")
    x = ex.pipeline_dispatch_global(stack, nows, gb, ga, upd)
    return f, x


def _assert_outputs_equal(f, x, oracle, tag):
    wf, lf, mf, _ = f
    wx, lx, mx, _ = x
    words, limits, mism = oracle
    for name, a, b in (("words", wf, wx), ("limits", lf, lx),
                       ("mism", mf, mx)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{tag}: fused vs legacy {name}")
    np.testing.assert_array_equal(np.asarray(wf), words,
                                  err_msg=f"{tag}: words vs oracle")
    np.testing.assert_array_equal(np.asarray(lf), limits,
                                  err_msg=f"{tag}: limits vs oracle")
    np.testing.assert_array_equal(np.asarray(mf), mism,
                                  err_msg=f"{tag}: mism vs oracle")


def _assert_states_equal(ef, ex, oracle_states, tag):
    for name, pf, px in zip(kernel.ArenaPlanes._fields, ef.state, ex.state):
        np.testing.assert_array_equal(np.asarray(pf), np.asarray(px),
                                      err_msg=f"{tag}: state.{name}")
    rows = kernel.arena_to_rows(
        kernel.ArenaPlanes(*[np.asarray(p) for p in ef.state]))
    for name, af in zip(kernel.BucketState._fields, rows):
        for s in range(len(oracle_states)):
            np.testing.assert_array_equal(
                af[s], np.asarray(getattr(oracle_states[s], name)),
                err_msg=f"{tag}: shard {s} state.{name} vs oracle")


# ---------------------------------------------------------------------------
# the differential contract on the 8-device mesh


def test_mesh_fused_drain_differential(monkeypatch):
    """Two consecutive composed drains (K windows each) over all 8
    shards: fused == legacy == oracle on every response word, limit
    lane, mismatch flag, and every arena plane — the second drain also
    proves the donated plane carry across dispatches."""
    rng = np.random.default_rng(42)
    ef, ex = _mk_engine(), _mk_engine()
    oracle_states = [kernel.BucketState.zeros(C) for _ in range(S)]
    for rnd in range(2):
        stack = _random_stack(rng, K, S, B, C)
        nows = np.asarray(
            [T0 + rnd * 10_000_000 + 1000 * k for k in range(K)], np.int64)
        gb, ga, upd = ef.empty_drain_control()
        f, x = _dispatch_pair(monkeypatch, ef, ex, stack, nows, gb, ga, upd)
        want = _oracle_drain(oracle_states, stack, nows)
        _assert_outputs_equal(f, x, want, f"round {rnd}")
    _assert_states_equal(ef, ex, oracle_states, "final")


def test_mesh_fused_uneven_shard_occupancy(monkeypatch):
    """Unevenly occupied mesh: shard 0 saturated, most shards partial,
    shards 6-7 staging nothing, plus one all-PAD window mesh-wide.  The
    inert shards/windows must not perturb the busy ones on either body."""
    rng = np.random.default_rng(43)
    ef, ex = _mk_engine(), _mk_engine()
    stack = _random_stack(rng, K, S, B, C, empty_shards=(6, 7))
    stack[0, 0] = np.asarray(kernel.encode_batch_host(
        np.arange(B, dtype=np.int32),            # shard 0 fully occupied
        np.ones(B, np.int64), np.full(B, 9, np.int64),
        np.full(B, 60_000, np.int64), np.zeros(B, np.int32),
        np.ones(B, bool)))
    stack[2] = 0                                  # window 2: all-PAD mesh-wide
    nows = np.asarray([T0 + 1000 * k for k in range(K)], np.int64)
    gb, ga, upd = ef.empty_drain_control()
    f, x = _dispatch_pair(monkeypatch, ef, ex, stack, nows, gb, ga, upd)
    oracle_states = [kernel.BucketState.zeros(C) for _ in range(S)]
    want = _oracle_drain(oracle_states, stack, nows)
    _assert_outputs_equal(f, x, want, "uneven")
    _assert_states_equal(ef, ex, oracle_states, "uneven")
    # the empty shards' arenas stayed untouched
    for name, pf in zip(kernel.ArenaPlanes._fields, ef.state):
        for s in (6, 7):
            np.testing.assert_array_equal(
                np.asarray(pf)[s],
                np.asarray(getattr(kernel.ArenaPlanes.zeros(C), name)),
                err_msg=f"idle shard {s} state.{name}")


def test_mesh_fused_global_psum_traffic(monkeypatch):
    """GLOBAL lanes staged on three different shards for one slot: the
    drain's single reconciliation psum must apply the summed hits ONCE
    to the replicated arena, and the per-lane reads must follow the
    miss-then-prior-psum model — identically on fused and legacy."""
    ef, ex = _mk_engine(), _mk_engine()
    for e in (ef, ex):
        e.register_global_keys([("pg_g", 50, 60_000, 0)], now=T0)
    slot = ef.gtable.peek("pg_g")
    assert slot is not None and slot == ex.gtable.peek("pg_g")

    def staged_control(eng):
        gb, ga, upd = eng.empty_drain_control()
        for s in range(3):
            gb.slot[s, 0] = slot
            gb.hits[s, 0] = 1
            gb.limit[s, 0] = 50
            gb.duration[s, 0] = 60_000
            ga[s, 0] = 1
        return gb, ga, upd

    stack = np.zeros((K, S, B, 2), np.int64)  # regular lanes inert
    nows = np.asarray([T0 + 10 + k for k in range(K)], np.int64)
    remaining = {}
    gstate_rem = {}
    for drain in range(2):
        gb, ga, upd = staged_control(ef)
        monkeypatch.setenv("GUBER_PALLAS_FUSED", "1")
        _, _, _, gff = ef.pipeline_dispatch_global(stack, nows, gb, ga, upd)
        monkeypatch.setenv("GUBER_PALLAS_FUSED", "0")
        _, _, _, gfx = ex.pipeline_dispatch_global(stack, nows, gb, ga, upd)
        gff, gfx = np.asarray(gff), np.asarray(gfx)
        np.testing.assert_array_equal(gff, gfx,
                                      err_msg=f"drain {drain} gfused")
        remaining[drain] = [int(gff[s, 0, 2]) for s in range(3)]
        for name, a, b in zip(kernel.BucketState._fields,
                              ef.gstate, ex.gstate):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"drain {drain} gstate.{name}")
        gstate_rem[drain] = int(np.asarray(ef.gstate.remaining)[slot])
    # drain 0: each lane reads the miss path independently (limit - own
    # hits), then the psum lands the TOTAL (3) exactly once: 50 -> 47
    assert remaining[0] == [49, 49, 49]
    assert gstate_rem[0] == 47
    # drain 1: cached reads return the reconciled value, then another psum
    assert remaining[1] == [47, 47, 47]
    assert gstate_rem[1] == 44


# ---------------------------------------------------------------------------
# the executed-kernel census: why the fused mesh path exists


def test_mesh_fused_census_vs_legacy_step():
    """ISSUE acceptance bar: the composed fused drain must trace to >=5x
    fewer executed kernels PER WINDOW than the legacy mesh step (the
    per-tick compact step, one window + its own psum per dispatch)."""
    eng = _mk_engine()
    KC = 8  # deeper stack: the scan body counts once, so K only amortizes
    fused = engine_mod._compiled_pipeline_step_global_impl(
        eng.mesh, False, True, True, True)
    legacy = engine_mod._compiled_step_compact_impl(
        eng.mesh, False, True, False)
    packed = np.zeros((KC, S, B, 2), np.int64)
    nows = np.full(KC, T0, np.int64)
    gb, ga, upd = eng.empty_drain_control()
    cf = pk.kernel_census(jax.make_jaxpr(fused)(
        eng.state, eng.gstate, eng.gcfg, packed, gb, ga, upd, nows))
    gbe, gae, upde, upse = eng.empty_control()
    cl = pk.kernel_census(jax.make_jaxpr(legacy)(
        eng.state, eng.gstate, eng.gcfg, packed[0], gbe, gae, upde, upse,
        jnp.int64(T0)))
    # per-window fused cost (cf / KC) * 5 <= legacy per-window cost (cl)
    assert cf * 5 <= cl * KC, (
        f"composed fused drain census {cf} over {KC} windows not >=5x "
        f"below the legacy step census {cl} per window")


def test_composed_window_census_budget():
    """Kernel-ladder gate: the fully-composed serving window (fused drain
    + GLOBAL sub-window + analytics reduction, one executable, K=8 stack)
    must trace to >=8x fewer executed kernels per window than the
    pre-ladder anchor — 1257 drain + 283 analytics kernels over a K=8
    stack = 192.5/window, measured at the head the ladder work branched
    from, when analytics was a second dispatch and GLOBAL paid a
    read+apply pair per window — AND stay under the ABSOLUTE staged
    budget of 24 kernels/window (the folded-shoulders ladder: one drain
    grid kernel, one GLOBAL pair kernel, one analytics finisher, plus
    the psum and the shard_map block glue; measured 20.5 at this PR).
    The census is box-independent (a property of the traced program), so
    both bars are pinned constants, not stashes.  Secondary bar: the
    composed XLA lowering (the arm CPU smoke serves) must not creep past
    its measured ceiling either."""
    from gubernator_tpu.config import AnalyticsConfig

    ANCHOR_KPW = 192.5   # (1257 + 283) / 8: pre-ladder composed window
    BUDGET_KPW = 24      # absolute staged ladder budget (ISSUE 17 bar)
    # composed+analytics XLA arm: measured 1473 at the PR 16 collapse,
    # 2463 once the algorithm plane's 5-way select ladders landed (the
    # GCRA/sliding/concurrency transitions fuse into the SAME launches —
    # equation growth on the XLA shoulder, zero new kernels on the
    # staged arms, see BASELINE.md "select depth, not kernels"), 3621
    # at PR 24: the XLA GLOBAL sub-window runs its replica reads and its
    # post-psum apply as two ladders again so shard_map's replication
    # check can prove the GLOBAL arena replicated (one concatenated
    # ladder marks the apply half shard-varying) — equations, not time:
    # the count is of a traced program, its device cost is not measured;
    # 3749 at PR 33: the resident arena's eleven uint32 planes are gathered
    # and committed one by one and joined / split at B width, where six
    # int64 gathers and scatters stood (16 equations a window more; on the
    # chip the 15 arena-sized conversions they replace were 4.7 ms a drain)
    XLA_CEILING = 3800

    eng = _mk_engine()
    conf = AnalyticsConfig()
    eng.enable_analytics(conf)
    geom = (conf.sketch_depth, conf.sketch_width, conf.tenant_slots,
            conf.topk, conf.over_weight)
    KC = 8
    packed = np.zeros((KC, S, B, 2), np.int64)
    nows = np.full(KC, T0, np.int64)
    gb, ga, upd = eng.empty_drain_control()
    ten = np.zeros((KC, S, B), np.int32)
    args = (eng.state, eng.gstate, eng.gcfg, packed, gb, ga, upd, nows,
            eng._an_sketch, ten, jnp.int64(0))

    fused = engine_mod._compiled_pipeline_step_global_impl(
        eng.mesh, False, True, True, True, geom)
    cf = pk.kernel_census(jax.make_jaxpr(fused)(*args))
    assert cf * 8 <= ANCHOR_KPW * KC, (
        f"composed window census {cf} over {KC} windows = {cf / KC:.1f} "
        f"kernels/window, not >=8x below the {ANCHOR_KPW}/window anchor")
    assert cf <= BUDGET_KPW * KC, (
        f"composed window census {cf} over {KC} windows = {cf / KC:.1f} "
        f"kernels/window, over the absolute {BUDGET_KPW}/window budget")

    xla = engine_mod._compiled_pipeline_step_global_impl(
        eng.mesh, False, True, False, False, geom)
    cx = pk.kernel_census(jax.make_jaxpr(xla)(*args))
    assert cx <= XLA_CEILING, (
        f"composed XLA arm census {cx} crept past the {XLA_CEILING} "
        f"ceiling (measured 1473 at this PR)")


# ---------------------------------------------------------------------------
# end to end: the lockstep batcher serving through the fused drain


@pytest.mark.skipif(not native.available(),
                    reason="native router unavailable")
def test_lockstep_fused_serving_end_to_end(monkeypatch):
    """GUBER_PALLAS_FUSED=1 on an 8-device mesh batcher: the lockstep
    tick's drain lowers to the fused megakernel, regular traffic matches
    the reference-semantics oracle, GLOBAL singles ride the composed
    psum window, and the adoption/depth metrics advance."""
    monkeypatch.setenv("GUBER_PALLAS_FUSED", "1")
    eng = _mk_engine()
    clock = LockstepClock(T0, 0.02)
    m = Metrics()
    b = WindowBatcher(eng, BehaviorConfig(batch_wait=0.02, lockstep_stack=2),
                      metrics=m, lockstep_clock=clock)
    assert b.pipeline is not None and b.pipeline.lockstep
    assert b.pipeline.fused_serving  # B is a power of two
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
    # observability: the drains were counted and lowered to the fused
    # megakernel (what /v1/admin/debug shows as pipeline.fused_serving)
    drains = m.registry.get_sample_value("guber_tpu_windows_total")
    assert drains and drains > 0
    assert b.pipeline.fused_serving
