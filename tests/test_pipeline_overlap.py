"""Overlapped drain pipeline: differential suite vs the serial oracle.

The tentpole contract (core/pipeline.py + core/window_buffers.py): with
`GUBER_PIPELINE_DEPTH` > 1 the host encodes window N+1 into a recycled
arena while the device executes N and the fetch pool decodes N-1 — and
every decision must stay BIT-IDENTICAL to the serial path, because
per-key order is committed at dispatch (single engine thread, ordered)
and the completion queue only demuxes.  This suite pins that:

  * depth 1/2/3 match the full Python path over multi-window bursts
    (token + leaky, duplicate-key folds, GLOBAL singles interleaved)
  * out-of-order fetch completion (injected slow fetch) changes nothing
  * an injected `engine_dispatch` fault (net/faults.py) fails exactly
    the faulted drain's jobs with NO partial commit; neighbors and
    subsequent drains serve normally
  * window arenas actually recycle (reuse accounting + metric)
  * the occupancy gate's rule, and the same differential with the gate ON
    (the served configuration): a batch queued behind a drain in flight
    goes as soon as the engine thread is free
"""

import asyncio
import threading

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu import native
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.config import MAX_BATCH_SIZE, BehaviorConfig
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.net.faults import FAULTS, SEAM_ENGINE_DISPATCH
from gubernator_tpu.observability.metrics import Metrics
from tests.benchmark.helpers import time_limit

pytestmark = [
    pytest.mark.overlap,
    pytest.mark.skipif(not native.available(),
                       reason="native router unavailable"),
]

T0 = 1_700_000_000_000


def _engine(use_native="on", lanes=64):
    return RateLimitEngine(capacity_per_shard=256, batch_per_shard=lanes,
                           global_capacity=16, global_batch_per_shard=8,
                           max_global_updates=8, use_native=use_native)


def _batcher(eng, depth, now=T0, metrics=None):
    b = WindowBatcher(eng, BehaviorConfig(), metrics=metrics)
    assert b.pipeline is not None and b.pipeline.enabled
    b.pipeline.now_fn = lambda: now
    b.now_fn = lambda: now
    b.pipeline.depth = depth
    # the occupancy gate serializes small test windows behind an in-flight
    # drain (its job is throughput shaping, not correctness) — off, so the
    # suite actually exercises depth-N concurrent drains
    b.pipeline.gate_enabled = False
    return b


def _check(got, want, tag=""):
    assert len(got) == len(want)
    for j, (g, r) in enumerate(zip(got, want)):
        assert (int(g.status), g.limit, g.remaining, g.reset_time) == \
            (int(r.status), r.limit, r.remaining, r.reset_time), (tag, j, g, r)


def _burst(rng, round_idx, n=48, keys=12):
    """Mixed token/leaky burst with duplicate-key runs (fold coverage)."""
    return [
        RateLimitReq(name="ov", unique_key=f"k{rng.integers(0, keys)}",
                     hits=int(rng.integers(0, 3)), limit=20,
                     duration=60_000,
                     algorithm=int(rng.integers(0, 2)))
        for _ in range(n)
    ]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_depth_bit_identical_to_serial_oracle(depth):
    """Multi-window single-submit bursts at pipeline depth 1/2/3 must be
    bit-identical to the full Python path replaying the same bursts."""
    eng = _engine()
    ref = _engine(False)
    rng = np.random.default_rng(11 + depth)
    for w in range(4):
        now = T0 + w * 500
        b = _batcher(eng, depth, now)
        reqs = _burst(rng, w)

        async def run():
            return await asyncio.gather(*(b.submit(r) for r in reqs))

        got = asyncio.run(run())
        b.close()
        want = ref.process(reqs, now=now)
        _check(got, want, (depth, w))


@pytest.mark.parametrize("depth", [2, 3])
def test_concurrent_drains_match_oracle(depth):
    """Batches forced into SEPARATE overlapped drains (submit, yield, submit
    while the first is in flight) commit in dispatch order: per-batch
    results equal sequential oracle replay."""
    eng = _engine()
    ref = _engine(False)
    rng = np.random.default_rng(29)
    batches = [[RateLimitReq(name="cd", unique_key=f"c{rng.integers(0, 6)}",
                             hits=1, limit=30, duration=60_000,
                             algorithm=int(rng.integers(0, 2)))
                for _ in range(16)] for _ in range(depth * 2)]
    b = _batcher(eng, depth)

    async def run():
        tasks = []
        for batch in batches:
            tasks.append(asyncio.ensure_future(b.submit_now(batch)))
            # yield so this batch's drain dispatches before the next
            # batch queues — consecutive batches ride concurrent drains
            await asyncio.sleep(0)
        return await asyncio.gather(*tasks)

    try:
        got = asyncio.run(run())
    finally:
        b.close()
    for i, batch in enumerate(batches):
        _check(got[i], ref.process(batch, now=T0), i)
    assert b.pipeline.decisions_staged == sum(len(x) for x in batches)


def test_global_interleaved_with_pipeline_matches_oracle():
    """GLOBAL singles (listed lane, reconciliation accumulate) interleaved
    with pipeline-eligible traffic at depth 3: per-request results match
    the oracle processing the same mix — the two lanes commit through the
    same ordered engine thread, so reconciliation never reorders around
    the drains."""
    eng = _engine()
    ref = _engine(False)
    rng = np.random.default_rng(41)
    for w in range(3):
        now = T0 + w * 500
        b = _batcher(eng, 3, now)
        reqs = []
        for i in range(36):
            if i % 4 == 0:
                reqs.append(RateLimitReq(
                    name="ovg", unique_key=f"g{rng.integers(0, 3)}", hits=1,
                    limit=25, duration=60_000, behavior=Behavior.GLOBAL))
            else:
                reqs.append(RateLimitReq(
                    name="ovg", unique_key=f"r{rng.integers(0, 8)}", hits=1,
                    limit=25, duration=60_000,
                    algorithm=int(rng.integers(0, 2))))

        async def run():
            return await asyncio.gather(*(b.submit(r) for r in reqs))

        got = asyncio.run(run())
        b.close()
        want = ref.process(reqs, now=now)
        _check(got, want, w)


def test_out_of_order_fetch_completion_is_safe():
    """Delay the FIRST drain's fetch so a later drain's fetch completes
    first (two fetch workers): responses still match the oracle — per-key
    state was committed at dispatch, completion only demuxes."""
    eng = _engine()
    ref = _engine(False)
    b = _batcher(eng, 3)
    pipe = b.pipeline

    order = []
    inner = pipe._complete_sync
    slow = {"armed": True}

    def tardy(res):
        import time as _t
        if slow.pop("armed", None):
            _t.sleep(0.15)
        out = inner(res)
        order.append(res.n_decisions)
        return out

    pipe._complete_sync = tardy

    b1 = [RateLimitReq(name="oo", unique_key=f"a{i}", hits=1, limit=9,
                       duration=60_000) for i in range(8)]
    b2 = [RateLimitReq(name="oo", unique_key=f"b{i}", hits=1, limit=9,
                       duration=60_000, algorithm=Algorithm.LEAKY_BUCKET)
          for i in range(5)]

    async def run():
        t1 = asyncio.ensure_future(b.submit_now(b1))
        await asyncio.sleep(0.02)  # drain 1 dispatches, fetch now sleeping
        t2 = asyncio.ensure_future(b.submit_now(b2))
        return await asyncio.gather(t1, t2)

    try:
        got1, got2 = asyncio.run(run())
    finally:
        b.close()
    # the later drain really did complete first
    assert order == [len(b2), len(b1)], order
    _check(got1, ref.process(b1, now=T0), "b1")
    _check(got2, ref.process(b2, now=T0), "b2")


def test_dispatch_fault_fails_only_that_drain_no_partial_commit():
    """An injected engine_dispatch fault fails the faulted drain's jobs;
    the C router staging is aborted (no hits committed), and subsequent
    drains — including re-submits of the SAME keys — serve from untouched
    state."""
    eng = _engine()
    b = _batcher(eng, 3)
    faulted = [RateLimitReq(name="ft", unique_key=f"f{i}", hits=3, limit=10,
                            duration=60_000) for i in range(6)]
    probe = [RateLimitReq(name="ft", unique_key=f"f{i}", hits=0, limit=10,
                          duration=60_000) for i in range(6)]

    async def run():
        FAULTS.seed(3)
        FAULTS.configure(SEAM_ENGINE_DISPATCH, drop=1.0, times=1)
        try:
            with pytest.raises(Exception):
                await b.submit_now(faulted)
        finally:
            FAULTS.clear()
        return await b.submit_now(probe)

    try:
        resps = asyncio.run(run())
    finally:
        FAULTS.clear()
        b.close()
    for r in resps:
        # hits=0 probe: full budget ⇒ the faulted drain committed nothing
        assert r.error == "" and r.remaining == 10, r
    assert b.pipeline._in_flight == 0


def test_commit_queue_ordering_under_fault_between_drains():
    """Drain 2 faults while drains 1 and 3 serve: the completion queue
    commits 1 and 3 in dispatch order with correct per-key state (keys
    shared between 1 and 3 see exactly two rounds of hits)."""
    eng = _engine()
    ref = _engine(False)
    b = _batcher(eng, 3)
    keys = [f"s{i}" for i in range(5)]
    mk = lambda: [RateLimitReq(name="sq", unique_key=k, hits=1, limit=10,
                               duration=60_000) for k in keys]
    r1, r2, r3 = mk(), mk(), mk()

    async def run():
        got1 = await b.submit_now(r1)
        FAULTS.seed(5)
        FAULTS.configure(SEAM_ENGINE_DISPATCH, drop=1.0, times=1)
        try:
            with pytest.raises(Exception):
                await b.submit_now(r2)
        finally:
            FAULTS.clear()
        got3 = await b.submit_now(r3)
        return got1, got3

    try:
        got1, got3 = asyncio.run(run())
    finally:
        FAULTS.clear()
        b.close()
    want1 = ref.process(r1, now=T0)
    want3 = ref.process(r3, now=T0)  # round 2 on the oracle: r2 never landed
    _check(got1, want1, "round1")
    _check(got3, want3, "round3")


def _thousand(rng, tag, keys=40):
    """One whole batch (upstream's limit, 1000 items): token and leaky,
    every key many times over (folds), some with no hits."""
    return [RateLimitReq(name="gt", unique_key=f"{tag}{rng.integers(0, keys)}",
                         hits=int(rng.integers(0, 3)), limit=500,
                         duration=60_000, algorithm=int(rng.integers(0, 2)))
            for _ in range(MAX_BATCH_SIZE)]


@pytest.mark.parametrize("queued,engine_busy,want", [
    (MAX_BATCH_SIZE, False, None),          # a batch, engine free: it goes
    (MAX_BATCH_SIZE - 1, False, "gate"),    # under a batch: accumulates
    (MAX_BATCH_SIZE, True, "engine"),       # a batch, engine thread busy
])
def test_gate_with_a_drain_in_flight(queued, engine_busy, want):
    """The gate as served (on), with one drain out and room under the
    depth: a queued batch is dispatched as soon as the engine thread is
    free (the pipeline holds two drains, the second counted ahead="1");
    less than a batch is held under `gate` until the drain out commits; a
    batch behind a busy engine thread is held under `engine` and goes
    beside the first drain the moment that one has been dispatched.  Every
    answer is the oracle's."""
    eng = _engine()
    ref = _engine(False)
    m = Metrics()
    b = _batcher(eng, 3, metrics=m)
    p = b.pipeline
    p.gate_enabled = True
    assert p.coalesce_min == MAX_BATCH_SIZE
    rng = np.random.default_rng(53)
    first = _burst(rng, 0)
    second = _thousand(rng, "g")[:queued]
    fetch_go, engine_go = threading.Event(), threading.Event()
    inner = p._complete_sync

    def held_fetch(res):
        fetch_go.wait(10.0)     # a dispatched drain stays in flight
        return inner(res)
    p._complete_sync = held_fetch

    def ahead(n):
        return m.registry.get_sample_value(
            "guber_tpu_drain_overlap_total", {"ahead": n})

    async def until(cond):
        while not cond():
            await asyncio.sleep(0.005)

    async def run():
        if engine_busy:
            p._engine_executor.submit(engine_go.wait, 10.0)
        t1 = asyncio.ensure_future(b.submit_now(first))
        # pumped; and, with the engine thread free, dispatched
        await until(lambda: p._in_flight == 1
                    and p._predispatch == int(engine_busy))
        assert (ahead("0"), ahead("1")) == (1.0, 0.0)
        t2 = asyncio.ensure_future(b.submit_now(second))
        await asyncio.sleep(0.05)
        state = (p._hold_reason, p._in_flight, ahead("1"))
        if engine_busy:
            engine_go.set()     # the first is dispatched; its fetch waits
            await until(lambda: p._in_flight == 2)
        fetch_go.set()
        return state, await asyncio.gather(t1, t2)

    try:
        with time_limit(120):
            state, (got1, got2) = asyncio.run(run())
    finally:
        fetch_go.set()
        engine_go.set()
        b.close()
    if want is None:
        assert state == (None, 2, 1.0), state
    else:
        assert state == (want, 1, 0.0), state
        assert p.pump_hold[want] >= 0.04, p.pump_hold
    # held under `gate`, the work went when the drain out had committed,
    # with nothing ahead; in the other two it went beside the first
    assert p.drain_overlap == ({"0": 2, "1": 0, "2": 0} if want == "gate"
                               else {"0": 1, "1": 1, "2": 0})
    _check(got1, ref.process(first, now=T0), "first")
    _check(got2, ref.process(second, now=T0), "second")
    assert p._in_flight == 0 and p._predispatch == 0


@pytest.mark.parametrize("depth", [2, 3])
def test_gate_on_whole_batches_back_to_back_match_the_serial_oracle(depth):
    """The differential with the gate ON: 1000-item jobs submitted back to
    back ride overlapped drains (the gate lets each go once the engine
    thread is free, up to the depth) and every answer equals the depth-1
    oracle's, batch by batch in submission order."""
    eng = _engine()
    ref = _engine(False)
    rng = np.random.default_rng(61 + depth)
    batches = [_thousand(rng, "b") for _ in range(6)]
    b = _batcher(eng, depth)
    b.pipeline.gate_enabled = True

    async def run():
        tasks = []
        for batch in batches:
            tasks.append(asyncio.ensure_future(b.submit_now(batch)))
            await asyncio.sleep(0)
        return await asyncio.gather(*tasks)

    try:
        got = asyncio.run(run())
    finally:
        b.close()
    for i, batch in enumerate(batches):
        _check(got[i], ref.process(batch, now=T0), i)
    p = b.pipeline
    assert p.decisions_staged == 6 * MAX_BATCH_SIZE
    # the served configuration overlapped: some drain went beside another
    assert p.drain_overlap["1"] + p.drain_overlap["2"] >= 1, p.drain_overlap
    assert sum(p.drain_overlap.values()) >= 2
    if depth == 2:
        assert p.drain_overlap["2"] == 0


def test_arena_ring_recycles_buffers():
    """Steady-state drains run out of the preallocated arena ring: after
    the first windows, acquires are reuses, not allocations — and the
    reuse count is what /v1/admin/debug shows (pipeline.overlap)."""
    eng = _engine()
    m = Metrics()
    b = _batcher(eng, 2, metrics=m)
    reqs = [RateLimitReq(name="ar", unique_key=f"k{i % 7}", hits=1, limit=50,
                         duration=60_000) for i in range(10)]

    async def run():
        for _ in range(6):
            await b.submit_now(reqs)

    try:
        asyncio.run(run())
    finally:
        b.close()
    snap = b.pipeline.overlap_snapshot()
    assert snap["arena_reuse_events"] >= 4
    assert snap["arena_alloc_events"] <= 2
    assert b.pipeline._arena_ring.reuse_events == snap["arena_reuse_events"]
    # stage accounting accumulated and the ratio is well-formed
    assert sum(snap["stage_busy_seconds"].values()) > 0
    assert snap["active_wall_seconds"] > 0
    assert snap["inflight_windows"] == 0


def test_depth_env_knob(monkeypatch):
    monkeypatch.setenv("GUBER_PIPELINE_DEPTH", "2")
    eng = _engine()
    b = WindowBatcher(eng, BehaviorConfig())
    try:
        assert b.pipeline is not None and b.pipeline.depth == 2
    finally:
        b.close()
