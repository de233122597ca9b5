"""The mesh serving path's parts, each alone on the CPU: the lockstep clock
(wall-clock deadlines, skipped periods, agreement across processes), the
lockstep lane for whole RPCs with GLOBAL items beside BATCHING ones
(answer by answer against benchmark/reference/serial.py), the registrar's
batch, and a profile capture under lockstep serving."""

import asyncio
import os
import threading

import jax
import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from benchmark import reduce_trace
from benchmark.reference import serial
from gubernator_tpu import native
from gubernator_tpu.api import pb
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.config import BehaviorConfig, Config, EngineConfig
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.core.service import Instance
from gubernator_tpu.observability.metrics import Metrics
from gubernator_tpu.parallel.distributed import LockstepClock
from gubernator_tpu.parallel.mesh import make_mesh
from tests.benchmark.helpers import time_limit

T0 = 1_700_000_000_000


# ------------------------------------------------------------------ clock


class Wall:
    """A wall clock a test moves by hand (ms)."""

    def __init__(self, at):
        self.at = float(at)

    def __call__(self):
        return self.at


def test_clock_takes_its_epoch_at_the_first_tick_not_when_built():
    wall = Wall(5_000_000)
    clock = LockstepClock(None, 0.0005, wall_ms=wall)
    assert clock.epoch_ms is None
    wall.at += 93_000            # start-up, warm-up, an arena fill
    clock.start()
    assert clock.epoch_ms == 5_093_000
    assert clock.next_now() == 5_093_000
    clock.start()                # once only
    assert clock.epoch_ms == 5_093_000 and clock.tick == 1


def test_clock_deadlines_are_whole_periods_after_the_epoch():
    wall = Wall(1_000)
    clock = LockstepClock(None, 0.020, wall_ms=wall)
    clock.start()
    assert clock.until_next() == 0.0
    assert clock.next_now() == 1_000
    assert clock.until_next() == pytest.approx(0.020)
    wall.at += 7
    assert clock.until_next() == pytest.approx(0.013)
    wall.at += 13
    assert clock.until_next() <= 0.0
    assert clock.next_now() == 1_020 and clock.skipped == 0
    assert clock.lag_s == 0.0


@pytest.mark.parametrize("behind_ms,skipped", [(5, 0), (20, 1), (250, 12),
                                               (10_007, 500)])
def test_clock_skips_whole_periods_when_behind_and_never_runs_ahead(
        behind_ms, skipped):
    wall = Wall(2_000)
    clock = LockstepClock(None, 0.020, wall_ms=wall)
    clock.start()
    clock.next_now()                       # tick 0 at 2_000
    wall.at = 2_020 + behind_ms            # tick 1 was due at 2_020
    now = clock.next_now()
    assert clock.skipped == skipped
    assert clock.lag_s == pytest.approx(behind_ms / 1000.0)
    assert now <= wall.at < now + 20       # within one period, never ahead
    assert (now - 2_000) % 20 == 0
    assert clock.until_next() > 0          # the next deadline lies ahead


def test_clock_timestamps_never_decrease_at_sub_millisecond_ticks():
    wall = Wall(10_000)
    clock = LockstepClock(None, 0.0005, wall_ms=wall)
    clock.start()
    rng = np.random.default_rng(7)
    last = 0
    for step in rng.choice([0.1, 0.5, 0.6, 3.0, 41.7], size=4000):
        wall.at += float(step)
        if clock.until_next() > 0:
            continue                 # the loop sleeps to the deadline
        now = clock.next_now()
        assert last <= now <= wall.at
        last = now
    assert clock.skipped > 0


class Rendezvous:
    """The collective of two hosts in one thread: each host's `agree` is
    first asked what it would bring, then both are run with the larger of
    the two, as agree_max gives every process of a mesh."""

    def __init__(self, clocks):
        self.clocks = clocks

    def both(self, step):
        brought = []
        for c in self.clocks:
            c._agree = brought.append
            saved = (c.epoch_ms, c._offset_ms, c.tick, c.skipped, c.lag_s)
            try:
                step(c)
            except TypeError:
                pass        # the clock went on with None: undone below
            c.epoch_ms, c._offset_ms, c.tick, c.skipped, c.lag_s = saved
        agreed = max(brought)
        out = []
        for c in self.clocks:
            c._agree = lambda v: agreed
            out.append(step(c))
        return out


def test_two_processes_derive_identical_timestamps_from_the_agreed_epoch():
    """Two hosts with clocks 3 ms apart that wake late by different
    amounts: the epoch and every tick's index go through `agree`
    (agree_max on a mesh), so both tell the same timestamps."""
    walls = [Wall(50_000), Wall(50_003)]
    clocks = [LockstepClock(None, 0.020, wall_ms=w, agree=lambda v: v)
              for w in walls]
    assert all(c.agrees for c in clocks)
    mesh = Rendezvous(clocks)

    def start(c):
        c.start()
        return c.epoch_ms
    assert mesh.both(start) == [50_003, 50_003]
    rng = np.random.default_rng(11)
    told = []
    for _ in range(300):
        for w in walls:
            w.at += float(rng.choice([20, 20, 21, 35, 140]))
        a, b = mesh.both(LockstepClock.next_now)
        assert a == b
        told.append(a)
    assert told == sorted(told) and clocks[0].tick == clocks[1].tick
    assert clocks[0].skipped == clocks[1].skipped > 0
    # never ahead of the host whose clock is ahead, within a period of it
    ahead = max(w.at for w in walls)
    assert told[-1] <= ahead < told[-1] + 20


def test_a_given_epoch_starts_the_timeline_there_at_the_walls_pace():
    wall = Wall(9_000_000)
    clock = LockstepClock(T0, 0.020, wall_ms=wall)
    assert clock.now_ms() == T0       # usable before the first tick
    clock.start()
    assert clock.next_now() == T0
    wall.at += 61
    assert clock.next_now() == T0 + 60
    assert clock.now_ms() == T0 + 61


# ------------------------------------------------------------------- lane

pytestmark_native = pytest.mark.skipif(not native.available(),
                                       reason="native router unavailable")
NAME, GNAME = "requests_per_account", "requests_per_tenant"


def rpc_bytes(reqs):
    return pb.GetRateLimitsReq(
        requests=[pb.req_to_pb(r) for r in reqs]).SerializeToString()


def answers(data):
    return [pb.resp_from_pb(m)
            for m in pb.GetRateLimitsResp.FromString(data).responses]


def item(key, algo=Algorithm.TOKEN_BUCKET, limit=1000, glob=False):
    return RateLimitReq(
        name=GNAME if glob else NAME, unique_key=key, hits=1, limit=limit,
        duration=60_000, algorithm=algo,
        behavior=Behavior.GLOBAL if glob else Behavior.BATCHING)


def thousand(seed):
    """A 1000-item RPC: 100 GLOBAL items over 8 tenants, 40 of them one
    tenant; the rest BATCHING over 300 accounts (token and leaky), 60 of
    them one account."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(1000):
        if i % 10 == 3:
            t = 0 if i % 25 < 10 else int(rng.integers(1, 8))
            out.append(item(f"tenant:{t}", limit=100_000, glob=True))
        elif i % 16 == 5:
            out.append(item("account:hot", limit=100))
        else:
            k = int(rng.integers(0, 300))
            out.append(item(f"account:{k}", limit=[10, 100, 1000][k % 3],
                            algo=Algorithm(k % 2)))
    return out


def lockstep_batcher(metrics=None, global_lanes=32, updates=32):
    eng = RateLimitEngine(mesh=make_mesh(jax.devices()[:4]),
                          capacity_per_shard=2048, batch_per_shard=512,
                          global_capacity=256,
                          global_batch_per_shard=global_lanes,
                          max_global_updates=updates)
    b = WindowBatcher(eng, BehaviorConfig(batch_wait=0.005), metrics,
                      lockstep_clock=LockstepClock(T0, 0.005))
    assert b.pipeline is not None and b.pipeline.lockstep
    eng.warmup(now=T0, k_stack=1)
    # what each drain took as its window's timestamp, and its GLOBAL items
    nows = []
    inner = b.pipeline._drain_sync_inner

    def spy(jobs, now=None, **kw):
        res = inner(jobs, now=now, **kw)
        nows.append((res.now, res.n_decisions, res.n_global))
        return res
    b.pipeline._drain_sync_inner = spy
    return eng, b, nows


class Reference:
    """benchmark/reference/serial.py over a served history: BATCHING items
    serially, GLOBAL items window by window under `global_window`."""

    def __init__(self):
        self.rows = {}

    def window(self, rpcs, now):
        """The answers one drain owes to `rpcs` (lists of requests)."""
        out = [[None] * len(r) for r in rpcs]
        glob = {}
        for j, reqs in enumerate(rpcs):
            for i, r in enumerate(reqs):
                key = r.hash_key()
                args = (r.hits, r.limit, r.duration, int(r.algorithm))
                if r.behavior == Behavior.GLOBAL:
                    glob.setdefault(key, []).append((j, i, args))
                else:
                    self.rows[key], out[j][i] = serial.apply(
                        self.rows.get(key), *args, now)
        for key, members in glob.items():
            self.rows[key], resps = serial.global_window(
                self.rows.get(key), [a for _, _, a in members], now)
            for (j, i, _), resp in zip(members, resps):
                out[j][i] = resp
        return out


def same(got, want, where):
    assert not got.error, (where, got)
    assert (int(got.status), got.limit, got.remaining, got.reset_time) == \
        tuple(want), (where, got, want)


@pytestmark_native
def test_whole_rpcs_with_global_items_match_the_reference_answer_by_answer():
    m = Metrics()
    eng, b, nows = lockstep_batcher(m)
    a, c, d = thousand(1), thousand(2), thousand(3)
    ref = Reference()
    warm = eng.windows_processed

    async def run():
        b.start_lockstep()
        # two RPCs inside one tick (both queued before it), then a third
        # on a tick of its own
        first = await asyncio.gather(b.submit_rpc(rpc_bytes(a)),
                                     b.submit_rpc(rpc_bytes(c)))
        second = await b.submit_rpc(rpc_bytes(d))
        return first, second
    try:
        with time_limit(240):
            first, second = asyncio.run(run())
    finally:
        b.close()
    assert all(x is not None for x in first) and second is not None
    served = [n for n in nows if n[1]]
    assert [n[1] for n in served] == [2000, 1000], nows
    assert [n[2] for n in served] == [200, 100]
    want = ref.window([a, c], served[0][0])
    for j, data in enumerate(first):
        got = answers(data)
        assert len(got) == 1000
        for i, g in enumerate(got):
            same(g, want[j][i], ("tick 1", j, i))
    want = ref.window([d], served[1][0])
    for i, g in enumerate(answers(second)):
        same(g, want[0][i], ("tick 2", i))
    # the hot tenant's 80 hits of tick 1 landed once, after the window:
    # every answer of tick 1 shows the bucket as made by its own hit, and
    # tick 2 reads 80 fewer
    hot = [i for i, r in enumerate(a) if r.unique_key == "tenant:0"]
    assert {answers(first[0])[i].remaining for i in hot} == {99_999}
    assert {answers(second)[i].remaining
            for i, r in enumerate(d) if r.unique_key == "tenant:0"} == \
        {100_000 - 2 * len(hot)}
    # every decision came in a whole RPC, nothing was deferred, and the
    # ticks between the drains dispatched nothing
    p = b.pipeline
    assert p.lane_decisions == {"raw": 3000, "item": 0, "legacy": 0}
    assert p.global_items == {"staged": 300, "deferred": 0}
    assert p.rpc_served == 3 and p.lockstep_ticks["drain"] == 2
    assert p.lockstep_ticks["idle"] + p.lockstep_ticks["held"] > 0
    assert eng.windows_processed - warm == 2   # no idle tick dispatched
    text = m.expose().decode()
    assert 'guber_tpu_lockstep_decisions_total{lane="raw"} 3000.0' in text
    assert "guber_tpu_global_decisions_total 300.0" in text
    assert 'guber_tpu_stage_duration_ms_count{stage="tick_lag"}' in text


@pytestmark_native
def test_a_second_lockstep_drain_goes_while_one_is_out():
    """The mesh's tick asks the same gate as the standalone pump: with a
    drain out and the engine thread free, an RPC queued meanwhile (a batch
    by itself) goes on the next tick, counted ahead="1"; each drain answers
    under its own tick's timestamp, and the hot tenant's hits of the first
    have landed, once, before the second reads the row."""
    m = Metrics()
    eng, b, nows = lockstep_batcher(m)
    p = b.pipeline
    assert p.gate_enabled and p.depth >= 2
    a, c = thousand(4), thousand(5)
    ref = Reference()
    fetch_go = threading.Event()
    inner = p._complete_sync

    def held_fetch(res):
        fetch_go.wait(30.0)     # a dispatched drain stays in flight
        return inner(res)
    p._complete_sync = held_fetch

    async def until(cond):
        while not cond():
            await asyncio.sleep(0.005)

    async def run():
        b.start_lockstep()
        first = asyncio.ensure_future(b.submit_rpc(rpc_bytes(a)))
        await until(lambda: p._in_flight == 1 and p._predispatch == 0)
        second = asyncio.ensure_future(b.submit_rpc(rpc_bytes(c)))
        await until(lambda: p._in_flight == 2)
        fetch_go.set()
        return await asyncio.gather(first, second)
    try:
        with time_limit(240):
            first, second = asyncio.run(run())
    finally:
        fetch_go.set()
        b.close()
    assert p.drain_overlap == {"0": 1, "1": 1, "2": 0}
    assert m.registry.get_sample_value(
        "guber_tpu_drain_overlap_total", {"ahead": "1"}) == 1.0
    served = [n for n in nows if n[1]]
    assert [n[1] for n in served] == [1000, 1000], nows
    assert served[0][0] < served[1][0]
    for data, reqs, (now, _, _) in ((first, a, served[0]),
                                    (second, c, served[1])):
        want = ref.window([reqs], now)
        for i, g in enumerate(answers(data)):
            same(g, want[0][i], (now, i))
    hot = [i for i, r in enumerate(a) if r.unique_key == "tenant:0"]
    assert {answers(second)[i].remaining
            for i, r in enumerate(c) if r.unique_key == "tenant:0"} == \
        {100_000 - len(hot)}


@pytestmark_native
def test_global_items_a_window_has_no_lane_for_ride_a_later_tick():
    """8 GLOBAL lanes and 2 update lanes a drain: an RPC asking for 12 new
    tenants is answered whole, its overflow on later ticks, every answer
    the rule's."""
    eng, b, nows = lockstep_batcher(global_lanes=2, updates=2)
    reqs = [item(f"tenant:{i % 12}", limit=5000, glob=True)
            if i % 4 == 0 else item(f"account:{i}") for i in range(120)]

    async def run():
        b.start_lockstep()
        return await b.submit_rpc(rpc_bytes(reqs))
    try:
        with time_limit(240):
            data = asyncio.run(run())
    finally:
        b.close()
    got = answers(data)
    assert len(got) == 120 and not any(g.error for g in got)
    for r, g in zip(reqs, got):
        if r.behavior == Behavior.GLOBAL:
            # first sight in whatever window it rode: its own hit shows;
            # a later window reads what landed before it
            assert g.limit == 5000 and 4990 <= g.remaining <= 4999, g
        else:
            assert (g.limit, g.remaining) == (1000, 999)
    p = b.pipeline
    assert p.global_items["deferred"] > 0
    assert p.global_items["staged"] == 30
    assert p.lane_decisions["raw"] + p.lane_decisions["item"] == 120


@pytestmark_native
def test_an_rpc_the_lane_cannot_take_keeps_the_full_path():
    eng, b, nows = lockstep_batcher()
    gcra = [item("account:1"),
            RateLimitReq(name=GNAME, unique_key="tenant:1", hits=1,
                         limit=10, duration=60_000,
                         algorithm=Algorithm.GCRA, behavior=Behavior.GLOBAL)]
    pad = [item(f"account:p{i}") for i in range(80)]

    async def run():
        b.start_lockstep()
        return await b.submit_rpc(rpc_bytes(gcra + pad))
    try:
        with time_limit(120):
            assert asyncio.run(run()) is None   # the caller routes per item
    finally:
        b.close()
    assert b.pipeline.rpc_served == 0


# -------------------------------------------------------------- registrar


def test_an_rpcs_first_seen_global_keys_register_in_one_batch():
    """Per-item path of a mesh instance: 300 first-seen GLOBAL keys in one
    RPC make one registration at the registrar, on the default timeout."""
    from gubernator_tpu.discovery.static import StaticPool
    from gubernator_tpu.server import GrpcServer

    async def run():
        inst = Instance(Config(
            behaviors=BehaviorConfig(batch_wait=0.005),
            engine=EngineConfig(capacity_per_shard=1024, batch_per_shard=256,
                                global_capacity=512)),
            mesh=make_mesh(jax.devices()[:4]), mesh_peers=["pending"])
        assert inst.conf.behaviors.global_timeout == 0.5
        srv = GrpcServer(inst, "127.0.0.1:0")
        await srv.start()
        inst.mesh_peers = [srv.address]
        inst.advertise_address = srv.address
        inst._picker = type(inst._picker).for_mesh(inst.engine.mesh,
                                                   [srv.address])
        inst.engine.warmup(now=inst.batcher.clock.now_ms(), k_stack=1)
        pool = StaticPool([srv.address], srv.address, inst.set_peers)
        await pool.start()
        inst.batcher.start_lockstep()
        calls = []
        real = inst.register_globals

        async def counted(specs):
            calls.append(len(specs))
            return await real(specs)
        inst.register_globals = counted
        try:
            reqs = [item(f"tenant:{i}", limit=1000, glob=True)
                    for i in range(300)]
            # twice at once: the second RPC waits for the first's keys
            # instead of sending them again
            got = await asyncio.gather(inst.get_rate_limits(reqs),
                                       inst.get_rate_limits(reqs))
        finally:
            inst.batcher.close()
            await srv.stop(0)
        return inst, got, calls
    with time_limit(280):
        inst, got, calls = asyncio.run(run())
    for resps in got:
        assert len(resps) == 300
        assert not [r.error for r in resps if r.error]
        assert {r.limit for r in resps} == {1000}
    assert calls == [300], calls
    assert all(inst.engine.global_ready(f"{GNAME}_tenant:{i}")
               for i in range(300))
    hist = inst.metrics.registry.get_sample_value
    assert hist("guber_tpu_global_register_batch_keys_count") == 1.0
    assert hist("guber_tpu_global_register_batch_keys_sum") == 300.0


# ---------------------------------------------------------------- capture


@pytestmark_native
def test_a_capture_under_lockstep_serving_counts_drains_not_idle_ticks(
        tmp_path):
    """POST /v1/admin/profile's capture in mesh mode: the armed drains are
    counted on ticks that dispatched, the capture ends, and its trace
    holds the tick's annotation beside the drain's."""
    eng, b, nows = lockstep_batcher()
    cap = str(tmp_path / "cap")
    prof = b.profile

    async def run():
        b.start_lockstep()
        await b.submit_rpc(rpc_bytes(thousand(5)[:200]))
        assert prof.arm(3, cap)["armed"]
        i = 0
        while prof.status()["active"]:
            if prof.status()["remaining"] > 0:
                await b.submit_rpc(rpc_bytes(thousand(6 + i)[:200]))
                i += 1
            await asyncio.sleep(0.01)
        return i
    try:
        with time_limit(240):
            sent = asyncio.run(run())
    finally:
        b.close()
    assert sent >= 3
    names = {}
    for path in reduce_trace.find_traces(cap):
        for _, lines in reduce_trace.read_planes(path):
            for _, events in lines:
                for name, _, dur in events:
                    if name.startswith("guber_") and dur > 0:
                        names[name] = names.get(name, 0) + 1
    assert names.get("guber_drain", 0) >= 3, names
    assert names.get("guber_tick", 0) >= 3, names
    assert os.path.isdir(cap)
