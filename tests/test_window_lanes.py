"""Lane-bucketed serving drain (PR 29): a single-window drain runs the
narrowest warmed lane bucket that holds its fullest shard.

  * (a) the same lanes dispatched narrow and dispatched full from the same
    arena give the same words, limits, mismatch flags and planes
  * (b) which width a drain takes
  * (c) the served path: 2-item RPCs ride narrow drains, a 1000-item RPC a
    full one, a stored-limit mismatch decodes right at a narrow width, and
    /metrics, /v1/admin/debug and the layer-metric file count them
  * (d) nothing compiles after warmup(), at any width
"""

import asyncio
import logging
import os
import types

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

import gubernator_tpu  # noqa: F401
from benchmark import harness
from gubernator_tpu import native
from gubernator_tpu.api import pb
from gubernator_tpu.api.http_gateway import build_app
from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.client import AsyncClient
from gubernator_tpu.config import BehaviorConfig, Config, EngineConfig
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.core.service import Instance
from gubernator_tpu.observability.metrics import DRAIN_WIDTHS, Metrics
from gubernator_tpu.ops import kernel
from gubernator_tpu.parallel.mesh import make_mesh
from gubernator_tpu.server import GrpcServer

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native router unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = 1_700_000_000_000
C, B = 4096, 1024
WIDTHS = (64, 256, 1024)   # B/16, B/4, B


def _engine(shards=None, use_native="on", lanes=B):
    mesh = None if shards is None else make_mesh(jax.devices("cpu")[:shards])
    return RateLimitEngine(capacity_per_shard=C, batch_per_shard=lanes,
                           global_capacity=16, global_batch_per_shard=8,
                           max_global_updates=8, use_native=use_native,
                           mesh=mesh)


def test_the_widths_are_the_engines_lane_buckets():
    assert tuple(_engine(1)._lane_bucket_list) == WIDTHS


# ------------------------------------------------- (a) narrow == full, bitwise


def _lanes(rng, shards, fill, seen, relimit):
    """One window [1, S, B, 2] with `fill` lanes a shard: token and leaky
    slots drawn from a few hot ones (duplicates, so the fold runs) and a
    cold tail; `relimit` asks a live slot for another limit (the mismatch
    flag)."""
    packed = np.zeros((1, shards, B, 2), np.int64)
    for s in range(shards):
        slot = np.where(rng.random(fill) < 0.5, rng.integers(0, 6, fill),
                        rng.integers(0, C, fill)).astype(np.int32)
        first = np.zeros(fill, bool)
        first[np.unique(slot, return_index=True)[1]] = True
        is_init = first & ~np.isin(slot, list(seen[s]))
        seen[s].update(slot.tolist())
        limit = 5 + (slot % 7).astype(np.int64)
        if relimit:
            limit = np.where(slot % 3 == 0, limit + 4, limit)
        packed[0, s, :fill] = kernel.encode_batch_host(
            slot, rng.integers(0, 3, fill).astype(np.int64), limit,
            np.full(fill, 60_000, np.int64), (slot % 2).astype(np.int32),
            is_init)
    return packed


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("width", WIDTHS)
def test_narrow_dispatch_equals_full_dispatch(width, seed):
    shards = 2
    rng = np.random.default_rng(1000 * seed + width)
    narrow, full = _engine(shards), _engine(shards)
    seen = [set() for _ in range(shards)]
    for i, fill in enumerate((width, int(rng.integers(1, width + 1)))):
        packed = _lanes(rng, shards, fill, seen, relimit=i == 1)
        nows = np.full(1, T0 + 700 * i, np.int64)
        wn, ln, mn = narrow.pipeline_dispatch(
            np.ascontiguousarray(packed[:, :, :width]), nows)
        wf, lf, mf = full.pipeline_dispatch(packed, nows)
        assert wn.shape == (1, shards, width) == ln.shape
        np.testing.assert_array_equal(np.asarray(wn),
                                      np.asarray(wf)[..., :width])
        np.testing.assert_array_equal(np.asarray(ln),
                                      np.asarray(lf)[..., :width])
        np.testing.assert_array_equal(np.asarray(mn), np.asarray(mf))
        if i == 1:
            assert np.asarray(mn).any()   # the limits plane mattered
        for name, a, b in zip(kernel.ArenaPlanes._fields, narrow.state,
                              full.state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"state.{name} drain {i}")


# --------------------------------------------------------- (b) which width


def _pipeline(eng, metrics=None):
    b = WindowBatcher(eng, BehaviorConfig(), metrics=metrics)
    assert b.pipeline is not None and b.pipeline.enabled
    b.pipeline.now_fn = lambda: T0
    return b


def _fills(*per_shard, k=1):
    f = np.zeros((8, len(per_shard)), np.int32)
    f[:k] = per_shard
    return f


@pytest.mark.parametrize("fill,want", [
    (1, 64), (64, 64), (65, 256), (256, 256), (257, 1024), (1024, 1024)])
def test_a_single_window_takes_the_narrowest_bucket_that_holds_it(fill, want):
    b = _pipeline(_engine(2))
    try:
        assert b.pipeline._drain_lanes(_fills(3, fill), 1) == want
    finally:
        b.close()


@pytest.mark.parametrize("case", ["stacked", "lockstep", "multiprocess",
                                  "analytics"])
def test_these_drains_keep_the_full_width(case, monkeypatch):
    eng = _engine(2)
    b = _pipeline(eng)
    p = b.pipeline
    try:
        k_used = 1
        if case == "stacked":
            k_used = 2
        elif case == "lockstep":
            monkeypatch.setattr(p, "lockstep", True)
        elif case == "multiprocess":
            monkeypatch.setattr(eng, "multiprocess", True)
        else:
            monkeypatch.setattr(p, "analytics", types.SimpleNamespace())
        assert p._drain_lanes(_fills(3, 5, k=k_used), k_used) == B
    finally:
        b.close()


# ------------------------------------------------------- (c) the served path


def _reqs(prefix, n, limit=9):
    # token buckets of ten minutes: the server and the reference answer on
    # their own wall clocks, seconds apart when a shape compiles between
    return [RateLimitReq(name="dl", unique_key=f"{prefix}{i}", hits=1,
                         limit=limit, duration=600_000)
            for i in range(n)]


def _answers(rs):
    return [(int(r.status), r.limit, r.remaining) for r in rs]


@pytest.fixture(scope="module")
def node():
    """A standalone Instance behind its real gRPC server and HTTP gateway:
    eight CPU shards of 128 lanes, so buckets 64 and 128.  Not warmed up:
    the two or three shapes the test serves compile as they come."""
    loop = asyncio.new_event_loop()
    inst = Instance(Config(engine=EngineConfig(
        capacity_per_shard=2048, batch_per_shard=128, global_capacity=64,
        global_batch_per_shard=16, max_global_updates=16)))

    async def up():
        server = GrpcServer(inst, "127.0.0.1:0")
        await server.start()
        http = TestClient(TestServer(build_app(inst)))
        await http.start_server()
        return server, http
    server, http = loop.run_until_complete(up())
    yield loop, inst, server, http

    async def down():
        await http.close()
        await server.stop(0.2)
    loop.run_until_complete(down())
    inst.close()
    loop.close()


async def _snapshot(http):
    prom = harness.parse_prom(await (await http.get("/metrics")).text())
    debug = await (await http.get("/v1/admin/debug")).json()
    return {"prom": prom, "debug": debug}


def test_small_rpcs_ride_narrow_drains_and_a_big_one_a_full_drain(node):
    loop, inst, server, http = node
    ref = RateLimitEngine(capacity_per_shard=2048, batch_per_shard=128,
                          global_capacity=64, global_batch_per_shard=16,
                          max_global_updates=16, use_native=False)
    spec = harness.Bench(REPO).layer_file("narrow_drain_pct.lat")

    def pct(before, after):
        return harness.evaluate(spec["read"],
                                {"before": before, "after": after})

    def count(snap, width):
        return snap["prom"][("guber_tpu_drains_total", (("width", width),))]

    async def body():
        client = AsyncClient(server.address)
        try:
            idle = await _snapshot(http)
            assert [count(idle, w) for w in DRAIN_WIDTHS] == [0.0, 0.0]
            assert idle["debug"]["pipeline"]["drain_widths"] == {
                "64": 0, "128": 0}
            assert pct(idle, idle) is None   # no drain yet: nothing to read
            for i in range(5):
                batch = _reqs(f"s{i}_", 2)
                got = await client.get_rate_limits(batch)
                assert _answers(got) == _answers(ref.process(batch))
            # a live bucket asked for another limit answers with the stored
            # one: the mismatch flag fires and the limits plane is fetched
            # and reshaped at the narrow width
            again = _reqs("s0_", 2, limit=25)
            got = await client.get_rate_limits(again)
            assert _answers(got) == _answers(ref.process(again))
            assert [r.limit for r in got] == [9, 9]
            small = await _snapshot(http)
            assert count(small, "narrow") == 6.0 and count(small, "full") == 0
            assert small["debug"]["pipeline"]["drain_widths"] == {
                "64": 6, "128": 0}
            assert pct(idle, small) == 100.0
            big = _reqs("b", 1000)
            got = await client.get_rate_limits(big)
            assert _answers(got) == _answers(ref.process(big))
            after = await _snapshot(http)
            assert count(after, "narrow") == 6.0
            n_full = count(after, "full")
            assert n_full >= 1.0
            assert after["debug"]["pipeline"]["drain_widths"] == {
                "64": 6, "128": int(n_full)}
            assert pct(small, after) == 0.0
            assert pct(idle, after) == pytest.approx(600.0 / (6 + n_full))
        finally:
            await client.close()
    loop.run_until_complete(body())


# ------------------------------------------- (d) no compile after warmup()


def _rpc(prefix, n):
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name="dl", unique_key=f"{prefix}{i}", hits=1,
                        limit=9, duration=60_000, algorithm=i % 2)
        for i in range(n)]).SerializeToString()


def test_no_drain_compiles_after_warmup(caplog):
    eng = _engine(1)
    eng.warmup(now=T0)
    b = _pipeline(eng)
    p = b.pipeline

    def compiles():
        return [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("Compiling ")]
    try:
        with jax.log_compiles(), caplog.at_level(logging.WARNING, "jax"):
            for i, (n, width) in enumerate(zip((2, 200, 1000), WIDTHS)):
                out = asyncio.run(b.submit_rpc(_rpc(f"w{i}_", n)))
                got = pb.GetRateLimitsResp.FromString(out).responses
                assert [r.remaining for r in got] == [8] * n
                assert p.drain_widths[width] == 1, (width, p.drain_widths)
            assert compiles() == []
            # the detector does see a shape nothing warmed
            eng.pipeline_dispatch(np.zeros((1, 1, 32, 2), np.int64),
                                  np.full(1, T0, np.int64), n_windows=0)
            assert len(compiles()) == 1
    finally:
        b.close()
