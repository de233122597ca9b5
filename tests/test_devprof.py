"""Device-time flight recorder: measured kernel attribution, window
clocks, and trace exemplars (observability/devprof.py).

The headline assertion: every probe arm (`build_probe_arms`) must get a
NONZERO measured ms/window entry from a REAL parsed `jax.profiler` trace.
Around it:

  * trace parsing: synthetic chrome-trace events exercise self-time
    nesting and annotation-window arm attribution deterministically;
    malformed / empty traces degrade to a logged no-op
  * the always-on WindowClock: EWMA math, the never-slow first
    observation, lazy exemplar thunks, and the bounded slow ring
  * DevprofController.run_once: one deterministic continuous-mode cycle
    folding a capture of REAL drains into the rolling table
  * the shm trace region (core/shm_ring.py): set/clear/pop roundtrip of
    the worker-propagated traceparent, including slab-reuse hygiene
  * the `/v1/admin/kernels` plane on a live Instance
"""

import asyncio
import json
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

import gubernator_tpu  # noqa: F401
from gubernator_tpu.api.http_gateway import build_app
from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.config import Config, EngineConfig
from gubernator_tpu.core import shm_ring
from gubernator_tpu.core.service import Instance
from gubernator_tpu.observability.devprof import (
    ARM_DRAIN,
    ARM_FETCH,
    ARM_OTHER,
    Devprof,
    DevprofController,
    KernelTable,
    WindowClock,
    build_probe_arms,
    load_trace_events,
    measure_probe_arms,
    parse_run_dir,
    self_times,
)
from gubernator_tpu.observability.metrics import Metrics

pytestmark = pytest.mark.devprof

PROBE_ARMS = ("int64_xla", "compact32_xla", "composed_drain",
              "composed_mixed_algos", "composed_analytics")


# --------------------------------------------------------------- trace parsing


def test_malformed_and_empty_traces_degrade(tmp_path):
    run = tmp_path / "plugins" / "profile" / "t1"
    run.mkdir(parents=True)
    # not an XSpace at all
    bad = run / "host.xplane.pb"
    bad.write_bytes(b"definitely not a protobuf")
    assert load_trace_events(str(bad)) == []
    # an empty XSpace: no planes, no events
    empty = run / "h2.xplane.pb"
    empty.write_bytes(b"")
    assert load_trace_events(str(empty)) == []
    assert parse_run_dir(str(tmp_path)) == []
    # a real capture: zero-duration runtime markers are filtered, the
    # annotation span and the executable's ops are kept in microseconds
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    cap = tmp_path / "cap"
    jax.profiler.start_trace(str(cap))
    try:
        with jax.profiler.TraceAnnotation("guber_drain"):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    evs = parse_run_dir(str(cap))
    assert evs and all(e["ph"] == "X" and e["dur"] > 0 for e in evs)
    span = [e for e in evs if e["name"] == "guber_drain"]
    assert len(span) == 1
    inside = [e for e in evs if not e["name"].startswith("$")
              and span[0]["ts"] <= e["ts"]
              and e["ts"] + e["dur"] <= span[0]["ts"] + span[0]["dur"]
              and e is not span[0]]
    assert inside, "no runtime event landed inside the annotation span"
    # a run dir with no trace files at all
    assert parse_run_dir(str(tmp_path / "nothing-here")) == []
    # folding an empty capture is a counted no-op, never an error
    t = KernelTable()
    assert t.fold([]) == 0
    assert t.ms_per_window() == {}
    snap = t.snapshot()
    assert snap["rows"] == [] and snap["folds"] == 0


def test_self_times_nesting_and_arm_attribution():
    # annotations on the engine thread's track (1,1); kernels on the
    # runtime executor's track (2,2) — the cross-track midpoint join
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "name": "guber_drain:step",
         "ts": 0.0, "dur": 200.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "guber_fetch",
         "ts": 100.0, "dur": 50.0},
        # outer kernel with a nested child: self = 80 - 30 us
        {"ph": "X", "pid": 2, "tid": 2, "name": "fusion.1",
         "ts": 0.0, "dur": 80.0},
        {"ph": "X", "pid": 2, "tid": 2, "name": "convert.2",
         "ts": 10.0, "dur": 30.0},
        # midpoint 120 sits in BOTH guber_drain and guber_fetch: the
        # narrower annotation wins
        {"ph": "X", "pid": 2, "tid": 2, "name": "copy.3",
         "ts": 110.0, "dur": 20.0},
        # outside every annotation: the XLA shoulder
        {"ph": "X", "pid": 2, "tid": 2, "name": "stray.4",
         "ts": 500.0, "dur": 10.0},
        # host noise never masquerades as a kernel
        {"ph": "X", "pid": 2, "tid": 2, "name": "ThunkExecutor",
         "ts": 0.0, "dur": 1000.0},
    ]
    rows = {name: (ms, arm) for name, ms, arm in self_times(events)}
    assert set(rows) == {"fusion.1", "convert.2", "copy.3", "stray.4"}
    assert rows["fusion.1"] == (0.05, ARM_DRAIN)
    assert rows["convert.2"] == (0.03, ARM_DRAIN)
    assert rows["copy.3"] == (0.02, ARM_FETCH)
    assert rows["stray.4"] == (0.01, ARM_OTHER)
    # an arm-scoped capture overrides the annotation join wholesale
    hinted = {arm for _n, _ms, arm in
              self_times(events, arm_hint="compact32_xla")}
    assert hinted == {"compact32_xla"}


def test_kernel_table_keys_by_arm_and_name():
    # the same HLO instruction name from two arms must not collapse
    ev = [{"ph": "X", "pid": 0, "tid": 0, "name": "fusion.1",
           "ts": 0.0, "dur": 100.0}]
    t = KernelTable()
    assert t.fold(ev, windows=1, arm_hint="composed_drain") == 1
    assert t.fold(ev, windows=1, arm_hint="compact32_xla") == 1
    mpw = t.ms_per_window()
    assert set(mpw) == {"composed_drain", "compact32_xla"}
    assert mpw["composed_drain"] == pytest.approx(0.05)
    assert mpw["compact32_xla"] == pytest.approx(0.05)
    arms_in_rows = {r["arm"] for r in t.snapshot()["rows"]}
    assert arms_in_rows == {"composed_drain", "compact32_xla"}


# --------------------------------------------------------- measured probe arms


def test_every_probe_arm_gets_measured_time():
    """Every probe arm gets a nonzero measured ms/window entry from a real
    parsed trace, and the admin payload carries it per arm."""
    arms = build_probe_arms(k=2)
    assert {s["name"] for s in arms} == set(PROBE_ARMS)

    dev = Devprof()
    out = measure_probe_arms(arms=arms, iters=1, table=dev.table)
    for name in PROBE_ARMS:
        row = out["arms"][name]
        assert row["kernel_events"] > 0, f"{name}: no kernel events parsed"
        assert row["measured_ms_per_window"] > 0, \
            f"{name}: zero measured time"
    kt = out["kernel_table"]
    assert kt["rows"] and kt["windows"] > 0

    snap = dev.kernels_snapshot()
    for name in PROBE_ARMS:
        slot = snap["arms"][name]
        assert slot["measured_ms_per_window"] > 0
    json.dumps(snap)  # admin-plane payload must be JSON-safe


# ---------------------------------------------------------------- window clock


def test_window_clock_ewma_and_first_observation_never_slow():
    clk = WindowClock(metrics=None, ring=4, slow_ms=0.0)
    # first observation seeds the EWMA at ms, so ms < 3*ewma always
    assert clk.observe("composed_drain", 5.0) is False
    snap = clk.snapshot()
    assert snap["arms"]["composed_drain"]["ewma_ms"] == 5000.0
    # exact EWMA step: 10ms then 20ms -> 10 + 0.2*(20-10) = 12
    clk2 = WindowClock(metrics=None, ring=4, slow_ms=0.0)
    clk2.observe("a", 0.010)
    clk2.observe("a", 0.020)
    arms = clk2.snapshot()["arms"]
    assert arms["a"]["ewma_ms"] == pytest.approx(12.0)
    assert arms["a"]["count"] == 2


def test_window_clock_exemplars_are_lazy_and_ring_is_bounded():
    clk = WindowClock(metrics=Metrics(), ring=2, slow_ms=10.0)

    def boom():
        raise AssertionError("exemplar thunk ran on a fast window")

    clk.observe("arm", 0.001, trace_ids=boom)   # fast: thunk untouched
    clk.observe("arm", 0.001, trace_ids=boom)
    # a window past the floor AND 3x the arm's norm records an exemplar
    slow = clk.observe("arm", 5.0, trace_ids=lambda: ["t-1", "t-2"],
                       windows=3)
    assert slow is True
    rec = clk.snapshot()["slow_windows"][-1]
    assert rec["trace_ids"] == ["t-1", "t-2"]
    assert rec["arm"] == "arm" and rec["windows"] == 3
    # alternating tiny/huge keeps every huge window slow; the ring caps
    for _ in range(6):
        clk.observe("arm", 0.000001)
        clk.observe("arm", 50.0, trace_ids=list)
    assert len(clk.snapshot()["slow_windows"]) == 2


def test_window_clock_feeds_metrics():
    m = Metrics()
    clk = WindowClock(metrics=m, ring=4, slow_ms=1000.0)
    clk.observe("compact32_xla", 0.004)
    g = m.registry.get_sample_value
    assert g("guber_tpu_device_window_ms_count",
             {"arm": "compact32_xla"}) == 1.0
    assert clk.snapshot()["arms"]["compact32_xla"]["ewma_ms"] == \
        pytest.approx(4.0)


# ------------------------------------------------------------ shm trace region


def test_shm_trace_region_roundtrip():
    name = f"gtd-{os.getpid()}"
    ch = shm_ring.WorkerChannel.create(name, slots=4, slab_bytes=1 << 15)
    try:
        slot = ch.alloc()
        # high bits set on every word: the region must be unsigned-clean
        hi, lo, span = 0xDEADBEEF00000001, 0x8000000000000002, 0xFFFF0000ABCD0003
        ch.set_trace(slot, hi, lo, span)
        ch.commit_cols(slot, req_id=7, n=0, key_len=0)
        ch.submit(slot)
        (rec,) = ch.pop()
        assert rec.trace == (hi, lo, span)
        # slab reuse hygiene: the next tenant without a traceparent must
        # clear the previous one's words
        ch.clear_trace(slot)
        ch.commit_cols(slot, req_id=8, n=0, key_len=0)
        ch.submit(slot)
        (rec2,) = ch.pop()
        assert rec2.trace is None
        # RAW records carry no trace region at all
        s2 = ch.alloc()
        assert ch.write_raw(s2, shm_ring.KIND_RAW, 9, b"payload")
        ch.submit(s2)
        (rec3,) = ch.pop()
        assert rec3.trace is None
    finally:
        ch.close()


def test_worker_traceparent_parses_invocation_metadata():
    from gubernator_tpu.frontdoor import _Worker

    class _Ctx:
        def __init__(self, md):
            self._md = md

        def invocation_metadata(self):
            return self._md

    tp = f"00-{'ab' * 16}-{'cd' * 8}-01"
    got = _Worker.traceparent(None, _Ctx([("traceparent", tp)]))
    assert got == (int("ab" * 8, 16), int("ab" * 8, 16), int("cd" * 8, 16))
    # bytes-valued metadata parses the same
    assert _Worker.traceparent(
        None, _Ctx([("traceparent", tp.encode())])) == got
    # absent / malformed / unsampled all degrade to None
    assert _Worker.traceparent(None, _Ctx([])) is None
    assert _Worker.traceparent(
        None, _Ctx([("traceparent", "garbage")])) is None
    assert _Worker.traceparent(
        None, _Ctx([("traceparent", tp[:-2] + "00")])) is None
    assert _Worker.traceparent(None, object()) is None


# ------------------------------------------------ live instance: clock + admin


@pytest.fixture(scope="module")
def inst():
    conf = Config(engine=EngineConfig(
        capacity_per_shard=512, batch_per_shard=128,
        global_capacity=128, global_batch_per_shard=32,
        max_global_updates=32), trace_sample=1.0)
    inst = Instance(conf)
    inst.engine.warmup()
    yield inst
    inst.close()


def _reqs(n=8, pfx="dp"):
    return [RateLimitReq(name="dp", unique_key=f"{pfx}{i}", hits=1,
                         limit=1 << 20, duration=60_000)
            for i in range(n)]


def test_admin_kernels_endpoint(inst):
    async def body():
        server = TestServer(build_app(inst))
        client = TestClient(server)
        await client.start_server()
        try:
            payload = {"requests": [{"name": "dk", "uniqueKey": "k1",
                                     "hits": "1", "limit": "10",
                                     "duration": "60000"}]}
            r = await client.post("/v1/GetRateLimits", json=payload)
            assert r.status == 200
            r = await client.get("/v1/admin/kernels")
            assert r.status == 200
            out = await r.json()
            json.dumps(out)
            assert set(out) >= {"arms", "table", "windows", "clock"}
            # the always-on window clock saw the drain the request rode
            arms = out["clock"]["arms"]
            assert arms, "no window-clock observation for a served request"
            for arm, stats in arms.items():
                assert arm in ("compact32_xla", "composed_drain",
                               "composed_analytics")
                assert stats["count"] >= 1
                assert stats["ewma_ms"] >= 0.0
            # a measure request conflicts with an armed capture
            assert inst.batcher.profile.arm(4, "/tmp/gtd-armed")["armed"]
            r = await client.get("/v1/admin/kernels?measure=1")
            assert r.status == 409
            inst.batcher.profile.cancel()
            # devprof status rides the debug snapshot
            r = await client.get("/v1/admin/debug")
            assert r.status == 200
            snap = await r.json()
            assert snap["devprof"]["mode"] == "off"
            assert snap["devprof"]["table"]["folds"] >= 0
        finally:
            await client.close()
    asyncio.run(body())


def test_controller_run_once_folds_real_drains(inst):
    """One deterministic continuous-mode cycle: arm a 2-drain capture,
    serve real traffic through the instance, and the controller folds the
    parsed trace into the rolling table (then discards the trace dir)."""
    table = KernelTable()
    ctl = DevprofController(
        inst.batcher.profile, table, interval=60.0, drains=2,
        metrics=inst.metrics,
        windows_fn=lambda: int(inst.engine.windows_processed))
    result = {}
    th = threading.Thread(
        target=lambda: result.update(ok=ctl.run_once(capture_timeout=30.0)))
    th.start()

    async def drive():
        deadline = time.monotonic() + 25.0
        i = 0
        while th.is_alive() and time.monotonic() < deadline:
            await inst.get_rate_limits(_reqs(pfx=f"c{i}"))
            i += 1
            await asyncio.sleep(0.01)

    asyncio.run(drive())
    th.join(timeout=35.0)
    assert not th.is_alive()
    assert result.get("ok") is True, ctl.status()
    assert ctl.cycles == 1 and ctl.kernel_rows > 0
    snap = table.snapshot()
    assert snap["windows"] >= 1 and snap["rows"]
    assert table.ms_per_window()
    # the capture counter recorded the folded cycle
    assert inst.metrics.registry.get_sample_value(
        "guber_tpu_devprof_captures_total", {"status": "folded"}) >= 1.0
    # a second cycle sheds while an operator capture is armed
    assert inst.batcher.profile.arm(8, "/tmp/gtd-op")["armed"]
    try:
        assert ctl.run_once() is False
        assert ctl.sheds == 1
    finally:
        inst.batcher.profile.cancel()
