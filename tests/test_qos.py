"""QoS subsystem suite (gubernator_tpu/qos/): admission control, AIMD
congestion window, tenant-fair slotting, and peer-lane circuit breaking.

All state machines run on injectable monotonic clocks (no sleeps except
the real event-loop drains in the overload integration tests), so the
suite is deterministic on CPU — the same discipline as the lockstep
tests (tests/test_lockstep_drain.py).
"""

import asyncio
import time

import grpc
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu.api import pb
from gubernator_tpu.api.types import Behavior, RateLimitReq, Second, Status
from gubernator_tpu.config import (
    BehaviorConfig,
    Config,
    EngineConfig,
    QoSConfig,
    config_from_env,
)
from gubernator_tpu.core.service import Instance
from gubernator_tpu.net.peers import BreakerOpenError, PeerClient, PeerError
from gubernator_tpu.qos import (
    AdmissionController,
    CircuitBreaker,
    CongestionController,
    QoSManager,
    interleave_by_tenant,
    shed_response,
)
from gubernator_tpu.qos.admission import (
    SHED_BREAKER_OPEN,
    SHED_DEADLINE,
    SHED_QUEUE_FULL,
)
from gubernator_tpu.qos.breaker import CLOSED, HALF_OPEN, OPEN, backoff_delays

pytestmark = pytest.mark.qos


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _qconf(**kw):
    base = dict(max_pending=8, min_window=4, max_window=64,
                target_drain_latency=0.1, aimd_increase=8.0,
                aimd_decrease=0.5, latency_ewma_alpha=1.0)
    base.update(kw)
    return QoSConfig(**base)


# ---------------------------------------------------------------- congestion


def test_aimd_additive_increase_to_max():
    clk = FakeClock()
    c = CongestionController(_qconf(min_window=4, max_window=32,
                                    aimd_increase=8.0), now_fn=clk)
    c._cwnd = 4.0
    for _ in range(10):
        c.observe_drain(0.01)  # well under target: probe upward
    assert c.effective_window() == 32  # clamped at max_window
    assert c.increases > 0 and c.decreases == 0


def test_aimd_multiplicative_decrease_with_cooldown():
    clk = FakeClock()
    c = CongestionController(_qconf(max_window=64), now_fn=clk)
    assert c.effective_window() == 64
    c.observe_drain(0.5)  # 5x target: decrease
    assert c.effective_window() == 32
    assert c.decreases == 1
    # a burst of stale slow completions within the cooldown must NOT
    # collapse the window further
    c.observe_drain(0.5)
    c.observe_drain(0.5)
    assert c.effective_window() == 32 and c.decreases == 1
    # after one EWMA'd cycle has passed, the next slow drain decreases again
    clk.advance(1.0)
    c.observe_drain(0.5)
    assert c.effective_window() == 16 and c.decreases == 2
    # and the floor holds no matter how congested
    for _ in range(50):
        clk.advance(10.0)
        c.observe_drain(5.0)
    assert c.effective_window() == c.min_window


def test_aimd_recovers_after_congestion_clears():
    clk = FakeClock()
    c = CongestionController(_qconf(max_window=64, aimd_increase=8.0),
                             now_fn=clk)
    clk.advance(1.0)
    c.observe_drain(1.0)
    assert c.congested and c.effective_window() == 32
    c.observe_drain(0.01)  # alpha=1.0: EWMA snaps back under target
    assert not c.congested
    assert c.effective_window() == 40  # additive step back up
    assert c.effective_depth(4) >= 1


def test_effective_depth_scales_with_cwnd():
    c = CongestionController(_qconf(min_window=4, max_window=64))
    assert c.effective_depth(4) == 4  # full cwnd: full depth
    c._cwnd = 16.0
    assert c.effective_depth(4) == 1
    c._cwnd = 32.0
    assert c.effective_depth(4) == 2


# ----------------------------------------------------------------- admission


def test_admission_bounded_queue():
    clk = FakeClock()
    cong = CongestionController(_qconf(), now_fn=clk)
    adm = AdmissionController(_qconf(max_pending=4), cong, now_fn=clk)
    for _ in range(4):
        assert adm.try_admit() is None
    assert adm.try_admit() == SHED_QUEUE_FULL
    assert adm.saturated
    assert adm.pending_peak == 4
    adm.release(2)
    assert not adm.saturated
    assert adm.try_admit() is None
    assert adm.shed_counts[SHED_QUEUE_FULL] == 1


def test_admission_deadline_shedding():
    clk = FakeClock()
    conf = _qconf(max_pending=100, target_drain_latency=0.1)
    cong = CongestionController(conf, now_fn=clk)
    adm = AdmissionController(conf, cong, now_fn=clk)
    # unobserved controller: the target is the prior cycle estimate, so
    # estimate_wait() ~= 0.1s; a 1ms deadline is unserviceable NOW
    assert adm.try_admit(deadline=clk() + 0.001) == SHED_DEADLINE
    # an already-expired deadline sheds regardless of queue state
    assert adm.try_admit(deadline=clk() - 1.0) == SHED_DEADLINE
    # a comfortable deadline admits
    assert adm.try_admit(deadline=clk() + 10.0) is None
    # once drains are observed fast, tighter deadlines become serviceable
    cong.observe_drain(0.001)
    assert adm.try_admit(deadline=clk() + 0.05) is None
    assert adm.shed_counts[SHED_DEADLINE] == 2


def test_shed_response_shape():
    r = RateLimitReq(name="t", unique_key="k", hits=1, limit=7,
                     duration=Second)
    resp = shed_response(r, SHED_QUEUE_FULL)
    assert resp.status == Status.OVER_LIMIT
    assert resp.limit == 7 and resp.remaining == 0
    assert resp.metadata["shed"] == "true"
    assert resp.metadata["shed_reason"] == SHED_QUEUE_FULL


# ------------------------------------------------------------------ fairness


def test_interleave_round_robin_stable_within_tenant():
    items = [("a", 1), ("a", 2), ("a", 3), ("b", 1), ("b", 2), ("c", 1)]
    out = interleave_by_tenant(items, lambda it: it[0])
    assert out == [("a", 1), ("b", 1), ("c", 1),
                   ("a", 2), ("b", 2), ("a", 3)]
    # per-tenant order is preserved (per-key sequential semantics)
    for t in "abc":
        sub = [i for tt, i in out if tt == t]
        assert sub == sorted(sub)


def test_interleave_single_tenant_passthrough_and_weights():
    items = [("a", i) for i in range(5)]
    assert interleave_by_tenant(items, lambda it: it[0]) == items
    mixed = [("a", i) for i in range(4)] + [("b", i) for i in range(2)]
    out = interleave_by_tenant(mixed, lambda it: it[0],
                               weight_of=lambda t: 2 if t == "a" else 1)
    assert out == [("a", 0), ("a", 1), ("b", 0),
                   ("a", 2), ("a", 3), ("b", 1)]


# ------------------------------------------------------------------- breaker


def test_breaker_trips_and_recovers_through_half_open():
    clk = FakeClock()
    states = []
    b = CircuitBreaker(fail_threshold=3, open_duration=2.0,
                       half_open_probes=1, now_fn=clk,
                       on_state_change=states.append)
    # consecutive-failure trip; a success resets the streak
    b.record_failure()
    b.record_failure()
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED
    b.record_failure()
    assert b.state == OPEN
    assert not b.allow()  # open: rejected locally
    # open window elapses: half-open lets exactly one probe through
    clk.advance(2.0)
    assert b.allow()
    assert b.state == HALF_OPEN
    assert not b.allow()  # probe budget consumed
    b.record_success()
    assert b.state == CLOSED
    assert b.allow()
    assert states == [OPEN, HALF_OPEN, CLOSED]


def test_breaker_half_open_failure_reopens():
    clk = FakeClock()
    b = CircuitBreaker(fail_threshold=1, open_duration=1.0, now_fn=clk)
    b.record_failure()
    assert b.state == OPEN
    clk.advance(1.0)
    assert b.allow() and b.state == HALF_OPEN
    b.record_failure()
    assert b.state == OPEN  # fresh open window
    assert not b.allow()
    clk.advance(1.0)
    assert b.allow()


def test_backoff_delays_jittered_and_capped():
    import random
    delays = list(backoff_delays(5, 0.025, 0.1, rng=random.Random(7)))
    assert len(delays) == 5
    assert all(0 < d <= 0.1 for d in delays)


# ----------------------------------------------------------------- peer lane


class _FakeRpcError(grpc.RpcError):
    def __init__(self, code, details="boom"):
        self._code = code
        self._details = details

    def code(self):
        return self._code

    def details(self):
        return self._details


def _peer(qos=None):
    return PeerClient(BehaviorConfig(), "127.0.0.1:1", qos=qos)


def test_peer_error_normalization():
    async def body():
        p = _peer()
        calls = {"n": 0}

        async def do():
            calls["n"] += 1
            raise _FakeRpcError(grpc.StatusCode.INVALID_ARGUMENT, "bad req")

        async def no_sleep(_):
            pass
        p._sleep = no_sleep
        with pytest.raises(PeerError) as ei:
            await p._call(do)
        # typed, host attached, NOT retried (non-transient)
        assert "127.0.0.1:1" in str(ei.value)
        assert ei.value.code == grpc.StatusCode.INVALID_ARGUMENT
        assert not ei.value.retryable
        assert calls["n"] == 1
        # an application-level answer proves the peer alive: breaker closed
        assert p.breaker.state == CLOSED
        await p.channel.close()
    asyncio.run(body())


def test_peer_retry_then_breaker_trip_and_recovery():
    async def body():
        clk = FakeClock()
        qos = QoSManager(_qconf(peer_retries=2, breaker_fail_threshold=2,
                                breaker_open_duration=5.0),
                         now_fn=clk)
        p = _peer(qos)
        sleeps = []

        async def no_sleep(d):
            sleeps.append(d)
        p._sleep = no_sleep
        calls = {"n": 0}

        async def unavailable():
            calls["n"] += 1
            raise _FakeRpcError(grpc.StatusCode.UNAVAILABLE)

        # transient UNAVAILABLE: retried with jittered backoff, then the
        # final failure counts against the breaker
        with pytest.raises(PeerError) as ei:
            await p._call(unavailable)
        assert ei.value.retryable
        assert calls["n"] == 3  # 1 attempt + 2 retries
        assert len(sleeps) == 2 and all(0 < d <= 0.25 for d in sleeps)
        assert p.breaker.state == CLOSED  # one strike of two
        with pytest.raises(PeerError):
            await p._call(unavailable)
        assert p.breaker.state == OPEN  # second strike trips it
        # open: rejected locally without touching the network
        before = calls["n"]
        with pytest.raises(BreakerOpenError):
            await p._call(unavailable)
        assert calls["n"] == before
        # recovery through half-open
        clk.advance(5.0)

        async def healthy():
            return "ok"
        assert await p._call(healthy) == "ok"
        assert p.breaker.state == CLOSED
        await p.channel.close()
    asyncio.run(body())


def test_peer_timeout_normalizes_retryable():
    async def body():
        p = _peer()

        async def no_sleep(_):
            pass
        p._sleep = no_sleep

        async def slow():
            raise asyncio.TimeoutError()
        with pytest.raises(PeerError) as ei:
            await p._call(slow)
        assert ei.value.retryable
        assert ei.value.code == grpc.StatusCode.DEADLINE_EXCEEDED
        await p.channel.close()
    asyncio.run(body())


# ------------------------------------------------------- service integration


def _req(key, name="tenant", hits=1, limit=1000, behavior=Behavior.BATCHING):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=60 * Second, behavior=behavior)


def _instance(qos_conf=None, use_native="auto"):
    inst = Instance(Config(
        behaviors=BehaviorConfig(),
        engine=EngineConfig(capacity_per_shard=2048, batch_per_shard=128,
                            global_capacity=64, global_batch_per_shard=16,
                            max_global_updates=16, use_native=use_native),
        qos=qos_conf or QoSConfig()))
    inst.engine.warmup()
    return inst


def test_overload_bounded_queue_goodput_and_inband_sheds():
    """The acceptance scenario: sustained 5x overload — the bounded queue
    never exceeds its cap, every shed is in-band with a reason, admitted
    requests all complete, and goodput does not collapse vs the
    unsaturated baseline."""
    async def body():
        cap = 64
        inst = _instance(QoSConfig(max_pending=cap, min_window=16,
                                   max_window=4096,
                                   target_drain_latency=0.25),
                         use_native=False)  # classic window path
        try:
            adm = inst.qos.admission

            async def burst(n, salt):
                reqs = [_req(f"k{salt}-{i}") for i in range(n)]
                t0 = time.monotonic()
                resps = await inst.get_rate_limits(reqs)
                dt = time.monotonic() - t0
                served = [r for r in resps
                          if not (r.metadata or {}).get("shed_reason")]
                shed = [r for r in resps
                        if (r.metadata or {}).get("shed_reason")]
                return served, shed, dt

            # unsaturated baseline: 1x capacity per burst
            served1 = shed1 = 0
            t1 = 0.0
            for i in range(3):
                s, sh, dt = await burst(cap, f"base{i}")
                served1 += len(s)
                shed1 += len(sh)
                t1 += dt
            assert shed1 == 0 and served1 == 3 * cap

            # sustained 5x overload
            served5 = shed5 = 0
            t5 = 0.0
            for i in range(3):
                s, sh, dt = await burst(5 * cap, f"load{i}")
                served5 += len(s)
                shed5 += len(sh)
                t5 += dt
                for r in sh:
                    assert r.status == Status.OVER_LIMIT
                    assert r.metadata["shed"] == "true"
                    assert r.metadata["shed_reason"] == SHED_QUEUE_FULL
                    assert r.error == ""  # in-band, not an error
            # the bounded queue NEVER exceeded its cap
            assert adm.pending_peak <= cap
            assert shed5 > 0 and served5 >= 3 * cap
            # no congestion collapse: goodput under 5x overload stays
            # comparable to unsaturated (target: within 10%; the CI bound
            # is looser because shared-runner wall clocks are noisy)
            goodput1 = served1 / t1
            goodput5 = served5 / t5
            assert goodput5 >= 0.5 * goodput1, (goodput1, goodput5)
            assert adm.pending == 0  # every admission slot released
        finally:
            inst.close()
    asyncio.run(body())


def test_no_batching_jumps_window_while_admission_saturated():
    async def body():
        inst = _instance(QoSConfig(max_pending=4))
        try:
            adm = inst.qos.admission
            adm.pending = adm.max_pending  # pin the batched lane shut
            shed = (await inst.get_rate_limits([_req("batched")]))[0]
            assert shed.metadata["shed_reason"] == SHED_QUEUE_FULL
            jumped = (await inst.get_rate_limits(
                [_req("urgent", behavior=Behavior.NO_BATCHING)]))[0]
            # the jump-the-window lane is not admission-gated: it serves
            assert not (jumped.metadata or {}).get("shed_reason")
            assert jumped.error == ""
            assert jumped.remaining == 999
            adm.pending = 0
        finally:
            inst.close()
    asyncio.run(body())


def test_health_check_reflects_liveness_and_saturation():
    async def body():
        inst = _instance(QoSConfig(max_pending=4))
        try:
            assert (await inst.health_check()).status == "healthy"
            inst.qos.admission.pending = 4
            h = await inst.health_check()
            assert h.status == "unhealthy"
            assert "saturated" in h.message
            inst.qos.admission.pending = 0
            # batcher fail-stop (lockstep dispatch failure) wins over the
            # last set_peers result
            inst.batcher._failed = True
            h = await inst.health_check()
            assert h.status == "unhealthy"
            assert "left the mesh" in h.message
            inst.batcher._failed = False
        finally:
            inst.close()
    asyncio.run(body())


def test_breaker_fallback_fail_open_and_fail_closed():
    async def body():
        inst = _instance(QoSConfig())
        try:
            r = _req("somekey")
            resp = await inst._breaker_fallback(r, "10.0.0.9:81", None)
            # fail-open: a real local decision, flagged non-authoritative
            assert resp.error == ""
            assert resp.metadata["degraded"] == "true"
            assert resp.metadata["non_authoritative"] == "true"
            assert resp.metadata["owner"] == "10.0.0.9:81"
            assert resp.remaining == 999
            # fail-closed sheds in-band with reason breaker_open
            inst.qos.conf.fail_open = False
            resp = await inst._breaker_fallback(r, "10.0.0.9:81", None)
            assert resp.metadata["shed_reason"] == SHED_BREAKER_OPEN
            assert inst.qos.admission.shed_counts[SHED_BREAKER_OPEN] == 1
        finally:
            inst.close()
    asyncio.run(body())


def test_grpc_deadline_sheds_with_metadata_on_wire():
    """gRPC deadline propagation end-to-end at the servicer layer: a
    context with ~no time remaining sheds, and shed_reason survives proto
    serialization."""
    from gubernator_tpu.server import _V1Servicer

    async def body():
        inst = _instance(QoSConfig(target_drain_latency=0.2))
        try:
            svc = _V1Servicer(inst)

            class Ctx:
                def time_remaining(self):
                    return 0.001  # cannot cover even one drain cycle

                async def abort(self, *a):  # pragma: no cover
                    raise AssertionError("abort not expected")

            data = pb.GetRateLimitsReq(requests=[pb.req_to_pb(
                _req("deadline-key"))]).SerializeToString()
            out = await svc.GetRateLimits(data, Ctx())
            resp = pb.GetRateLimitsResp.FromString(out).responses[0]
            assert resp.metadata["shed_reason"] == SHED_DEADLINE
            assert resp.status == int(Status.OVER_LIMIT)
        finally:
            inst.close()
    asyncio.run(body())


def test_adaptive_window_replaces_static_batch_limit():
    """The batcher's flush threshold follows the congestion window, not
    the static batch_limit cliff."""
    async def body():
        inst = _instance(QoSConfig(min_window=16, max_window=4096))
        try:
            b = inst.batcher
            assert b._window_limit() == min(b.behaviors.batch_limit, 4096)
            inst.qos.congestion._cwnd = 32.0
            assert b._window_limit() == 32
            inst.qos.congestion._cwnd = 1.0  # floor wins
            assert b._window_limit() == 16
        finally:
            inst.close()
    asyncio.run(body())


def test_qos_config_from_env(monkeypatch):
    monkeypatch.setenv("GUBER_QOS_MAX_PENDING", "123")
    monkeypatch.setenv("GUBER_QOS_TARGET_DRAIN_MS", "50")
    monkeypatch.setenv("GUBER_QOS_BREAKER_FAILURES", "7")
    monkeypatch.setenv("GUBER_QOS_FAIL_OPEN", "false")
    monkeypatch.setenv("GUBER_QOS_DEFAULT_DEADLINE_MS", "1500")
    c = config_from_env()
    assert c.qos.max_pending == 123
    assert c.qos.target_drain_latency == pytest.approx(0.05)
    assert c.qos.breaker_fail_threshold == 7
    assert c.qos.fail_open is False
    assert c.qos.default_deadline == pytest.approx(1.5)


def test_qos_metrics_exposed():
    async def body():
        inst = _instance(QoSConfig(max_pending=16))
        try:
            inst.qos.admission.record_shed(SHED_QUEUE_FULL)
            text = inst.metrics.expose().decode()
            assert "guber_qos_queue_depth" in text
            assert 'guber_qos_shed_total{reason="queue_full"}' in text
            assert "guber_qos_effective_window" in text
        finally:
            inst.close()
    asyncio.run(body())


# -------------------------------------------------------------- HTTP gateway


def test_http_gateway_shed_metadata_end_to_end():
    """Satellite: shed responses carry shed_reason metadata through the
    HTTP gateway's proto3-JSON mapping, for both queue_full (saturated
    admission) and deadline (X-Guber-Timeout-Ms header)."""
    from aiohttp.test_utils import TestClient, TestServer

    from gubernator_tpu.api.http_gateway import build_app

    async def body():
        inst = _instance(QoSConfig(max_pending=4, target_drain_latency=0.2))
        client = TestClient(TestServer(build_app(inst)))
        await client.start_server()
        try:
            payload = {"requests": [{
                "name": "http_qos", "uniqueKey": "acct:1", "hits": "1",
                "limit": "5", "duration": "60000"}]}
            # healthy: serves normally
            r = await client.post("/v1/GetRateLimits", json=payload)
            data = await r.json()
            assert "shedReason" not in str(data)
            # saturated admission: queue_full shed, in-band
            inst.qos.admission.pending = 4
            r = await client.post("/v1/GetRateLimits", json=payload)
            data = await r.json()
            md = data["responses"][0]["metadata"]
            assert md["shed_reason"] == "queue_full"
            assert md["shed"] == "true"
            assert data["responses"][0]["status"] == "OVER_LIMIT"
            inst.qos.admission.pending = 0
            # deadline header: 10us cannot cover a drain cycle estimate
            # (the estimate is what the healthy call above measured: on
            # the CPU a 64-lane drain can come in under a millisecond)
            r = await client.post("/v1/GetRateLimits", json=payload,
                                  headers={"X-Guber-Timeout-Ms": "0.01"})
            data = await r.json()
            assert (data["responses"][0]["metadata"]["shed_reason"]
                    == "deadline")
            # malformed header is a 400, not a silent default
            r = await client.post("/v1/GetRateLimits", json=payload,
                                  headers={"X-Guber-Timeout-Ms": "nan ms"})
            assert r.status == 400
        finally:
            await client.close()
            inst.close()
    asyncio.run(body())
