"""gRPC server hosting an Instance (V1 + PeersV1 services).

The reference takes a caller-owned *grpc.Server (config.go:30-31) and
registers onto it (gubernator.go:66-67); here the server wrapper owns a
grpc.aio server bound to one address, with per-RPC metrics equivalent to the
reference's stats-handler pipeline (prometheus.go:104-145).

The RPC bodies live in module-level serve_* functions taking (instance,
payload, context) so the frontdoor engine consumer (frontdoor.py) runs
LITERALLY the same code for records arriving over the shm ring as the
in-process servicers run for direct connections — byte-identical responses
in both serving modes by construction, not by parallel implementation.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

import grpc
from jax.profiler import TraceAnnotation

from gubernator_tpu.api import pb
from gubernator_tpu.api.grpc_api import add_peers_servicer, add_v1_servicer
from gubernator_tpu.api.types import Algorithm as _Algorithm
from gubernator_tpu.core.service import BatchTooLargeError, Instance
from gubernator_tpu.observability.tracing import TRACEPARENT

# Only RPCs at least this large take the native pipeline RPC lane; smaller
# ones go through the per-item path, whose requests aggregate with
# everything else pending in the next pipeline drain anyway (the reference's
# BATCHING default, peers.go:143-172).  ~32B/item on the wire, so this is
# roughly a 64-item batch.
FASTPATH_MIN_BYTES = 2048


def _client_id_from(context) -> Optional[str]:
    """Caller identity for the concurrency-lease book: the transport-level
    source ADDRESS (ports are ephemeral per connection, so identity sticks
    across reconnects; a forwarding peer's grants attribute to its host)."""
    peer = getattr(context, "peer", None)
    if not callable(peer):
        return None
    try:
        p = peer()
    except Exception:
        return None
    if not p:
        return None
    if p.startswith(("ipv4:", "ipv6:")):
        p = p.split(":", 1)[1].rsplit(":", 1)[0]
    return p or None


def _arm_lease_stream_close(inst: Instance, context,
                            client_id: Optional[str]) -> None:
    """Release a client's concurrency leases when its RPC is torn down
    before the response is delivered (gRPC cancel = the stream closed
    under us): the grants this RPC made never reached the holder, and a
    vanished holder cannot release them itself."""
    if client_id is None:
        return
    lease_conf = getattr(inst.conf, "leases", None)
    if lease_conf is not None and not lease_conf.release_on_stream_close:
        return
    add_cb = getattr(context, "add_done_callback", None)
    if not callable(add_cb):
        return
    loop = asyncio.get_running_loop()

    def _on_done(ctx, cid=client_id, loop=loop):
        cancelled = getattr(ctx, "cancelled", None)
        try:
            was = cancelled() if callable(cancelled) else False
        except Exception:
            was = False
        if was and inst.leases.holds(cid):
            loop.call_soon_threadsafe(
                lambda: loop.create_task(
                    inst.release_client_leases(cid,
                                               reason="stream_close")))

    try:
        add_cb(_on_done)
    except Exception:
        pass


def _traceparent_from(context) -> Optional[str]:
    """The caller's `traceparent` invocation-metadata entry, if any (the
    gRPC leg of W3C trace propagation — net/peers.py sets it)."""
    try:
        for k, v in context.invocation_metadata() or ():
            if k == TRACEPARENT:
                return v
    except Exception:
        return None
    return None


async def serve_get_rate_limits(inst: Instance, data: bytes,
                                context) -> bytes:
    """V1.GetRateLimits engine-side body: bytes in, response bytes out.
    `context` only needs time_remaining() and abort() (which must raise) —
    satisfied by both grpc.aio contexts and the frontdoor shim."""
    kind, val = await serve_get_rate_limits_inner(inst, data, context)
    if kind == "bytes":
        return val
    # the handler's synchronous half after its await, in the device trace
    with TraceAnnotation("guber_rpc_out"):
        return pb.GetRateLimitsResp(
            responses=[pb.resp_to_pb(r) for r in val]).SerializeToString()


def _parse_get_rate_limits(inst: Instance, data: bytes, context):
    """The Python path's synchronous half before its await: protobuf
    parse, the propagated deadline, request objects, caller identity.
    Returns (reqs, deadline, client_id), or None for malformed bytes.
    `guber_rpc_in` puts it into the device trace; it wraps no await (a
    wait is not work)."""
    with TraceAnnotation("guber_rpc_in"):
        try:
            request = pb.GetRateLimitsReq.FromString(data)
        except Exception:
            return None
        deadline = None
        if inst.qos is not None:
            remaining = None
            tr = getattr(context, "time_remaining", None)
            if callable(tr):
                remaining = tr()
            deadline = inst.qos.deadline_from_timeout(remaining)
        reqs = [pb.req_from_pb(r) for r in request.requests]
        client_id = _client_id_from(context)
        if any(r.algorithm == _Algorithm.CONCURRENCY for r in reqs):
            _arm_lease_stream_close(inst, context, client_id)
        return reqs, deadline, client_id


async def serve_get_rate_limits_inner(inst: Instance, data: bytes, context):
    """GetRateLimits body WITHOUT the final serialization: returns
    ("bytes", out) when the native RPC lane already encoded, or
    ("resps", [RateLimitResp]) from the Python path.  The frontdoor hub
    uses this directly so the response direction has ONE code path — it
    ships decision columns to the worker (which encodes in its own
    process) instead of serializing on the engine loop; the in-process
    server wraps it with the classic engine-side serialize above."""
    m = inst.metrics
    start = time.monotonic()
    # QoS: propagate the client's gRPC deadline into admission control,
    # and BYPASS the bytes-level native lane while the admission queue
    # is saturated — sheds must be decided per item on the Python path
    # so the response carries shed_reason metadata in-band
    qos_saturated = (inst.qos is not None
                     and inst.qos.admission.saturated)
    if not qos_saturated and len(data) >= FASTPATH_MIN_BYTES:
        # native RPC lane: C parse -> stacked compact dispatch -> C
        # encode (core/pipeline.py).  In cluster mode the C parser
        # classifies items per key against the installed ring and
        # forwards non-owned items to their peers; the drain re-checks
        # the gate on the engine thread, so a membership change that
        # races this RPC falls back to the full path below instead of
        # deciding keys this node does not own.  A mesh served by one
        # process takes the same lane in its lockstep form: staged now,
        # drained on the tick (submit_rpc answers None on a mesh of
        # several hosts, which routes per item)
        out = await inst.batcher.submit_rpc(data)
        if out is not None:
            m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start,
                          ok=True)
            return "bytes", out
    parsed = _parse_get_rate_limits(inst, data, context)
    if parsed is None:
        m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=False)
        await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                            "malformed GetRateLimitsReq")
    reqs, deadline, client_id = parsed
    try:
        resps = await inst.get_rate_limits(
            reqs, deadline=deadline, client_id=client_id)
    except BatchTooLargeError as e:
        m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=False)
        await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
    m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=True)
    return "resps", resps


async def serve_peer_rate_limits(inst: Instance, data: bytes,
                                 context) -> bytes:
    """PeersV1.GetPeerRateLimits engine-side body."""
    m = inst.metrics
    start = time.monotonic()
    if not inst.mesh_mode:
        # authoritative relay through the native lane: identical wire
        # shape to GetRateLimits, ring ignored (we are the owner for
        # whatever arrives, gubernator.go:210-227)
        out = await inst.batcher.submit_rpc(data, peer_mode=True)
        if out is not None:
            m.observe_rpc("/pb.gubernator.PeersV1/GetPeerRateLimits",
                          start, ok=True)
            return out
    try:
        request = pb.GetPeerRateLimitsReq.FromString(data)
    except Exception:
        m.observe_rpc("/pb.gubernator.PeersV1/GetPeerRateLimits", start,
                      ok=False)
        await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                            "malformed GetPeerRateLimitsReq")
    try:
        resps = await inst.get_peer_rate_limits(
            [pb.req_from_pb(r) for r in request.requests],
            client_id=_client_id_from(context))
    except BatchTooLargeError as e:
        m.observe_rpc("/pb.gubernator.PeersV1/GetPeerRateLimits", start,
                      ok=False)
        await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
    m.observe_rpc("/pb.gubernator.PeersV1/GetPeerRateLimits", start, ok=True)
    return pb.GetPeerRateLimitsResp(
        rate_limits=[pb.resp_to_pb(r) for r in resps]).SerializeToString()


async def serve_transfer_buckets(inst: Instance, data: bytes,
                                 context) -> bytes:
    """Bucket-migration import lane (state/migrate.py): bytes in
    (versioned JSON rows), ack bytes out."""
    from gubernator_tpu.state.migrate import MigrationError
    start = time.monotonic()
    m = inst.metrics
    try:
        ack = await inst.transfer_buckets(data)
    except MigrationError as e:
        m.observe_rpc("/pb.gubernator.PeersV1/TransferBuckets", start,
                      ok=False)
        await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
    except Exception as e:
        m.observe_rpc("/pb.gubernator.PeersV1/TransferBuckets", start,
                      ok=False)
        await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
    m.observe_rpc("/pb.gubernator.PeersV1/TransferBuckets", start,
                  ok=True)
    return ack


async def serve_register_globals(inst: Instance, request,
                                 context) -> "pb.RegisterGlobalsResp":
    start = time.monotonic()
    m = inst.metrics
    specs = [(s.key, s.limit, s.duration, int(s.algorithm))
             for s in request.specs]
    try:
        await inst.register_globals(specs)
    except Exception as e:
        m.observe_rpc("/pb.gubernator.PeersV1/RegisterGlobals", start,
                      ok=False)
        await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
    m.observe_rpc("/pb.gubernator.PeersV1/RegisterGlobals", start,
                  ok=True)
    return pb.RegisterGlobalsResp()


async def serve_apply_global_registration(
        inst: Instance, request,
        context) -> "pb.ApplyGlobalRegistrationResp":
    start = time.monotonic()
    m = inst.metrics
    specs = [(s.key, s.limit, s.duration, int(s.algorithm))
             for s in request.specs]
    try:
        await inst.apply_global_registration(
            specs, request.now, request.activate)
    except Exception as e:
        m.observe_rpc("/pb.gubernator.PeersV1/ApplyGlobalRegistration",
                      start, ok=False)
        await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
    m.observe_rpc("/pb.gubernator.PeersV1/ApplyGlobalRegistration",
                  start, ok=True)
    return pb.ApplyGlobalRegistrationResp()


async def serve_update_peer_globals(inst: Instance, request,
                                    context) -> "pb.UpdatePeerGlobalsResp":
    from gubernator_tpu.api.types import UpdatePeerGlobal
    start = time.monotonic()
    ups = [
        UpdatePeerGlobal(
            key=g.key,
            status=pb.resp_from_pb(g.status),
            algorithm=g.algorithm,
            duration=g.duration,
        )
        for g in request.globals
    ]
    await inst.update_peer_globals(ups)
    inst.metrics.observe_rpc(
        "/pb.gubernator.PeersV1/UpdatePeerGlobals", start, ok=True)
    return pb.UpdatePeerGlobalsResp()


class _V1Servicer:
    def __init__(self, instance: Instance):
        self.instance = instance

    async def GetRateLimits(self, data: bytes, context):
        tracer = self.instance.tracer
        if tracer is None or not tracer.enabled:
            return await serve_get_rate_limits(self.instance, data, context)
        with tracer.start_trace("rpc", _traceparent_from(context)):
            return await serve_get_rate_limits(self.instance, data, context)

    async def HealthCheck(self, request, context):
        # the reference's stats-handler observes EVERY RPC, HealthCheck
        # included (prometheus.go:104-137)
        start = time.monotonic()
        h = await self.instance.health_check()
        self.instance.metrics.observe_rpc(
            "/pb.gubernator.V1/HealthCheck", start, ok=True)
        return pb.HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count)


class _PeersServicer:
    def __init__(self, instance: Instance):
        self.instance = instance

    async def GetPeerRateLimits(self, data: bytes, context):
        # owner-side root of a forwarded request: the traceparent metadata
        # the forwarding node attached stitches this node's spans into the
        # SAME trace (one trace across owner and non-owner)
        tracer = self.instance.tracer
        if tracer is None or not tracer.enabled:
            return await serve_peer_rate_limits(self.instance, data, context)
        with tracer.start_trace("peer_rpc", _traceparent_from(context)):
            return await serve_peer_rate_limits(self.instance, data, context)

    async def TransferBuckets(self, data: bytes, context):
        return await serve_transfer_buckets(self.instance, data, context)

    async def RegisterGlobals(self, request, context):
        return await serve_register_globals(self.instance, request, context)

    async def ApplyGlobalRegistration(self, request, context):
        return await serve_apply_global_registration(
            self.instance, request, context)

    async def UpdatePeerGlobals(self, request, context):
        return await serve_update_peer_globals(
            self.instance, request, context)


class GrpcServer:
    def __init__(self, instance: Instance, address: str,
                 max_message_mb: int = 1,
                 reuse_port: Optional[bool] = None):
        self.instance = instance
        # 1MB max receive, like the reference (cmd/gubernator/main.go:59-61)
        options = [
            ("grpc.max_receive_message_length", max_message_mb * 1024 * 1024),
        ]
        if reuse_port is not None:
            # frontdoor workers set this explicitly: True shards one
            # listening port across worker processes (kernel-level accept
            # balancing), False forces distinct per-worker ports
            options.append(("grpc.so_reuseport", 1 if reuse_port else 0))
        self.server = grpc.aio.server(options=options)
        add_v1_servicer(self.server, _V1Servicer(instance))
        add_peers_servicer(self.server, _PeersServicer(instance))
        self.port = self.server.add_insecure_port(address)
        host = address.rsplit(":", 1)[0]
        self.address = f"{host}:{self.port}"

    async def start(self) -> None:
        await self.server.start()

    async def stop(self, grace: Optional[float] = 1.0) -> None:
        await self.server.stop(grace)
