"""HTTP JSON gateway: REST access to the same service.

Replaces the reference's grpc-gateway reverse proxy
(gubernator.pb.gw.go:59-148, wired in cmd/gubernator/main.go:107-116) with a
thin aiohttp app speaking the same proto3-JSON mapping (field names
camelCased, enums as strings — via google.protobuf.json_format, the same
conversion rules grpc-gateway uses):

  POST /v1/GetRateLimits   body: GetRateLimitsReq JSON
  GET  /v1/HealthCheck
  GET  /metrics            prometheus text format (main.go:113-116)
  GET  /v1/admin/debug     runtime introspection snapshot (JSON)
  GET  /v1/admin/topk      traffic analytics: hot-key top-K + tenants (JSON)
  POST /v1/admin/profile   arm a jax.profiler capture of the next N drains

Unlike the gateway in the reference (which dials the node's own gRPC port
over TCP), this calls the Instance in-process.
"""

from __future__ import annotations

import time

from aiohttp import web
from google.protobuf import json_format

from gubernator_tpu.api import pb
from gubernator_tpu.core.service import BatchTooLargeError, Instance
from gubernator_tpu.observability import (
    CONTENT_TYPE_LATEST,
    build_debug_snapshot,
)
from gubernator_tpu.observability.tracing import TRACEPARENT


def build_app(instance: Instance) -> web.Application:
    # The reference's gateway dials its own gRPC port, so gateway traffic
    # flows through the gRPC stats handler and is counted per-RPC
    # (prometheus.go:104-137).  This gateway is in-process, so the handlers
    # observe the same metric names themselves.
    async def get_rate_limits(request: web.Request) -> web.Response:
        # HTTP leg of trace propagation: continue an incoming traceparent
        # (or sample a new root) and echo the context back so callers can
        # correlate their logs with ours
        tracer = instance.tracer
        if tracer is None or not tracer.enabled:
            return await _get_rate_limits(request)
        with tracer.start_trace(
                "http", request.headers.get(TRACEPARENT)) as root:
            resp = await _get_rate_limits(request)
            if root.ctx is not None:
                resp.headers[TRACEPARENT] = root.ctx.traceparent()
            return resp

    async def _get_rate_limits(request: web.Request) -> web.Response:
        m = instance.metrics
        start = time.monotonic()
        ok = False
        try:
            try:
                body = await request.text()
                msg = json_format.Parse(body, pb.GetRateLimitsReq())
            except json_format.ParseError as e:
                return web.json_response({"error": str(e), "code": 3},
                                         status=400)
            # QoS deadline propagation: X-Guber-Timeout-Ms carries the
            # client's remaining budget (grpc-gateway's grpc-timeout
            # analog); admission sheds what cannot be served in time
            deadline = None
            if instance.qos is not None:
                timeout_ms = request.headers.get("X-Guber-Timeout-Ms")
                timeout_s = None
                if timeout_ms:
                    try:
                        timeout_s = float(timeout_ms) / 1000.0
                    except ValueError:
                        return web.json_response(
                            {"error": "invalid X-Guber-Timeout-Ms header",
                             "code": 3}, status=400)
                deadline = instance.qos.deadline_from_timeout(timeout_s)
            try:
                resps = await instance.get_rate_limits(
                    [pb.req_from_pb(r) for r in msg.requests],
                    deadline=deadline)
            except BatchTooLargeError as e:
                return web.json_response({"error": str(e), "code": 11},
                                         status=400)
            ok = True
            out = pb.GetRateLimitsResp(
                responses=[pb.resp_to_pb(r) for r in resps])
            return web.json_response(
                json_format.MessageToDict(out,
                                          preserving_proto_field_name=False))
        finally:
            # every RPC is observed, including unexpected 500s — during an
            # incident the failure rate must show up in the counters
            m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=ok)

    async def health_check(request: web.Request) -> web.Response:
        start = time.monotonic()
        h = await instance.health_check()
        instance.metrics.observe_rpc(
            "/pb.gubernator.V1/HealthCheck", start, ok=True)
        msg = pb.HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count)
        return web.json_response(
            json_format.MessageToDict(msg, preserving_proto_field_name=False))

    async def metrics(request: web.Request) -> web.Response:
        # the full prometheus content type, charset parameter included —
        # aiohttp's content_type kwarg rejects parameters, so it goes in
        # as a raw header
        return web.Response(
            body=instance.metrics.expose(),
            headers={"Content-Type": CONTENT_TYPE_LATEST},
        )

    # state-lifecycle admin plane (cmd/cli.py snapshot/restore): the
    # snapshot blob travels as-is — it is already versioned + checksummed
    async def admin_snapshot(request: web.Request) -> web.Response:
        data = await instance.export_snapshot_bytes(
            layout=request.query.get("layout", "auto"))
        return web.Response(body=data,
                            content_type="application/octet-stream")

    async def admin_restore(request: web.Request) -> web.Response:
        from gubernator_tpu.state.snapshot import SnapshotError
        data = await request.read()
        rebase = request.query.get("rebase_to")
        try:
            n = await instance.restore_snapshot_bytes(
                data, rebase_to=int(rebase) if rebase else None)
        except SnapshotError as e:
            return web.json_response({"error": str(e), "code": 3},
                                     status=400)
        return web.json_response({"restoredKeys": n})

    async def admin_debug(request: web.Request) -> web.Response:
        return web.json_response(build_debug_snapshot(instance))

    async def admin_topk(request: web.Request) -> web.Response:
        # hot-key view of the traffic analytics (cmd/cli.py `top`):
        # 404 when the subsystem is off so the CLI can say why
        an = getattr(instance, "analytics", None)
        if an is None:
            return web.json_response(
                {"error": "analytics disabled (set GUBER_ANALYTICS=1)",
                 "code": 12}, status=404)
        try:
            n = int(request.query.get("n", an.conf.topk))
        except ValueError:
            return web.json_response({"error": "invalid n", "code": 3},
                                     status=400)
        snap = an.snapshot()
        snap["topk"] = an.topk_snapshot(n)
        return web.json_response(snap)

    async def admin_profile(request: web.Request) -> web.Response:
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:
                return web.json_response(
                    {"error": "malformed JSON body", "code": 3}, status=400)
        drains = body.get("drains", request.query.get("drains", 1))
        trace_dir = body.get("dir", request.query.get("dir", ""))
        try:
            drains = int(drains)
        except (TypeError, ValueError):
            return web.json_response({"error": "invalid drains", "code": 3},
                                     status=400)
        out = instance.batcher.profile.arm(drains, trace_dir)
        # already-armed is a conflict, not a new capture
        return web.json_response(out,
                                 status=200 if out.get("armed") else 409)

    async def admin_kernels(request: web.Request) -> web.Response:
        """Measured ms/window per serving arm, the rolling kernel table,
        and the window clock (observability/devprof.py).  `?measure=1`
        runs the arm-scoped measured probe inline (seconds of compile on a
        cold process; 409 while a capture is armed)."""
        import asyncio as _aio
        devprof = getattr(instance, "devprof", None)
        if devprof is None:
            return web.json_response(
                {"error": "devprof unavailable", "code": 12}, status=501)
        q = request.query
        measured = None
        if q.get("measure") in ("1", "true"):
            if instance.batcher.profile.armed:
                return web.json_response(
                    {"error": "capture already in progress", "code": 10},
                    status=409)
            try:
                iters = max(1, int(q.get("iters", 2)))
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": "invalid iters", "code": 3}, status=400)
            from gubernator_tpu.observability.devprof import (
                measure_probe_arms,
            )
            measured = await _aio.get_running_loop().run_in_executor(
                None, lambda: measure_probe_arms(iters=iters,
                                                 table=devprof.table))
        out = devprof.kernels_snapshot()
        if measured is not None:
            out["measured"] = measured["arms"]
            for arm, row in measured["arms"].items():
                out["arms"][arm] = {
                    "measured_ms_per_window": row["measured_ms_per_window"]}
        return web.json_response(out)

    # a full-arena snapshot blob is tens of MB at default capacity — far
    # past aiohttp's 1 MiB default body cap, which would 413 every real
    # admin restore
    app = web.Application(client_max_size=1 << 30)
    app.router.add_post("/v1/GetRateLimits", get_rate_limits)
    app.router.add_get("/v1/HealthCheck", health_check)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/v1/admin/snapshot", admin_snapshot)
    app.router.add_post("/v1/admin/restore", admin_restore)
    app.router.add_get("/v1/admin/debug", admin_debug)
    app.router.add_get("/v1/admin/topk", admin_topk)
    app.router.add_post("/v1/admin/profile", admin_profile)
    app.router.add_get("/v1/admin/kernels", admin_kernels)
    return app


class HttpGateway:
    def __init__(self, instance: Instance, address: str):
        self.app = build_app(instance)
        host, _, port = address.rpartition(":")
        self.host = host or "127.0.0.1"
        self.port = int(port)
        self._runner: web.AppRunner | None = None

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
