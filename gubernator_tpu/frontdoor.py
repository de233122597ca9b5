"""Multi-process front door: SO_REUSEPORT-sharded gRPC acceptors with
shared-memory columnar hand-off to the engine process.

One GIL-bound asyncio process doing accept/parse/encode caps e2e serving
far below what the host pipeline can drain (~566k/s served vs ~1.34M/s
drained, BASELINE.md round 6).  This module splits serving into N
frontend WORKER processes and the one ENGINE process:

  * every worker binds the SAME public port via SO_REUSEPORT (the kernel
    load-balances accepted connections across workers); when the kernel
    or a port collision refuses that, a worker degrades to its own
    ephemeral port, published in the status block for per-worker-port
    discovery (surfaced in `cli debug`);
  * each worker runs its own event loop and parses GetRateLimitsReq
    bytes ONCE, in C (native frontdoor_parse_req), straight into packed
    request columns inside a shared-memory slab (core/shm_ring.py) — the
    request never re-crosses the process boundary as Python objects;
  * the engine keeps sole ownership of the device, the lockstep drain,
    GLOBAL sync, and the arena.  COLS records ride the pipeline as
    ColsJobs; everything else (small RPCs, full-path requests, the whole
    PeersV1 plane) ships as RAW bytes and runs LITERALLY the same
    server.py serve_* coroutines the single-process servicers run —
    byte-identical decisions and responses by construction;
  * the RESPONSE direction mirrors the request one
    (GUBER_FRONTDOOR_ENCODE=worker, the default): the engine's completion
    writes packed DECISION columns (status/limit/remaining/reset + shed
    flag) into the completion-ring slab and each WORKER serializes the
    protobuf in its own process (native frontdoor_encode_resp, pb
    fallback) — protobuf encode never runs on the engine loop, for COLS
    and RAW/shed GetRateLimits paths alike.  Responses that cannot be
    expressed as columns (error strings, exotic metadata) fall back to
    engine-side serialization, counted in encode_fallbacks;
  * workers coalesce wire reads (GUBER_FRONTDOOR_BATCH_READS): RPCs that
    land in the same event-loop tick parse into ONE slab as a
    KIND_BATCH_COLS record — one ring publish, one pipeline job — and
    the completion columns split back per-RPC by the counts region;
  * workers answer HealthCheck locally from the engine-heartbeated
    status block (a health probe never queues behind a saturated engine
    loop) and shed in-band — no cross-process round-trip — on the shared
    draining/saturation flags and on ring exhaustion (shed_reason
    ring_full).  The saturation shed is deliberately coarser than the
    engine's per-item admission (which may still admit while saturated):
    a transient divergence under overload, traded for the CONCUR-style
    zero-round-trip shed; draining sheds match the single-process path
    exactly.

Workers import jax only as a side effect of the package __init__ (x64
flag); they pin jax_platforms=cpu before any possible backend init so
the engine's accelerator is never touched from a worker process.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import multiprocessing
import os
import threading
import time
from typing import Dict, List, Optional

import grpc

import numpy as np

from gubernator_tpu.core import shm_ring
from gubernator_tpu.core.shm_ring import (
    FLAG_COLS_OK,
    FLAG_DRAINING,
    FLAG_SATURATED,
    KIND_APPLY_GREG,
    KIND_BATCH_COLS,
    KIND_COLS,
    KIND_PEER_RL,
    KIND_RAW,
    KIND_REGISTER,
    KIND_TRANSFER,
    KIND_UPDATE_GLOBALS,
    MAX_BATCH_RPCS,
    SHED_CODE_REASONS,
    SHED_REASON_CODES,
    FrontdoorStatus,
    WorkerChannel,
)

log = logging.getLogger("gubernator.frontdoor")

_PREFIX_SEQ = itertools.count()

_INTERNAL = 13  # grpc.StatusCode.INTERNAL.value[0]
_CODE_BY_VALUE = {c.value[0]: c for c in grpc.StatusCode}


class FrontdoorAbort(Exception):
    """Engine-side analog of grpc context.abort(): carries the status the
    worker must abort the client RPC with."""

    def __init__(self, code: grpc.StatusCode, message: str):
        super().__init__(message)
        self.code = code
        self.message = message


class _EngineContext:
    """The slice of grpc.aio's ServicerContext the server.py serve_*
    bodies actually touch, backed by a shm record."""

    def __init__(self, deadline: float = 0.0):
        self._deadline = deadline  # absolute time.monotonic(); 0 = none

    def time_remaining(self) -> Optional[float]:
        if not self._deadline:
            return None
        return max(0.0, self._deadline - time.monotonic())

    def invocation_metadata(self):
        return ()

    async def abort(self, code: grpc.StatusCode, message: str = ""):
        raise FrontdoorAbort(code, message)


# =========================================================== worker process


class _Worker:
    """Per-process state of one frontdoor worker (runs in the spawned
    child; never imports the engine)."""

    def __init__(self, worker_id: int, chan: WorkerChannel,
                 status: FrontdoorStatus, fastpath_min: int,
                 encode_mode: str = "worker", batch_reads: int = 8):
        self.worker_id = worker_id
        self.chan = chan
        self.status = status
        self.fastpath_min = fastpath_min
        self.encode_mode = encode_mode
        # coalescing implies worker-side encode: a batch completion is
        # columnar (or per-RPC parts), never one engine-encoded buffer
        self.batch_reads = batch_reads if encode_mode == "worker" else 0
        from gubernator_tpu import native
        from gubernator_tpu.api import pb, types
        self.native = native
        self.native_ok = native.available()
        self.pb = pb
        self.types = types
        self._req_id = 0
        self._waiters: Dict[int, asyncio.Future] = {}
        self._batches: Dict[int, tuple] = {}  # rid -> (futs, counts)
        self._pending: List[tuple] = []       # (data, fut, deadline, tp)
        self._ebuf: Optional[np.ndarray] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    def _bump(self, field: int, n: int = 1) -> None:
        self.status.bump_w(self.worker_id, field, n)

    def traceparent(self, context) -> Optional[tuple]:
        """The RPC's sampled W3C traceparent as shm trace-region ints
        (trace_id_hi, trace_id_lo, span_id), or None when absent,
        malformed, or unsampled.  Parsed once HERE, in the worker — the
        engine only ever sees the three fixed-width words (the front-door
        trace blackout fix)."""
        md = getattr(context, "invocation_metadata", None)
        if not callable(md):
            return None
        raw = None
        for k, v in (md() or ()):
            if k == "traceparent":
                raw = v if isinstance(v, str) else \
                    bytes(v).decode("ascii", "replace")
                break
        if not raw:
            return None
        from gubernator_tpu.observability.tracing import parse_traceparent
        ctx = parse_traceparent(raw)
        if ctx is None:
            return None
        return (int(ctx.trace_id[:16], 16), int(ctx.trace_id[16:], 16),
                int(ctx.span_id, 16))

    # -------------------------------------------------------- response encode

    def encode_cols(self, st, li, re, rs, fl, off: int, n: int) -> bytes:
        """Serialize n decisions starting at column offset `off` — the
        worker-side response encode.  Native lane first (byte-compatible
        with the engine's fastpath_encode_w), pb objects as the fallback
        (byte-identical to the classic engine serialization, same
        runtime)."""
        if self.native_ok:
            need = n * 96 + 64
            if self._ebuf is None or self._ebuf.nbytes < need:
                self._ebuf = np.empty(max(need, 1 << 16), np.uint8)
            m = self.native.frontdoor_encode_resp(
                st[off:off + n], li[off:off + n], re[off:off + n],
                rs[off:off + n], fl[off:off + n], n, self._ebuf)
            if m >= 0:
                return bytes(self._ebuf[:m])
        pb, types = self.pb, self.types
        resps = []
        for i in range(off, off + n):
            code = int(fl[i])
            md = ({"shed": "true", "shed_reason": SHED_CODE_REASONS[code]}
                  if code else {})
            resps.append(types.RateLimitResp(
                status=int(st[i]), limit=int(li[i]),
                remaining=int(re[i]), reset_time=int(rs[i]), metadata=md))
        return pb.GetRateLimitsResp(responses=[
            pb.resp_to_pb(r) for r in resps]).SerializeToString()

    # ------------------------------------------------------------- transport

    async def roundtrip(self, slot: int, req_id: int, context) -> bytes:
        """Submit a written slab and await its completion; abort the
        client RPC when the engine said to."""
        fut = self._loop.create_future()
        self._waiters[req_id] = fut
        self.chan.submit(slot)
        try:
            status, payload = await fut
        finally:
            self._waiters.pop(req_id, None)
        if status != 0:
            await context.abort(
                _CODE_BY_VALUE.get(status, grpc.StatusCode.INTERNAL),
                payload.decode("utf-8", "replace"))
        self._bump(shm_ring.W_RPCS)
        return payload

    async def poll_loop(self) -> None:
        """Completion pump: the only consumer of the completion ring.
        Columnar completions (length < 0) are ENCODED here, while the
        worker still owns the slab; the slot is freed only after its
        response has been materialized."""
        while True:
            comps = self.chan.poll_completions_raw()
            if comps:
                for slot, req_id, status, length in comps:
                    try:
                        self._deliver(slot, req_id, status, length)
                    finally:
                        self.chan.free_slot(slot)
                await asyncio.sleep(0)
            else:
                await asyncio.sleep(0.0005)

    def _deliver(self, slot: int, req_id: int, status: int,
                 length: int) -> None:
        batch = self._batches.pop(req_id, None)
        if batch is not None:
            self._deliver_batch(batch, slot, status, length)
            return
        fut = self._waiters.pop(req_id, None)
        if fut is None or fut.done():
            return
        if length < 0:  # decision columns: worker-side encode
            n = -length
            st, li, re, rs, fl = self.chan.resp_views(slot)
            payload = self.encode_cols(st, li, re, rs, fl, 0, n)
            self._bump(shm_ring.W_ENCODES)
            fut.set_result((0, payload))
        else:
            if status == 0:
                self._bump(shm_ring.W_ENC_FALLBACK)
            fut.set_result((status, bytes(self.chan.slab(slot)[:length])))

    def _deliver_batch(self, batch: tuple, slot: int, status: int,
                       length: int) -> None:
        futs, counts = batch
        if status != 0:  # abort fans out to every coalesced RPC
            payload = bytes(self.chan.slab(slot)[:length])
            for f in futs:
                if not f.done():
                    f.set_result((status, payload))
            return
        if length < 0:  # concatenated decision columns, split by counts
            st, li, re, rs, fl = self.chan.resp_views(slot)
            off = 0
            for f, cnt in zip(futs, counts):
                payload = self.encode_cols(st, li, re, rs, fl, off, cnt)
                off += cnt
                self._bump(shm_ring.W_ENCODES)
                if not f.done():
                    f.set_result((0, payload))
        else:  # bytes-form fallback: per-RPC serialized parts
            lengths, view = self.chan.batch_payload(slot, len(futs), length)
            off = 0
            for f, ln in zip(futs, lengths):
                payload = bytes(view[off:off + ln])
                off += ln
                self._bump(shm_ring.W_ENC_FALLBACK)
                if not f.done():
                    f.set_result((0, payload))

    def flush_batch(self) -> None:
        """Coalesce this tick's pending GetRateLimits RPCs into ONE
        KIND_BATCH_COLS slab + ONE ring publish.  RPCs the C parser
        rejects (or that overflow the slab) resolve to None and rerun
        the classic single-record path in their handler."""
        pending = self._pending
        self._pending = []
        if not pending:
            return
        if len(pending) == 1:  # nothing to amortize
            if not pending[0][1].done():
                pending[0][1].set_result(None)
            return
        slot = self.chan.alloc()
        if slot is None:  # handlers shed ring_full on their own alloc
            for _, fut, _, _ in pending:
                if not fut.done():
                    fut.set_result(None)
            return
        kb, ke, hi, li, du, al, nl = self.chan.cols_views(slot)
        counts: List[int] = []
        futs: List[asyncio.Future] = []
        tps: List[Optional[tuple]] = []
        singles: List[asyncio.Future] = []
        base, koff = 0, 0
        dmin = 0.0
        for data, fut, deadline, tp in pending:
            n = -1
            if base < self.chan.cap_items and len(counts) < MAX_BATCH_RPCS:
                n = self.native.frontdoor_parse_req(
                    data, kb[koff:], ke[base:], hi[base:], li[base:],
                    du[base:], al[base:], nl[base:],
                    self.chan.cap_items - base)
            if n <= 0:
                singles.append(fut)
                continue
            if koff:
                ke[base:base + n] += koff
            koff = int(ke[base + n - 1])
            base += n
            counts.append(n)
            futs.append(fut)
            tps.append(tp)
            if deadline and (dmin == 0.0 or deadline < dmin):
                dmin = deadline
        # ONE trace region per record: the first traced member's context
        # rides the slab; every other traced member is an honest drop
        # (trace_drops per worker in /v1/admin/debug, frontdoor.per_worker)
        carried = next((t for t in tps if t is not None), None)
        extra = sum(1 for t in tps if t is not None) - (1 if carried else 0)
        if extra > 0:
            self._bump(shm_ring.W_TRACE_DROPS, extra)
        if not counts:
            self.chan.unalloc(slot)
        elif len(counts) == 1:  # degenerate: a plain COLS record
            rid = self.next_id()
            if carried is not None:
                self.chan.set_trace(slot, *carried)
            else:
                self.chan.clear_trace(slot)
            self.chan.commit_cols(slot, rid, counts[0], koff, dmin)
            self._waiters[rid] = futs[0]
            self.chan.submit(slot)
        else:
            rid = self.next_id()
            if carried is not None:
                self.chan.set_trace(slot, *carried)
            else:
                self.chan.clear_trace(slot)
            self.chan.commit_batch(slot, rid, counts, koff, dmin)
            self._batches[rid] = (futs, counts)
            self._bump(shm_ring.W_BATCH_FLUSHES)
            self._bump(shm_ring.W_BATCH_RPCS, len(counts))
            self.chan.submit(slot)
        for fut in singles:
            if not fut.done():
                fut.set_result(None)

    def next_id(self) -> int:
        self._req_id += 1
        return self._req_id

    def shed_bytes(self, pb, data: bytes, reason: str):
        """In-band worker-local shed: the same shed_response items the
        engine's admission controller would build, without the ring trip."""
        from gubernator_tpu.qos.admission import shed_response
        try:
            req = pb.GetRateLimitsReq.FromString(data)
        except Exception:
            return None  # caller aborts INVALID_ARGUMENT
        self._bump(shm_ring.W_SHEDS, max(1, len(req.requests)))
        return pb.GetRateLimitsResp(responses=[
            pb.resp_to_pb(shed_response(r, reason)) for r in req.requests
        ]).SerializeToString()


class _WorkerV1:
    def __init__(self, w: _Worker):
        self.w = w
        from gubernator_tpu.api import pb
        self.pb = pb

    async def GetRateLimits(self, data: bytes, context):
        from gubernator_tpu.qos.admission import (SHED_DRAINING,
                                                  SHED_QUEUE_FULL,
                                                  SHED_RING_FULL)
        w = self.w
        st = w.status
        reason = None
        slot = None
        use_batch = (w.batch_reads > 1 and w.native_ok
                     and st.flag(FLAG_COLS_OK))
        if st.flag(FLAG_DRAINING):
            reason = SHED_DRAINING
        elif st.flag(FLAG_SATURATED):
            reason = SHED_QUEUE_FULL
        elif not use_batch:  # batching defers alloc to the flush
            slot = w.chan.alloc()
            if slot is None:
                # every slab in flight: the producer-side stall signal
                w._bump(shm_ring.W_STALLS)
                reason = SHED_RING_FULL
        if reason is not None:
            out = w.shed_bytes(self.pb, data, reason)
            if out is None:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    "malformed GetRateLimitsReq")
            return out
        deadline = 0.0
        tr = getattr(context, "time_remaining", None)
        if callable(tr):
            rem = tr()
            if rem is not None:
                deadline = time.monotonic() + rem
        tp = w.traceparent(context)
        if use_batch:
            # batched wire reads: park this RPC for the tick's flush —
            # RPCs of any size coalesce into one slab + one publish (the
            # COLS size floor does not apply: a batch of small RPCs IS a
            # big columnar record).  None = the parser rejected it (or
            # the batch filled); rerun the classic single path below.
            fut = w._loop.create_future()
            w._pending.append((data, fut, deadline, tp))
            if len(w._pending) == 1:
                w._loop.call_soon(w.flush_batch)
            elif len(w._pending) >= min(w.batch_reads, MAX_BATCH_RPCS):
                w.flush_batch()
            res = await fut
            if res is not None:
                status, payload = res
                if status != 0:
                    await context.abort(
                        _CODE_BY_VALUE.get(status,
                                           grpc.StatusCode.INTERNAL),
                        payload.decode("utf-8", "replace"))
                w._bump(shm_ring.W_RPCS)
                return payload
            slot = w.chan.alloc()
            if slot is None:
                w._bump(shm_ring.W_STALLS)
                out = w.shed_bytes(self.pb, data, SHED_RING_FULL)
                if out is None:
                    await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                        "malformed GetRateLimitsReq")
                return out
        rid = w.next_id()
        if (w.native_ok and st.flag(FLAG_COLS_OK)
                and len(data) >= w.fastpath_min):
            # the zero-copy lane: C-parse the request columns STRAIGHT
            # into the shm slab.  Any rejection (full-path behaviors,
            # range fallbacks, malformed bytes, oversize) ships RAW so
            # the engine decides exactly like the single-process path.
            kb, ke, hi, li, du, al, nl = w.chan.cols_views(slot)
            n = w.native.frontdoor_parse_req(data, kb, ke, hi, li, du,
                                             al, nl, w.chan.cap_items)
            if n > 0:
                if tp is not None:
                    w.chan.set_trace(slot, *tp)
                else:
                    w.chan.clear_trace(slot)
                w.chan.commit_cols(slot, rid, n, int(ke[n - 1]), deadline)
                return await w.roundtrip(slot, rid, context)
        if tp is not None:
            # RAW records carry the original request bytes, not the trace
            # region — the caller's trace cannot follow this record
            w._bump(shm_ring.W_TRACE_DROPS)
        if not w.chan.write_raw(slot, KIND_RAW, rid, data, deadline):
            w.chan.unalloc(slot)
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                "request exceeds shm slab")
        return await w.roundtrip(slot, rid, context)

    async def HealthCheck(self, request, context):
        # served ENTIRELY worker-local from the engine-heartbeated status
        # block: a health probe never shares the saturated engine loop
        # (the thundering-herd p99 fix)
        w = self.w
        w._bump(shm_ring.W_HEALTHCHECKS)
        status, message, peer_count = w.status.health()
        if w.status.heartbeat_age() > 15.0:
            status, message = 1, "engine heartbeat stale"
        return self.pb.HealthCheckResp(
            status="healthy" if status == 0 else "unhealthy",
            message=message, peer_count=peer_count)


class _WorkerPeers:
    def __init__(self, w: _Worker):
        self.w = w
        from gubernator_tpu.api import pb
        self.pb = pb

    async def _raw(self, kind: int, data: bytes, context) -> bytes:
        w = self.w
        slot = w.chan.alloc()
        if slot is None:
            w._bump(shm_ring.W_STALLS)
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                "frontdoor ring full")
        rid = w.next_id()
        deadline = 0.0
        tr = getattr(context, "time_remaining", None)
        if callable(tr):
            rem = tr()
            if rem is not None:
                deadline = time.monotonic() + rem
        if not w.chan.write_raw(slot, kind, rid, data, deadline):
            w.chan.unalloc(slot)
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                "request exceeds shm slab")
        return await w.roundtrip(slot, rid, context)

    async def GetPeerRateLimits(self, data: bytes, context):
        return await self._raw(KIND_PEER_RL, data, context)

    async def TransferBuckets(self, data: bytes, context):
        return await self._raw(KIND_TRANSFER, data, context)

    async def RegisterGlobals(self, request, context):
        out = await self._raw(KIND_REGISTER, request.SerializeToString(),
                              context)
        return self.pb.RegisterGlobalsResp.FromString(out)

    async def ApplyGlobalRegistration(self, request, context):
        out = await self._raw(KIND_APPLY_GREG, request.SerializeToString(),
                              context)
        return self.pb.ApplyGlobalRegistrationResp.FromString(out)

    async def UpdatePeerGlobals(self, request, context):
        out = await self._raw(KIND_UPDATE_GLOBALS,
                              request.SerializeToString(), context)
        return self.pb.UpdatePeerGlobalsResp.FromString(out)


async def _worker_amain(worker_id: int, prefix: str, slots: int,
                        slab_bytes: int, listen_host: str, port_hint: int,
                        fastpath_min: int, encode_mode: str = "worker",
                        batch_reads: int = 8) -> None:
    from gubernator_tpu.api.grpc_api import (add_peers_servicer,
                                             add_v1_servicer)
    chan = WorkerChannel.attach(f"{prefix}_r{worker_id}", slots, slab_bytes)
    status = FrontdoorStatus.attach(f"{prefix}_st",
                                    workers=port_hint_workers(prefix))
    w = _Worker(worker_id, chan, status, fastpath_min,
                encode_mode=encode_mode, batch_reads=batch_reads)
    w._loop = asyncio.get_running_loop()

    server = grpc.aio.server(options=[
        ("grpc.max_receive_message_length", 1024 * 1024),
        ("grpc.so_reuseport", 1),
    ])
    add_v1_servicer(server, _WorkerV1(w))
    add_peers_servicer(server, _WorkerPeers(w))

    if worker_id == 0:
        port = server.add_insecure_port(f"{listen_host}:{port_hint}")
    else:
        # wait for worker 0 to publish the shared port, then join it via
        # SO_REUSEPORT; a refused bind degrades to an own ephemeral port
        p0 = 0
        for _ in range(300):
            p0 = status.get_w(0, shm_ring.W_PORT)
            if p0:
                break
            await asyncio.sleep(0.05)
        port = server.add_insecure_port(f"{listen_host}:{p0}") if p0 else 0
        if port == 0:
            port = server.add_insecure_port(f"{listen_host}:0")
    if port == 0:
        log.error("frontdoor worker %d could not bind", worker_id)
        return
    await server.start()
    status.set_w(worker_id, shm_ring.W_PORT, port)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    import signal as _signal
    for sig in (_signal.SIGINT, _signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):
            pass
    poller = asyncio.create_task(w.poll_loop())

    ppid = os.getppid()
    while not stop.is_set():
        try:
            await asyncio.wait_for(stop.wait(), timeout=1.0)
        except asyncio.TimeoutError:
            pass
        # orphan guard: the engine died without SIGTERMing us
        if os.getppid() != ppid or w.status.heartbeat_age() > 30.0:
            break
    poller.cancel()
    await server.stop(grace=0.25)
    chan.close()
    status.close()


def port_hint_workers(prefix: str) -> int:
    """Worker count is encoded in the segment prefix by the hub so the
    status block can be attached without an extra argument."""
    return int(prefix.rsplit("_w", 1)[1])


def worker_main(worker_id: int, prefix: str, slots: int, slab_bytes: int,
                listen_host: str, port_hint: int, fastpath_min: int,
                encode_mode: str = "worker", batch_reads: int = 8) -> None:
    """Spawn entry point (multiprocessing 'spawn' context).  The package
    __init__ imported jax; pin this process to the CPU platform before
    anything could lazily initialize a backend — the accelerator belongs
    to the engine process alone.  A worker that cannot pin itself dies
    here: the hub sees a dead worker, never a second claimant of the
    chip."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    logging.basicConfig(level=logging.INFO)
    asyncio.run(_worker_amain(worker_id, prefix, slots, slab_bytes,
                              listen_host, port_hint, fastpath_min,
                              encode_mode, batch_reads))


# ============================================================ engine process


def columnify_resps(resps):
    """Pack a list of RateLimitResp into decision columns for a
    complete_cols completion (worker-side response encode), or None when
    any response cannot be expressed as columns — an error string, or
    metadata other than exactly qos/admission.py's shed shape — in which
    case the hub serializes engine-side (counted in encode_fallbacks)."""
    n = len(resps)
    st = np.empty(n, np.int64)
    li = np.empty(n, np.int64)
    re = np.empty(n, np.int64)
    rs = np.empty(n, np.int64)
    fl = np.zeros(n, np.int32)
    for i, r in enumerate(resps):
        if r.error:
            return None
        md = r.metadata
        if md:
            code = (SHED_REASON_CODES.get(md.get("shed_reason", ""))
                    if len(md) == 2 and md.get("shed") == "true" else None)
            if code is None:
                return None
            fl[i] = code
        st[i] = r.status
        li[i] = r.limit
        re[i] = r.remaining
        rs[i] = r.reset_time
    return st, li, re, rs, fl


class FrontdoorHub:
    """Engine-side owner of the front door: creates the shm segments,
    spawns/monitors/restarts the workers, consumes every submission ring,
    and serves each record on the engine event loop through the SAME
    server.py serve_* bodies the single-process servicers use."""

    def __init__(self, instance, workers: int, ring_slots: int,
                 slab_bytes: int, listen_address: str,
                 encode: str = "worker", batch_reads: int = 8):
        self.instance = instance
        self.workers = workers
        self.ring_slots = ring_slots
        self.slab_bytes = slab_bytes
        self.encode = encode if encode in ("worker", "engine") else "worker"
        self.batch_reads = batch_reads
        # responses that could NOT be columnified (error strings, exotic
        # metadata) and fell back to engine-side serialization
        self.encode_fallbacks = 0
        host, _, port = listen_address.rpartition(":")
        self._listen_host = host or "localhost"
        self._port_hint = int(port or 0)
        # pid + per-process sequence keeps segment names unique even when
        # several hubs coexist in one engine process (tests, blue/green)
        self.prefix = f"gfd{os.getpid()}x{next(_PREFIX_SEQ)}_w{workers}"
        self.status: Optional[FrontdoorStatus] = None
        self.chans: List[WorkerChannel] = []
        self.procs: List[Optional[multiprocessing.Process]] = []
        self.epochs: List[int] = []
        self.restarts = 0
        self.records_served = 0
        self.address = ""
        self.port = 0
        self._locks: List[threading.Lock] = []
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stop_evt = threading.Event()
        self._consumer: Optional[threading.Thread] = None
        self._tasks: List[asyncio.Task] = []
        self._mp = multiprocessing.get_context("spawn")

    # ------------------------------------------------------------- lifecycle

    def _spawn(self, i: int) -> None:
        from gubernator_tpu.server import FASTPATH_MIN_BYTES
        p = self._mp.Process(
            target=worker_main,
            args=(i, self.prefix, self.ring_slots, self.slab_bytes,
                  # after the first bind, respawns must re-claim the SAME
                  # public port (an ephemeral hint of 0 would move it)
                  self._listen_host, self.port or self._port_hint,
                  FASTPATH_MIN_BYTES, self.encode, self.batch_reads),
            daemon=True)
        p.start()
        self.procs[i] = p
        self.status.set_w(i, shm_ring.W_PID, p.pid)
        self.status.set_w(i, shm_ring.W_EPOCH, self.epochs[i])

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self.status = FrontdoorStatus.create(f"{self.prefix}_st",
                                             self.workers)
        self.status.beat()
        self._refresh_flags()
        self.chans = [
            WorkerChannel.create(f"{self.prefix}_r{i}", self.ring_slots,
                                 self.slab_bytes)
            for i in range(self.workers)
        ]
        self._locks = [threading.Lock() for _ in range(self.workers)]
        self.procs = [None] * self.workers
        self.epochs = [0] * self.workers
        for i in range(self.workers):
            self._spawn(i)
        self._consumer = threading.Thread(target=self._consume_loop,
                                          name="frontdoor-consumer",
                                          daemon=True)
        self._consumer.start()
        self._tasks = [
            asyncio.create_task(self._status_loop()),
            asyncio.create_task(self._monitor_loop()),
        ]
        # the public address is worker 0's bound port (every worker shares
        # it under SO_REUSEPORT; stragglers publish their fallback ports
        # in the status block, visible in `cli debug`)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            self.port = self.status.get_w(0, shm_ring.W_PORT)
            if self.port:
                break
            await asyncio.sleep(0.05)
        if not self.port:
            raise RuntimeError("frontdoor worker 0 never bound its port")
        self.address = f"{self._listen_host}:{self.port}"

    def set_draining(self) -> None:
        if self.status is not None:
            self.status.set_flag(FLAG_DRAINING, True)

    async def stop(self) -> None:
        self.set_draining()
        for t in self._tasks:
            t.cancel()
        self._tasks = []
        for p in self.procs:
            if p is not None and p.is_alive():
                p.terminate()
        joins = [p for p in self.procs if p is not None]
        if joins:
            def _join():
                for p in joins:
                    p.join(timeout=3.0)
                    if p.is_alive():
                        p.kill()
                        p.join(timeout=1.0)
            await self._loop.run_in_executor(None, _join)
        self._stop_evt.set()
        if self._consumer is not None:
            self._consumer.join(timeout=2.0)
            self._consumer = None
        for ch in self.chans:
            ch.close()
        self.chans = []
        if self.status is not None:
            self.status.close()
            self.status = None

    # ----------------------------------------------------- engine-side loops

    def _refresh_flags(self) -> None:
        inst = self.instance
        st = self.status
        st.beat()
        saturated = (inst.qos is not None
                     and inst.qos.admission.saturated)
        st.set_flag(FLAG_SATURATED, bool(saturated))
        pl = getattr(inst.batcher, "pipeline", None)
        from gubernator_tpu import native
        cols_ok = bool(
            native.available() and pl is not None and pl.enabled
            and pl.rpc_enabled and inst.engine._compact_enabled
            and not pl._ring_peers and not inst.mesh_mode)
        st.set_flag(FLAG_COLS_OK, cols_ok)

    async def _status_loop(self) -> None:
        from gubernator_tpu.core.service import HEALTHY
        while True:
            try:
                self._refresh_flags()
                h = await self.instance.health_check()
                self.status.set_health(0 if h.status == HEALTHY else 1,
                                       h.message, h.peer_count)
            except Exception:
                log.exception("frontdoor status refresh failed")
            await asyncio.sleep(0.2)

    async def _monitor_loop(self) -> None:
        backoff = [0.5] * self.workers
        next_ok = [0.0] * self.workers
        while True:
            await asyncio.sleep(0.5)
            for i, p in enumerate(self.procs):
                if p is None or p.is_alive():
                    backoff[i] = 0.5
                    continue
                now = time.monotonic()
                if now < next_ok[i]:
                    continue
                # exponential respawn backoff: a worker that dies at boot
                # (bad port, broken env) must not melt the engine loop
                next_ok[i] = now + backoff[i]
                backoff[i] = min(5.0, backoff[i] * 2)
                # crash-restart: a dead worker's in-flight records are
                # client-visible connection drops already (their TCP
                # connections died with the worker).  Bump the epoch so
                # late completions drop, reset the rings BEFORE the
                # respawn so the fresh worker sees empty queues — no
                # partial commit can survive the boundary.
                log.warning("frontdoor worker %d (pid %s) died; restarting",
                            i, p.pid)
                self.restarts += 1
                self.epochs[i] += 1
                self.status.bump_w(i, shm_ring.W_RESTARTS)
                with self._locks[i]:
                    self.chans[i].reset()
                self._spawn(i)

    def _consume_loop(self) -> None:
        """Submission-ring consumer thread: pops records and hands each to
        the engine event loop.  The pop itself is lock-free against the
        worker; the per-channel lock only serializes against monitor
        resets."""
        while not self._stop_evt.is_set():
            got = False
            for i in range(self.workers):
                with self._locks[i]:
                    recs = self.chans[i].pop()
                    epoch = self.epochs[i]
                for rec in recs:
                    got = True
                    asyncio.run_coroutine_threadsafe(
                        self._serve(i, epoch, rec), self._loop)
            if not got:
                time.sleep(0.0005)

    # -------------------------------------------------------------- serving

    async def _serve(self, wid: int, epoch: int, rec) -> None:
        try:
            payload = await self._dispatch(rec)
            status = 0
        except FrontdoorAbort as e:
            status = e.code.value[0]
            payload = e.message.encode()
        except Exception as e:  # engine bug: surface as INTERNAL
            log.exception("frontdoor record failed (kind %d)", rec.kind)
            status = _INTERNAL
            payload = str(e).encode()
        self.records_served += 1
        # epoch guard: after a crash-restart the slot belongs to the NEW
        # worker's free pool — a stale completion (bytes OR columns) must
        # not touch it: the respawned worker would otherwise encode a
        # dead epoch's decisions against a recycled slab
        if self.epochs[wid] != epoch:
            return
        ch = self.chans[wid]
        if status == 0 and isinstance(payload, tuple):
            if payload[0] == "cols":  # worker-side encode
                st, li, re, rs, fl = payload[1]
                ch.complete_cols(rec.slot, rec.req_id, st, li, re, rs, fl)
            else:  # "bparts": per-RPC serialized parts of a batch
                ch.complete_batch_bytes(rec.slot, rec.req_id, payload[1])
        else:
            ch.complete(rec.slot, rec.req_id, status, payload)

    async def _dispatch(self, rec):
        from gubernator_tpu import server as srv
        from gubernator_tpu.api import pb
        inst = self.instance
        ctx = _EngineContext(rec.deadline)
        if rec.kind == KIND_COLS:
            return await self._serve_cols(rec, ctx)
        if rec.kind == KIND_BATCH_COLS:
            return await self._serve_batch(rec, ctx)
        if rec.kind == KIND_RAW:
            # ONE code path for the response direction: the inner body
            # returns resps from the Python path, and worker-encode mode
            # ships them as columns just like the COLS lane — small and
            # exotic requests no longer fall back to engine serialization
            kind, val = await srv.serve_get_rate_limits_inner(
                inst, rec.payload, ctx)
            if kind == "bytes":
                return val
            return self._finish_resps(val)
        if rec.kind == KIND_PEER_RL:
            return await srv.serve_peer_rate_limits(inst, rec.payload, ctx)
        if rec.kind == KIND_TRANSFER:
            return await srv.serve_transfer_buckets(inst, rec.payload, ctx)
        if rec.kind == KIND_REGISTER:
            req = pb.RegisterGlobalsReq.FromString(rec.payload)
            out = await srv.serve_register_globals(inst, req, ctx)
            return out.SerializeToString()
        if rec.kind == KIND_APPLY_GREG:
            req = pb.ApplyGlobalRegistrationReq.FromString(rec.payload)
            out = await srv.serve_apply_global_registration(inst, req, ctx)
            return out.SerializeToString()
        if rec.kind == KIND_UPDATE_GLOBALS:
            req = pb.UpdatePeerGlobalsReq.FromString(rec.payload)
            out = await srv.serve_update_peer_globals(inst, req, ctx)
            return out.SerializeToString()
        raise FrontdoorAbort(grpc.StatusCode.UNIMPLEMENTED,
                             f"unknown frontdoor record kind {rec.kind}")

    async def _serve_cols(self, rec, ctx: _EngineContext):
        """Worker-parsed columns: the mirror of serve_get_rate_limits with
        the C parse already done.  The columns passed frontdoor_parse_req's
        acceptance rules — exactly the native lane's — so the pipeline
        never range-falls-back on them; the Python fallback below only
        runs on saturation or a pipeline/membership gate, and reconstructs
        the requests exactly (name_lens splits each assembled hash key)."""
        inst = self.instance
        m = inst.metrics
        start = time.monotonic()
        want_cols = self.encode == "worker"
        qos_saturated = (inst.qos is not None
                         and inst.qos.admission.saturated)
        if not qos_saturated:
            out = await inst.batcher.submit_cols(rec.cols, rec.n,
                                                 want_cols=want_cols,
                                                 ctx=self._span_ctx(rec))
            if out is not None:
                m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start,
                              ok=True)
                if want_cols:  # (status, limit, remaining, reset) arrays
                    return ("cols", (*out, None))
                return out
        resps = await self._py_fallback(rec, ctx, m, start)
        return self._finish_resps(resps)

    def _span_ctx(self, rec):
        """Rebuild the worker-propagated traceparent (shm trace region)
        as a SpanContext so the pipeline roots its drain spans under the
        caller's trace; None when the record carried no trace or tracing
        is off."""
        tr = getattr(self.instance, "tracer", None)
        if rec.trace is None or tr is None or not tr.enabled:
            return None
        from gubernator_tpu.observability.tracing import SpanContext
        hi, lo, span = rec.trace
        return SpanContext(f"{hi:016x}{lo:016x}", f"{span:016x}")

    async def _py_fallback(self, rec, ctx: _EngineContext, m, start):
        """Reconstruct the record's requests from its columns and run the
        engine's full Python path (shared by COLS and BATCH fallbacks)."""
        from gubernator_tpu.api.types import RateLimitReq
        from gubernator_tpu.core.service import BatchTooLargeError
        inst = self.instance
        kb, ke, hits, limits, durations, algos = rec.cols
        key_all = bytes(kb)
        reqs = []
        prev = 0
        for j in range(rec.n):
            end = int(ke[j])
            nl = int(rec.name_lens[j])
            k = key_all[prev:end]
            reqs.append(RateLimitReq(
                name=k[:nl].decode("utf-8", "replace"),
                unique_key=k[nl + 1:].decode("utf-8", "replace"),
                hits=int(hits[j]), limit=int(limits[j]),
                duration=int(durations[j]), algorithm=int(algos[j])))
            prev = end
        deadline = None
        if inst.qos is not None:
            deadline = inst.qos.deadline_from_timeout(ctx.time_remaining())
        try:
            resps = await inst.get_rate_limits(reqs, deadline=deadline)
        except BatchTooLargeError as e:
            m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=False)
            raise FrontdoorAbort(grpc.StatusCode.OUT_OF_RANGE, str(e))
        m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start, ok=True)
        return resps

    async def _serve_batch(self, rec, ctx: _EngineContext):
        """A KIND_BATCH_COLS record: several coalesced RPCs' columns as
        ONE pipeline job, completed as ONE columnar entry the worker
        splits back per-RPC by the counts region.  Batches only exist in
        worker-encode mode, so the completion is columns (or per-RPC
        bytes parts on the rare non-columnifiable fallback)."""
        inst = self.instance
        m = inst.metrics
        start = time.monotonic()
        qos_saturated = (inst.qos is not None
                         and inst.qos.admission.saturated)
        if not qos_saturated:
            out = await inst.batcher.submit_cols(rec.cols, rec.n,
                                                 want_cols=True,
                                                 ctx=self._span_ctx(rec))
            if out is not None:
                for _ in rec.counts:
                    m.observe_rpc("/pb.gubernator.V1/GetRateLimits", start,
                                  ok=True)
                return ("cols", (*out, None))
        resps = await self._py_fallback(rec, ctx, m, start)
        cols = columnify_resps(resps)
        if cols is not None:
            return ("cols", cols)
        # per-RPC serialized parts: split the responses by the request
        # counts so every coalesced RPC still gets ITS response
        from gubernator_tpu.api import pb
        parts = []
        off = 0
        for cnt in rec.counts:
            parts.append(pb.GetRateLimitsResp(responses=[
                pb.resp_to_pb(r) for r in resps[off:off + cnt]
            ]).SerializeToString())
            off += cnt
        self.encode_fallbacks += 1
        return ("bparts", parts)

    def _finish_resps(self, resps):
        """The response-direction tail shared by every GetRateLimits
        fallback: columnify for worker-side encode, or (engine mode /
        non-columnifiable responses) serialize here and count it."""
        from gubernator_tpu.api import pb
        if self.encode == "worker":
            cols = columnify_resps(resps)
            if cols is not None:
                return ("cols", cols)
            self.encode_fallbacks += 1
        return pb.GetRateLimitsResp(
            responses=[pb.resp_to_pb(r) for r in resps]).SerializeToString()

    # -------------------------------------------------------- observability

    def stats(self) -> dict:
        """Aggregates for the metrics scrape hook (watch_frontdoor)."""
        s = {"workers": self.workers, "restarts": self.restarts,
             "rpcs": 0, "sheds": 0, "healthchecks": 0, "stalls": 0,
             "depth": 0, "inflight": 0, "encodes": 0, "enc_fallbacks": 0,
             "batch_rpcs": 0, "batch_flushes": 0, "trace_drops": 0,
             "engine_encode_fallbacks": self.encode_fallbacks}
        if self.status is None:
            return s
        for i in range(self.workers):
            s["rpcs"] += self.status.get_w(i, shm_ring.W_RPCS)
            s["sheds"] += self.status.get_w(i, shm_ring.W_SHEDS)
            s["healthchecks"] += self.status.get_w(i, shm_ring.W_HEALTHCHECKS)
            s["stalls"] += self.status.get_w(i, shm_ring.W_STALLS)
            s["encodes"] += self.status.get_w(i, shm_ring.W_ENCODES)
            s["enc_fallbacks"] += self.status.get_w(i,
                                                    shm_ring.W_ENC_FALLBACK)
            s["batch_rpcs"] += self.status.get_w(i, shm_ring.W_BATCH_RPCS)
            s["batch_flushes"] += self.status.get_w(i,
                                                    shm_ring.W_BATCH_FLUSHES)
            s["trace_drops"] += self.status.get_w(i, shm_ring.W_TRACE_DROPS)
        for ch in self.chans:
            s["depth"] += ch.sub_depth()
            s["inflight"] += ch.inflight()
        return s

    def debug_snapshot(self) -> dict:
        ports = [self.status.get_w(i, shm_ring.W_PORT)
                 for i in range(self.workers)] if self.status else []
        rows = []
        for i in range(self.workers):
            rows.append({
                "pid": self.status.get_w(i, shm_ring.W_PID),
                "port": ports[i],
                "epoch": self.epochs[i],
                "restarts": self.status.get_w(i, shm_ring.W_RESTARTS),
                "rpcs": self.status.get_w(i, shm_ring.W_RPCS),
                "sheds": self.status.get_w(i, shm_ring.W_SHEDS),
                "healthchecks": self.status.get_w(i, shm_ring.W_HEALTHCHECKS),
                "stalls": self.status.get_w(i, shm_ring.W_STALLS),
                "encodes": self.status.get_w(i, shm_ring.W_ENCODES),
                "enc_fallbacks": self.status.get_w(i,
                                                   shm_ring.W_ENC_FALLBACK),
                "batch_rpcs": self.status.get_w(i, shm_ring.W_BATCH_RPCS),
                "batch_flushes": self.status.get_w(i,
                                                   shm_ring.W_BATCH_FLUSHES),
                "trace_drops": self.status.get_w(i, shm_ring.W_TRACE_DROPS),
                "ring_depth": self.chans[i].sub_depth() if self.chans else 0,
                "inflight": self.chans[i].inflight() if self.chans else 0,
            })
        return {
            "workers": self.workers,
            "address": self.address,
            "port_mode": ("reuseport"
                          if len(set(p for p in ports if p)) <= 1
                          else "per-worker-ports"),
            "ring_slots": self.ring_slots,
            "slab_bytes": self.slab_bytes,
            "restarts": self.restarts,
            "records_served": self.records_served,
            "encode_mode": self.encode,
            "batch_reads": self.batch_reads,
            "engine_encode_fallbacks": self.encode_fallbacks,
            "per_worker": rows,
        }
