"""Pipelined serving drain: pending work → stacked compact windows → one
async dispatch → fetch on a small worker pool.

Why this shape (the constants on the attached chip are not measured —
PERF.md):

  * ISSUING a device dispatch is ~free (async);
  * any synchronous device→host fetch pays a fixed round trip regardless
    of size, plus bytes/bandwidth;
  * outstanding fetches overlap each other only partially.

Serving throughput is therefore decisions-per-fetch ÷ fetch-time.  The drain
maximizes the numerator and hides the denominator:

  1. everything pending — whole serialized RPCs and already-parsed request
     lists alike — is packed into ONE stack of K compact windows, filling
     windows to the lane cap ACROSS job boundaries (the C router spills
     per-shard to later windows with monotonic cursors, preserving
     sequential per-key order through the device-side scan);
  2. the stack dispatches as one executable call (engine.pipeline_dispatch)
     that returns un-fetched device arrays;
  3. a small fetch pool (two workers — outstanding device→host fetches
     overlap partially, measured ~2x) materializes the response words and
     demuxes them (C proto encode for RPC jobs, vectorized numpy for list
     jobs) while the engine thread is already packing and dispatching the
     NEXT drain.  Demux per drain is self-contained (stateless C encoders
     over caller buffers), so completing out of order is safe; per-key
     ordering was committed at dispatch on the engine thread.

The OVERLAPPED drain pipeline (GUBER_PIPELINE_DEPTH, default 3) runs these
stages double/triple-buffered: while drain N's device execution is in
flight, the engine thread is already host-encoding drain N+1 and a fetch
worker is decoding drain N-1.  Commits still flow through ONE ordered
completion queue — every _on_completed runs on the event loop, and all
device work serializes on the single-thread engine executor — so results
are bit-identical to a serial (depth-1) pipeline regardless of completion
order (tests/test_pipeline_overlap.py proves this differentially).  Host
staging comes from a ring of preallocated arenas (core/window_buffers.py)
instead of fresh numpy allocations: an arena is reused only after its
drain's fetch completed (device provably done reading the H2D buffers),
and error paths drop the arena rather than risk recycling one a transfer
may still be reading.  Single-request submits accumulate into columnar
arrays at submit time (RequestColumns), so window packing takes zero-copy
column slices instead of walking request objects.

The pump is occupancy-gated (GUBER_PIPELINE_GATE): with a drain already in
flight, the next one is held for one of two reasons, both observed.  Less
than one batch (coalesce_min decisions, the estimate _pump compares with
it when nothing is out) is queued: below a batch a drain's cost is fixed,
so accumulating while one is out is free.  Or the engine thread has not
finished packing and enqueuing the drain before (_predispatch >= 1): a
drain pumped now would only wait in the single-thread executor with its
jobs already taken, so it is left to absorb what arrives meanwhile.
Otherwise it goes, and its pack runs while the device executes the drain
in flight: from a batch upward a drain's cost follows its fill (lane
buckets, a pack linear in its items), so waiting for a full window buys
nothing.  _on_dispatched and every completion re-pump and the gate is off
at in_flight == 0, so it can never strand work.

Reference analog: a peer draining its queue ships batches back-to-back
without waiting for each response (peers.go:143-172); the reference's
500µs/1000-item aggregation window (config.go:60-62) corresponds to the
natural accumulation that happens while the pipeline is at depth.

Mesh (lockstep) serving runs the SAME drain: the tick's drain executable is
the GLOBAL-composed variant (engine.pipeline_dispatch_global) — every chip
runs the compact32 window body over its own plane-arena shard, with ONE
GLOBAL reconciliation psum composed around the K-scan per drain — so mesh
mode gets the same one-dispatch-per-drain, overlapped-fetch structure as a
single chip, and GLOBAL singles ride the drain's composed window
(_GlobalJob) instead of the legacy step.  Only out-of-range configs and
GLOBAL traffic outside lockstep mode stay on the legacy step path — the
pipeline and that path serialize on the same single-thread engine
executor, so state mutation order is well defined.

A mesh whose shards all belong to this process (one daemon over the chips
of one host) takes whole RPCs too: the raw-RPC lane stages them as on a
standalone node and the tick drains them.  The C parser marks an RPC's
token and leaky GLOBAL items instead of refusing the RPC; _GlobalStage
folds them into the drain's GLOBAL window (one lane for every distinct
key, hits and config: the answers of a window are reads of the row as it
stood before it, so equal requests get equal answers, and the lane's
summed hits land once through the psum), and the fetch side appends their
answers to the response words as rows of their own, so that the C encoder
writes the whole RPC.  With no other host waiting on this one's
collectives, a tick dispatches only when something is staged and the
pipeline has room.  Several hosts keep one fixed dispatch every tick and
route per item.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    millisecond_now,
)
from gubernator_tpu.config import (CHAIN_LINGER_MS_DEFAULT,
                                   FETCH_STRIDE_DEFAULT,
                                   FETCH_STRIDE_MAX_DEFAULT, MAX_BATCH_SIZE,
                                   env_bool, env_float, env_int)
from gubernator_tpu.core.engine import PIPELINE_K_BUCKETS
from gubernator_tpu.core.window_buffers import RequestColumns, WindowArenaRing
from gubernator_tpu.net.faults import FAULTS, SEAM_ENGINE_DISPATCH
from gubernator_tpu.observability.metrics import (DRAIN_AHEAD,
                                                  LOCKSTEP_LANES,
                                                  LOCKSTEP_TICK_KINDS,
                                                  PUMP_HOLD_REASONS)
from gubernator_tpu.observability.tracing import current_context
from gubernator_tpu.ops import kernel
from gubernator_tpu.qos import interleave_by_tenant
from gubernator_tpu.qos.fairness import tenant_of

log = logging.getLogger("gubernator.pipeline")


def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _frame(body: bytes) -> bytes:
    """One repeated-field-1 entry (identical framing in GetRateLimitsResp
    and GetPeerRateLimitsResp)."""
    return b"\x0a" + _varint(len(body)) + body


def _walk_frames(data: bytes) -> List[bytes]:
    """Split a serialized response into its field-1 entry FRAMES (tag +
    length + body), preserving order; skips unknown fields."""
    frames = []
    i, n = 0, len(data)
    while i < n:
        start = i
        tag = 0
        shift = 0
        while True:
            b = data[i]
            i += 1
            tag |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        wt = tag & 7
        if wt == 2:
            ln = 0
            shift = 0
            while True:
                b = data[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
            end = i + ln
            if tag >> 3 == 1:
                frames.append(data[start:end])
            i = end
        elif wt == 0:
            while data[i] & 0x80:
                i += 1
            i += 1
        else:
            raise ValueError("unsupported wire type in peer response")
    return frames


# metadata entry framing for the coordinator annotation the slow path puts
# on forwarded responses (gubernator.go:151): RateLimitResp.metadata is
# map<string,string> field 6; one entry is a {key=1, value=2} submessage.
_META_OWNER_KEY = b"\x0a\x05owner"


def _owner_metadata(host: str) -> bytes:
    h = host.encode("utf-8")
    entry = _META_OWNER_KEY + b"\x12" + _varint(len(h)) + h
    return b"\x32" + _varint(len(entry)) + entry


def _append_owner(frame: bytes, host: str) -> bytes:
    """Annotate a framed RateLimitResp with metadata['owner'] by appending
    the map entry to the body (protobuf fields concatenate)."""
    body = _walk_body(frame) + _owner_metadata(host)
    return _frame(body)


def _walk_body(frame: bytes) -> bytes:
    """Strip the tag+length framing off one field-1 entry."""
    i = 1  # tag byte 0x0a
    ln = 0
    shift = 0
    while True:
        b = frame[i]
        i += 1
        ln |= (b & 0x7F) << shift
        if not (b & 0x80):
            break
        shift += 7
    return frame[i:i + ln]


class RpcJob:
    """A whole serialized GetRateLimitsReq served natively: C parse →
    stacked lanes → C proto encode.  Resolves to response BYTES, or None
    when the RPC needs the full Python path.

    Cluster mode: items the ring assigns to OTHER peers come back from the
    parser as out_row < -1 markers with their serialized byte ranges; they
    forward to their owners as spliced GetPeerRateLimitsReq BYTES (no
    Python protobuf objects anywhere on the path) while the local items'
    stacked fetch is in flight, and the response splices both back together
    positionally (_assemble_mixed).  peer_mode marks the authoritative
    peer-plane lane (GetPeerRateLimits): the ring is ignored and everything
    is local, like the reference owner (gubernator.go:210-227)."""

    __slots__ = ("data", "fut", "n", "row", "lane", "pos", "limit", "off",
                 "mlen", "remote_idx", "forward_task", "peer_mode",
                 "ctx", "enq", "committed", "gdefer")

    def __init__(self, data: bytes, fut: asyncio.Future,
                 peer_mode: bool = False):
        self.data = data
        self.fut = fut
        self.peer_mode = peer_mode
        # trace context + enqueue stamp (observability): the sampled
        # SpanContext this RPC rode in on, and when it joined the queue
        self.ctx = None
        self.enq = 0.0
        # when the commit resolved this job's future (reply_wake's start)
        self.committed = 0.0
        self.n = 0
        self.row = None
        self.lane = None
        self.pos = None
        self.limit = None
        self.off = None
        self.mlen = None
        self.remote_idx = ()
        self.forward_task = None
        # lockstep lane: the GLOBAL items this tick's window had no lane
        # for, as (item index, request); they ride a later tick
        self.gdefer = ()

    def finish(self, pipeline, wflat, clflat, now):
        # the encode target is a per-fetch-thread scratch buffer: bytes()
        # copies out before this thread touches another job, so reuse is
        # safe and the hot path allocates nothing proportional to n
        if not len(self.remote_idx):
            resp_buf = pipeline._resp_buf(self.n * 64 + 64)
            m = pipeline.engine.native.fastpath_encode_w(
                wflat, self.limit, now, wflat.shape[-1], self.n,
                self.row, self.lane, self.pos, resp_buf, climit=clflat)
            return bytes(resp_buf[:m])
        # mixed RPC: encode the LOCAL items as framed per-item segments;
        # forwarded slots splice in later (_assemble_mixed).  item_off/
        # item_len escape into the async splice, so they stay per-job.
        seg_buf = pipeline._resp_buf(self.n * 64 + 64)
        item_off = np.empty(self.n, np.int64)
        item_len = np.empty(self.n, np.int32)
        pipeline.engine.native.fastpath_encode_parts(
            wflat, self.limit, now, wflat.shape[-1], self.n,
            self.row, self.lane, self.pos, seg_buf, item_off, item_len,
            climit=clflat)
        return bytes(seg_buf), item_off, item_len


class ListJob:
    """Already-parsed requests (batcher singles, peer-forwarded batches)
    packed columnar through the same stack.  Resolves each request's future
    (singles) or one future with the response list (batch)."""

    __slots__ = ("reqs", "futs", "fut", "row", "lane", "pos", "n", "_cols",
                 "ctxs", "enq", "enq_lag", "committed")

    def __init__(self, reqs: Sequence[RateLimitReq],
                 futs: Optional[List[asyncio.Future]] = None,
                 fut: Optional[asyncio.Future] = None,
                 ctxs: Optional[List] = None, enq: float = 0.0):
        self.reqs = list(reqs)
        self.futs = futs
        self.fut = fut
        # sampled SpanContexts riding this job (aligned with reqs for
        # singles chunks, single-element for batch jobs) + oldest enqueue
        self.ctxs = ctxs
        self.enq = enq
        # a singles chunk holds len(futs) requests, each with an enqueue
        # time of its own: enq_lag = Σ (t_enq − enq), so that the chunk's
        # summed queue wait is n·(started − enq) − enq_lag
        self.enq_lag = 0.0
        self.committed = 0.0
        self.n = len(self.reqs)
        self.row = None
        self.lane = None
        self.pos = None
        self._cols = None

    def columns(self):
        if self._cols is None:
            keys = [r.hash_key().encode("utf-8") for r in self.reqs]
            self._cols = (
                np.frombuffer(b"".join(keys), dtype=np.uint8),
                np.cumsum([len(k) for k in keys]).astype(np.int64),
                np.asarray([r.hits for r in self.reqs], np.int64),
                np.asarray([r.limit for r in self.reqs], np.int64),
                np.asarray([r.duration for r in self.reqs], np.int64),
                np.asarray([r.algorithm for r in self.reqs], np.int32),
            )
        return self._cols

    def finish(self, pipeline, wflat, clflat, now) -> List[RateLimitResp]:
        w = wflat[self.row, self.lane]
        enc = (w >> 32) & 0xFFFFFFFF
        # aggregated/synthesizable items (pos >= 0, see host_router.cc
        # decode_word_item): the word carries r_start; derive each item's
        # response from its 0-based run position.  Plain items (pos == -1)
        # decode the word directly.
        pos = self.pos
        synth = pos >= 0
        p = np.where(synth, pos & 0x3FFFFFFF, 0)
        algo1 = (pos >> 30) & 1
        r_start = w & 0x7FFFFFFF
        under = p < r_start
        remaining = np.where(
            synth, np.where(under, r_start - p - 1, 0),
            w & 0x7FFFFFFF).tolist()
        status = np.where(
            synth, np.where(under, 0, 1), (w >> 31) & 1).tolist()
        reset_plain = np.where(enc == 0, 0, now + enc - 1)
        reset = np.where(
            synth & (algo1 == 1) & under, 0, reset_plain).tolist()
        if clflat is not None:
            limits = clflat[self.row, self.lane].tolist()
        else:
            limits = self.columns()[3].tolist()
        return [
            RateLimitResp(status=status[i], limit=limits[i],
                          remaining=remaining[i], reset_time=reset[i])
            for i in range(self.n)
        ]


class ColsJob:
    """Frontdoor shm lane (frontdoor.py): request columns a WORKER process
    already parsed AND validated — native frontdoor_parse_req applies
    exactly the RpcJob parser's acceptance rules, so a ColsJob never
    range-falls-back.  Staged like a ListJob (pack_stack_fast over the
    column 6-tuple, zero-copy views into the worker's shm slab) but
    finished like an RpcJob: straight to C-encoded response bytes the hub
    memcpys back into the slab — or, with want_cols (worker-side response
    encode, GUBER_FRONTDOOR_ENCODE=worker), to packed DECISION columns
    (status, limit, remaining, reset int64 arrays) the hub ships through
    complete_cols so the WORKER serializes the protobuf instead of the
    engine.  Resolves to bytes/columns, or None when the drain routes it
    to fallback (the hub then runs the full Python path).

    No _cols slot on purpose: leftover re-queues skip the materialization
    copy because the slab stays valid until the hub completes the record."""

    __slots__ = ("cols", "futs", "fut", "row", "lane", "pos", "n",
                 "ctxs", "enq", "want_cols", "committed")

    def __init__(self, cols: tuple, n: int, fut: asyncio.Future,
                 want_cols: bool = False):
        self.cols = cols
        self.fut = fut
        self.futs = None
        self.ctxs = None
        self.enq = 0.0
        self.committed = 0.0
        self.n = n
        self.row = None
        self.lane = None
        self.pos = None
        self.want_cols = want_cols

    def columns(self):
        return self.cols

    def finish(self, pipeline, wflat, clflat, now):
        if not self.want_cols:
            resp_buf = pipeline._resp_buf(self.n * 64 + 64)
            m = pipeline.engine.native.fastpath_encode_w(
                wflat, self.cols[3], now, wflat.shape[-1], self.n,
                self.row, self.lane, self.pos, resp_buf, climit=clflat)
            return bytes(resp_buf[:m])
        # decision columns: the vectorized decode_word_item (see
        # ListJob.finish) kept as arrays — no Python response objects,
        # no serialization; the worker encodes from the completion slab
        w = wflat[self.row, self.lane]
        enc = (w >> 32) & 0xFFFFFFFF
        pos = self.pos
        synth = pos >= 0
        p = np.where(synth, pos & 0x3FFFFFFF, 0)
        algo1 = (pos >> 30) & 1
        r_start = w & 0x7FFFFFFF
        under = p < r_start
        remaining = np.where(
            synth, np.where(under, r_start - p - 1, 0), w & 0x7FFFFFFF)
        status = np.where(synth, np.where(under, 0, 1), (w >> 31) & 1)
        reset = np.where(
            synth & (algo1 == 1) & under, 0,
            np.where(enc == 0, 0, now + enc - 1))
        if clflat is not None:
            limits = clflat[self.row, self.lane]
        else:
            # copy: cols[3] views the shm slab that complete_cols will
            # overwrite with these very response columns
            limits = self.cols[3][:self.n].astype(np.int64)
        return (status.astype(np.int64), limits.astype(np.int64),
                remaining.astype(np.int64), reset.astype(np.int64))


class _GlobalJob:
    """GLOBAL singles riding the lockstep drain's composed psum window
    (full wire format — GLOBAL lanes are exempt from the compact range
    caps).  Staged round-robin over local shards by _drain_sync, resolved
    per-request like a ListJob with futs; decodes the drain's gfused
    response block ([S_local, Bg, 4] = status/limit/remaining/reset_time)
    directly."""

    __slots__ = ("reqs", "futs", "fut", "n", "shard", "lane")

    def __init__(self, reqs: Sequence[RateLimitReq],
                 futs: List[asyncio.Future]):
        self.reqs = list(reqs)
        self.futs = futs
        self.fut = None
        self.n = len(self.reqs)
        self.shard = np.empty(self.n, np.int32)
        self.lane = np.empty(self.n, np.int32)

    def finish_global(self, gflat) -> List[RateLimitResp]:
        s, ln = self.shard, self.lane
        status = gflat[s, ln, 0].tolist()
        limit = gflat[s, ln, 1].tolist()
        remaining = gflat[s, ln, 2].tolist()
        reset = gflat[s, ln, 3].tolist()
        return [
            RateLimitResp(status=status[i], limit=limit[i],
                          remaining=remaining[i], reset_time=reset[i])
            for i in range(self.n)
        ]


class _GlobalStage:
    """One drain's GLOBAL window while it is staged (engine thread).

    Requests for one key with the same hits and config share a lane: under
    the window rule every answer is a read of the row as it stood before
    the window, so they get the same answer, and the lane accumulates their
    summed hits for the psum.  Lanes go round-robin over the local shards
    (the psum is shard-agnostic).  A dynamic engine (one that holds every
    shard) configures a slot in the same dispatch, through the drain's
    bounded update lanes, when the key is new or its config changed since
    this pipeline last staged it; `add` answers -1 where the window has no
    lane or no update lane left, and the caller defers the item."""

    __slots__ = ("eng", "now", "gbatch", "gacc", "upd", "SL", "cap", "Kg",
                 "lanes", "slots", "acc", "cfg_upd", "resets", "items",
                 "cfg_seen", "dynamic")

    def __init__(self, eng, now: int, cfg_seen: dict):
        self.eng, self.now = eng, now
        self.gbatch, self.gacc, self.upd = eng.empty_drain_control()
        self.SL = eng.num_local_shards
        self.cap = self.SL * eng.global_batch_per_shard
        self.Kg = eng.max_global_updates
        self.lanes: dict = {}    # (key, hits, limit, duration, algo) -> lane
        self.slots: dict = {}    # key -> arena slot, looked up this drain
        self.acc: List[int] = []  # summed hits per lane
        self.cfg_upd: dict = {}  # slot -> config staged for update
        self.resets: List[int] = []
        self.items = 0
        self.cfg_seen = cfg_seen
        self.dynamic = eng._dynamic_global
        eng.gtable.begin_window()

    def add(self, key: str, hits: int, limit: int, duration: int,
            algo: int) -> int:
        lk = (key, hits, limit, duration, algo)
        flat = self.lanes.get(lk)
        if flat is None:
            flat = len(self.acc)
            if flat >= self.cap:
                return -1
            slot = self.slots.get(key)
            is_init = False
            if slot is None:
                # a key new to the arena takes a reset and an update lane;
                # it is looked up only once both are known to be free (a
                # lookup allocates, and this window's commit would swallow
                # an initialisation that was never staged)
                if self.dynamic and max(len(self.cfg_upd),
                                        len(self.resets)) >= self.Kg:
                    return -1
                slot, is_init = self.eng.gtable.lookup(key, self.now,
                                                       duration)
                self.slots[key] = slot
                if is_init and self.dynamic:
                    self.resets.append(slot)
            if self.dynamic:
                cfg = (limit, duration, algo)
                if is_init or self.cfg_seen.get(slot) != cfg:
                    if (slot not in self.cfg_upd
                            and len(self.cfg_upd) >= self.Kg):
                        return -1
                    self.cfg_upd[slot] = cfg
            s, lane = flat % self.SL, flat // self.SL
            gb = self.gbatch
            gb.slot[s, lane] = slot
            gb.hits[s, lane] = hits
            gb.limit[s, lane] = limit
            gb.duration[s, lane] = duration
            gb.algo[s, lane] = algo
            gb.is_init[s, lane] = is_init
            self.lanes[lk] = flat
            self.acc.append(0)
        self.acc[flat] += hits
        self.items += 1
        return flat

    def control(self) -> tuple:
        """(gbatch, gacc, upd) of the staged window, for the dispatch."""
        n = len(self.acc)
        if n:
            flat = np.arange(n)
            self.gacc[flat % self.SL, flat // self.SL] = self.acc
        upd = self.upd
        for j, (slot, cfg) in enumerate(self.cfg_upd.items()):
            upd[0][j] = slot
            upd[1][j], upd[2][j], upd[3][j] = cfg
        for j, slot in enumerate(self.resets):
            upd[4][j] = slot
        return self.gbatch, self.gacc, upd

    def committed(self) -> None:
        """The window was dispatched: its allocations and configs hold."""
        self.eng.gtable.commit_window()
        self.cfg_seen.update(self.cfg_upd)


class _DrainResult:
    __slots__ = ("words", "limits", "mism", "gfused", "stats", "stats_host",
                 "an_decay", "staged", "fallback",
                 "leftover", "now", "n_decisions", "n_lanes", "k_used",
                 "error", "started", "ring_peers",
                 "pumped", "pack_done", "dispatch_done", "dispatched_cb",
                 "fetch_start", "fetch_ready", "fetch_done", "completed_cb",
                 "committed",
                 "oldest_enq", "arena", "cols_owner", "cfut", "deferred",
                 "arm", "lanes", "chain_fetch_start", "chain_fetch_done",
                 "n_raw", "n_global", "gdeferred", "glimit")

    def __init__(self):
        self.words = None
        self.limits = None
        self.mism = None
        self.gfused = None
        # staging ownership: the drain's arena (returned to the ring only
        # on clean completion), the RequestColumns its singles sliced from,
        # and the early-submitted fetch future (engine-thread hop cut)
        self.arena = None
        self.cols_owner = None
        self.cfut = None
        # deferred-fetch chain member: the engine thread dispatched this
        # drain but submitted NO fetch — the loop appends it to the chain
        # and one stacked fetch every stride windows commits the group
        self.deferred = False
        # traffic analytics (ops/analytics.py): the un-fetched device stats
        # array, its host copy, and whether this drain's reduction decayed
        self.stats = None
        self.stats_host = None
        self.an_decay = 0
        self.staged = []
        self.fallback = []
        self.leftover = []
        self.now = 0
        self.n_decisions = 0
        self.n_lanes = 0
        self.k_used = 0
        self.error = None
        self.started = 0.0
        self.ring_peers = ()
        # stage boundaries (time.monotonic(), each taken where the work
        # happens), in pipeline order:
        #   pumped         loop: _pump hands the drain to the engine executor
        #   started        engine thread: the drain begins
        #   pack_done      engine thread: every job parsed/packed
        #   dispatch_done  engine thread: the executable is enqueued
        #   dispatched_cb  loop: _on_dispatched runs (beside the fetch
        #                  when the engine thread submitted it itself)
        #   fetch_start    fetch thread: a worker picks the drain up
        #   fetch_ready    fetch thread: the device reads have returned
        #   fetch_done     fetch thread: every job.finish() done
        #   completed_cb   loop: _on_completed runs
        #   committed      loop: every future resolved
        # engine_queue = pumped→started, window_fill = started→pack_done,
        # device_dispatch = pack_done→dispatch_done, dispatch_hop =
        # dispatch_done→dispatched_cb, fetch_queue = dispatch_done→
        # fetch_start, device_wait = fetch_start→fetch_ready, decode =
        # fetch_ready→fetch_done (drain_commit = both), complete_hop =
        # fetch_done→completed_cb, commit = completed_cb→committed;
        # admission_wait = oldest_enq→started.  Without dispatch_hop,
        # which runs beside the fetch, they add up to committed − pumped.
        # 0.0 = the boundary was never reached (error paths observe nothing)
        self.pumped = 0.0
        self.pack_done = 0.0
        self.dispatch_done = 0.0
        self.dispatched_cb = 0.0
        self.fetch_start = 0.0
        self.fetch_ready = 0.0
        self.fetch_done = 0.0
        self.completed_cb = 0.0
        self.committed = 0.0
        self.oldest_enq = 0.0
        # devprof attribution: which executable family served this drain
        # (composed_analytics / composed_drain / compact32_xla: devprof's
        # arm names), and the shared stacked-fetch window when the drain
        # committed through a deferred-fetch chain (satellite span +
        # chain_fetch stage; 0.0 = not chained)
        self.arm = ""
        # lane width the drain's executable ran at (0 = nothing dispatched)
        self.lanes = 0
        self.chain_fetch_start = 0.0
        self.chain_fetch_done = 0.0
        # lockstep lane: decisions whole RPCs brought (the rest came per
        # item), GLOBAL items the window holds, GLOBAL singles it had no
        # lane for ((req, fut), back to the queue's front), and where whole
        # RPCs brought GLOBAL items the request limits of the GLOBAL lanes
        # ([S_local, Bg], for the stored-limit mismatch check)
        self.n_raw = 0
        self.n_global = 0
        self.gdeferred = []
        self.glimit = None


class DispatchPipeline:
    """Owns the drain/fetch pipeline for ONE engine.

    All device work runs on the caller-provided single-thread engine
    executor (shared with the legacy step path — mutation order stays
    total); fetch + demux run on the pipeline's own fetch thread.  `depth`
    drains may be in flight at once, which is what hides the fetch round
    trip behind the next drain's packing and dispatch.
    """

    def __init__(self, engine, engine_executor: ThreadPoolExecutor,
                 metrics=None, k_max: int = PIPELINE_K_BUCKETS[-1],
                 depth: Optional[int] = None, lockstep: Optional[bool] = None,
                 qos=None, tracer=None, profile=None, analytics=None,
                 slo=None):
        self.engine = engine
        # traffic analytics + SLO engine (observability/analytics.py), or
        # None: the disabled serving path pays exactly ONE attribute check
        # per DRAIN (not per request) and dispatches nothing extra — the
        # drain executables are byte-identical either way
        # (tests/test_analytics.py).
        self.analytics = analytics
        self.slo = slo
        # observability: span recorder (None = tracing off everywhere) and
        # the armable jax.profiler capture shared with the batcher
        self.tracer = tracer
        self.profile = profile
        # QoSManager or None: feeds the AIMD from observed drain wall time
        # and caps decisions-per-drain + in-flight depth by the congestion
        # window (None = legacy static behavior, used by existing tests)
        self.qos = qos
        # LOCKSTEP mode (any engine served behind a cluster tick clock;
        # REQUIRED for multiprocess engines): staging is continuous, but
        # drains dispatch only on the tick (lockstep_pump) with a fixed
        # stack shape, so every process issues the identical executable
        # sequence — and all serving shares the tick's cluster-agreed
        # clock (one time base per arena).  The raw-RPC lane stays off
        # only where other hosts own some of the shards (rpc_enabled).
        self.lockstep = (engine.multiprocess if lockstep is None
                         else lockstep)
        if engine.multiprocess and not self.lockstep:
            raise ValueError(
                "a multiprocess engine's pipeline must run in lockstep "
                "mode (tick-driven drains keep the collective sequence "
                "identical on every process)")
        # Requires the native router; tiers (state/tiers.py) imply Python
        # routing so the gate below stays False with tiers on — defensive,
        # since enable_tiers already rejects native engines.
        self.enabled = engine.native is not None and engine._tiers is None
        self.metrics = metrics
        self._engine_executor = engine_executor
        self.k_max = k_max
        # pipeline depth = maximum concurrently in-flight drains (host
        # encodes N+1 while the device executes N and a fetch worker
        # decodes N-1).  Depth 1 degenerates to the serial oracle the
        # differential suite compares against.
        self.depth = env_int("GUBER_PIPELINE_DEPTH", 3) if depth is None \
            else depth
        # occupancy gate (see module docstring and _held): with a drain in
        # flight, hold the next dispatch while less than one batch is
        # queued or the engine thread is still busy with the drain before.
        self.gate_enabled = env_bool("GUBER_PIPELINE_GATE", True)
        # DEBUG ONLY: block until the device finishes each dispatch so the
        # stage stamps attribute wall time exactly (host-encode vs device
        # vs fetch).  This is a deliberate host sync point — it serializes
        # the pipeline and must never be on in production (the audit of
        # _drain_sync_inner found no unconditional syncs; this flag is the
        # one opt-in exception).
        self.sync_debug = env_bool("GUBER_PIPELINE_SYNC_DEBUG", False)
        # injectable clock (tests pin it for differential comparisons)
        self.now_fn: Callable[[], int] = millisecond_now
        # gate for the raw-RPC lane: requires a standalone instance or a
        # cluster ring installed in the C parser (set_ring) so every item
        # classifies local-vs-forward correctly.  Instance.set_peers flips
        # it; the drain re-reads it on the ENGINE thread so a membership
        # change that races an in-flight RPC falls back instead of deciding
        # keys this node does not own.
        # A mesh routes by shard, not by ring, so the lane also serves a
        # lockstep engine whose shards are all this process's own.
        self.rpc_enabled = self.enabled and not engine.multiprocess
        # always-on per-executable window clock (observability/devprof.py):
        # dispatch→fetch-ready wall time per drain, labelled by its arm.
        # None (no metrics) keeps the commit path at one attribute check.
        self.devclock = None
        if metrics is not None:
            from gubernator_tpu.observability.devprof import WindowClock
            self.devclock = WindowClock(metrics=metrics)
        # set by the batcher: async callable (reqs, accumulate) -> resps,
        # used when a list job needs the full path (legacy lane)
        self.legacy: Optional[Callable] = None
        # PeerClients indexed like the C ring's peer indices; swapped
        # ONLY on the engine thread (set_ring) so each drain snapshot is
        # consistent with the markers the parser emitted
        self._ring_peers: tuple = ()
        # truncation of the warmed bucket ladder (engine.warmup compiles
        # exactly PIPELINE_K_BUCKETS; never invent shapes it didn't warm)
        self._k_buckets = tuple(
            b for b in PIPELINE_K_BUCKETS if b < k_max) + (k_max,)
        self._closed = False
        if not self.enabled:
            return
        # TWO fetch workers by default: outstanding device→host fetches
        # overlap partially, and each
        # drain's demux is independent so out-of-order completion is safe
        # — per-key ordering was already committed at dispatch.
        # GUBER_FETCH_WORKERS tunes the pool once the transfer-overlap
        # factor is re-measured on real hardware.
        self._fetch_executor = ThreadPoolExecutor(
            max_workers=env_int("GUBER_FETCH_WORKERS", 2),
            thread_name_prefix="guber-fetch")
        # staging arenas (ring of reusable buffers) + columnar singles
        # accumulation — see core/window_buffers.py and module docstring
        self._arena_ring = WindowArenaRing()
        self._cols = RequestColumns()
        self._cols_pool: List[RequestColumns] = []
        # per-fetch-thread response encode buffer (RpcJob.finish)
        self._tls = threading.local()
        # overlap accounting: cumulative per-stage busy seconds and the
        # wall time the pipeline spent non-idle (in_flight > 0).  The
        # overlap ratio Σbusy/active_wall is 1.0 for a perfectly serial
        # pipeline and approaches the stage count under full overlap.
        self.stage_busy = {"host_encode": 0.0, "device_dispatch": 0.0,
                           "fetch_decode": 0.0}
        self.active_wall = 0.0
        self._active_since = 0.0
        # why the pump is not dispatching right now, since when, and the
        # seconds every reason has held so far (loop thread only)
        self._hold_reason: Optional[str] = None
        self._hold_since = 0.0
        self.pump_hold = dict.fromkeys(PUMP_HOLD_REASONS, 0.0)
        # drains dispatched per lane width (engine thread; see _drain_lanes)
        self.drain_widths = dict.fromkeys(engine._lane_bucket_list, 0)
        # drains by how many others were in flight when the pump let them
        # go (loop thread; see _note_dispatch)
        self.drain_overlap = dict.fromkeys(DRAIN_AHEAD, 0)
        # reply_wake of the requests that have resumed since the last
        # commit (plain floats; the next commit flushes them)
        self._wake_seconds = 0.0
        self._wake_requests = 0
        # lockstep lane (engine thread): the config this pipeline last
        # staged for each GLOBAL slot (an unchanged one takes no update
        # lane), and parsed GLOBAL items by their message bytes (a key's
        # requests repeat byte for byte)
        self._gcfg_seen: dict = {}
        self._gparse: dict = {}
        # ticks by what they did, GLOBAL items staged and deferred, and
        # decisions by how they came (loop thread; /v1/admin/debug)
        self.lockstep_ticks = dict.fromkeys(LOCKSTEP_TICK_KINDS, 0)
        self.global_items = {"staged": 0, "deferred": 0}
        self.lane_decisions = dict.fromkeys(LOCKSTEP_LANES, 0)
        self._singles: List[tuple] = []   # (req, fut, t_enq, ctx, col_idx)
        # GLOBAL singles (lockstep mode only): staged into the tick drain's
        # composed GLOBAL window, never mixed into regular ListJobs
        self._gsingles: List[tuple] = []  # (req, fut)
        self._jobs: List[object] = []     # FIFO of RpcJob/ListJob
        self._in_flight = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # observability: RPCs fully served by this lane (tests assert the
        # lane actually engaged rather than silently falling back)
        self.rpc_served = 0
        # duplicate-run aggregation telemetry (engine-thread only):
        # decisions_staged / lanes_staged = the fold factor
        self.decisions_staged = 0
        self.lanes_staged = 0
        # strong refs to every in-flight delivery-path task (the loop keeps
        # only weak ones; a GC'd task would hang the futures it owes)
        self._tasks: set = set()
        # Submit-side coalescing (the reference's 500µs BatchWait,
        # config.go:60-62): when drain slots are FREE and the queue is
        # small, wait up to coalesce_wait for more arrivals instead of
        # dispatching a tiny drain.  Every fetch pays a fixed cost
        # regardless of size, so drains-per-fetch-slot matters: a herd of
        # single-item RPCs otherwise burns the fetch pool on near-empty
        # drains.
        # Saturated mode is unaffected: completion callbacks pump with
        # force=True, so at depth the cadence is completion-driven.
        # The batcher overrides coalesce_wait with the configured
        # BehaviorConfig.batch_wait (this default mirrors its default).
        self.coalesce_wait = 0.0005
        self.coalesce_min = MAX_BATCH_SIZE  # decisions that skip the wait
        self._coalesce_handle = None
        # Deferred-fetch dispatch chain (ROADMAP item 1): successive drains
        # already chain on-device through the donated state carry — the
        # blocking D2H fetch is the ONLY per-drain round trip.  With
        # stride N the pipeline keeps up to N dispatched drains pending
        # fetch and issues ONE stacked device_get for the whole group,
        # committing every member in dispatch order through the same
        # ordered completion queue (bit-identical to stride 1; see
        # tests/test_fetch_chain.py).  GUBER_FETCH_STRIDE is the floor the
        # operator pins (1 = fetch every drain, today's behavior);
        # GUBER_FETCH_STRIDE_MAX caps how far the AIMD stride controller
        # (qos/congestion.py observe_chain) may grow it as backlog
        # deepens.  Lockstep mode never chains: the tick's collective
        # sequence commits each drain on its own tick.
        self.fetch_stride = max(1, env_int("GUBER_FETCH_STRIDE",
                                           FETCH_STRIDE_DEFAULT))
        self.fetch_stride_max = max(self.fetch_stride,
                                    env_int("GUBER_FETCH_STRIDE_MAX",
                                            FETCH_STRIDE_MAX_DEFAULT))
        # linger backstop: a chained drain held behind the occupancy gate
        # (queued work too small to dispatch) must still commit promptly
        self.chain_linger = env_float("GUBER_CHAIN_LINGER_MS",
                                      CHAIN_LINGER_MS_DEFAULT) / 1000.0
        self._stride_target = 1 if self.lockstep else self.fetch_stride
        self._chain: List[_DrainResult] = []  # loop-owned, dispatch order
        self._chain_timer = None
        # drains pumped but not yet through _on_dispatched: the only
        # drains that can still JOIN the chain.  (A drain mid-fetch is in
        # flight too but will never chain — idle decisions must not wait
        # on it.)
        self._predispatch = 0
        # observability: fetches the chain elided, flush count
        self.fetch_elided = 0
        self.chain_flushes = 0

    def _spawn(self, coro) -> None:
        """create_task with a strong reference held until completion."""
        t = self._loop.create_task(coro)
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)

    def _resp_buf(self, size: int) -> np.ndarray:
        """This fetch thread's reusable proto-encode buffer (grown to
        fit; callers bytes()-copy out before returning)."""
        buf = getattr(self._tls, "buf", None)
        if buf is None or buf.nbytes < size:
            buf = self._tls.buf = np.empty(
                max(size, MAX_BATCH_SIZE * 64 + 64), np.uint8)
        return buf

    def _note_inflight(self, delta: int) -> None:
        """All in-flight transitions route through here (event loop only):
        keeps the gauge, the QoS admission view, and the pipeline-active
        wall clock (overlap denominator) consistent."""
        self._in_flight += delta
        now = time.monotonic()
        if delta > 0 and self._in_flight == 1:
            self._active_since = now
        elif delta < 0 and self._in_flight == 0 and self._active_since:
            self.active_wall += now - self._active_since
            self._active_since = 0.0
        if self.metrics is not None:
            self.metrics.pipeline_inflight_windows.set(self._in_flight)
        if self.qos is not None:
            self.qos.admission.note_inflight(self._in_flight)

    def overlap_snapshot(self) -> dict:
        """Point-in-time overlap statistics (admin introspection + the
        open-loop probe, scripts/probe_overlap.py): per-stage busy
        seconds, pipeline-active wall seconds, and their ratio."""
        wall = self.active_wall
        if self._active_since:
            wall += time.monotonic() - self._active_since
        busy = sum(self.stage_busy.values())
        return {
            "stage_busy_seconds": dict(self.stage_busy),
            "active_wall_seconds": wall,
            "overlap_ratio": (busy / wall) if wall > 0 else 0.0,
            "inflight_windows": self._in_flight,
            "arena_reuse_events": self._arena_ring.reuse_events,
            "arena_alloc_events": self._arena_ring.alloc_events,
            "fetch_stride_target": self._stride_target,
            "chained_pending": len(self._chain),
            "fetch_elided": self.fetch_elided,
            "chain_flushes": self.chain_flushes,
        }

    def install_ring(self, points, peer_of, peers, self_idx) -> None:
        """Install the cluster ring (engine thread): the C parser's point
        table and the aligned PeerClient list for forwards.  Empty points
        clears back to standalone (everything local)."""
        self.engine.native.set_ring(points, peer_of, self_idx)
        self._ring_peers = tuple(peers)

    # ------------------------------------------------------------ submit API

    async def submit_rpc(self, data: bytes,
                         peer_mode: bool = False) -> Optional[bytes]:
        """Serve a whole serialized GetRateLimitsReq (or, with peer_mode,
        a GetPeerRateLimitsReq — same wire shape — authoritatively); None
        => the caller must run the full Python path."""
        if not (self.enabled and self.rpc_enabled
                and self.engine._compact_enabled) or self._closed:
            return None
        self._loop = asyncio.get_running_loop()
        fut = self._loop.create_future()
        job = RpcJob(data, fut, peer_mode=peer_mode)
        job.enq = time.monotonic()
        job.ctx = current_context()
        if self.tracer is not None and job.ctx is not None:
            job.ctx.enqueued_at = job.enq
            self.tracer.record_span(job.ctx, "enqueue", job.enq, job.enq)
        self._jobs.append(job)
        self._pump()
        out = await fut
        self._woke(job, job.ctx)
        return out

    async def submit_cols(self, cols: tuple, n: int,
                          want_cols: bool = False,
                          ctx=None) -> Optional[bytes]:
        """Serve worker-parsed GetRateLimitsReq COLUMNS (the frontdoor shm
        lane): (key_bytes, key_ends, hits, limits, durations, algos) views
        into the worker's slab pack-stack directly — parsed once, in the
        worker, never re-materialized as Python objects.  With want_cols
        the job resolves to DECISION columns for a complete_cols
        completion (worker-side encode) instead of engine-encoded bytes.
        None => the hub must run the engine-side Python fallback.  COLS
        is only sound standalone: pack_stack_fast never consults the
        ring, so installed peers force the fallback (the hub mirrors this
        gate into the status block so workers stop sending COLS records
        at all)."""
        if not (self.enabled and self.rpc_enabled
                and self.engine._compact_enabled) or self._closed:
            return None
        if self._ring_peers:
            return None
        self._loop = asyncio.get_running_loop()
        fut = self._loop.create_future()
        job = ColsJob(cols, n, fut, want_cols=want_cols)
        job.enq = time.monotonic()
        if ctx is not None:
            # frontdoor-propagated traceparent (shm trace region): root the
            # engine's drain spans under the caller's trace exactly like
            # submit_rpc does for in-process contexts
            job.ctxs = [ctx]
            if self.tracer is not None:
                ctx.enqueued_at = job.enq
                self.tracer.record_span(ctx, "enqueue", job.enq, job.enq)
        self._jobs.append(job)
        self._pump()
        out = await fut
        self._woke(job, ctx)
        return out

    async def submit_one(self, req: RateLimitReq) -> RateLimitResp:
        self._loop = asyncio.get_running_loop()
        fut = self._loop.create_future()
        t_enq = time.monotonic()
        ctx = current_context()
        if self.tracer is not None and ctx is not None:
            ctx.enqueued_at = t_enq
            self.tracer.record_span(ctx, "enqueue", t_enq, t_enq)
        if req.behavior == Behavior.GLOBAL:
            # only reachable through eligible_global (lockstep mode):
            # GLOBAL singles keep their own queue so regular ListJobs
            # never mix behaviors (the C router shard-routes by key hash;
            # GLOBAL lanes spread round-robin instead)
            self._gsingles.append((req, fut))
        else:
            # columnar accumulation at submit time: the drain takes window
            # columns as slices of self._cols instead of re-walking
            # request objects (core/window_buffers.py)
            self._singles.append((req, fut, t_enq, ctx,
                                  self._cols.append(req)))
        self._pump()
        out = await fut
        # _take_jobs hung the chunk this request rode in on its future
        self._woke(getattr(fut, "job", None), ctx)
        return out

    async def submit_many(self, reqs: Sequence[RateLimitReq]
                          ) -> List[RateLimitResp]:
        self._loop = asyncio.get_running_loop()
        fut = self._loop.create_future()
        ctx = current_context()
        job = ListJob(reqs, fut=fut,
                      ctxs=[ctx] if ctx is not None else None,
                      enq=time.monotonic())
        self._jobs.append(job)
        self._pump()
        out = await fut
        self._woke(job, ctx)
        return out

    def _woke(self, job, ctx) -> None:
        """The coroutine that awaited a request runs again (loop thread):
        reply_wake = its drain's commit → now, added to plain floats that
        the next commit flushes into the counters.  A request that never
        rode a committed drain (fallback, GLOBAL single) has no stamp."""
        if job is None or not job.committed:
            return
        now = time.monotonic()
        self._wake_seconds += now - job.committed
        self._wake_requests += 1
        if ctx is not None and self.tracer is not None:
            self.tracer.record_span(ctx, "reply_wake", job.committed, now)

    def flush_reply_wake(self) -> None:
        """Move the reply_wake of the requests that resumed since the last
        commit into guber_tpu_request_stage_*_total (loop thread; every
        commit and close() call it)."""
        n, seconds = self._wake_requests, self._wake_seconds
        self._wake_requests, self._wake_seconds = 0, 0.0
        if n and self.metrics is not None:
            self.metrics.observe_request_stage("reply_wake", seconds, n)

    def eligible(self, req: RateLimitReq) -> bool:
        """May this request ride the pipeline?  Mirrors the C-side range
        checks exactly, so a pipeline job never range-falls-back.

        Lockstep (mesh) mode gates on _compact_sound — per-host staging
        soundness — instead of _compact_enabled (which is off for mesh
        legacy dispatch), and additionally requires the key to route to
        THIS process's shards (mis-routed keys take the legacy lane,
        which fails them individually with the routing error)."""
        if not (self.enabled
                and not self._closed
                and req.behavior != Behavior.GLOBAL
                and req.algorithm in (Algorithm.TOKEN_BUCKET,
                                      Algorithm.LEAKY_BUCKET)
                and 0 <= req.hits < kernel.COMPACT_MAX_HITS
                and 0 <= req.limit < kernel.COMPACT_MAX_LIMIT
                and 0 <= req.duration < kernel.COMPACT_MAX_DURATION):
            return False
        if self.lockstep:
            return (self.engine._compact_sound
                    and self.engine.routing_error(req) is None)
        return self.engine._compact_enabled

    def eligible_global(self, req: RateLimitReq) -> bool:
        """May this GLOBAL request ride the lockstep drain's composed
        GLOBAL window?  Lockstep mode only: there the tick's drain
        executable (engine.pipeline_dispatch_global) carries full-format
        GLOBAL lanes and one reconciliation psum per drain, so GLOBAL
        singles no longer need the legacy step.  No compact range checks —
        GLOBAL lanes are exempt (full wire format).  Outside lockstep mode
        GLOBAL traffic keeps the legacy path (the non-lockstep drain
        dispatches the collective-free regular executable)."""
        if not (self.enabled
                and self.lockstep
                and not self._closed
                and req.behavior == Behavior.GLOBAL
                and req.algorithm in (Algorithm.TOKEN_BUCKET,
                                      Algorithm.LEAKY_BUCKET)):
            return False
        return self.engine.routing_error(req) is None

    # ------------------------------------------------------------ pump

    def _take_jobs(self) -> tuple:
        """Snapshot pending work into drain jobs (loop thread).  Returns
        (jobs, cols_owner): cols_owner is the detached RequestColumns the
        singles chunks slice from — it belongs to THIS drain until its
        completion releases it back to the pool (ListJob.finish still
        reads the limit column on the fetch thread)."""
        jobs: List[object] = []
        cols_owner = None
        if self._singles:
            singles, self._singles = self._singles, []
            cols_owner = self._cols
            self._cols = (self._cols_pool.pop() if self._cols_pool
                          else RequestColumns())
            if self.qos is not None:
                if self.qos.fair_slotting:
                    # tenant-fair lane filling: a hot tenant's burst must
                    # not occupy every lane of the drain (stable within
                    # tenant, so per-key order is preserved)
                    singles = interleave_by_tenant(
                        singles, lambda t: tenant_of(t[0]))
                # the congestion window caps decisions-per-drain; the
                # excess stays queued and rides the next pump (completion
                # callbacks re-pump with force=True)
                budget = self.qos.congestion.effective_window()
                if len(singles) > budget:
                    singles, deferred = (singles[:budget],
                                         singles[budget:])
                    # the deferred tail re-accumulates into the NEW
                    # columns (its old indices die with cols_owner)
                    self._singles = [
                        (req, fut, t_enq, ctx, self._cols.append(req))
                        for req, fut, t_enq, ctx, _ in deferred]
            for base in range(0, len(singles), MAX_BATCH_SIZE):
                chunk = singles[base:base + MAX_BATCH_SIZE]
                job = ListJob([t[0] for t in chunk],
                              futs=[t[1] for t in chunk],
                              ctxs=[t[3] for t in chunk],
                              enq=min(t[2] for t in chunk))
                job.enq_lag = (sum(t[2] for t in chunk)
                               - len(chunk) * job.enq)
                for t in chunk:
                    t[1].job = job  # submit_one reads job.committed
                # zero-copy when the chunk is contiguous in submission
                # order (the common no-QoS case); a tenant-fair or
                # budget-cut permutation gathers instead
                idx = np.fromiter((t[4] for t in chunk), np.int64,
                                  len(chunk))
                if len(idx) == 1 or bool((np.diff(idx) == 1).all()):
                    job._cols = cols_owner.take(None, int(idx[0]),
                                                int(idx[-1]) + 1)
                else:
                    job._cols = cols_owner.take(idx, 0, len(idx))
                jobs.append(job)
        jobs.extend(self._jobs)
        self._jobs = []
        return jobs, cols_owner

    def _cols_release(self, cols) -> None:
        """Return a drain's RequestColumns to the pool (loop thread, at
        completion).  Unlike arenas there is no transfer-safety concern —
        the device never reads these buffers (pack copies into the arena
        synchronously) — so error paths release too."""
        if cols is None:
            return
        cols.reset()
        if len(self._cols_pool) < 4:
            self._cols_pool.append(cols)

    def _pump(self, force: bool = False) -> None:
        if self.lockstep:
            return  # drains happen only on the cluster tick (lockstep_pump)
        depth = (self.depth if self.qos is None
                 else self.qos.congestion.effective_depth(self.depth))
        stride = self._stride_target = self._stride_current()
        if stride > 1:
            # the chain needs stride drains pending fetch PLUS one being
            # packed/dispatched, or it could never reach its stride
            depth = max(depth, stride + 1)
        if self._closed:
            return
        if self._held(depth):
            return
        if not force and self.coalesce_wait > 0:
            if 0 < self._pending_decisions() < self.coalesce_min:
                if self._coalesce_handle is None:
                    self._coalesce_handle = self._loop.call_later(
                        self.coalesce_wait, self._coalesce_fire)
                self._note_hold("coalesce")
                return
        if self._coalesce_handle is not None:
            self._coalesce_handle.cancel()
            self._coalesce_handle = None
        jobs, cols = self._take_jobs()
        if not jobs:
            self._cols_release(cols)
            if self._chain and self._predispatch == 0:
                # nothing queued and nothing still heading for dispatch:
                # no drain can join the chain anymore, so holding it only
                # adds latency (e.g. a prior unchained drain just
                # committed and re-pumped an empty queue)
                self._chain_flush()
            self._note_hold("empty")
            return
        self._note_dispatch()
        fut = self._loop.run_in_executor(self._engine_executor,
                                         self._drain_sync, jobs, None, None,
                                         None, cols, time.monotonic())
        fut.add_done_callback(lambda f: self._on_dispatched(f, jobs))

    def _pending_decisions(self) -> int:
        """Decisions queued behind the pipeline (loop thread).  RpcJobs are
        unparsed here: their items are estimated from the wire size (an
        item is >= ~16 B, so this overestimates: a big RPC always counts
        as a batch)."""
        return (len(self._singles) + len(self._gsingles)
                + sum(len(j.data) // 16 if isinstance(j, RpcJob)
                      else j.n for j in self._jobs))

    def _held(self, depth: int) -> bool:
        """Do the pipeline's depth or its occupancy gate hold the next
        dispatch back?  Notes the reason when they do (loop thread)."""
        if self._in_flight >= depth:
            # full, with work behind it: held by depth.  Full with nothing
            # queued is no hold at all.
            self._note_hold("depth" if self._singles or self._jobs
                            or self._gsingles else None)
            return True
        if self.gate_enabled and self._in_flight >= 1:
            # occupancy gate: a drain is already hiding the device time.
            # Under one batch a drain's cost is fixed, so what is queued
            # goes on accumulating (`gate`); with the engine thread still
            # on the drain before, a drain pumped now would only wait in
            # its executor with its jobs already taken (`engine`).  No
            # timer needed: _on_dispatched and the in-flight drain's
            # completion re-pump (in lockstep the next tick asks again),
            # and at in_flight == 0 the gate is off — it can never strand
            # work.
            pending = self._pending_decisions()
            if pending < self.coalesce_min:
                self._note_hold("gate" if pending else "empty")
                return True
            if self._predispatch >= 1:
                self._note_hold("engine")
                return True
        return False

    def _note_dispatch(self) -> None:
        """The pump lets a drain go (loop thread): the hold ends, the drain
        counts under how many others are in flight ahead of it
        (guber_tpu_drain_overlap_total; DRAIN_AHEAD's last value stands
        for itself and more), and it is in flight and heading for the
        engine thread."""
        self._note_hold(None)
        ahead = DRAIN_AHEAD[min(self._in_flight, len(DRAIN_AHEAD) - 1)]
        self.drain_overlap[ahead] += 1
        if self.metrics is not None:
            self.metrics.drain_overlap.labels(ahead=ahead).inc()
        self._note_inflight(1)
        self._predispatch += 1

    def _note_hold(self, reason: Optional[str]) -> None:
        """_pump returns without dispatching for `reason`, or dispatches
        (None).  The reason and its start are noted once; the seconds go
        to guber_tpu_pump_hold_seconds_total{reason} when the reason
        changes or a dispatch ends the hold.  `empty` runs only while
        nothing is queued and fewer drains are in flight than the depth
        allows: every submit pumps, and that pump either dispatches or
        names another reason."""
        held = self._hold_reason
        if reason == held:
            return
        now = time.monotonic()
        if held is not None:
            seconds = now - self._hold_since
            self.pump_hold[held] += seconds
            if self.metrics is not None:
                self.metrics.pump_hold_seconds.labels(
                    reason=held).inc(seconds)
        self._hold_reason, self._hold_since = reason, now

    def pump_hold_snapshot(self) -> dict:
        """Seconds held per reason so far, the running hold included."""
        out = dict(self.pump_hold)
        if self._hold_reason is not None:
            out[self._hold_reason] += time.monotonic() - self._hold_since
        return out

    def _coalesce_fire(self) -> None:
        self._coalesce_handle = None
        self._pump(force=True)

    # ------------------------------------------------------------ fetch chain

    def _stride_current(self) -> int:
        """Drains per stacked fetch the chain should target right now
        (loop thread; the engine thread reads the cached _stride_target).
        Floor = the operator-pinned GUBER_FETCH_STRIDE; the AIMD stride
        controller may grow it with backlog up to GUBER_FETCH_STRIDE_MAX,
        but never past the admission deadline bound — a chained drain's
        oldest member must still commit inside the propagated deadline,
        so thundering-herd p99 stays bounded instead of scaling with the
        chain."""
        if self.lockstep:
            return 1
        if self.fetch_stride_max <= 1 or self.qos is None:
            return min(self.fetch_stride, self.fetch_stride_max)
        cc = self.qos.congestion
        stride = max(self.fetch_stride, cc.effective_stride())
        bound = cc.stride_bound(self.qos.conf.default_deadline)
        return max(1, min(stride, self.fetch_stride_max, bound))

    def _backlog_windows(self) -> float:
        """Queued decisions behind the pipeline, in window units (loop
        thread) — the stride controller's growth signal."""
        fold = (self.decisions_staged / self.lanes_staged
                if self.lanes_staged > MAX_BATCH_SIZE else 1.0)
        eng = self.engine
        lanes = eng.batch_per_shard * eng.num_local_shards
        return (self._pending_decisions() / max(fold, 1.0)) / max(lanes, 1)

    def _chain_add(self, res: _DrainResult) -> None:
        """Append a dispatched-but-unfetched drain to the chain (loop
        thread).  Flush when the stride is reached, or when nothing else
        is coming — an empty queue with no drain still heading for
        dispatch means waiting only adds latency, so light load
        degenerates to stride 1 (the depth-1 oracle's cadence).  Work
        held back by the occupancy gate re-arms the linger timer as the
        backstop: a chained commit is never more than chain_linger late."""
        self._chain.append(res)
        idle = (not self._jobs and not self._singles
                and self._predispatch == 0)
        if len(self._chain) >= self._stride_target or idle or self._closed:
            self._chain_flush()
        elif self._chain_timer is None:
            self._chain_timer = self._loop.call_later(
                self.chain_linger, self._chain_flush)

    def _chain_flush(self) -> None:
        """Issue ONE stacked fetch for every chained drain (loop thread).
        The group commits in dispatch order — the chain list preserves
        it, and _on_chain_completed walks it front to back through the
        same ordered completion queue as unchained drains."""
        if self._chain_timer is not None:
            self._chain_timer.cancel()
            self._chain_timer = None
        if not self._chain:
            return
        group, self._chain = self._chain, []
        self.chain_flushes += 1
        self.fetch_elided += len(group) - 1
        if self.metrics is not None:
            self.metrics.chain_fetch_stride.set(self._stride_target)
        if self.qos is not None:
            self.qos.congestion.observe_chain(self._backlog_windows(),
                                              self.fetch_stride_max)
        cfut = self._loop.run_in_executor(self._fetch_executor,
                                          self._complete_chain_sync, group)
        cfut.add_done_callback(lambda f: self._on_chain_completed(f, group))

    def _complete_chain_sync(self, group: List[_DrainResult]) -> list:
        """Fetch thread: ONE device_get materializes every chained
        drain's response words and mismatch planes (engine
        fetch_stacked_many), then each member demuxes in dispatch order.
        The members' device time already overlapped at dispatch (donated
        state chains them on-device); this collapses their N fetch round
        trips into one."""
        t0 = time.monotonic()
        eng = self.engine
        B = eng.batch_per_shard
        arrs: List[object] = []
        for res in group:
            if res.words is not None:
                arrs.extend((res.words, res.mism))
        fetched = iter(eng.fetch_stacked_many(arrs) if arrs else ())
        t_fetched = time.monotonic()
        pairs = []
        for res in group:
            # stage accounting: the SHARED stacked fetch is its own
            # (chain_fetch) window — charging its full wall time to every
            # member's drain_commit would over-count it stride× in the
            # stage sums (tests/test_tracing.py asserts the accounting at
            # stride 4).  Each member's drain_commit covers only its own
            # demux.
            res.chain_fetch_start = t0
            res.chain_fetch_done = t_fetched
            res.fetch_start = time.monotonic()
            if res.words is None:  # all-forwarded member: nothing local
                wflat = np.empty((0, B), np.int64)
                clflat = None
            else:
                words = np.ascontiguousarray(next(fetched))
                mism = next(fetched)
                wflat, clflat = self._flat_outputs(res, words, mism)
            if res.stats is not None:
                # same contract as _complete_sync: analytics must never
                # fail a drain, so its fetch stays separately guarded
                # (the async copy landed long ago — this is near-free)
                try:
                    res.stats_host = eng._fetch_local(res.stats)
                except Exception:
                    log.exception("analytics stats fetch failed")
            res.fetch_ready = time.monotonic()
            with TraceAnnotation("guber_decode"):
                outs = [job.finish(self, wflat, clflat, res.now)
                        for job in res.staged]
            res.fetch_done = time.monotonic()
            pairs.append((res, outs))
        return pairs

    def _on_chain_completed(self, fut, group: List[_DrainResult]) -> None:
        """Loop thread: commit every chained member in dispatch order
        through the same completion path as an unchained drain.  A failed
        group fetch fails EVERY member's jobs — one stacked fetch means
        one failure domain, and none of the members' arenas can prove the
        device finished with them (all dropped)."""
        t_cb = time.monotonic()
        try:
            pairs = fut.result()
        except Exception as e:
            log.exception("pipeline chain fetch failed")
            for res in group:
                self._fail_completed(res, e)
            return
        if self.metrics is not None and pairs:
            # ONE shared-fetch observation per group (not per member):
            # stage_snapshot appends non-canonical stages after STAGES, so
            # chain_fetch shows up in /v1/admin/debug without widening the
            # canonical per-request stage set
            head = pairs[0][0]
            if head.chain_fetch_done > head.chain_fetch_start:
                self.metrics.observe_stage(
                    "chain_fetch",
                    head.chain_fetch_done - head.chain_fetch_start)
        for res, outs in pairs:
            res.completed_cb = t_cb
            self._commit_completed(res, outs)

    def _take_global_job(self) -> Optional[_GlobalJob]:
        """Snapshot the queued GLOBAL singles into one _GlobalJob for this
        tick's drain (loop thread).  Invalid requests (unregistered GLOBAL
        key in non-dynamic mesh mode) fail individually here — mirroring
        the batcher's _take_window — so staging can never raise for them
        on the engine thread.  At most a window's GLOBAL lanes are taken
        (equal requests share a lane, so all may well fit); what the
        window then has no lane for comes back through res.gdeferred and
        rides the NEXT tick, at the queue's front."""
        if not self._gsingles:
            return None
        eng = self.engine
        cap = eng.num_local_shards * eng.global_batch_per_shard
        items, self._gsingles = self._gsingles[:cap], self._gsingles[cap:]
        ok: List[tuple] = []
        for r, f in items:
            err = eng.routing_error(r)
            if err is None:
                ok.append((r, f))
            elif not f.done():
                f.set_exception(ValueError(err))
        if not ok:
            return None
        return _GlobalJob([r for r, _ in ok], [f for _, f in ok])

    def lockstep_pump(self, now: int, k_stack: int, must: bool = True):
        """This tick's drain (mesh mode, event loop).  `must`: other hosts
        wait on this one's collectives, so the dispatch ALWAYS happens —
        the drain executable is slot 1 of the tick's collective sequence
        on every process, staged lanes or not.  A process that holds every
        shard dispatches only what a standalone pump would: something is
        queued, and neither the pipeline's depth nor its occupancy gate
        holds it; otherwise the tick costs no device work and None comes
        back.  The drain runs on the single-thread engine executor, so the
        caller orders the tick's legacy dispatch after it by submitting
        second.  Returns the dispatch future: awaiting it surfaces an
        irrecoverable dispatch failure (collective desync) for the
        batcher's fail-stop."""
        assert self.lockstep
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
        if not must:
            if self._closed:
                return None
            if not (self._jobs or self._singles or self._gsingles):
                self._note_hold("empty" if self._in_flight < self.depth
                                else None)
                return None
            if self._held(self.depth):
                return None
        jobs, cols = self._take_jobs() if not self._closed else ([], None)
        gjob = self._take_global_job() if not self._closed else None
        all_jobs = jobs + ([gjob] if gjob is not None else [])
        self._note_dispatch()
        pumped = time.monotonic()
        fut = self._loop.run_in_executor(
            self._engine_executor,
            lambda: self._drain_sync(jobs, now=now, k_fixed=k_stack,
                                     gjob=gjob, cols=cols, pumped=pumped))
        fut.add_done_callback(lambda f: self._on_dispatched(f, all_jobs))
        return fut

    def _on_dispatched(self, fut, jobs) -> None:
        t_cb = time.monotonic()
        self._predispatch -= 1
        try:
            res: _DrainResult = fut.result()
            res.dispatched_cb = t_cb
        except Exception as e:  # drain itself crashed (bug): fail ITS jobs
            log.exception("pipeline drain failed")
            self._note_inflight(-1)
            for job in jobs:
                self._resolve_error(job, e)
            self._chain_flush()
            self._pump(force=True)
            return
        # fallback jobs re-route outside the pipeline
        for job in res.fallback:
            self._route_fallback(job)
        if res.gdeferred:
            # GLOBAL singles this tick's window had no lane for: first in
            # line for the next tick
            self._gsingles[:0] = res.gdeferred
            self.global_items["deferred"] += len(res.gdeferred)
            if self.metrics is not None:
                self.metrics.global_deferred.inc(len(res.gdeferred))
        # leftover jobs did not fit this stack: front of the queue.  A
        # leftover singles chunk borrows column views from THIS drain's
        # cols_owner, which is released at completion — materialize copies
        # so the repack (a later drain) never reads recycled buffers.
        if res.leftover:
            for job in res.leftover:
                cols = getattr(job, "_cols", None)
                if cols is not None:
                    job._cols = cols[:2] + tuple(np.array(c)
                                                 for c in cols[2:])
            self._jobs[:0] = res.leftover
        if res.error is not None:
            self._note_inflight(-1)
            self._cols_release(res.cols_owner)
            for job in res.staged:
                self._resolve_error(job, res.error)
            # a dispatch fault breaks the chain's cadence: commit the
            # members already in flight now instead of lingering
            self._chain_flush()
            self._pump(force=True)
            return
        if not res.staged:
            self._note_inflight(-1)
            self._cols_release(res.cols_owner)
            if not self.lockstep:
                # nothing staged ⇒ nothing dispatched against the arena:
                # safe to recycle immediately.  (A lockstep idle tick DOES
                # dispatch its all-zero stack — there the arena is simply
                # dropped, matching the old fresh-allocation cost.)
                self._arena_ring.release(res.arena)
            res.arena = None
            self._pump(force=True)
            return
        # start forwards for cluster-mode mixed RPCs NOW, so the peer round
        # trips overlap the local stack's fetch.  Forwards COALESCE across
        # every mixed RPC of the drain: one relay per owner per drain (the
        # reference aggregates per-peer across requests the same way,
        # peers.go:143-172)
        mixed = [j for j in res.staged
                 if isinstance(j, RpcJob) and len(j.remote_idx)]
        if mixed and self.lockstep:
            # no ring in a mesh: these are GLOBAL items deferred to a
            # later tick, answered through the singles' queue
            for job in mixed:
                self._spawn_global_deferred(job)
        elif mixed:
            self._spawn_forwards(mixed, res.ring_peers)
        if res.deferred:
            # deferred-fetch chain: no fetch was submitted for this drain —
            # it joins the chain and ONE stacked fetch commits the whole
            # group every stride windows.  Forwards (above) were spawned
            # first, so a mixed member's splice finds its forward_task.
            self._chain_add(res)
            self._pump(force=True)
            return
        if res.cfut is not None:
            # fetch was already submitted from the engine thread at the end
            # of the drain (hop cut: no event-loop round trip between
            # dispatch and fetch).  Completion still lands on the loop —
            # the single ordered completion queue — via call_soon_threadsafe.
            res.cfut.add_done_callback(
                lambda f: self._loop.call_soon_threadsafe(
                    self._on_completed, f, res))
        else:
            cfut = self._loop.run_in_executor(self._fetch_executor,
                                              self._complete_sync, res)
            cfut.add_done_callback(lambda f: self._on_completed(f, res))
        # a second drain may dispatch while this one's fetch is in flight
        self._pump(force=True)

    def _spawn_forwards(self, jobs: List[RpcJob], ring_peers) -> None:
        """Forward the drain's remote items to their ring owners as spliced
        BYTES: per owner, every mixed RPC's serialized RateLimitReq frames
        concatenate into one GetPeerRateLimitsReq (same field-1 framing) —
        the reference's per-peer batch relay (peers.go:143-207) without
        materializing a single Python protobuf object.  Each job's
        forward_task resolves ({item_index: framed RateLimitResp bytes},
        per-item error semantics) as soon as ITS items are answered, so one
        slow owner delays only the RPCs that actually touched it."""
        from gubernator_tpu.api import pb

        by_owner: dict = {}
        pending: dict = {}
        results: dict = {}
        n_fwd = 0
        for job in jobs:
            job.forward_task = self._loop.create_future()
            pending[id(job)] = len(job.remote_idx)
            results[id(job)] = {}
            n_fwd += len(job.remote_idx)
            for i in job.remote_idx.tolist():
                by_owner.setdefault(-2 - int(job.row[i]),
                                    []).append((job, int(i)))
        if self.metrics is not None and n_fwd:
            self.metrics.cluster_forwarded.inc(n_fwd)

        def deliver(job, i, frame):
            jid = id(job)
            results[jid][i] = frame
            pending[jid] -= 1
            if pending[jid] == 0 and not job.forward_task.done():
                job.forward_task.set_result(results[jid])

        async def one_chunk(owner_idx, items):
            # EVERYTHING is inside the try: forward_task has no
            # set_exception path by design (the error contract is
            # per-item), so any escape here — bad owner index from a
            # shrunk ring, corrupt staging values — would otherwise leave
            # the jobs' futures unresolved forever
            peer = None
            try:
                peer = ring_peers[owner_idx]
                body = b"".join(
                    b"\x0a" + _varint(int(job.mlen[i]))
                    + job.data[int(job.off[i]):
                               int(job.off[i]) + int(job.mlen[i])]
                    for job, i in items)
                resp = await peer.get_peer_rate_limits_raw(body)
                frames = _walk_frames(resp)
                if len(frames) != len(items):
                    raise RuntimeError(
                        "number of rate limits in peer response does not "
                        "match request")
                for (job, i), fr in zip(items, frames):
                    deliver(job, i, _append_owner(fr, peer.host))
            except BaseException as e:  # noqa: BLE001 — nothing may
                # escape without resolving the chunk's items: even
                # CancelledError (a BaseException) would otherwise strand
                # the jobs' forward futures forever
                host = getattr(peer, "host", f"ring#{owner_idx}")
                err = pb.RateLimitResp(
                    error=(f"while fetching rate limit from peer "
                           f"{host} - '{e}'")).SerializeToString()
                fr = _frame(err)
                for job, i in items:
                    deliver(job, i, fr)
                if not isinstance(e, Exception):
                    raise  # CancelledError / KeyboardInterrupt / SystemExit

        for owner_idx, items in by_owner.items():
            # the owner enforces the reference's 1000-item RPC cap
            for base in range(0, len(items), MAX_BATCH_SIZE):
                self._spawn(
                    one_chunk(owner_idx, items[base:base + MAX_BATCH_SIZE]))

    def _spawn_global_deferred(self, job: RpcJob) -> None:
        """A whole RPC's GLOBAL items that this tick's window had no lane
        for ride a later tick through the GLOBAL singles' queue; their
        framed answers splice into the RPC's response like a forwarded
        item's (_assemble_mixed), errors per item."""
        from gubernator_tpu.api import pb

        job.forward_task = self._loop.create_future()
        n = len(job.gdefer)
        self.global_items["deferred"] += n
        if self.metrics is not None:
            self.metrics.global_deferred.inc(n)

        async def run():
            frames = {}
            try:
                resps = await asyncio.gather(
                    *(self.submit_one(r) for _, r in job.gdefer),
                    return_exceptions=True)
                for (i, _), r in zip(job.gdefer, resps):
                    if not isinstance(r, RateLimitResp):
                        r = RateLimitResp(error=str(r))
                    frames[i] = _frame(pb.resp_to_pb(r).SerializeToString())
            finally:
                # nothing may escape without resolving the RPC's future
                err = _frame(pb.RateLimitResp(
                    error="pipeline closed").SerializeToString())
                for i, _ in job.gdefer:
                    frames.setdefault(i, err)
                if not job.forward_task.done():
                    job.forward_task.set_result(frames)

        self._spawn(run())

    def _on_completed(self, fut, res: _DrainResult) -> None:
        res.completed_cb = time.monotonic()
        try:
            _, outs = fut.result()
        except Exception as e:  # fetch/demux failed: fail THIS drain's jobs
            log.exception("pipeline fetch failed")
            self._fail_completed(res, e)
            return
        self._commit_completed(res, outs)

    def _fail_completed(self, res: _DrainResult, err: Exception) -> None:
        """Completion-path failure (loop thread): fail the drain's jobs.
        Shared by the single-drain and chained fetch paths."""
        self._note_inflight(-1)
        self._cols_release(res.cols_owner)
        res.cols_owner = None
        # the arena is NOT released: a failed fetch gives no proof the
        # device finished reading its buffers, so the ring self-heals
        # by allocating a replacement later
        res.arena = None
        if self.slo is not None:  # availability evidence: errored work
            self.slo.observe_error(max(1, res.n_decisions))
        for job in res.staged:
            self._resolve_error(job, err)
        self._pump(force=True)

    def _commit_completed(self, res: _DrainResult, outs) -> None:
        with TraceAnnotation("guber_commit"):
            self._commit(res, outs)

    def _commit(self, res: _DrainResult, outs) -> None:
        self._note_inflight(-1)
        self._cols_release(res.cols_owner)
        res.cols_owner = None
        # CLEAN completion: the fetch materialized the drain's outputs, so
        # the device provably consumed the staged stack — the arena may be
        # recycled for a future drain
        self._arena_ring.release(res.arena)
        res.arena = None
        if self.lockstep:
            self.lane_decisions["raw"] += res.n_raw
            self.lane_decisions["item"] += res.n_decisions - res.n_raw
            self.global_items["staged"] += res.n_global
        for job, out in zip(res.staged, outs):
            if isinstance(job, RpcJob):
                self.rpc_served += 1
                if job.forward_task is not None:
                    self._spawn(self._assemble_mixed(job, out, res.now))
                elif not job.fut.done():
                    job.fut.set_result(out)
            elif job.futs is not None:
                for f, r in zip(job.futs, out):
                    if not f.done():
                        f.set_result(r)
            else:
                if isinstance(job, ColsJob):
                    self.rpc_served += 1
                if not job.fut.done():
                    job.fut.set_result(out)
        res.committed = t_commit = time.monotonic()
        # one request's stages, summed over this drain's requests: each
        # job's own wait (a singles chunk is len(futs) requests, see
        # ListJob.enq_lag), and the stamp its coroutine measures
        # reply_wake from (no coroutine runs before this callback returns)
        n_req, queue_wait = 0, 0.0
        for job in res.staged:
            enq = getattr(job, "enq", 0.0)
            if not enq:
                continue  # a _GlobalJob carries no enqueue stamp
            futs = getattr(job, "futs", None)
            n = 1 if futs is None else len(futs)
            n_req += n
            queue_wait += (n * (res.started - enq)
                           - getattr(job, "enq_lag", 0.0))
            job.committed = t_commit
        # ONE clock for control and observability: the drain wall time is
        # the traced stage boundary (started→fetch_done), so the AIMD's
        # EWMA and the guber_tpu_stage_duration_ms histograms read the
        # same number for the same drain
        drain_wall = (res.fetch_done or time.monotonic()) - res.started
        # per-stage busy seconds: the overlap numerator, and the AIMD's
        # stage-boundary observe points (when pipelined, the cycle estimate
        # is the BOTTLENECK stage, not the stage sum — overlapped stages
        # hide behind the slowest one)
        t_he = res.pack_done - res.started if res.pack_done else 0.0
        t_disp = (res.dispatch_done - res.pack_done
                  if res.dispatch_done and res.pack_done else 0.0)
        t_fetch = (res.fetch_done - res.fetch_start
                   if res.fetch_done and res.fetch_start else 0.0)
        sb = self.stage_busy
        sb["host_encode"] += t_he
        sb["device_dispatch"] += t_disp
        sb["fetch_decode"] += t_fetch
        if self.qos is not None and res.n_decisions:
            self.qos.congestion.observe_drain(
                drain_wall, depth=max(1, res.k_used))
            self.qos.congestion.observe_stages(t_he, t_disp, t_fetch,
                                               pipelined=self.depth > 1)
        # traffic analytics + SLO evidence, from the same completion clock
        # the AIMD and stage histograms read
        if self.analytics is not None and res.stats_host is not None:
            try:
                self.analytics.ingest(res.stats_host, res.an_decay)
            except Exception:
                log.exception("analytics ingest failed")
        if self.slo is not None and (res.n_decisions or not self.lockstep):
            # idle lockstep ticks carry no serving evidence — feeding
            # their (fast, empty) drains into drain_p99 would let a
            # saturated-but-slow server hide behind idle ticks
            self.slo.observe_drain(drain_wall, res.n_decisions)
        if self.metrics is not None:
            m = self.metrics
            m.window_count.inc()
            m.window_occupancy.observe(res.n_decisions)
            m.window_duration.observe(drain_wall)
            m.agg_decisions.inc(res.n_decisions)
            m.agg_lanes.inc(res.n_lanes)
            if self.lockstep:
                m.lockstep_decisions.labels(lane="raw").inc(res.n_raw)
                m.lockstep_decisions.labels(lane="item").inc(
                    res.n_decisions - res.n_raw)
                m.global_decisions.inc(res.n_global)
            # stage-latency decomposition from the drain's boundary stamps
            # (0.0 boundary = never reached, e.g. an idle lockstep tick)
            if res.oldest_enq:
                m.observe_stage("admission_wait", res.started - res.oldest_enq)
            if res.pumped:
                m.observe_stage("engine_queue", res.started - res.pumped)
            if res.pack_done:
                m.observe_stage("window_fill", res.pack_done - res.started)
            if res.dispatch_done and res.pack_done:
                m.observe_stage("device_dispatch",
                                res.dispatch_done - res.pack_done)
            if res.dispatch_done and res.dispatched_cb:
                m.observe_stage("dispatch_hop",
                                res.dispatched_cb - res.dispatch_done)
            if res.fetch_done and res.fetch_start:
                if res.dispatch_done and not res.chain_fetch_start:
                    # a chained drain waits for its group, not for a worker
                    m.observe_stage("fetch_queue",
                                    res.fetch_start - res.dispatch_done)
                m.observe_stage("drain_commit",
                                res.fetch_done - res.fetch_start)
                if res.fetch_ready:
                    m.observe_stage("device_wait",
                                    res.fetch_ready - res.fetch_start)
                    m.observe_stage("decode",
                                    res.fetch_done - res.fetch_ready)
                if res.completed_cb:
                    m.observe_stage("complete_hop",
                                    res.completed_cb - res.fetch_done)
            if res.completed_cb:
                m.observe_stage("commit", t_commit - res.completed_cb)
            m.observe_request_stage("queue_wait", queue_wait, n_req)
            m.observe_request_stage("in_drain",
                                    n_req * (t_commit - res.started), n_req)
            self.flush_reply_wake()
        # window clock (observability/devprof.py): dispatch→fetch-ready
        # per executable arm, EWMA + histogram; slow windows capture
        # trace-ID exemplars lazily (the thunk only runs on a slow window)
        dc = self.devclock
        if (dc is not None and res.arm and res.dispatch_done
                and res.fetch_done):
            staged = res.staged
            def _trace_ids(_jobs=staged):
                ids = []
                for job in _jobs:
                    c = getattr(job, "ctx", None)
                    if c is not None:
                        ids.append(c.trace_id)
                    for c in (getattr(job, "ctxs", None) or ()):
                        if c is not None:
                            ids.append(c.trace_id)
                return ids[:4]
            dc.observe(res.arm, res.fetch_done - res.dispatch_done,
                       trace_ids=_trace_ids, windows=max(1, res.k_used))
        tr = self.tracer
        if tr is not None and tr.enabled:
            ctxs = set()
            for job in res.staged:
                c = getattr(job, "ctx", None)
                if c is not None:
                    ctxs.add(c)
                for c in (getattr(job, "ctxs", None) or ()):
                    if c is not None:
                        ctxs.add(c)
            for c in ctxs:
                if c.enqueued_at:
                    tr.record_span(c, "admission_wait", c.enqueued_at,
                                   res.started)
                    tr.record_span(c, "queue_wait", c.enqueued_at,
                                   res.started)
                tr.record_span(c, "in_drain", res.started, t_commit)
                if res.pack_done:
                    tr.record_span(c, "window_fill", res.started,
                                   res.pack_done)
                if res.dispatch_done and res.pack_done:
                    tr.record_span(c, "device_dispatch", res.pack_done,
                                   res.dispatch_done)
                if res.fetch_done and res.fetch_start:
                    tr.record_span(c, "drain_commit", res.fetch_start,
                                   res.fetch_done)
                if res.chain_fetch_done > res.chain_fetch_start:
                    # the SHARED stacked fetch window (deferred-fetch
                    # chain): one span per request context so stage sums
                    # reconcile with e2e at stride > 1
                    tr.record_span(c, "chain_fetch", res.chain_fetch_start,
                                   res.chain_fetch_done)
        self._pump(force=True)

    async def _assemble_mixed(self, job: RpcJob, local_parts, now) -> None:
        """Splice a mixed RPC's locally-encoded framed segments with its
        forwarded framed responses, positionally, into the final
        GetRateLimitsResp bytes."""
        try:
            seg_buf, item_off, item_len = local_parts
            fwd = await job.forward_task
            parts = []
            for i in range(job.n):
                if item_len[i]:
                    o = int(item_off[i])
                    parts.append(seg_buf[o:o + int(item_len[i])])
                else:
                    parts.append(fwd[i])
            if not job.fut.done():
                # reply_wake runs from the resolve, not from the drain's
                # commit: the wait for the owners is no wake-up latency
                if job.committed:
                    job.committed = time.monotonic()
                job.fut.set_result(b"".join(parts))
        except BaseException as e:  # noqa: BLE001 — a cancelled task must
            # still resolve the RPC future it owes (same contract as
            # one_chunk), then let non-Exception signals propagate
            if not job.fut.done():
                job.fut.set_exception(
                    e if isinstance(e, Exception)
                    else RuntimeError(f"pipeline shutdown ({type(e).__name__})"))
            if not isinstance(e, Exception):
                raise

    def _route_fallback(self, job) -> None:
        if isinstance(job, (RpcJob, ColsJob)):
            if not job.fut.done():
                job.fut.set_result(None)  # caller runs the full path
            return
        # list job needing the full path (legacy lane handles chunking,
        # full wire format, every semantic)
        async def run():
            try:
                resps = await self.legacy(job.reqs)
            except BaseException as e:  # noqa: BLE001 — a cancelled task
                # must still resolve the futures it owes, then let
                # non-Exception signals propagate
                self._resolve_error(
                    job, e if isinstance(e, Exception) else RuntimeError(
                        f"pipeline shutdown ({type(e).__name__})"))
                if not isinstance(e, Exception):
                    raise
                return
            if job.futs is not None:
                for f, r in zip(job.futs, resps):
                    if not f.done():
                        f.set_result(r)
            elif not job.fut.done():
                job.fut.set_result(resps)
        self._spawn(run())

    def _resolve_error(self, job, err: Exception) -> None:
        futs = ([job.fut] if getattr(job, "futs", None) is None
                else job.futs)
        for f in futs:
            if f is not None and not f.done():
                f.set_exception(
                    err if isinstance(err, Exception) else RuntimeError(err))

    # ------------------------------------------------------------ engine side

    def _drain_sync(self, jobs: List[object], now: Optional[int] = None,
                    k_fixed: Optional[int] = None,
                    gjob: Optional[_GlobalJob] = None,
                    cols: Optional[RequestColumns] = None,
                    pumped: float = 0.0) -> _DrainResult:
        """Engine-thread drain entry: counts the drain into the running
        jax.profiler capture when POST /v1/admin/profile armed one.  The
        capture's own thread starts and stops the profiler
        (observability/introspect.py); this thread reads one bool and,
        under a capture, decrements one int."""
        prof = self.profile
        if prof is not None and prof.tracing:
            try:
                return self._drain_sync_inner(jobs, now=now,
                                              k_fixed=k_fixed, gjob=gjob,
                                              cols=cols, pumped=pumped)
            finally:
                prof.after_drain()
        return self._drain_sync_inner(jobs, now=now, k_fixed=k_fixed,
                                      gjob=gjob, cols=cols, pumped=pumped)

    def _drain_sync_inner(self, jobs: List[object],
                          now: Optional[int] = None,
                          k_fixed: Optional[int] = None,
                          gjob: Optional[_GlobalJob] = None,
                          cols: Optional[RequestColumns] = None,
                          pumped: float = 0.0) -> _DrainResult:
        """Pack every job into one stacked compact dispatch (engine thread).

        Staging comes from the arena ring (core/window_buffers.py): the
        previous drain's arrays may still be feeding an in-flight
        host→device transfer, so a drain's arena is recycled only after ITS
        OWN fetch completed — never while this drain could overwrite it.

        Host sync audit: this path contains NO unconditional blocking
        device reads.  copy_to_host_async() starts the D2H copies without
        waiting; the only blocking fetches live in _complete_sync (on the
        fetch pool, off this thread); GUBER_PIPELINE_SYNC_DEBUG opts into
        one deliberate block-until-ready per dispatch for exact stage
        attribution.  The legacy step path's _dispatch does fetch
        synchronously on this thread — that is the fallback lane, not the
        drain.

        Lockstep mode (k_fixed set): `now` is the tick's cluster-agreed
        timestamp and the dispatch shape is ALWAYS [k_fixed] — issued even
        with nothing staged, because the drain is part of the tick's
        collective sequence on every process.  The tick drain is the
        GLOBAL-composed executable (engine.pipeline_dispatch_global): the
        fused K-scan plus ONE reconciliation psum per drain, with `gjob`'s
        GLOBAL singles staged round-robin into its full-format lanes."""
        eng = self.engine
        native = eng.native
        S = eng.num_local_shards
        B = eng.batch_per_shard
        K = self.k_max if k_fixed is None else k_fixed
        res = _DrainResult()
        res.pumped = pumped
        res.started = time.monotonic()
        if now is None:
            now = self.now_fn()
        res.now = now
        res.cols_owner = cols
        rpc_ok = self.rpc_enabled and eng._compact_enabled
        list_ok = (eng._compact_sound if self.lockstep
                   else eng._compact_enabled)
        # the lockstep lane takes an RPC's GLOBAL items along: the parser
        # marks them, and they fold into this drain's GLOBAL window (gst,
        # made when the first GLOBAL request of the drain shows).  Their
        # answers come back as rows after the K*S regular ones, at the
        # regular lanes' width.
        mark_global = (self.lockstep and eng._dynamic_global
                       and eng.global_batch_per_shard <= B)
        gst: Optional[_GlobalStage] = None

        with TraceAnnotation("guber_pack"):
            arena = self._arena_ring.acquire(K, S, B)
            res.arena = arena
            arena.dirty = True
            # the arena may be deeper than K (ring matches K >=); trailing
            # rows stay zero, and the k-stride is K-independent, so the C
            # calls and the [:kb] dispatch slices below are unaffected
            packed = arena.packed
            fills = arena.fills
            kcur = arena.kcur
            native.drain_begin()
            stack_empty = True
            res.ring_peers = self._ring_peers
            for idx, job in enumerate(jobs):
                if isinstance(job, RpcJob):
                    if not rpc_ok:
                        res.fallback.append(job)
                        continue
                    scr = arena.acquire_scratch()
                    job.row, job.lane, job.pos = scr.row, scr.lane, scr.pos
                    job.limit, job.off, job.mlen = scr.limit, scr.off, scr.mlen
                    n = native.parse_stack_fast(
                        job.data, now, B, K, MAX_BATCH_SIZE, arena, scr,
                        use_ring=not job.peer_mode, mark_global=mark_global)
                    if n >= 0:
                        job.n = n
                        job.remote_idx = np.flatnonzero(job.row[:n] < -1)
                        if mark_global:
                            gidx = np.flatnonzero(job.row[:n] == -1)
                            if len(gidx):
                                if gst is None:
                                    gst = _GlobalStage(eng, now,
                                                       self._gcfg_seen)
                                self._stage_rpc_globals(job, gidx, gst,
                                                        K * S)
                                res.glimit = gst.gbatch.limit
                        res.staged.append(job)
                        if len(job.remote_idx):
                            # the forward coroutines keep reading off/mlen on
                            # the loop after this drain completes: the block
                            # leaves the pool with the job (recycle drops it)
                            scr.leased = True
                        if len(job.remote_idx) < n:
                            stack_empty = False
                    elif n == -6 and not stack_empty:
                        res.leftover = jobs[idx:]
                        break
                    else:
                        res.fallback.append(job)
                else:
                    if not list_ok:
                        res.fallback.append(job)
                        continue
                    jcols = job.columns()
                    if job.n > MAX_BATCH_SIZE:
                        # oversized submit_many batch: the C router rejects it
                        # (-3) before writing, but the scratch block could not
                        # hold its demux anyway — route it to the legacy lane
                        res.fallback.append(job)
                        continue
                    scr = arena.acquire_scratch()
                    # slice to job.n: finish()'s fancy-indexed demux must see
                    # exactly n entries (the views share the cached C pointers)
                    job.row = scr.row[:job.n]
                    job.lane = scr.lane[:job.n]
                    job.pos = scr.pos[:job.n]
                    rc = native.pack_stack_fast(*jcols, now, B, K, arena, scr)
                    if rc >= 0:
                        res.staged.append(job)
                        stack_empty = False
                    elif rc == -6 and not stack_empty:
                        res.leftover = jobs[idx:]
                        break
                    else:
                        res.fallback.append(job)

        res.pack_done = time.monotonic()
        enqs = [e for e in (getattr(j, "enq", 0.0) for j in res.staged) if e]
        res.oldest_enq = min(enqs) if enqs else 0.0
        if not res.staged and gjob is None and not self.lockstep:
            return res
        k_used = int(fills.any(axis=1).sum())
        res.k_used = k_used
        if self.lockstep:
            # Stage the tick's GLOBAL singles into the drain's composed
            # window (full wire format, round-robin over local shards —
            # the psum is shard-agnostic, mirroring _stage_requests),
            # beside the GLOBAL items whole RPCs brought.
            if gjob is not None:
                fresh = gst is None
                try:
                    if fresh:
                        gst = _GlobalStage(eng, now, self._gcfg_seen)
                    reqs, futs, flats = [], [], []
                    for r, f in zip(gjob.reqs, gjob.futs):
                        flat = gst.add(r.hash_key(), r.hits, r.limit,
                                       r.duration, int(r.algorithm))
                        if flat < 0:
                            res.gdeferred.append((r, f))
                        else:
                            reqs.append(r)
                            futs.append(f)
                            flats.append(flat)
                    if reqs:
                        held = _GlobalJob(reqs, futs)
                        fl = np.asarray(flats, np.int32)
                        held.shard[:] = fl % gst.SL
                        held.lane[:] = fl // gst.SL
                        res.staged.append(held)
                except Exception:
                    # staging failed (arena full, ...): the fresh
                    # allocations stay pending (no commit) and the job
                    # re-routes through the legacy lane; the drain still
                    # dispatches with inert GLOBAL padding.  Beside GLOBAL
                    # items of whole RPCs, whose lanes cannot be taken
                    # back, the drain fails as a whole instead.
                    if not fresh:
                        raise
                    res.fallback.append(gjob)
                    res.gdeferred = []
                    self._gcfg_seen.clear()  # the legacy lane configures
                    gst = None
            if gst is not None and gst.items:
                gbatch, gacc, upd = gst.control()
                res.n_global = gst.items
            else:
                gbatch, gacc, upd = eng.empty_drain_control()
            # the tick's drain dispatch is unconditional and fixed-shape:
            # every process issues it at the same sequence position.
            # Analytics (when wired) is COMPOSED into this same dispatch —
            # tenants are staged up front so the reduction rides the drain
            # executable instead of occupying a second collective-sequence
            # slot; staging failures degrade to inert zero tenants, never
            # to a differently-shaped dispatch.
            an_args = (self._analytics_stage(res, packed, K, now)
                       if self.analytics is not None else None)
            res.arm = ("composed_analytics" if an_args is not None
                       else "composed_drain")
            res.lanes = B
            before = eng.windows_processed
            dispatched = False
            try:
                out = eng.pipeline_dispatch_global(
                    packed[:K], np.full(K, now, np.int64), gbatch, gacc,
                    upd, n_windows=k_used, analytics_args=an_args)
                words, limits, mism, gfused = out[:4]
                dispatched = True  # sentinel: windows_processed advances
                # by k_used, which is 0 on an idle tick — the counter
                # alone cannot distinguish 'dispatched 0 windows' from
                # 'never dispatched' for the realign decision below
                native.commit()
                if gst is not None:
                    gst.committed()
            except Exception as e:
                native.abort()
                res.error = e  # _on_dispatched fails the staged jobs
                # keep the collective sequence aligned: this process MUST
                # still issue the tick's drain executable (unless the
                # failed call already did).  Retry with an inert all-zero
                # stack; if even that cannot dispatch, the host can never
                # rejoin the lockstep — raise so the batcher fail-stops
                # instead of silently desyncing.
                if not dispatched and eng.windows_processed == before:
                    zeros = np.zeros_like(packed[:K])
                    zb, za, zu = eng.empty_drain_control()
                    for attempt in range(3):
                        try:
                            # same executable as the failed call (the
                            # analytics-composed variant when wired): the
                            # collective sequence is per-EXECUTABLE
                            eng.pipeline_dispatch_global(
                                zeros, np.full(K, now, np.int64),
                                zb, za, zu, n_windows=0,
                                analytics_args=an_args)
                            break
                        except Exception:
                            if attempt == 2:
                                raise
                            time.sleep(0.05)
                return res
            if res.staged:
                try:
                    words.copy_to_host_async()
                    mism.copy_to_host_async()
                    if res.n_global:
                        gfused.copy_to_host_async()
                except Exception:
                    pass  # fetch path will block instead
                res.words, res.limits, res.mism = words, limits, mism
                if res.n_global:
                    res.gfused = gfused
            if an_args is not None:
                # composed analytics: the stats row came out of the drain
                # dispatch itself — just start its async copy alongside
                # the drain's own fetches
                stats = out[4]
                try:
                    stats.copy_to_host_async()
                except Exception:
                    pass  # fetch path will block instead
                res.stats = stats
                res.an_decay = an_args[1]
        elif k_used:  # an all-forwarded drain has nothing to dispatch
            res.arm = "compact32_xla"
            kb = next(b for b in self._k_buckets if b >= k_used)
            res.lanes = lanes = self._drain_lanes(fills, k_used)
            # the C router stages a shard's lanes as a prefix, so the
            # narrow copy keeps every (row, lane) job.finish will read
            stack = (packed[:kb] if lanes == B
                     else np.ascontiguousarray(packed[:1, :, :lanes]))
            try:
                # fault seam: an injected dispatch failure aborts the C
                # router's staged allocations (no partial commit) and fails
                # exactly this drain's jobs — neighbors in flight commit
                # through the ordered completion queue untouched
                if FAULTS.enabled:
                    FAULTS.on_sync(SEAM_ENGINE_DISPATCH, "pipeline")
                words, limits, mism = eng.pipeline_dispatch(
                    stack, np.full(kb, now, np.int64), n_windows=k_used)
                native.commit()
            except Exception as e:
                native.abort()
                res.error = e
                return res
            # start the device→host copies NOW, overlapping the next drain
            try:
                words.copy_to_host_async()
                mism.copy_to_host_async()
            except Exception:
                pass  # fetch path will block instead
            res.words, res.limits, res.mism = words, limits, mism
            if self.analytics is not None:
                self._analytics_dispatch(res, packed, words, now)
        else:
            native.commit()  # nothing staged: empty by construction
        if self.sync_debug and res.words is not None:
            # DEBUG host sync (see __init__): make dispatch_done include
            # device execution so the stage stamps are exact
            import jax
            jax.block_until_ready(res.words)
        res.dispatch_done = time.monotonic()
        if res.lanes:
            self.drain_widths[res.lanes] += 1
            if self.metrics is not None:
                self.metrics.drains.labels(
                    width="narrow" if res.lanes < B else "full").inc()
        # forwarded items are the OWNER's decisions, not ours — counting
        # them here would double-count cluster-wide (the owner's peer-lane
        # drain counts them)
        res.n_decisions = sum(
            j.n - len(getattr(j, "remote_idx", ())) for j in res.staged)
        res.n_raw = sum(j.n - len(getattr(j, "remote_idx", ()))
                        for j in res.staged
                        if isinstance(j, (RpcJob, ColsJob)))
        # counted here, ON the engine thread — the legacy path's
        # engine.process increments the same attribute from this thread,
        # so updating it from the event loop would race (lost updates)
        eng.decisions_processed += res.n_decisions
        # duplicate-run aggregation observability: decisions vs lanes
        # actually staged — the fold factor a bench can report
        res.n_lanes = int(fills.sum())
        self.decisions_staged += res.n_decisions
        self.lanes_staged += res.n_lanes
        # deferred-fetch chain: with a stride target above 1 this drain
        # submits NO fetch at all — the loop appends it to the chain and
        # one stacked fetch commits the whole group (the stride target is
        # a plain int the loop refreshes every pump; a stale read here
        # only shifts WHERE the fetch is submitted, never correctness).
        if res.staged and self._stride_target > 1 and not self.lockstep:
            res.deferred = True
            return res
        # hop cut: submit the fetch from HERE (engine thread) instead of
        # bouncing through the event loop first — the fetch worker starts
        # the blocking device read one loop-latency earlier.  Mixed RPCs
        # keep the loop hop: their forward tasks must exist (spawned in
        # _on_dispatched) before completion can demux them.
        if res.staged and not any(isinstance(j, RpcJob)
                                  and len(j.remote_idx)
                                  for j in res.staged):
            res.cfut = self._fetch_executor.submit(self._complete_sync, res)
        return res

    def _stage_rpc_globals(self, job: RpcJob, gidx, gst: _GlobalStage,
                           row0: int) -> None:
        """Fold a whole RPC's marked GLOBAL items (out_row == -1) into the
        drain's GLOBAL window (engine thread).  A staged item's (row, lane)
        then point at the row its window's answers take behind the `row0`
        regular ones (_with_global_rows), so the C encoder writes it like
        any other; an item the window has no lane for keeps its mark and
        is answered by a later tick (job.gdefer, _spawn_global_deferred)."""
        from gubernator_tpu.api import pb

        data, memo = job.data, self._gparse
        ok_idx, ok_flat, defer = [], [], []
        for i, o, ln in zip(gidx.tolist(), job.off[gidx].tolist(),
                            job.mlen[gidx].tolist()):
            raw = data[o:o + ln]
            item = memo.get(raw)
            if item is None:
                m = pb.RateLimitReq.FromString(raw)
                item = (m.name + "_" + m.unique_key, m.hits, m.limit,
                        m.duration, int(m.algorithm))
                if len(memo) >= 65536:
                    memo.clear()
                memo[raw] = item
            flat = gst.add(*item)
            if flat < 0:
                defer.append((i, pb.req_from_pb(
                    pb.RateLimitReq.FromString(raw))))
            else:
                ok_idx.append(i)
                ok_flat.append(flat)
        if ok_idx:
            fl = np.asarray(ok_flat, np.int32)
            job.row[ok_idx] = row0 + fl % gst.SL
            job.lane[ok_idx] = fl // gst.SL
        if defer:
            job.gdefer = defer
            job.remote_idx = np.asarray([i for i, _ in defer])

    def _with_global_rows(self, res: _DrainResult, wflat, clflat,
                          gflat) -> tuple:
        """The fetched response words with the GLOBAL window's answers
        ([S_local, Bg, 4] = status / limit / remaining / reset_time)
        appended as S_local rows in the words' own format (ops/kernel.py
        encode_output_word), so that a whole RPC's GLOBAL items encode
        through the same C call as its regular ones.  The limits plane
        follows only where a stored limit differs from the request's."""
        SL, Bg = gflat.shape[:2]
        lanes = wflat.shape[1]
        st, lim, rem, rst = (gflat[..., c].astype(np.int64)
                             for c in range(4))
        enc = np.where(rst > 0, np.maximum(rst - res.now + 1, 1), 0)
        ext = np.zeros((SL, lanes), np.int64)
        ext[:, :Bg] = (enc << 32) | ((st & 1) << 31) | (rem & 0x7FFFFFFF)
        used = res.glimit != 0
        if clflat is None and bool((used & (lim != res.glimit)).any()):
            clflat = np.ascontiguousarray(
                self.engine._fetch_local_stacked(res.limits)
            ).reshape(-1, lanes)
        if clflat is not None:
            ext_l = np.zeros((SL, lanes), np.int64)
            ext_l[:, :Bg] = lim
            clflat = np.concatenate([clflat, ext_l])
        return np.concatenate([wflat, ext]), clflat

    def _drain_lanes(self, fills, k_used: int) -> int:
        """Lane width of this drain's executable: the narrowest lane
        bucket (engine._lane_bucket) that holds the fullest shard.  The
        window's device time follows its lane count, whatever the lanes
        hold, so a drain of twenty decisions runs the B/16 shape.  A
        drain that stacks windows has overflowed B or met the replay
        bound and stays full.  So does one the standalone analytics
        reduction follows (its executable is keyed on the same shapes
        and warms at none).  The lockstep tick dispatches its own
        composed executable and a mesh engine's _lane_bucket answers B:
        one fixed executable per tick keeps the collective sequence
        aligned across hosts."""
        if k_used != 1 or self.lockstep or self.analytics is not None:
            return self.engine.batch_per_shard
        return self.engine._lane_bucket(int(fills.max()))

    def _analytics_stage(self, res: _DrainResult, packed, kd: int,
                         now: int):
        """Host-side staging for the COMPOSED analytics reduction: build
        the tenant lanes + slot labels BEFORE the drain dispatch so the
        stats reduction can ride the drain executable itself (lockstep
        mode; engine.pipeline_dispatch_global analytics_args).

        Same tenant/label semantics as _analytics_dispatch below.  Any
        failure degrades to inert zero tenants and decay=0 — analytics
        must never fail a drain, and the lockstep dispatch must keep its
        shape either way (only the VALUES degrade; the executable is
        picked by config-level geometry)."""
        from gubernator_tpu.ops.analytics import _SLOT_MASK
        eng = self.engine
        S = eng.num_local_shards
        tenants = np.zeros((kd, S, eng.batch_per_shard), np.int32)
        decay = 0
        try:
            an = self.analytics
            for job in res.staged:
                reqs = getattr(job, "reqs", None)
                rows = getattr(job, "row", None)
                if reqs is None or rows is None:
                    continue
                for i in range(job.n):
                    row = int(rows[i])
                    if row < 0:
                        continue
                    k, s = divmod(row, S)
                    if k >= kd:
                        continue
                    lane = int(job.lane[i])
                    r = reqs[i]
                    tenants[k, s, lane] = an.tenant_id(tenant_of(r))
                    slot = int(packed[k, s, lane, 0] & _SLOT_MASK) - 1
                    if slot >= 0:
                        an.label_slot(s, slot, r.hash_key())
            decay = an.decay_flag(now)
        except Exception:
            log.exception("analytics staging failed (drain unaffected)")
        return tenants, decay

    def _analytics_dispatch(self, res: _DrainResult, packed, words,
                            now: int) -> None:
        """Stage the tenant lanes + slot labels for this drain and issue
        the stats reduction (engine thread; analytics enabled only).

        Tenant ids come from the fairness tenant (the request `name`,
        qos/fairness.tenant_of) of each staged ListJob lane; RpcJob lanes
        stay id 0 ("other") — the native fastpath never materializes key
        strings on the host.  The reduction consumes the drain's own
        packed stack (re-staged host→device, the cheap direction) and its
        RESIDENT response words, and its stats output joins the drain
        result's async copies — zero extra device→host round trips.  Any
        failure here is logged and dropped: analytics must never fail a
        drain."""
        from gubernator_tpu.ops.analytics import _SLOT_MASK
        eng = self.engine
        try:
            an = self.analytics
            S = eng.num_local_shards
            kd = int(words.shape[0])
            tenants = np.zeros((kd, S, eng.batch_per_shard), np.int32)
            for job in res.staged:
                reqs = getattr(job, "reqs", None)
                rows = getattr(job, "row", None)
                if reqs is None or rows is None:
                    continue
                for i in range(job.n):
                    row = int(rows[i])
                    if row < 0:
                        continue
                    k, s = divmod(row, S)
                    if k >= kd:
                        continue
                    lane = int(job.lane[i])
                    r = reqs[i]
                    tenants[k, s, lane] = an.tenant_id(tenant_of(r))
                    slot = int(packed[k, s, lane, 0] & _SLOT_MASK) - 1
                    if slot >= 0:
                        an.label_slot(s, slot, r.hash_key())
            decay = an.decay_flag(now)
            stats = eng.analytics_dispatch(packed[:kd], words, tenants,
                                           now, decay)
            try:
                stats.copy_to_host_async()
            except Exception:
                pass  # fetch path will block instead
            res.stats = stats
            res.an_decay = decay
        except Exception:
            log.exception("analytics reduction failed (drain unaffected)")

    # ------------------------------------------------------------ fetch side

    def _flat_outputs(self, res: _DrainResult, words, mism) -> tuple:
        """A fetched drain's words as rows of k * S_local + shard, at the
        lane width the drain ran (a chain may hold drains of different
        widths), and its limits plane the same way, fetched only when a
        stored-limit mismatch fired."""
        lanes = words.shape[-1]
        clflat = None
        if mism.any():
            clflat = np.ascontiguousarray(
                self.engine._fetch_local_stacked(res.limits)
            ).reshape(-1, lanes)
        return words.reshape(-1, lanes), clflat

    def _complete_sync(self, res: _DrainResult):
        res.fetch_start = time.monotonic()
        eng = self.engine
        B = eng.batch_per_shard
        if res.words is None:  # all-forwarded drain: nothing was dispatched
            wflat = np.empty((0, B), np.int64)
            clflat = None
        else:
            # ONE device_get for the response words AND the mismatch flags
            # (engine.fetch_stacked_many): each separate blocking fetch is
            # its own host sync point on the transfer stream, and the
            # mism plane is tiny — fetching it separately doubled the
            # fixed round-trip cost of every drain.  The limits plane
            # stays conditional: it is only read when a stored-limit
            # mismatch actually fired (rare), so the common path never
            # moves it.  Rows index as k * S_local + shard, exactly how
            # the C router staged them.
            words, mism = eng.fetch_stacked_many([res.words, res.mism])
            wflat, clflat = self._flat_outputs(
                res, np.ascontiguousarray(words), mism)
        gflat = None
        if res.gfused is not None:
            # this process's GLOBAL response rows [S_local, Bg, 4], indexed
            # exactly as the round-robin staging wrote (shard, lane)
            gflat = eng._fetch_local(res.gfused)
            if res.glimit is not None:
                wflat, clflat = self._with_global_rows(res, wflat, clflat,
                                                       gflat)
        if res.stats is not None:
            # analytics stats ride the same fetch stage as the drain's own
            # outputs (their async copy started at dispatch)
            try:
                res.stats_host = eng._fetch_local(res.stats)
            except Exception:
                log.exception("analytics stats fetch failed")
        res.fetch_ready = time.monotonic()
        with TraceAnnotation("guber_decode"):
            outs = [job.finish_global(gflat) if isinstance(job, _GlobalJob)
                    else job.finish(self, wflat, clflat, res.now)
                    for job in res.staged]
        res.fetch_done = time.monotonic()
        return res, outs

    def close(self) -> None:
        if not self.enabled:
            return
        self._closed = True
        if self._coalesce_handle is not None:
            self._coalesce_handle.cancel()
            self._coalesce_handle = None
        # fail still-queued jobs: _pump returns early once closed, so their
        # futures would otherwise never resolve and callers hang
        err = RuntimeError("pipeline closed")
        jobs, self._jobs = self._jobs, []
        singles, self._singles = self._singles, []
        gsingles, self._gsingles = self._gsingles, []
        for job in jobs:
            self._resolve_error(job, err)
        for entry in singles:
            if not entry[1].done():
                entry[1].set_exception(err)
        for _, f in gsingles:
            if not f.done():
                f.set_exception(err)
        # chained drains still pending fetch commit NOW: the flush submits
        # before shutdown, and shutdown(wait=False) still runs work that
        # was already queued
        self._chain_flush()
        self._fetch_executor.shutdown(wait=False)
        self.flush_reply_wake()
