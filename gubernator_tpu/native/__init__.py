"""Native host runtime: ctypes bindings for the C++ window router.

Compiles host_router.cc on first use (g++ -O2 -shared) into a .so named
after the source's content hash, next to the source — so a tree copy, a
checkout or an edit can never pair a stale library with a newer source.
A host without a C++ toolchain has no native router (`available()` is
False and `use_native="auto"` engines route in Python); a toolchain that
FAILS to build or load the committed source is an error, never a quiet
fallback.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("gubernator.native")

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "host_router.cc")

_lib = None
_lib_lock = threading.Lock()


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libhost_router-{digest}.so")


def _build(so: str) -> None:
    # build under a private name, then rename: concurrent first users (test
    # workers, front-door workers) never load a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native router build failed (rc={proc.returncode}): "
            f"{proc.stderr[-2000:]}")
    os.replace(tmp, so)


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            if shutil.which("g++") is None:
                return None
            _build(so)
        lib = ctypes.CDLL(so)

        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.router_new.restype = ctypes.c_void_p
        lib.router_new.argtypes = [ctypes.c_int32, ctypes.c_int32]
        lib.router_new_mesh.restype = ctypes.c_void_p
        lib.router_new_mesh.argtypes = [ctypes.c_int32] * 4
        lib.router_free.argtypes = [ctypes.c_void_p]
        for fn in ("router_pack", "router_pack_window"):
            getattr(lib, fn).restype = ctypes.c_int64
            getattr(lib, fn).argtypes = [
                ctypes.c_void_p, u8p, i64p, ctypes.c_int64,
                i64p, i64p, i64p, i32p, ctypes.c_int64, ctypes.c_int32,
                i32p, i64p, i64p, i64p, i32p, u8p, i32p, i32p, i32p,
            ]
        for fn in ("router_size", "router_hits", "router_misses"):
            getattr(lib, fn).restype = ctypes.c_int64
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.router_heap_size.restype = ctypes.c_int64
        lib.router_heap_size.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        for fn in ("router_commit", "router_drain_begin", "router_abort",
                   "router_set_exact"):
            getattr(lib, fn).restype = None
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.router_set_replay_cap.restype = None
        lib.router_set_replay_cap.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int32]
        lib.fastpath_parse_stack.restype = ctypes.c_int64
        lib.fastpath_parse_stack.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
            i64p, i32p, i32p, i32p, i32p, i32p, i64p, i64p, i32p,
        ]
        lib.fastpath_encode_parts.restype = ctypes.c_int64
        lib.fastpath_encode_parts.argtypes = [
            i64p, i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
            i32p, i32p, i32p, i64p, u8p, ctypes.c_int64, i64p, i32p,
        ]
        lib.router_set_ring.restype = None
        lib.router_set_ring.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32), i32p,
            ctypes.c_int32, ctypes.c_int32,
        ]
        lib.router_pack_stack.restype = ctypes.c_int64
        lib.router_pack_stack.argtypes = [
            ctypes.c_void_p, u8p, i64p, ctypes.c_int64,
            i64p, i64p, i64p, i32p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, i64p, i32p, i32p, i32p, i32p, i32p,
        ]
        lib.fastpath_encode_w.restype = ctypes.c_int64
        lib.fastpath_encode_w.argtypes = [
            i64p, i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
            i32p, i32p, i32p, i64p, u8p, ctypes.c_int64,
        ]
        lib.frontdoor_parse_req.restype = ctypes.c_int64
        lib.frontdoor_parse_req.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            u8p, i64p, i64p, i64p, i64p, i32p, i32p,
        ]
        lib.frontdoor_encode_resp.restype = ctypes.c_int64
        lib.frontdoor_encode_resp.argtypes = [
            i64p, i64p, i64p, i64p, i32p, ctypes.c_int64,
            u8p, ctypes.c_int64,
        ]
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.router_export_keys.restype = ctypes.c_int64
        lib.router_export_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, u64p, i32p, i64p,
        ]
        lib.router_import_keys.restype = ctypes.c_int64
        lib.router_import_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, u64p, i32p, i64p,
            ctypes.c_int64,
        ]
        lib.router_occupancy.restype = None
        lib.router_occupancy.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, i64p, i64p, i64p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def frontdoor_parse_req(data: bytes, key_bytes: np.ndarray,
                        key_ends: np.ndarray, hits: np.ndarray,
                        limits: np.ndarray, durations: np.ndarray,
                        algos: np.ndarray, name_lens: np.ndarray,
                        max_items: int) -> int:
    """Stateless worker-side parse: serialized GetRateLimitsReq -> request
    columns in caller-owned buffers (the frontdoor worker writes straight
    into its shared-memory slab, core/shm_ring.py).  No Router* involved —
    frontdoor workers never hold engine state.  Returns n >= 0 (requests
    parsed) or a negative fallback code (the worker then ships the raw
    bytes instead); see host_router.cc frontdoor_parse_req.  Callers must
    check available() first."""
    lib = _load()
    if lib is None:
        return -1
    buf = ctypes.cast(ctypes.c_char_p(data), ctypes.POINTER(ctypes.c_uint8))
    return lib.frontdoor_parse_req(
        buf, len(data), max_items, key_bytes.nbytes,
        _ptr(key_bytes, ctypes.c_uint8), _ptr(key_ends, ctypes.c_int64),
        _ptr(hits, ctypes.c_int64), _ptr(limits, ctypes.c_int64),
        _ptr(durations, ctypes.c_int64), _ptr(algos, ctypes.c_int32),
        _ptr(name_lens, ctypes.c_int32))


def frontdoor_encode_resp(status: np.ndarray, limit: np.ndarray,
                          remaining: np.ndarray, reset: np.ndarray,
                          flags, n: int, out: np.ndarray) -> int:
    """Stateless worker-side encode: decision columns (ripped straight out
    of the completion-ring slab, core/shm_ring.py) -> serialized
    GetRateLimitsResp bytes in `out`.  The response-direction mirror of
    frontdoor_parse_req: the engine ships columns, the worker owns the
    protobuf.  flags is an int32 column (0 = plain decision, 1..5 = shed
    reason code per shm_ring.SHED_REASON_CODES) or None.  Returns the byte
    length, or -1 (out too small) / -2 (unknown shed code) — callers fall
    back to the Python pb encoder.  Check available() first."""
    lib = _load()
    if lib is None:
        return -1
    fl = _ptr(flags, ctypes.c_int32) if flags is not None else None
    return lib.frontdoor_encode_resp(
        _ptr(status, ctypes.c_int64), _ptr(limit, ctypes.c_int64),
        _ptr(remaining, ctypes.c_int64), _ptr(reset, ctypes.c_int64),
        fl, n, _ptr(out, ctypes.c_uint8), out.nbytes)


class NativeRouter:
    """Batch key→(shard, slot) resolution + window packing in one C call."""

    def __init__(self, num_shards: int, capacity_per_shard: int,
                 num_global_shards: int = None, shard_offset: int = 0):
        """num_shards = LOCAL shards staged by this process; in mesh mode
        keys hash over num_global_shards and mis-routed keys come back
        marked out_shard == -1 (reject before dispatching)."""
        lib = _load()
        if lib is None:
            raise RuntimeError("native router library unavailable")
        self._lib = lib
        if num_global_shards is None:
            num_global_shards = num_shards
        self._handle = lib.router_new_mesh(
            num_global_shards, shard_offset, num_shards, capacity_per_shard)
        self.num_shards = num_shards
        self.capacity_per_shard = capacity_per_shard
        self.exact = False

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.router_free(handle)
            self._handle = None

    def pack(
        self,
        key_bytes: np.ndarray,   # uint8 concatenated keys
        key_ends: np.ndarray,    # int64 exclusive end offsets
        hits: np.ndarray, limits: np.ndarray, durations: np.ndarray,
        algos: np.ndarray, now: int, lanes: int,
        out_slot: np.ndarray, out_hits: np.ndarray, out_limit: np.ndarray,
        out_duration: np.ndarray, out_algo: np.ndarray,
        out_is_init: np.ndarray,
        out_shard: np.ndarray, out_lane: np.ndarray,
        shard_fill: np.ndarray,
    ) -> int:
        """Returns how many of the n requests were packed (< n on lane
        overflow; ship the window and repack the remainder)."""
        return self._pack_impl(self._lib.router_pack, key_bytes, key_ends,
                               hits, limits, durations, algos, now, lanes,
                               out_slot, out_hits, out_limit, out_duration,
                               out_algo, out_is_init, out_shard, out_lane,
                               shard_fill)

    def pack_window(self, *args) -> int:
        """router_pack under an open drain (shared pack sequence,
        accumulating commits): one caller-delimited window of a stacked
        dispatch.  Same arguments and return as pack()."""
        return self._pack_impl(self._lib.router_pack_window, *args)

    def _pack_impl(self, fn, key_bytes, key_ends, hits, limits, durations,
                   algos, now, lanes, out_slot, out_hits, out_limit,
                   out_duration, out_algo, out_is_init, out_shard, out_lane,
                   shard_fill) -> int:
        return fn(
            self._handle,
            _ptr(key_bytes, ctypes.c_uint8), _ptr(key_ends, ctypes.c_int64),
            len(key_ends),
            _ptr(hits, ctypes.c_int64), _ptr(limits, ctypes.c_int64),
            _ptr(durations, ctypes.c_int64), _ptr(algos, ctypes.c_int32),
            now, lanes,
            _ptr(out_slot, ctypes.c_int32), _ptr(out_hits, ctypes.c_int64),
            _ptr(out_limit, ctypes.c_int64), _ptr(out_duration, ctypes.c_int64),
            _ptr(out_algo, ctypes.c_int32), _ptr(out_is_init, ctypes.c_uint8),
            _ptr(out_shard, ctypes.c_int32), _ptr(out_lane, ctypes.c_int32),
            _ptr(shard_fill, ctypes.c_int32),
        )

    def commit(self) -> None:
        """Confirm the window(s) staged since the last drain_begin / pack
        were dispatched (clears their entries' init-pending flags)."""
        self._lib.router_commit(self._handle)

    def drain_begin(self) -> None:
        """Open a drain: one pack sequence shared by the following
        parse_stack/pack_stack calls, committed or aborted as a unit."""
        self._lib.router_drain_begin(self._handle)

    def abort(self) -> None:
        """The drain's dispatch failed: keep its fresh allocations pending
        so their next touch re-initializes the (never-written) slots."""
        self._lib.router_abort(self._handle)

    def set_exact_keys(self) -> None:
        """Opt-in exact-key collision guard (stores full keys; a 64-bit
        fingerprint collision then probes onward instead of merging two
        keys' counters).  Call before any key is inserted."""
        self._lib.router_set_exact(self._handle)
        self.exact = True

    def set_replay_cap(self, cap: int) -> None:
        """Bound on a NON-uniform duplicate-key run per device window:
        when one key accumulates `cap` mixed-config/zero-hit lanes in a
        window, its next lane opens a fresh window of the stack, keeping
        the kernel's per-window replay loop bounded (an unbounded replay
        is a multi-hundred-ms device execution — a DoS lever through the
        public RPC surface, and large enough ones crashed the TPU runtime
        worker).  Uniform hot-key duplicates are unaffected (closed form).
        0 disables; the default is 128."""
        self._lib.router_set_replay_cap(self._handle, int(cap))

    def fastpath_parse_stack(self, data: bytes, now: int, lanes: int,
                             K: int, max_items: int, packed: np.ndarray,
                             kcur: np.ndarray, shard_fill: np.ndarray,
                             out_row: np.ndarray, out_lane: np.ndarray,
                             out_pos: np.ndarray,
                             out_limit: np.ndarray, out_off: np.ndarray,
                             out_mlen: np.ndarray,
                             use_ring: bool = True) -> int:
        """Serialized GetRateLimitsReq -> lanes staged across a K-window
        compact stack.  Returns n >= 0 (requests parsed; ring-remote items
        are NOT staged and come back as out_row < -1 markers with their
        message byte ranges in out_off/out_mlen) or a negative fallback
        code; see host_router.cc.  use_ring=False treats every item as
        local (the authoritative peer-plane lane)."""
        # zero-copy read-only view of the immutable bytes
        buf = ctypes.cast(ctypes.c_char_p(data),
                          ctypes.POINTER(ctypes.c_uint8))
        return self._lib.fastpath_parse_stack(
            self._handle, buf, len(data), now, lanes, K, max_items,
            1 if use_ring else 0,
            _ptr(packed, ctypes.c_int64), _ptr(kcur, ctypes.c_int32),
            _ptr(shard_fill, ctypes.c_int32),
            _ptr(out_row, ctypes.c_int32), _ptr(out_lane, ctypes.c_int32),
            _ptr(out_pos, ctypes.c_int32),
            _ptr(out_limit, ctypes.c_int64), _ptr(out_off, ctypes.c_int64),
            _ptr(out_mlen, ctypes.c_int32),
        )

    def parse_stack_fast(self, data: bytes, now: int, lanes: int,
                         K: int, max_items: int, arena, scr,
                         use_ring: bool = True,
                         mark_global: bool = False) -> int:
        """fastpath_parse_stack against a WindowArena + JobScratch
        (core/window_buffers.py): identical semantics, but every output
        pointer was derived once at buffer allocation instead of per call
        — the per-call ctypes pointer derivation is a measured fixed cost
        on the drain's host-encode stage.  mark_global: token and leaky
        GLOBAL items take no lane and come back as out_row == -1 with
        their byte ranges, for the caller to stage (the lockstep lane)."""
        buf = ctypes.cast(ctypes.c_char_p(data),
                          ctypes.POINTER(ctypes.c_uint8))
        return self._lib.fastpath_parse_stack(
            self._handle, buf, len(data), now, lanes, K, max_items,
            (1 if use_ring else 0) | (2 if mark_global else 0),
            arena.p_packed, arena.p_kcur, arena.p_fills,
            scr.p_row, scr.p_lane, scr.p_pos,
            scr.p_limit, scr.p_off, scr.p_mlen,
        )

    def pack_stack_fast(self, key_bytes: np.ndarray, key_ends: np.ndarray,
                        hits: np.ndarray, limits: np.ndarray,
                        durations: np.ndarray, algos: np.ndarray, now: int,
                        lanes: int, K: int, arena, scr) -> int:
        """router_pack_stack against a WindowArena + JobScratch (cached
        stack/demux pointers; the per-chunk request columns still derive
        theirs per call — they are fresh slices each drain)."""
        return self._lib.router_pack_stack(
            self._handle,
            _ptr(key_bytes, ctypes.c_uint8), _ptr(key_ends, ctypes.c_int64),
            len(key_ends),
            _ptr(hits, ctypes.c_int64), _ptr(limits, ctypes.c_int64),
            _ptr(durations, ctypes.c_int64), _ptr(algos, ctypes.c_int32),
            now, lanes, K,
            arena.p_packed, arena.p_kcur, arena.p_fills,
            scr.p_row, scr.p_lane, scr.p_pos,
        )

    def fastpath_encode_parts(self, w0: np.ndarray, item_limit: np.ndarray,
                              now: int, lanes: int, n: int,
                              out_row: np.ndarray, out_lane: np.ndarray,
                              out_pos: np.ndarray,
                              resp_buf: np.ndarray, item_off: np.ndarray,
                              item_len: np.ndarray,
                              climit: Optional[np.ndarray] = None) -> int:
        """Per-item FRAMED response segments for splicing with forwarded
        peers' bytes (mixed-ownership RPCs); see host_router.cc."""
        cl = _ptr(climit, ctypes.c_int64) if climit is not None else None
        m = self._lib.fastpath_encode_parts(
            _ptr(w0, ctypes.c_int64), _ptr(item_limit, ctypes.c_int64),
            now, lanes, n,
            _ptr(out_row, ctypes.c_int32), _ptr(out_lane, ctypes.c_int32),
            _ptr(out_pos, ctypes.c_int32),
            cl, _ptr(resp_buf, ctypes.c_uint8), resp_buf.nbytes,
            _ptr(item_off, ctypes.c_int64), _ptr(item_len, ctypes.c_int32),
        )
        if m < 0:
            raise RuntimeError("fastpath_encode_parts: buffer too small")
        return m

    def set_ring(self, points: np.ndarray, peer_of: np.ndarray,
                 self_idx: int) -> None:
        """Install (or clear, empty points) the cluster consistent-hash
        ring for per-item local-vs-forward classification.  Must run on the
        engine thread (serialized with staging calls)."""
        n = len(points)
        self._lib.router_set_ring(
            self._handle,
            points.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            _ptr(peer_of, ctypes.c_int32), n, self_idx,
        )

    def pack_stack(self, key_bytes: np.ndarray, key_ends: np.ndarray,
                   hits: np.ndarray, limits: np.ndarray,
                   durations: np.ndarray, algos: np.ndarray, now: int,
                   lanes: int, K: int, packed: np.ndarray,
                   kcur: np.ndarray, shard_fill: np.ndarray,
                   out_row: np.ndarray, out_lane: np.ndarray,
                   out_pos: np.ndarray) -> int:
        """Columnar request list -> lanes staged across the K-window stack
        (same drain protocol as fastpath_parse_stack)."""
        return self._lib.router_pack_stack(
            self._handle,
            _ptr(key_bytes, ctypes.c_uint8), _ptr(key_ends, ctypes.c_int64),
            len(key_ends),
            _ptr(hits, ctypes.c_int64), _ptr(limits, ctypes.c_int64),
            _ptr(durations, ctypes.c_int64), _ptr(algos, ctypes.c_int32),
            now, lanes, K,
            _ptr(packed, ctypes.c_int64), _ptr(kcur, ctypes.c_int32),
            _ptr(shard_fill, ctypes.c_int32),
            _ptr(out_row, ctypes.c_int32), _ptr(out_lane, ctypes.c_int32),
            _ptr(out_pos, ctypes.c_int32),
        )

    def fastpath_encode_w(self, w0: np.ndarray, item_limit: np.ndarray,
                          now: int, lanes: int, n: int,
                          out_row: np.ndarray, out_lane: np.ndarray,
                          out_pos: np.ndarray, resp_buf: np.ndarray,
                          climit: Optional[np.ndarray] = None) -> int:
        """Fetched response-word plane -> serialized GetRateLimitsResp bytes
        (returns the length written into resp_buf).  climit: the device's
        limit plane, passed only when a stored-limit mismatch was flagged.
        out_pos: per-item synthesis info (aggregated runs), -1 = plain."""
        cl = _ptr(climit, ctypes.c_int64) if climit is not None else None
        m = self._lib.fastpath_encode_w(
            _ptr(w0, ctypes.c_int64), _ptr(item_limit, ctypes.c_int64),
            now, lanes, n,
            _ptr(out_row, ctypes.c_int32), _ptr(out_lane, ctypes.c_int32),
            _ptr(out_pos, ctypes.c_int32),
            cl, _ptr(resp_buf, ctypes.c_uint8), resp_buf.nbytes,
        )
        if m < 0:
            raise RuntimeError("fastpath_encode_w: response buffer too small")
        return m

    def export_keys(self, shard: int):
        """One local shard's resident committed entries, oldest first:
        (fp uint64[n], slot int32[n], expire int64[n]) — entry index ==
        device slot, so a snapshot needs no key strings to stay coherent
        with the restored arena planes."""
        cap = self.capacity_per_shard
        fp = np.empty(cap, np.uint64)
        slot = np.empty(cap, np.int32)
        expire = np.empty(cap, np.int64)
        n = self._lib.router_export_keys(
            self._handle, shard, _ptr(fp, ctypes.c_uint64),
            _ptr(slot, ctypes.c_int32), _ptr(expire, ctypes.c_int64))
        return fp[:n].copy(), slot[:n].copy(), expire[:n].copy()

    def import_keys(self, shard: int, fp: np.ndarray, slot: np.ndarray,
                    expire: np.ndarray) -> None:
        """Rebuild one local shard from export_keys output (oldest first).
        Raises on invalid slots or when the exact-key guard is active
        (exports carry no key bytes)."""
        fp = np.ascontiguousarray(fp, np.uint64)
        slot = np.ascontiguousarray(slot, np.int32)
        expire = np.ascontiguousarray(expire, np.int64)
        rc = self._lib.router_import_keys(
            self._handle, shard, _ptr(fp, ctypes.c_uint64),
            _ptr(slot, ctypes.c_int32), _ptr(expire, ctypes.c_int64),
            len(fp))
        if rc == -2:
            raise RuntimeError(
                "exact-keys native router cannot import a fingerprint-only "
                "snapshot")
        if rc != 0:
            raise ValueError("invalid or duplicate slot in key-map import")

    def occupancy(self, now: int):
        """(live, expired, free) slot counts over all local shards, judged
        by the host expiry estimate (engine.cache_stats)."""
        live = np.zeros(1, np.int64)
        expired = np.zeros(1, np.int64)
        free_slots = np.zeros(1, np.int64)
        self._lib.router_occupancy(
            self._handle, now, _ptr(live, ctypes.c_int64),
            _ptr(expired, ctypes.c_int64), _ptr(free_slots, ctypes.c_int64))
        return int(live[0]), int(expired[0]), int(free_slots[0])

    def heap_size(self, shard: int = 0) -> int:
        """Expiry-heap nodes (live + draining) for one shard — lets tests
        assert the bounded-heap guarantee at churn scale."""
        return self._lib.router_heap_size(self._handle, shard)

    @property
    def size(self) -> int:
        return self._lib.router_size(self._handle)

    @property
    def hits(self) -> int:
        return self._lib.router_hits(self._handle)

    @property
    def misses(self) -> int:
        return self._lib.router_misses(self._handle)
