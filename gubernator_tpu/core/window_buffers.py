"""Preallocated drain staging arenas + columnar request accumulators.

The overlapped drain pipeline (core/pipeline.py) keeps up to `depth`
drains in flight; each drain needs host-side staging that must stay
untouched until its device work has provably consumed it (the host→device
transfer of a dispatched stack may still be reading the numpy buffers
after dispatch returns).  Allocating that staging fresh per drain is
safe but wasteful — per drain it costs one K·S·B·2 int64 zeros call plus
six scratch arrays per RpcJob, and every native call re-derives ctypes
pointers from scratch (measured ~8% of host wall on the cpu smoke tier).

This module replaces the fresh-per-drain allocations with a ring of
reusable arenas:

  * `WindowArena` — one drain's packed stack / fills / kcur plus a pool
    of per-job demux scratch blocks, with ctypes pointers derived ONCE at
    allocation.  Recycling zeroes only the lanes the previous drain
    actually occupied (tracked per (k, shard) fill), not the whole stack.
  * `WindowArenaRing` — the free list.  Arenas are acquired on the
    engine thread at drain start and released only on CLEAN completion
    (fetch done ⇒ device execution done ⇒ the H2D transfer that read the
    buffers is finished).  Error paths simply drop the arena — the ring
    allocates a replacement later, which is self-healing and keeps the
    transfer-safety argument trivial.  Reuse vs. realloc counts are in
    /v1/admin/debug (pipeline.overlap.arena_reuse_events / _alloc_events).
  * `RequestColumns` — columnar accumulation of single-request submits:
    hits/limit/duration/algorithm land in preallocated numpy columns at
    submit time, so a drain takes window columns as array slices (the
    zero-copy path) or one fancy-indexed gather (tenant-fair slotting)
    instead of re-walking request objects in per-field list
    comprehensions.
"""

from __future__ import annotations

import ctypes
import threading
from typing import List, Optional, Sequence

import numpy as np

from gubernator_tpu.config import MAX_BATCH_SIZE


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


class JobScratch:
    """One job's demux staging (row/lane/pos per item, plus the RpcJob
    fastpath's limit/offset/length planes), sized to the 1000-item RPC
    cap with ctypes pointers cached at allocation.  A scratch block is
    valid for exactly one drain unless `leased` — a mixed-ownership RPC's
    forward coroutines keep reading off/mlen after the drain completes,
    so its block leaves the pool with the job instead of being recycled
    under it."""

    __slots__ = ("row", "lane", "pos", "limit", "off", "mlen",
                 "p_row", "p_lane", "p_pos", "p_limit", "p_off", "p_mlen",
                 "leased")

    def __init__(self):
        self.row = np.empty(MAX_BATCH_SIZE, np.int32)
        self.lane = np.empty(MAX_BATCH_SIZE, np.int32)
        self.pos = np.empty(MAX_BATCH_SIZE, np.int32)
        self.limit = np.empty(MAX_BATCH_SIZE, np.int64)
        self.off = np.empty(MAX_BATCH_SIZE, np.int64)
        self.mlen = np.empty(MAX_BATCH_SIZE, np.int32)
        self.p_row = _ptr(self.row, ctypes.c_int32)
        self.p_lane = _ptr(self.lane, ctypes.c_int32)
        self.p_pos = _ptr(self.pos, ctypes.c_int32)
        self.p_limit = _ptr(self.limit, ctypes.c_int64)
        self.p_off = _ptr(self.off, ctypes.c_int64)
        self.p_mlen = _ptr(self.mlen, ctypes.c_int32)
        self.leased = False


class WindowArena:
    """One drain's staging: the K-window packed stack, per-(k, shard)
    fills, per-shard window cursors, and a scratch-block pool."""

    __slots__ = ("K", "S", "B", "packed", "fills", "kcur",
                 "p_packed", "p_fills", "p_kcur",
                 "_scratch", "_scratch_idx", "scratch_allocs", "dirty")

    def __init__(self, K: int, S: int, B: int):
        self.K = K
        self.S = S
        self.B = B
        self.packed = np.zeros((K, S, B, 2), np.int64)
        self.fills = np.zeros((K, S), np.int32)
        self.kcur = np.zeros(S, np.int32)
        self.p_packed = _ptr(self.packed, ctypes.c_int64)
        self.p_fills = _ptr(self.fills, ctypes.c_int32)
        self.p_kcur = _ptr(self.kcur, ctypes.c_int32)
        self._scratch: List[JobScratch] = []
        self._scratch_idx = 0
        self.scratch_allocs = 0
        # has this arena staged anything since its last recycle?
        self.dirty = False

    def acquire_scratch(self) -> JobScratch:
        """Next scratch block for one job of the current drain (engine
        thread only)."""
        while self._scratch_idx < len(self._scratch):
            scr = self._scratch[self._scratch_idx]
            self._scratch_idx += 1
            if not scr.leased:
                return scr
        scr = JobScratch()
        self._scratch.append(scr)
        self._scratch_idx = len(self._scratch)
        self.scratch_allocs += 1
        return scr

    def recycle(self) -> None:
        """Make the arena ready for its next drain: zero exactly the lanes
        the previous drain occupied (per-(k, shard) fill prefixes), reset
        the cursors, and drop leased scratch blocks from the pool."""
        if self.dirty:
            fills = self.fills
            packed = self.packed
            for k, s in zip(*np.nonzero(fills)):
                packed[k, s, : fills[k, s]] = 0
            fills.fill(0)
            self.kcur.fill(0)
            self.dirty = False
        if any(scr.leased for scr in self._scratch):
            self._scratch = [s for s in self._scratch if not s.leased]
        self._scratch_idx = 0


class WindowArenaRing:
    """Free list of WindowArenas keyed by stack shape.  Acquire happens on
    the engine thread, release on the event loop (drain completion), so
    the list sits behind a lock."""

    def __init__(self, max_free: int = 8):
        self._free: List[WindowArena] = []
        self._lock = threading.Lock()
        self._max_free = max_free
        # read by overlap_snapshot (/v1/admin/debug), tests and the probe
        self.reuse_events = 0
        self.alloc_events = 0

    def acquire(self, K: int, S: int, B: int) -> WindowArena:
        arena = None
        with self._lock:
            for i, a in enumerate(self._free):
                if a.K >= K and a.S == S and a.B == B:
                    arena = self._free.pop(i)
                    break
        if arena is not None:
            self.reuse_events += 1
            return arena
        self.alloc_events += 1
        return WindowArena(K, S, B)

    def release(self, arena: Optional[WindowArena]) -> None:
        """Return a CLEANLY completed drain's arena (fetch done, so the
        device provably finished reading its buffers).  Error paths must
        NOT call this — dropping the arena instead keeps a possibly
        still-transferring buffer out of the pool."""
        if arena is None:
            return
        arena.recycle()
        with self._lock:
            if len(self._free) < self._max_free:
                self._free.append(arena)


class RequestColumns:
    """Columnar accumulator for single-request submits (the pipeline's
    `_singles` lane and the batcher's classic pending window).

    `append` writes the request's numeric fields into preallocated numpy
    columns and stashes the encoded hash key, so draining N singles costs
    column SLICES (contiguous take) or one fancy-indexed gather per column
    (tenant-fair permutation) — never a per-field Python list
    comprehension over request objects."""

    __slots__ = ("hits", "limit", "duration", "algo", "keys", "klen", "n")

    def __init__(self, cap: int = 1024):
        self.hits = np.empty(cap, np.int64)
        self.limit = np.empty(cap, np.int64)
        self.duration = np.empty(cap, np.int64)
        self.algo = np.empty(cap, np.int32)
        self.klen = np.empty(cap, np.int64)
        self.keys: List[bytes] = []
        self.n = 0

    def _grow(self) -> None:
        cap = len(self.hits) * 2
        for name in ("hits", "limit", "duration", "algo", "klen"):
            old = getattr(self, name)
            arr = np.empty(cap, old.dtype)
            arr[: self.n] = old[: self.n]
            setattr(self, name, arr)

    def append(self, req) -> int:
        """Accumulate one request; returns its column index."""
        i = self.n
        if i == len(self.hits):
            self._grow()
        self.hits[i] = req.hits
        self.limit[i] = req.limit
        self.duration[i] = req.duration
        self.algo[i] = req.algorithm
        key = req.hash_key().encode("utf-8")
        self.keys.append(key)
        self.klen[i] = len(key)
        self.n = i + 1
        return i

    def reset(self) -> None:
        self.n = 0
        self.keys.clear()

    def take(self, idx: Optional[Sequence[int]], start: int, stop: int):
        """One window chunk's native-router columns: (key_bytes, key_ends,
        hits, limit, duration, algo).  `idx` None means the chunk is the
        contiguous [start, stop) range of submission order — the numeric
        columns come back as zero-copy slices.  Otherwise `idx` is the
        drain's permutation (tenant-fair interleave / cwnd budget) and the
        chunk gathers idx[start:stop]."""
        if idx is None:
            keys = self.keys[start:stop]
            ends = np.cumsum(self.klen[start:stop])
            return (np.frombuffer(b"".join(keys), dtype=np.uint8), ends,
                    self.hits[start:stop], self.limit[start:stop],
                    self.duration[start:stop], self.algo[start:stop])
        sel = np.asarray(idx[start:stop], np.int64)
        keys = [self.keys[i] for i in sel]
        ends = np.cumsum(self.klen[sel])
        return (np.frombuffer(b"".join(keys), dtype=np.uint8), ends,
                self.hits[sel], self.limit[sel],
                self.duration[sel], self.algo[sel])
