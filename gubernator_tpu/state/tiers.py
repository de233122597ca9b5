"""Tiered key-state hierarchy: the fixed arena as a managed cache over an
unbounded (2^30+) logical keyspace.

Three tiers, coldest reconstructible from nothing:

  hot   the dense SoA device arena (ops/kernel.py BucketState) — layout,
        kernels and every bench path untouched; the SlotTable still owns
        which key occupies which slot.
  warm  this module: a host-side SoA store of LIVE bucket rows evicted
        from the arena, held in the snapshot serialization from
        state/snapshot.py — either absolute int64 times or compact32
        pair-rebased deltas against the store epoch, encoded/decoded in
        BATCHES through the snapshot's jitted codec
        (snapshot.rebase_encode/rebase_decode), which clips at the
        serving body's own rebase limit (ops/kernel.py REBASE_LIM).
  cold  nothing stored.  A miss in both tiers re-initializes from the
        request's self-describing config — exactly the reference's
        stateless-client semantics, so "arena full" becomes a cache-miss
        cost instead of a correctness cliff.

Demotion rides SlotTable._reclaim (state/arena.py spill hooks): evicting a
committed LIVE entry hands (key, slot) to `TierManager.on_spill`; the
engine gathers every spilled device row in ONE batched gather at the
pre-dispatch fence (core/engine.py _tier_fence), while the victim rows are
still intact on device.  Promotion happens at window-encode time: a
warm-resident key rehydrates into a freshly upserted slot and its row is
scattered back in the same fence, BEFORE the drain dispatches — so
decisions are bit-identical to an infinite-arena oracle (tests/
test_tiers.py runs the differential suite).  A key evicted and re-
requested within one un-dispatched drain short-circuits: the pending
spill becomes the promotion's row source (gather → scatter, never touching
the warm store), which keeps the demote→re-promote-mid-stream case exact.

Victim selection is heat-aware: the per-drain device analytics (PR 8
count-min hot-key scores, fetched at zero extra round trips) feed a
host-side heat estimate; the SlotTable ranks its LRU-head sample by heat
and spills the coldest.  With analytics off every heat reads 0.0 and the
policy degrades to the seed's strict LRU.

The warm tier requires the Python routing backend (the native C++ router
keeps fingerprints, not key strings — the same constraint as live key
migration) and a single-process engine; `RateLimitEngine.enable_tiers`
enforces both.
"""

from __future__ import annotations

import logging
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from gubernator_tpu.ops.kernel import REBASE_LIM
from gubernator_tpu.state.snapshot import rebase_decode, rebase_encode

log = logging.getLogger("gubernator.tiers")

_ROW_FIELDS = ("limit", "duration", "remaining", "tstamp", "expire", "algo")
_VAL_FIELDS = ("limit", "duration", "remaining")
_TIME_FIELDS = ("tstamp", "expire")

_I32 = 2 ** 31


def _pad_pow2(n: int) -> int:
    """Same shape bucketing as core/engine._pad_pow2: the jitted codec
    compiles for a handful of batch shapes, not one per call."""
    return max(8, 1 << (n - 1).bit_length())


class WarmStore:
    """Fixed-capacity host SoA store of demoted bucket rows.

    Rows live in one of two layouts (per store, chosen at construction):

      int64      every column int64 (algo int32) — always representable.
      compact32  limit/duration/remaining int32; tstamp/expire int32
                 deltas pair-rebased against the store epoch — half the
                 bytes per row.  Rows outside the rebase clip range or
                 int32 value range go to a small int64 overflow side map
                 instead of being truncated, so the layout choice is never
                 lossy.

    Keys index an insertion-ordered map (oldest first); on overflow the
    store evicts an EXPIRED resident first, else the oldest — cold is
    reconstructible, so dropping is a miss cost, not data loss.
    """

    def __init__(self, capacity: int, layout: str = "int64",
                 epoch: int = 0):
        if capacity <= 0:
            raise ValueError("warm capacity must be positive")
        if layout not in ("int64", "compact32"):
            raise ValueError(f"unknown warm layout {layout!r}")
        self.capacity = capacity
        self.layout = layout
        self.epoch = int(epoch)
        compact = layout == "compact32"
        vdt = np.int32 if compact else np.int64
        tdt = np.int32 if compact else np.int64
        self._cols: Dict[str, np.ndarray] = {
            "limit": np.zeros(capacity, vdt),
            "duration": np.zeros(capacity, vdt),
            "remaining": np.zeros(capacity, vdt),
            "tstamp": np.zeros(capacity, tdt),
            "expire": np.zeros(capacity, tdt),
            "algo": np.zeros(capacity, np.int32),
        }
        # absolute expire per row (int64) regardless of layout: expiry
        # checks and overflow eviction never pay a decode
        self._abs_expire = np.zeros(capacity, np.int64)
        self._index: "OrderedDict[str, int]" = OrderedDict()
        self._free = list(range(capacity - 1, -1, -1))
        # compact32 rows that failed the range check, canonical int64
        self._over: Dict[str, dict] = {}
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._index) + len(self._over)

    def __contains__(self, key: str) -> bool:
        return key in self._index or key in self._over

    def expire_of(self, key: str) -> Optional[int]:
        i = self._index.get(key)
        if i is not None:
            return int(self._abs_expire[i])
        row = self._over.get(key)
        return None if row is None else row["expire"]

    def nbytes(self) -> int:
        """Allocated SoA bytes plus the overflow side map estimate."""
        soa = sum(a.nbytes for a in self._cols.values())
        return soa + self._abs_expire.nbytes + 96 * len(self._over)

    # ----------------------------------------------------------------- put

    def _compact_ok(self, row: dict) -> bool:
        for f in _VAL_FIELDS:
            if not (-_I32 <= row[f] < _I32):
                return False
        for f in _TIME_FIELDS:
            d = row[f] - self.epoch
            if not (-REBASE_LIM <= d <= REBASE_LIM):
                return False
        return True

    def _alloc(self, key: str, now: int) -> Optional[int]:
        if self._free:
            i = self._free.pop()
        else:
            victim = None
            for scanned, (k, ri) in enumerate(self._index.items()):
                if self._abs_expire[ri] <= now:
                    victim = k
                    break
                if scanned >= 8:
                    break
            if victim is None:
                if not self._index:
                    return None  # capacity entirely held by overflow rows
                victim = next(iter(self._index))
            i = self._index.pop(victim)
            self.evictions += 1
        self._index[key] = i
        return i

    def put_batch(self, rows: List[dict], now: int) -> int:
        """Insert canonical int64 row dicts (encode once, batched).  A key
        already resident is overwritten in place.  Returns rows stored."""
        if not rows:
            return 0
        if self.layout == "compact32":
            fits = [self._compact_ok(r) for r in rows]
            for r, ok in zip(rows, fits):
                if not ok:
                    self._over[r["key"]] = {f: int(r[f]) for f in _ROW_FIELDS}
                    self._over[r["key"]]["key"] = r["key"]
                    self._index.pop(r["key"], None)
            rows = [r for r, ok in zip(rows, fits) if ok]
            if not rows:
                return len(fits)
        idxs = []
        kept = []
        for r in rows:
            key = r["key"]
            self._over.pop(key, None)
            i = self._index.get(key)
            if i is not None:
                self._index.move_to_end(key)
            else:
                i = self._alloc(key, now)
                if i is None:
                    self.evictions += 1
                    continue
            idxs.append(i)
            kept.append(r)
        if not kept:
            return 0
        n = len(kept)
        ii = np.asarray(idxs, np.int64)
        for f in _VAL_FIELDS + ("algo",):
            self._cols[f][ii] = [r[f] for r in kept]
        times = np.asarray([[r["tstamp"], r["expire"]] for r in kept],
                           np.int64)
        if self.layout == "compact32":
            m = _pad_pow2(n)
            padded = np.zeros((m, 2), np.int64)
            padded[:n] = times
            rel = rebase_encode(padded, np.zeros((m, 2), bool), self.epoch)
            self._cols["tstamp"][ii] = rel[:n, 0]
            self._cols["expire"][ii] = rel[:n, 1]
        else:
            self._cols["tstamp"][ii] = times[:, 0]
            self._cols["expire"][ii] = times[:, 1]
        self._abs_expire[ii] = times[:, 1]
        return n

    # ---------------------------------------------------------------- take

    def take(self, key: str, now: int) -> Optional[dict]:
        """Remove and return the row for `key`, or None when absent or
        already expired (an expired warm row reads as a miss on device
        anyway — promoting it would only ship dead weight).

        compact32 rows come back RAW (rel=True, int32 deltas): the caller
        batch-decodes at the dispatch fence through the kernel codec, so
        per-key takes stay allocation-only."""
        row = self._over.pop(key, None)
        if row is not None:
            if row["expire"] <= now:
                return None
            out = dict(row)
            out["rel"] = False
            return out
        i = self._index.pop(key, None)
        if i is None:
            return None
        self._free.append(i)
        if self._abs_expire[i] <= now:
            return None
        out = {f: int(self._cols[f][i]) for f in _ROW_FIELDS}
        out["key"] = key
        out["rel"] = self.layout == "compact32"
        out["abs_expire"] = int(self._abs_expire[i])
        return out

    # ------------------------------------------------------- serialization

    def export_rows(self) -> tuple:
        """(keys, {field: int64 array}) — every resident row in canonical
        absolute int64 form (snapshot persistence; state/snapshot.py packs
        these as optional npz arrays, old readers simply ignore them)."""
        keys = list(self._index.keys())
        cols = {}
        if keys:
            ii = np.asarray([self._index[k] for k in keys], np.int64)
            for f in _VAL_FIELDS + ("algo",):
                cols[f] = self._cols[f][ii].astype(np.int64)
            if self.layout == "compact32":
                n = len(keys)
                m = _pad_pow2(n)
                rel = np.zeros((m, 2), np.int32)
                rel[:n, 0] = self._cols["tstamp"][ii]
                rel[:n, 1] = self._cols["expire"][ii]
                out = rebase_decode(rel, self.epoch)
                cols["tstamp"] = out[:n, 0]
                cols["expire"] = out[:n, 1]
            else:
                cols["tstamp"] = self._cols["tstamp"][ii].astype(np.int64)
                cols["expire"] = self._cols["expire"][ii].astype(np.int64)
        else:
            cols = {f: np.empty(0, np.int64) for f in _ROW_FIELDS}
        for key, row in self._over.items():
            keys.append(key)
            for f in _ROW_FIELDS:
                cols[f] = np.append(cols[f], np.int64(row[f]))
        return keys, cols

    def restore_rows(self, keys: List[str], cols: Dict[str, np.ndarray],
                     now: int, shift: int = 0) -> int:
        """Re-insert exported rows (daemon restart: the warm tier rides the
        same snapshot machinery as the arena).  `shift` rebases times into
        a new clock domain, mirroring engine.import_state."""
        rows = []
        for j, key in enumerate(keys):
            row = {f: int(cols[f][j]) for f in _ROW_FIELDS}
            if shift and row["expire"]:
                row["tstamp"] += shift
                row["expire"] += shift
            row["key"] = key
            if row["expire"] > now:
                rows.append(row)
        return self.put_batch(rows, now)


class TierManager:
    """Bookkeeping between the SlotTable spill hooks, the warm store, and
    the engine's pre-dispatch fence.  All methods run on the engine's
    single dispatch thread (the same quiesce contract as migration), so no
    locking is needed."""

    def __init__(self, conf, epoch: int, analytics=None):
        self.conf = conf
        self.warm = WarmStore(conf.warm_rows, conf.layout, epoch)
        self.analytics = analytics
        self._heat: Dict[str, float] = {}
        self.fences = 0
        # key -> (shard, slot): committed victims evicted since the last
        # fence, device rows still intact until the next dispatch
        self.pending_spills: "OrderedDict[str, tuple]" = OrderedDict()
        # key -> [shard, slot, row|None, spill_src|None]: rows to scatter
        # at the fence.  row is a WarmStore.take dict; spill_src routes a
        # demote→re-promote-in-one-drain key straight from the gather.
        self.pending_promos: "OrderedDict[str, list]" = OrderedDict()
        self.counters = {
            "promotions": 0,
            "promotions_from_spill": 0,
            "demotions": 0,
            "demote_dropped_expired": 0,
            "demote_dropped_stale": 0,
            "warm_hits": 0,
            "cold_misses": 0,
        }

    # ------------------------------------------------------------ heat feed

    def heat(self, key: str) -> float:
        return self._heat.get(key, 0.0)

    def refresh_heat(self) -> None:
        """Pull the analytics rolling top-K into the per-key heat map the
        eviction sampler reads.  Cheap (top-K is small); called from
        tier_maintain and periodically from the fence."""
        if self.analytics is None:
            return
        try:
            self._heat = {r["key"]: float(r["score"])
                          for r in self.analytics.topk_snapshot()}
        except Exception:  # observability must never break serving
            log.exception("tier heat refresh failed")

    # --------------------------------------------------------- spill intake

    def on_spill(self, shard: int, key: str, slot: int, expire: int,
                 stale: bool) -> None:
        """SlotTable spill hook: a committed entry was evicted.  `stale`
        means the victim was touched by the current un-dispatched drain
        (only possible when every LRU-head candidate was) — its device row
        misses that drain's hits, so it drops to cold instead of storing a
        wrong row."""
        promo = self.pending_promos.pop(key, None)
        if promo is not None:
            # a key promoted THIS drain got evicted again before dispatch:
            # the row never reached the device, so just return it to warm
            # (or drop a from-spill promo back to the spill list)
            if promo[3] is not None:
                self.pending_spills[key] = promo[3]
            elif promo[2] is not None:
                self._restore_row(promo[2])
            return
        if stale:
            self.counters["demote_dropped_stale"] += 1
            return
        self.pending_spills[key] = (shard, slot)

    def _restore_row(self, row: dict) -> None:
        """Put a previously taken row back (promotion cancelled before its
        scatter).  Raw compact rows re-encode through put_batch after an
        exact python-side reabs (rel values are unclipped by construction,
        so epoch + rel is the codec's own inverse)."""
        canon = {f: int(row[f]) for f in _VAL_FIELDS + ("algo",)}
        if row.get("rel"):
            canon["tstamp"] = self.warm.epoch + int(row["tstamp"])
            canon["expire"] = self.warm.epoch + int(row["expire"])
        else:
            canon["tstamp"] = int(row["tstamp"])
            canon["expire"] = int(row["expire"])
        canon["key"] = row["key"]
        self.warm.put_batch([canon], now=0)

    # ----------------------------------------------------- staging promotion

    def stage_promote(self, shard: int, table, key: str, now: int,
                      duration: int) -> Optional[int]:
        """Called from engine._stage_requests for a key absent from the hot
        table.  Returns the upserted slot when the key rehydrates from the
        warm tier (or from a same-drain pending spill), else None — the
        caller then takes the ordinary cold-miss lookup path."""
        src = self.pending_spills.pop(key, None)
        if src is not None:
            # demoted earlier in this drain, now requested again: the old
            # device row is still intact — route it through the fence
            # gather into the new slot
            slot = table.upsert(key, now, now + duration)
            self.pending_promos[key] = [shard, slot, None, src]
            self.counters["warm_hits"] += 1
            self.counters["promotions_from_spill"] += 1
            return slot
        row = self.warm.take(key, now)
        if row is None:
            self.counters["cold_misses"] += 1
            return None
        expire = row["abs_expire"] if row.get("rel") else row["expire"]
        slot = table.upsert(key, now, expire)
        self.pending_promos[key] = [shard, slot, row, None]
        self.counters["warm_hits"] += 1
        return slot

    # ------------------------------------------------------------- the fence

    def drain_pending(self) -> tuple:
        """Hand the fence its work lists and reset: (spills, promos) where
        spills is [(key, shard, slot)] and promos is the pending_promos
        values with their keys."""
        spills = [(k, s[0], s[1]) for k, s in self.pending_spills.items()]
        promos = [(k, p) for k, p in self.pending_promos.items()]
        self.pending_spills = OrderedDict()
        self.pending_promos = OrderedDict()
        return spills, promos

    def decode_rows(self, rows: List[dict]) -> List[dict]:
        """Batch-decode raw compact32 rows to canonical int64 through the
        kernel codec (one call per fence, padded shape bucketing)."""
        rel_rows = [r for r in rows if r.get("rel")]
        if rel_rows:
            n = len(rel_rows)
            m = _pad_pow2(n)
            rel = np.zeros((m, 2), np.int32)
            for j, r in enumerate(rel_rows):
                rel[j, 0] = r["tstamp"]
                rel[j, 1] = r["expire"]
            out = rebase_decode(rel, self.warm.epoch)
            for j, r in enumerate(rel_rows):
                r["tstamp"] = int(out[j, 0])
                r["expire"] = int(out[j, 1])
                r["rel"] = False
        return rows

    # ------------------------------------------------------------- reporting

    def stats(self) -> dict:
        out = dict(self.counters)
        out.update({
            "warm_rows": len(self.warm),
            "warm_capacity": self.warm.capacity,
            "warm_bytes": self.warm.nbytes(),
            "warm_evictions": self.warm.evictions,
            "warm_layout": self.warm.layout,
            "fences": self.fences,
            "pending_spills": len(self.pending_spills),
            "pending_promotions": len(self.pending_promos),
        })
        return out
