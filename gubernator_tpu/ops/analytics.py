"""Device-side traffic analytics: the per-drain stats reduction.

The serving drain already moves every number the operator wants — which
slots were hit, how hard, which lanes went over limit, whether a lane
initialized a bucket — it just throws them away after encoding the
response words.  `shard_stats` is a second, tiny executable over the SAME
arrays the drain consumed/produced (the compact request stack and the
response words of `engine.pipeline_dispatch`, plus the resident expiry
plane), so it composes with both drains unchanged: the standalone drain
and the mesh's GLOBAL-composed drain feed it the identical (packed, words)
pair.  Per shard it accumulates:

  * outcome counts — occupied lanes, total hits, under/over-limit, inits
    (arena churn), plus post-drain live/expired slot counts from the
    expiry plane (occupancy);
  * a count-min sketch over slot ids, persistent on device across drains
    (decayed by halving on a host-driven cadence), weighted
    `hits + over_weight * over` so keys burning their limit rank above
    merely chatty ones;
  * a candidate top-K: the drain's touched slots ranked by their
    CUMULATIVE sketch estimate (not just this drain's sample), shipped as
    (slot, estimate, drain_hits, drain_over) rows for the host's rolling
    merge (observability/analytics.py);
  * per-tenant rows (decisions, hits, over) keyed by the small-int tenant
    ids the host staged alongside the lanes (qos/fairness tenant = the
    request `name`).

Everything packs into ONE flat i64 stats vector per shard so the host
fetch piggybacks on the drain result's async copies — no extra
device→host round trip, and nothing here touches the drain executables
themselves (the analytics-off serving path is byte-identical).

`oracle_stats` is the numpy mirror used by the differential tests and the
hot-key probe: same hash mix, same decay, same candidate rule, exact.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops.kernel import AGG_SLOT_BIT, COMPACT_MAX_HITS

# The native compact path tags every lane's slot+1 field with the
# aggregated-run flag (host_router.cc AGG_W0_BIT, even for n=1 runs);
# analytics wants the arena slot, so the flag is stripped on decode.
# An AGG lane's hits field already carries the folded run's TOTAL n.
_SLOT_MASK = 0xFFFFFFFF & ~AGG_SLOT_BIT

# Stats-vector layout: [HEADER | T tenant rows x 3 | K candidate rows x 4]
HEADER = 8
(IDX_LANES, IDX_HITS, IDX_UNDER, IDX_OVER, IDX_INIT, IDX_LIVE, IDX_EXPIRED,
 IDX_RESERVED) = range(HEADER)
TENANT_COLS = 3   # decisions, hits, over
CAND_COLS = 4     # slot, sketch estimate, drain hits, drain over

# Odd 62-bit multipliers (splitmix64-flavored) — one per sketch row.  The
# mask keeps every intermediate non-negative so `>>` and `%` behave the
# same in jnp (arithmetic shift) and numpy: the oracle must be bit-exact.
_MASK62 = (1 << 62) - 1
_MULTS = (
    0x2545F4914F6CDD1D, 0x369DEA0F31A53F85, 0x27BB2EE687B0B0FD,
    0x106689D45497FDB5, 0x1B873593CC9E2D51, 0x2127599BF4325C37,
    0x0B4B82E749B0A2F5, 0x3C6EF372FE94F82B,
)
MAX_SKETCH_DEPTH = len(_MULTS)


def stats_len(tenant_slots: int, topk: int) -> int:
    return HEADER + tenant_slots * TENANT_COLS + topk * CAND_COLS


def hash_slots(xp, slots, row: int, width: int):
    """Sketch row hash of slot ids, shared by device and oracle (xp is
    jnp or np; `slots` i64).  Multiply-xorshift keeps rows pairwise
    independent enough for the count-min guarantee to hold in practice."""
    x = ((slots + 1 + row) * _MULTS[row % MAX_SKETCH_DEPTH]) & _MASK62
    x = x ^ (x >> 31)
    return x % width


class DecodedLanes(NamedTuple):
    """Per-lane fields the reduction reads from the drain's wire arrays."""

    slot: jax.Array     # i32, PAD lanes < 0
    occupied: jax.Array  # i64 0/1
    hits: jax.Array     # i64, 0 on PAD
    is_init: jax.Array  # i64 0/1, 0 on PAD
    over: jax.Array     # i64 0/1, 0 on PAD


def _decode(xp, packed, words) -> DecodedLanes:
    """Compact request word0 + response word → the reduction's inputs
    (kernel.decode_batch / encode_output_word wire layout)."""
    w0 = packed[..., 0]
    slot = (w0 & _SLOT_MASK) - 1
    occ = (slot >= 0).astype(w0.dtype)
    return DecodedLanes(
        slot=slot,
        occupied=occ,
        hits=((w0 >> 34) & (COMPACT_MAX_HITS - 1)) * occ,
        is_init=((w0 >> 32) & 1) * occ,
        over=((words >> 31) & 1) * occ,
    )


def shard_stats(sketch, packed, words, tenants, expire, now, decay, *,
                tenant_slots: int, topk: int, over_weight: int):
    """One shard's per-drain reduction (runs under the engine's shard_map).

    sketch  i64[D, W]  persistent count-min rows (carried across drains)
    packed  i64[K, B, 2] the drain's compact request stack (this shard)
    words   i64[K, B]  the drain's response words (this shard)
    tenants i32[K, B]  host-staged tenant ids (0 = unattributed)
    expire  i64[C]     the post-drain expiry plane (resident, not copied)
    now     i64        the drain timestamp (ms)
    decay   i64        0 or 1: halve the sketch before accumulating

    Returns (new_sketch, stats i64[V]) with V = stats_len(T, K_top).
    """
    C = expire.shape[0]
    d = _decode(jnp, packed, words)
    cslot = jnp.clip(d.slot, 0, C - 1).ravel()

    # Dense per-slot aggregation of THIS drain (O(C) scratch, amortized
    # over all K windows).
    # ONE [C, 3] scatter-add instead of three [C] ones: integer adds are
    # exact and per-column independent, so the split arrays are
    # bit-identical to the oracle's three np.add.at passes — at a third
    # of the executed scatter kernels.
    dense = jnp.zeros((C, 3), jnp.int64).at[cslot].add(
        jnp.stack([d.hits.ravel(), d.over.ravel(), d.occupied.ravel()],
                  axis=-1))
    dense_h, dense_o, touched = dense[:, 0], dense[:, 1], dense[:, 2]
    dense_w = dense_h + over_weight * dense_o

    # Count-min update: decay-by-halving (decay is 0 or 1, so `>>` is a
    # no-op on the hot path — no branch), then scatter-add the drain's
    # per-slot weights into each hashed row.  All D rows go in ONE flat
    # [D*W] scatter (row r offset by r*W, so rows can never collide) —
    # same per-bucket integer sums as the oracle's per-row np.add.at
    # loop, D-fold fewer scatter/gather kernels.
    D, W = sketch.shape
    all_slots = jnp.arange(C, dtype=jnp.int64)
    rr = jnp.arange(D, dtype=jnp.int64)[:, None]
    mults = jnp.asarray([_MULTS[r % MAX_SKETCH_DEPTH] for r in range(D)],
                        jnp.int64)[:, None]
    x = ((all_slots[None, :] + 1 + rr) * mults) & _MASK62
    x = x ^ (x >> 31)
    h = x % W  # [D, C] — hash_slots for every row at once
    flat = (sketch >> decay).ravel().at[(rr * W + h).ravel()].add(
        jnp.broadcast_to(dense_w, (D, C)).ravel())
    new_sketch = flat.reshape(D, W)
    est = jnp.min(jnp.take_along_axis(new_sketch, h, axis=1), axis=0)

    # Candidates: slots touched this drain, ranked by cumulative estimate.
    score = jnp.where(touched > 0, est, jnp.int64(-1))
    top_est, top_slot = jax.lax.top_k(score, topk)
    valid = top_est >= 0
    cand = jnp.stack([
        jnp.where(valid, top_slot.astype(jnp.int64), -1),
        jnp.where(valid, top_est, 0),
        jnp.where(valid, dense_h[top_slot], 0),
        jnp.where(valid, dense_o[top_slot], 0),
    ], axis=-1)

    # Per-tenant rows (host staged ids; clip defends against garbage).
    # Same one-scatter shape as `dense` above.
    t = jnp.clip(tenants.astype(jnp.int64), 0, tenant_slots - 1).ravel()
    trows = jnp.zeros((tenant_slots, TENANT_COLS), jnp.int64).at[t].add(
        jnp.stack([d.occupied.ravel(), d.hits.ravel(), d.over.ravel()],
                  axis=-1))

    lanes = d.occupied.sum()
    over = d.over.sum()
    header = jnp.stack([
        lanes, d.hits.sum(), lanes - over, over, d.is_init.sum(),
        jnp.sum((expire > now).astype(jnp.int64)),
        jnp.sum(((expire != 0) & (expire <= now)).astype(jnp.int64)),
        jnp.int64(0),
    ])
    return new_sketch, jnp.concatenate([header, trows.ravel(), cand.ravel()])


def oracle_stats(sketch, packed, words, tenants, expire, now, decay, *,
                 tenant_slots: int, topk: int, over_weight: int):
    """Numpy mirror of `shard_stats` — the differential tests' ground
    truth.  Bit-exact by construction: same hash mix, same halving decay,
    same candidate rule (ties broken by slot index, like lax.top_k on the
    flipped-index tiebreak below)."""
    sketch = np.asarray(sketch, np.int64).copy()
    packed = np.asarray(packed, np.int64)
    words = np.asarray(words, np.int64)
    C = int(np.asarray(expire).shape[0])
    d = _decode(np, packed, words)
    cslot = np.clip(d.slot, 0, C - 1).ravel()

    dense_h = np.zeros(C, np.int64)
    dense_o = np.zeros(C, np.int64)
    touched = np.zeros(C, np.int64)
    np.add.at(dense_h, cslot, d.hits.ravel())
    np.add.at(dense_o, cslot, d.over.ravel())
    np.add.at(touched, cslot, d.occupied.ravel())
    dense_w = dense_h + over_weight * dense_o

    all_slots = np.arange(C, dtype=np.int64)
    ests = np.full((sketch.shape[0], C), np.iinfo(np.int64).max)
    for r in range(sketch.shape[0]):
        h = hash_slots(np, all_slots, r, sketch.shape[1])
        sketch[r] >>= decay
        np.add.at(sketch[r], h, dense_w)
        ests[r] = sketch[r][h]
    est = ests.min(axis=0)

    score = np.where(touched > 0, est, -1)
    # lax.top_k returns the FIRST index on ties; argsort on (-score, slot)
    order = np.lexsort((all_slots, -score))[:topk]
    cand = np.zeros((topk, CAND_COLS), np.int64)
    for i, s in enumerate(order):
        if score[s] >= 0:
            cand[i] = (s, score[s], dense_h[s], dense_o[s])
        else:
            cand[i] = (-1, 0, 0, 0)

    t = np.clip(np.asarray(tenants, np.int64), 0, tenant_slots - 1).ravel()
    trows = np.zeros((tenant_slots, TENANT_COLS), np.int64)
    np.add.at(trows[:, 0], t, d.occupied.ravel())
    np.add.at(trows[:, 1], t, d.hits.ravel())
    np.add.at(trows[:, 2], t, d.over.ravel())

    expire = np.asarray(expire, np.int64)
    lanes = int(d.occupied.sum())
    over = int(d.over.sum())
    header = np.array([
        lanes, d.hits.sum(), lanes - over, over, d.is_init.sum(),
        int((expire > now).sum()), int(((expire != 0) & (expire <= now)).sum()),
        0,
    ], np.int64)
    return sketch, np.concatenate([header, trows.ravel(), cand.ravel()])
