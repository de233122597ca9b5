"""The rate-limit window kernel: both bucket algorithms over dense SoA state.

This module is the TPU-native replacement for the reference's hot loop — the
`tokenBucket`/`leakyBucket` functions applied one key at a time under a global
cache mutex (reference algorithms.go:24-186, gubernator.go:236-251).  Here one
*window* of requests (the reference's 500µs BATCHING window, peers.go:143-172)
is evaluated as a single fused XLA computation over a batch:

  * State is a structure-of-arrays arena in device memory, replacing the
    map+linked-list LRU (reference cache/lru.go:30-96): `BucketState` is
    its int64 row form (the oracle's, and the replicated GLOBAL table's),
    `ArenaPlanes` the form the sharded arena is resident in — the same
    columns as (lo, hi) uint32 planes, so no executable converts anything
    of the arena's size.  A slot index replaces the string key; the host
    keeps the key→slot table (state/arena.py).
  * Every request in the window is routed to a slot.  Requests to *different*
    slots are data-parallel.  Requests to the *same* slot must observe
    sequential semantics (request N+1 sees N's decrement — the reference gets
    this from the cache mutex), which we reproduce with a sorted
    segment-replay: sort the window by slot, then run `max_duplicates` rounds
    of a fully-vectorized transition, each round applying the p-th request of
    every segment simultaneously.  A window of unique keys converges in one
    round; only hot-key duplicates add rounds.
  * Lazy TTL expiry (reference cache/lru.go:110-114: entry is a miss when
    `expireAt < now`) is evaluated *inside* the kernel, so the host table
    never needs to know whether an entry is live.

Branch semantics are reproduced exactly — including the subtle ones:
no-mutation-on-over-ask (algorithms.go:57-62,143-148), hits==0 read-only
(algorithms.go:46-49,150-153), exact-drain returns UNDER_LIMIT
(algorithms.go:51-55,136-141), OVER_LIMIT *is* stored on first-request
over-ask (algorithms.go:77-83,176-181), leaky's rate computed from the stored
duration but the *request's* limit (algorithms.go:107), the leaky timestamp
advancing even when the request is rejected (algorithms.go:118-121,143-148),
and repeated leak application when zero-hit reads interleave (a consequence of
algorithms.go:110-121).

Deliberate divergences from the reference (see SURVEY.md §7 "reference bugs
not to replicate"):
  * algorithm switch mid-stream resets the entry and re-runs it under the
    *requested* algorithm (the reference falls back to tokenBucket from
    leakyBucket, algorithms.go:100-104);
  * successful leaky decrement extends expiry to now + duration (the reference
    computes `now * duration`, algorithms.go:157);
  * leaky `rate` is clamped to ≥1ms (the reference divides by zero when
    limit > duration, algorithms.go:107-111 — a Go runtime panic).

All rate quantities are int64 (proto contract, gubernator.proto:104-117) and
timestamps are unix-epoch milliseconds (cache/lru.go:99-101) passed in as the
per-window `now` scalar — one timestamp per window instead of one per request.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Algorithm / status constants mirrored from the proto enums
# (proto/gubernator.proto:56-61,126-129).  Kept as plain ints so they can be
# used inside jit without host lookups.  Values 2..4 extend the wire enum
# beyond the reference (gubernator_tpu/algorithms/): GCRA as TAT arithmetic
# on the tstamp column, a weighted two-bucket sliding window packed into the
# remaining column, and concurrency leases with acquire/release semantics
# (negative hits releases held slots).  Any OTHER value degrades to token
# bucket — the reference's unknown-algorithm fallback (algorithms.go:100-104).
TOKEN_BUCKET = 0
LEAKY_BUCKET = 1
GCRA = 2
SLIDING_WINDOW = 3
CONCURRENCY = 4
UNDER_LIMIT = 0
OVER_LIMIT = 1

# Sliding-window packing: the remaining column carries BOTH window counters
# as cur | prev<<15, so sliding limits are clamped to 2^15-1 (documented
# divergence: a sliding request with limit > 32767 is served against 32767;
# the response's `limit` still echoes the stored config).  The interpolation
# weight is quantized to 1/1024ths so prev*(weight) stays exact in int32.
SLIDING_PACK_BITS = 15
SLIDING_MAX_LIMIT = (1 << SLIDING_PACK_BITS) - 1
SLIDING_WEIGHT_Q = 1024
# Sliding rows need now - window_start < 2*duration to stay inside the
# rebased-i32 exactness range of the compact serving path, so the compact
# eligibility cap for sliding durations is half the generic cap.
SLIDING_MAX_DURATION = 1 << 30

# Concurrency hits travel sign-extended through the 28-bit compact hits
# field (bit 27 is the sign), so releases are range-limited to |hits| < 2^27.
CONC_MAX_HITS = 1 << 27

# Slot value marking a padded (unused) lane of a window batch.
PAD_SLOT = -1

# Aggregated-run flag, carried in bit 30 of a lane's slot (arena capacities
# are <= 2^27, so the bit is free; pads are negative and unaffected).  The
# native router collapses a UNIFORM run of n identical hits=1, limit>0
# requests to one key into ONE lane with hits=n and this bit set; the
# device consumes k* = min(n, r_start) tokens and answers with r_start,
# from which the host synthesizes every item's response (status_i =
# i < r_start, remaining_i = max(r_start-(i+1), 0) — no n needed).  Only
# the compact serving path ever sets it (host_router.cc).
AGG_SLOT_BIT = 1 << 30

I32 = jnp.int32
I64 = jnp.int64
U64 = jnp.uint64
# status constants as int32 scalars (a python int would trace as a weak int64)
_UNDER = np.int32(UNDER_LIMIT)
_OVER = np.int32(OVER_LIMIT)


def floordiv(a, b):
    """`a // b` (jnp.floor_divide, bit for bit) whose int64 form compiles
    for the TPU.

    The TPU has no 64-bit integer divide.  XLA expands each one inline, and
    the v5e compiler then spends ~8 s PER int64 division (AOT-measured, PR
    24); the int64 ladders hold 20-55 of them, so every GLOBAL-carrying or
    full-format executable took minutes to compile and a cold daemon boot
    about twenty.  When the program lowers for a TPU, int64 operands
    therefore divide through a rolled restoring long division (64 steps, 8
    per loop trip) that compiles in ~0.4 s.  Every other backend, and
    int32 operands everywhere (the compact32 serving body), keep the
    native op."""
    if jnp.result_type(a, b) != I64:
        return a // b
    a, b = jnp.broadcast_arrays(jnp.asarray(a, I64), jnp.asarray(b, I64))
    return lax.platform_dependent(a, b, tpu=_floordiv_rolled,
                                  default=jnp.floor_divide)


def _floordiv_rolled(a, b):
    n, d = jnp.abs(a).astype(U64), jnp.abs(b).astype(U64)  # |INT64_MIN| ok
    one = jnp.uint64(1)

    def step(i, c):
        q, r = c
        r = (r << one) | ((n >> (jnp.uint64(63) - i.astype(U64))) & one)
        ge = r >= d
        return (q << one) | ge.astype(U64), jnp.where(ge, r - d, r)

    zero = (n ^ n) | (d ^ d)  # carries n's and d's shard_map variance
    q, r = lax.fori_loop(0, 64, step, (zero, zero), unroll=8)
    q = q.astype(I64)
    # toward zero -> toward -inf when the signs differ and it was inexact
    out = jnp.where((a < 0) != (b < 0), -q - (r != 0).astype(I64), q)
    # XLA's integer x/0 is -1; floor_divide's sign fix then makes it -2
    return jnp.where(b == 0, jnp.where(a != 0, I64(-2), I64(-1)), out)


class BucketState(NamedTuple):
    """Dense SoA bucket state as int64 rows, one row per key slot: what
    `window_step` (the int64 oracle) steps, what the replicated GLOBAL
    table is held as, and the form every reader outside a drain sees
    (snapshot, migration, tiers).  The engine's sharded arena holds the
    same columns as uint32 planes between drains: ArenaPlanes below.

    Replaces the reference's cacheRecord {value, expireAt} where value is
    either a *RateLimitResp (token) or a LeakyBucket (leaky)
    (cache/lru.go:42-46, algorithms.go:70-75,89-94,162-167):

      limit/duration: the stored config, captured at (re)initialization.
      remaining:      tokens left in the bucket.
      tstamp:         token: the bucket's reset_time (== window end, ms epoch);
                      leaky: the last-leak TimeStamp.
      expire:         cache-entry expiry (ms epoch).  0 == never initialized,
                      and `expire < now` == expired, both of which read as a
                      cache miss (lru.go:110-114).
      algo:           which algorithm initialized this slot; a mismatch with
                      the request's algorithm reads as a miss.
    """

    limit: jax.Array  # i64[C]
    duration: jax.Array  # i64[C]
    remaining: jax.Array  # i64[C]
    tstamp: jax.Array  # i64[C]
    expire: jax.Array  # i64[C]
    algo: jax.Array  # i32[C]

    @classmethod
    def zeros(cls, capacity: int) -> "BucketState":
        z64 = jnp.zeros((capacity,), dtype=I64)
        return cls(
            limit=z64,
            duration=z64,
            remaining=z64,
            tstamp=z64,
            expire=z64,
            algo=jnp.zeros((capacity,), dtype=I32),
        )


class ArenaPlanes(NamedTuple):
    """The RESIDENT arena: every int64 column of BucketState held as a
    (lo, hi) pair of uint32 planes, plus `algo` — the one layout
    `engine.state` has in device memory between drains (44 B a slot, as the
    int64 columns were).

    The TPU compiler keeps an `s64[C]` executable parameter as a (lo, hi)
    pair inside the program and converted every int64 plane of the arena on
    the way in (X64SplitLow/High) and on the way out (X64Combine), every
    drain, whatever the drain held: O(arena) work that did no rate limiting.
    With uint32 parameters nothing of size C is converted: a drain gathers
    `lo[g]`, `hi[g]` at its B lanes, joins them at B width
    (gather_registers), and commits with single-operand 32-bit scatters
    (commit_registers).  `hi` carries the sign, so all 64 bits survive:
    values >= 2^32, negative `remaining`, expire == 0 for a dead slot.

    BucketState stays the ROW form: the int64 oracle's state
    (window_step), the replicated GLOBAL tables, and what everything that
    is not a drain reads and writes through arena_to_rows /
    arena_from_rows (snapshot, migration, tiers: their formats are
    int64 rows, unchanged).
    """

    limit_lo: jax.Array  # u32[C]
    limit_hi: jax.Array
    duration_lo: jax.Array
    duration_hi: jax.Array
    remaining_lo: jax.Array
    remaining_hi: jax.Array
    tstamp_lo: jax.Array
    tstamp_hi: jax.Array
    expire_lo: jax.Array
    expire_hi: jax.Array
    algo: jax.Array  # i32[C]

    @classmethod
    def zeros(cls, capacity: int) -> "ArenaPlanes":
        return arena_from_rows(BucketState.zeros(capacity))


def split64(x):
    """int64 values -> (lo, hi) uint32 halves.  Elementwise on any shape,
    numpy or jax arrays alike."""
    return ((x & 0xFFFFFFFF).astype(np.uint32),
            ((x >> 32) & 0xFFFFFFFF).astype(np.uint32))


def join64(lo, hi):
    """(lo, hi) uint32 halves -> the int64 they hold (hi's top bit lands on
    bit 63: two's complement, so negatives round-trip).  Inverse of
    split64."""
    return (hi.astype(np.int64) << 32) | lo.astype(np.int64)


# The compact32 clip range: a time enters the rebased-int32 window body (and
# the compact32 snapshot layout) as clip(t - now, -REBASE_LIM, REBASE_LIM).
# The compact wire's duration cap (COMPACT_MAX_DURATION) is the same number,
# which is what keeps every live time inside the range.
REBASE_LIM = 2**31 - 16


def _u32(x):
    return lax.bitcast_convert_type(x, jnp.uint32)


def pair_rebase(t_lo, t_hi, n_lo, n_hi):
    """clip(t - now, -REBASE_LIM, REBASE_LIM) on (lo, hi) int32 halves.

    Exact vs the int64 form for every input: the borrow subtract yields the
    wrapped i64 difference's halves; when it fits int32 the clip sees the
    true difference, otherwise the hi half's sign picks the saturation end
    — identical to clipping the i64 value (verified over random i64s in
    tests/test_wire_window.py)."""
    d_lo = t_lo - n_lo
    borrow = (_u32(t_lo) < _u32(n_lo)).astype(I32)
    d_hi = t_hi - n_hi - borrow
    fits = d_hi == (d_lo >> 31)
    lim = jnp.int32(REBASE_LIM)
    return jnp.where(fits, jnp.clip(d_lo, -lim, lim),
                     jnp.where(d_hi < 0, -lim, lim))


def pair_reabs(rel, n_lo, n_hi):
    """now + rel on (lo, hi) int32 halves (exact i64 add: sign-extended rel,
    carry from unsigned lo overflow)."""
    a_lo = n_lo + rel
    carry = (_u32(a_lo) < _u32(rel)).astype(I32)
    a_hi = n_hi + (rel >> 31) + carry
    return a_lo, a_hi


def arena_from_rows(rows: BucketState) -> ArenaPlanes:
    """int64 rows (any shape, numpy or jax) -> the resident plane form."""
    halves = [h for col in rows[:5] for h in split64(col)]
    return ArenaPlanes(*halves, rows.algo)


def arena_to_rows(planes: ArenaPlanes) -> BucketState:
    """The resident plane form (any shape, numpy or jax) -> int64 rows."""
    cols = [join64(planes[2 * i], planes[2 * i + 1]) for i in range(5)]
    return BucketState(*cols, planes.algo)


class WindowBatch(NamedTuple):
    """One batching window's requests, routed to slots and padded to length B."""

    slot: jax.Array  # i32[B], PAD_SLOT for unused lanes
    hits: jax.Array  # i64[B]
    limit: jax.Array  # i64[B]
    duration: jax.Array  # i64[B]
    algo: jax.Array  # i32[B]
    is_init: jax.Array  # bool[B]: host just allocated this slot for a new key

    @classmethod
    def pad(cls, size: int) -> "WindowBatch":
        return cls(
            slot=jnp.full((size,), PAD_SLOT, dtype=I32),
            hits=jnp.zeros((size,), dtype=I64),
            limit=jnp.zeros((size,), dtype=I64),
            duration=jnp.zeros((size,), dtype=I64),
            algo=jnp.zeros((size,), dtype=I32),
            is_init=jnp.zeros((size,), dtype=jnp.bool_),
        )


class WindowOutput(NamedTuple):
    """Per-request responses (RateLimitResp fields, proto:131-143)."""

    status: jax.Array  # i32[B]
    limit: jax.Array  # i64[B]
    remaining: jax.Array  # i64[B]
    reset_time: jax.Array  # i64[B]


class _Reg(NamedTuple):
    """A segment's live bucket state during replay (same fields as BucketState)."""

    limit: jax.Array
    duration: jax.Array
    remaining: jax.Array
    tstamp: jax.Array
    expire: jax.Array
    algo: jax.Array


def _chain(pairs, default):
    """First-match-wins selection, mirroring the reference's if/else ladders."""
    out = default
    for cond, val in reversed(pairs):
        out = jnp.where(cond, val, out)
    return out


def _sliding_roll(R, T, D, L, now):
    """Advance a sliding-window register to the window containing `now`.

    Returns (prev1, cur1, ws1, est, sl_L): the rolled previous/current
    counters, the rolled window start, the weighted estimate the admission
    check runs against, and the clamped effective limit.  Shared verbatim
    by transition's hit ladder and fold_entering's prefix fold so the two
    cannot drift (the roll depends only on (register, now), which is fixed
    per window — that is what makes the sliding fold replay-free).

    Exactness across the int64 / rebased-int32 lowerings: k*maxD <= now-T
    and off is clipped into [0, maxD] BEFORE the weight multiply, so every
    product stays below 2^25 and no intermediate can wrap in int32."""
    dt = R.dtype
    Z = jnp.asarray(0, dt)
    ONE = jnp.asarray(1, dt)
    Q = jnp.asarray(SLIDING_WEIGHT_Q, dt)
    PMASK = jnp.asarray(SLIDING_MAX_LIMIT, dt)
    sl_L = jnp.minimum(L, jnp.asarray(SLIDING_MAX_LIMIT, dt))
    cur = R & PMASK
    prev = (R >> SLIDING_PACK_BITS) & PMASK
    maxD = jnp.maximum(D, ONE)
    k = jnp.maximum(floordiv(now - T, maxD), Z)
    prev1 = _chain([(k == Z, prev), (k == ONE, cur)], Z)
    cur1 = jnp.where(k == Z, cur, Z)
    ws1 = T + k * maxD
    offc = jnp.clip(now - ws1, Z, maxD)
    pos_q = jnp.where(maxD <= Q,
                      floordiv(offc * Q, maxD),
                      jnp.minimum(floordiv(offc, jnp.maximum(floordiv(maxD, Q),
                                                            ONE)), Q))
    pos_q = jnp.clip(pos_q, Z, Q)
    weighted = floordiv(prev1 * (Q - pos_q), Q)
    return prev1, cur1, ws1, weighted + cur1, sl_L


def transition(reg: _Reg, hits, req_limit, req_duration, req_algo, now, fresh,
               agg=None):
    """One request applied to one bucket, vectorized over the batch dimension.

    `fresh` marks lanes that must take the cache-miss/init path (new slot,
    expired entry, or algorithm switch).  Returns (new_reg, WindowOutput).

    The branch ladders reproduce algorithms.go:24-85 (token) and
    algorithms.go:88-186 (leaky) exactly; see the module docstring for the
    three documented divergences.

    `agg` (optional bool lanes) marks AGGREGATED runs (see AGG_SLOT_BIT):
    the lane's `hits` carries the run length n of identical hits=1
    requests, the state update consumes k* = min(n, r_start) exactly as n
    sequential hits=1 transitions would, and the response's `remaining`
    returns r_start (the pre-run balance) for host-side per-item synthesis.
    """
    L, D, R, T, E, A = reg
    h = hits
    is_token = req_algo == TOKEN_BUCKET
    is_leaky = req_algo == LEAKY_BUCKET
    is_gcra = req_algo == GCRA
    is_sliding = req_algo == SLIDING_WINDOW
    is_conc = req_algo == CONCURRENCY
    # counter dtype follows the inputs: i64 normally; the serving drain
    # runs the same ladder in rebased i32 (the compact-format range caps
    # make i32 exact — see window_step_compact32)
    Z = jnp.asarray(0, h.dtype)
    ONE = jnp.asarray(1, h.dtype)

    # ---- init path (cache miss): algorithms.go:68-84 / :161-185 ----
    # Per-algorithm only where the stored shape demands it; every init
    # default is the token image, so out-of-range algorithm values
    # degrade to token bucket here too (algorithms.go:100-104).
    # GCRA's emission interval, same stored-duration/request-limit quirk
    # as leaky's rate and clamped the same way.
    rate_q = jnp.maximum(
        floordiv(req_duration, jnp.maximum(req_limit, ONE)), ONE)
    sl_l0 = jnp.minimum(req_limit, jnp.asarray(SLIDING_MAX_LIMIT, h.dtype))
    eff_init_limit = jnp.where(is_sliding, sl_l0, req_limit)
    conc_rel0 = is_conc & (h < Z)  # release with nothing held: full bucket
    over_init = (h > eff_init_limit) & ~conc_rel0
    init_R = _chain([(conc_rel0, eff_init_limit), (over_init, Z)],
                    eff_init_limit - h)
    init_status = jnp.where(over_init, _OVER, _UNDER).astype(I32)
    # token stores reset_time = now+duration (:69-74); leaky stores
    # TimeStamp = now (:166) and its init response has ResetTime 0 (:173);
    # GCRA stores the theoretical-arrival-time (saturated to now+duration
    # on an over-ask so the burst refills at `rate_q`); sliding stores the
    # window start; concurrency stamps the last-touch time.
    init_T = _chain(
        [(is_leaky | is_sliding | is_conc, now),
         (is_gcra, jnp.where(over_init, now + req_duration,
                             now + h * rate_q))],
        now + req_duration)
    # sliding packs cur into the remaining column (prev == 0 at init);
    # an over-ask saturates the window so reads stay OVER until it rolls
    init_R_store = jnp.where(
        is_sliding, jnp.where(over_init, sl_l0, jnp.maximum(h, Z)), init_R)
    init_reg = _Reg(
        limit=req_limit,
        duration=req_duration,
        remaining=init_R_store,
        tstamp=init_T,
        expire=now + req_duration,
        algo=req_algo,
    )
    init_out = WindowOutput(
        status=init_status,
        limit=req_limit,
        remaining=init_R,
        reset_time=_chain(
            [(is_leaky | is_conc, Z),
             (is_gcra, jnp.where(over_init, now + rate_q,
                                 now + h * rate_q)),
             (is_sliding, now + req_duration)],
            now + req_duration),
    )

    # ---- token bucket hit path: algorithms.go:40-65 ----
    tb_at_zero = R == 0  # :41-44 -> OVER, remaining 0
    tb_read = h == 0  # :47-49 -> read-only
    tb_drain = h == R  # :52-55 -> UNDER, remaining -> 0
    tb_over = h > R  # :58-62 -> OVER, state NOT mutated
    t_status = _chain(
        [(tb_at_zero, _OVER), (tb_read, _UNDER), (tb_drain, _UNDER), (tb_over, _OVER)],
        _UNDER,
    ).astype(I32)
    t_resp_R = _chain(
        [(tb_at_zero, Z), (tb_read, R), (tb_drain, Z), (tb_over, R)],
        R - h,
    )
    t_new_R = _chain(
        [(tb_at_zero, R), (tb_read, R), (tb_drain, Z), (tb_over, R)],
        R - h,
    )
    token_reg = _Reg(limit=L, duration=D, remaining=t_new_R, tstamp=T, expire=E, algo=A)
    # all token hit responses carry the stored limit and stored reset_time
    token_out = WindowOutput(status=t_status, limit=L, remaining=t_resp_R, reset_time=T)

    # ---- leaky bucket hit path: algorithms.go:107-158 ----
    # rate = stored duration / REQUEST limit (:107) — a reference quirk we
    # keep; clamped to >=1ms where the reference would panic on a zero rate.
    rate = floordiv(D, jnp.maximum(req_limit, ONE))
    rate = jnp.maximum(rate, ONE)
    leak = floordiv(now - T, rate)  # :110-111
    # :113-115 clamp to stored limit; written add-after-min (equivalent
    # given R <= L) so the rebased-i32 body cannot overflow on R + leak
    R2 = R + jnp.minimum(leak, L - R)
    T2 = jnp.where(h != 0, now, T)  # :118-121 ts advances only on hits
    lb_at_zero = R2 == 0  # :130-134 -> OVER, reset now+rate
    lb_drain = h == R2  # :136-141 -> UNDER, remaining -> 0, reset 0
    lb_over = h > R2  # :143-148 -> OVER, no decrement, reset now+rate
    lb_read = h == 0  # :150-153 -> read-only
    l_status = _chain(
        [(lb_at_zero, _OVER), (lb_drain, _UNDER), (lb_over, _OVER), (lb_read, _UNDER)],
        _UNDER,
    ).astype(I32)
    l_resp_R = _chain(
        [(lb_at_zero, Z), (lb_drain, Z), (lb_over, R2), (lb_read, R2)],
        R2 - h,
    )
    l_reset = _chain(
        [(lb_at_zero, now + rate), (lb_drain, Z), (lb_over, now + rate), (lb_read, Z)],
        Z,
    )
    l_new_R = _chain(
        [(lb_at_zero, R2), (lb_drain, Z), (lb_over, R2), (lb_read, R2)],
        R2 - h,
    )
    # expiry extends only on a successful decrement (:155-157, with the
    # now*duration bug corrected to now+duration using the request's duration)
    l_hit = ~(lb_at_zero | lb_drain | lb_over | lb_read)
    l_new_E = jnp.where(l_hit, now + req_duration, E)
    leaky_reg = _Reg(limit=L, duration=D, remaining=l_new_R, tstamp=T2, expire=l_new_E, algo=A)
    leaky_out = WindowOutput(status=l_status, limit=L, remaining=l_resp_R, reset_time=l_reset)

    # ---- GCRA hit path: TAT arithmetic on the tstamp column ----
    # rate reuses leaky's stored-duration // request-limit emission
    # interval (computed above).  base = max(TAT, now); the burst
    # capacity is how many emission intervals fit between base and the
    # horizon now+D, clamped to the stored limit.  Consuming h advances
    # the TAT by h*rate; rejected and read lanes never mutate (the
    # no-mutation-on-over-ask contract carried over from token).
    g_base = jnp.maximum(T, now)
    g_raw = jnp.maximum(floordiv(now + D - g_base, rate), Z)
    g_cap = jnp.minimum(g_raw, L)
    g_at_zero = g_cap == 0
    g_read = h == 0
    g_drain = h == g_cap
    g_over = h > g_cap
    g_status = _chain(
        [(g_at_zero, _OVER), (g_read, _UNDER),
         (g_drain, _UNDER), (g_over, _OVER)],
        _UNDER,
    ).astype(I32)
    g_resp_R = _chain(
        [(g_at_zero, Z), (g_read, g_cap), (g_drain, Z), (g_over, g_cap)],
        g_cap - h,
    )
    g_consume = ~(g_at_zero | g_read | g_over)
    g_new_T = jnp.where(g_consume, g_base + h * rate, T)
    g_reset = _chain(
        [(g_at_zero, now + rate), (g_read, g_base), (g_over, now + rate)],
        g_new_T,
    )
    gcra_reg = _Reg(limit=L, duration=D, remaining=R, tstamp=g_new_T,
                    expire=E, algo=A)
    gcra_out = WindowOutput(status=g_status, limit=L, remaining=g_resp_R,
                            reset_time=g_reset)

    # ---- sliding-window hit path: weighted two-bucket interpolation ----
    # The register rolls to the window containing `now` on EVERY branch
    # (like leaky's leak, the roll commits even on reads/rejects — it is
    # idempotent, which is what keeps the prefix fold replay-free); only
    # an accepted request adds to the current counter and re-arms expiry.
    sl_prev1, sl_cur1, sl_ws, sl_est, sl_L = _sliding_roll(R, T, D, L, now)
    sl_full = sl_est >= sl_L
    sl_read = h == 0
    sl_over = sl_est + h > sl_L
    sl_status = _chain(
        [(sl_full, _OVER), (sl_read, _UNDER),
         (sl_over, _OVER)],
        _UNDER,
    ).astype(I32)
    sl_resp_R = _chain(
        [(sl_full, Z), (sl_read, sl_L - sl_est), (sl_over, sl_L - sl_est)],
        sl_L - sl_est - h,
    )
    sl_accept = ~(sl_full | sl_read | sl_over)
    sl_cur2 = jnp.where(sl_accept, sl_cur1 + h, sl_cur1)
    sl_new_R = sl_cur2 | (sl_prev1 << SLIDING_PACK_BITS)
    sl_new_E = jnp.where(sl_accept, now + req_duration, E)
    sliding_reg = _Reg(limit=L, duration=D, remaining=sl_new_R,
                       tstamp=sl_ws, expire=sl_new_E, algo=A)
    sliding_out = WindowOutput(
        status=sl_status, limit=L, remaining=sl_resp_R,
        reset_time=sl_ws + jnp.maximum(D, ONE))

    # ---- concurrency hit path: acquire/release over live leases ----
    # remaining counts FREE slots; positive hits acquires (token ladder),
    # negative hits releases (saturating add back toward the stored
    # limit, always UNDER).  reset_time is always the 0 sentinel — a
    # lease has no time-based reset; expiry re-arms on every mutation so
    # held leases keep the bucket (and the host lease book) alive.
    c_rel = h < Z
    c_at_zero = R == 0
    c_read = h == 0
    c_over = h > R
    # saturating release written add-after-min (leaky's R2 trick) so the
    # i32 lowering cannot overflow on R - h
    c_rel_R = R + jnp.minimum(-h, L - R)
    c_status = _chain(
        [(c_rel, _UNDER), (c_at_zero, _OVER),
         (c_read, _UNDER), (c_over, _OVER)],
        _UNDER,
    ).astype(I32)
    c_resp_R = _chain(
        [(c_rel, c_rel_R), (c_at_zero, Z), (c_read, R), (c_over, R)],
        R - h,
    )
    c_new_R = _chain(
        [(c_rel, c_rel_R), (c_at_zero, R), (c_read, R), (c_over, R)],
        R - h,
    )
    c_mut = c_rel | ~(c_at_zero | c_read | c_over)
    conc_reg = _Reg(limit=L, duration=D, remaining=c_new_R,
                    tstamp=jnp.where(c_mut, now, T),
                    expire=jnp.where(c_mut, now + req_duration, E),
                    algo=A)
    conc_out = WindowOutput(status=c_status, limit=L, remaining=c_resp_R,
                            reset_time=jnp.zeros_like(T))

    # ---- combine: requested algorithm picks the hit path (non-fresh lanes
    # are guaranteed to have stored algo == requested algo).  First-match
    # select chain over all five values with token as the DEFAULT, so an
    # out-of-range algorithm degrades to token bucket exactly like the
    # reference's fallback (algorithms.go:100-104). ----
    hit_reg, hit_out = token_reg, token_out
    for sel, breg, bout in (
            (is_leaky, leaky_reg, leaky_out),
            (is_gcra, gcra_reg, gcra_out),
            (is_sliding, sliding_reg, sliding_out),
            (is_conc, conc_reg, conc_out)):
        hit_reg = _Reg(*jax.tree.map(
            lambda b, t, s=sel: jnp.where(s, b, t), breg, hit_reg))
        hit_out = WindowOutput(*jax.tree.map(
            lambda b, t, s=sel: jnp.where(s, b, t), bout, hit_out))

    new_reg = jax.tree.map(lambda i, hh: jnp.where(fresh, i, hh), init_reg, hit_reg)
    out = jax.tree.map(lambda i, hh: jnp.where(fresh, i, hh), init_out, hit_out)
    new_reg, out = _Reg(*new_reg), WindowOutput(*out)
    if agg is None:
        return new_reg, out

    # ---- aggregated runs: n sequential hits=1 transitions in one lane ----
    # r_start: post-init balance for fresh lanes (init consumes via k*, so
    # the base is the full limit), else current balance with the leak
    # applied for leaky.  limit > 0 guaranteed by the router's aggregation
    # conditions (a fresh leaky limit=0 run's first item would need the
    # init-path ResetTime=0 special the synthesis cannot express).
    n = h
    a_L = jnp.where(fresh, req_limit, L)
    a_D = jnp.where(fresh, req_duration, D)
    a_base_tok = jnp.where(fresh, req_limit, R)
    a_base_lky = jnp.where(fresh, req_limit, R2)
    a_base = jnp.where(is_token, a_base_tok, a_base_lky)
    k = jnp.minimum(n, a_base)
    a_R = a_base - k
    a_rate = jnp.maximum(floordiv(a_D, jnp.maximum(req_limit, ONE)), ONE)
    # leaky expiry: extends iff any GENERIC decrement happened (the last
    # consume is a drain when the balance hits 0 — same accounting as
    # uniform_closed_form)
    lky_extended = (k - (a_R == 0)) >= 1
    a_reg = _Reg(
        limit=a_L,
        duration=a_D,
        remaining=a_R,
        tstamp=jnp.where(is_token, jnp.where(fresh, now + req_duration, T),
                         now),
        expire=jnp.where(
            is_token,
            jnp.where(fresh, now + req_duration, E),
            jnp.where(fresh | lky_extended, now + req_duration, E)),
        algo=req_algo,
    )
    a_out = WindowOutput(
        # host-synthesized per item; the word carries r_start and the
        # OVER-item reset (token: the bucket's reset_time; leaky:
        # now+rate — UNDER leaky items synthesize 0)
        status=jnp.where(k < n, _OVER, _UNDER).astype(I32),
        limit=a_L,
        remaining=a_base,
        reset_time=jnp.where(is_token,
                             jnp.where(fresh, now + req_duration, T),
                             now + a_rate),
    )
    new_reg = jax.tree.map(lambda a, b: jnp.where(agg, a, b), a_reg, new_reg)
    out = jax.tree.map(lambda a, b: jnp.where(agg, a, b), a_out, out)
    return _Reg(*new_reg), WindowOutput(*out)


def fold_entering(reg: _Reg, fresh0, h0, l0, d0, a0, pos, nz, n_lead,
                  hstar, now):
    """Closed-form ENTERING register for lane `pos` of a foldable segment
    (fold_classify's class): every nonzero hit in the segment equals
    `hstar`, config is uniform, no AGG lanes.  Reconstructing the register
    each lane would see lets ONE shared `transition` call replace the
    whole lane-by-lane replay — the generalization of the old
    uniform-segment closed form to mixed read/hit segments.

    The sequential recurrence folds because only three things evolve lane
    to lane: the balance (token: minus hstar per accept, accepts =
    min(#prior nonzero lanes, balance // hstar) by the greedy ladder;
    leaky: plus one read-leak per leading read, saturating at the limit,
    then the same accept arithmetic), the leaky tstamp (jumps to `now` at
    the first nonzero lane and freezes — so the read-leak is the SAME
    leak0 every application), and the leaky expiry (re-arms iff any
    generic decrement happened).  `st`/`reg` is the segment-start register
    replicated to every lane; all math is elementwise, i64 or rebased-i32
    exactly like transition.

    `nz` — exclusive count of nonzero-hit lanes before `pos` in-segment;
    `n_lead` — leading zero-hit lanes; `hstar` — the shared nonzero hits
    (0 if the segment is all reads).  All from fold_classify."""
    dt = hstar.dtype
    Z = jnp.asarray(0, dt)
    ONE = jnp.asarray(1, dt)
    is_lky = a0 == LEAKY_BUCKET
    is_gc = a0 == GCRA
    is_sl = a0 == SLIDING_WINDOW
    is_cc = a0 == CONCURRENCY
    # init path image: over-limit init stores a drained balance
    over0 = fresh0 & (h0 > l0)
    L_eff = jnp.where(fresh0, l0, reg.limit)
    D_eff = jnp.where(fresh0, d0, reg.duration)
    nzd = nz.astype(dt)

    # ---- token: balance only moves on accepts, T/E never move on hits ----
    Rt = jnp.where(fresh0, jnp.where(over0, Z, l0), reg.remaining)
    kt = jnp.minimum(nzd, floordiv(Rt, jnp.maximum(hstar, ONE)))
    entR_tok = Rt - hstar * kt
    T_tok = jnp.where(fresh0, now + d0, reg.tstamp)
    E_tok = jnp.where(fresh0, now + d0, reg.expire)

    # ---- leaky: leading reads each re-apply the SAME leak0 (tstamp is
    # frozen until the first nonzero hit), saturating at the limit ----
    rate0 = jnp.maximum(floordiv(D_eff, jnp.maximum(l0, ONE)), ONE)
    leak0 = jnp.where(fresh0, Z, floordiv(now - reg.tstamp, rate0))
    gap = L_eff - reg.remaining
    # first application count that saturates; while p < p_sat the product
    # p*leak0 < gap, so it cannot overflow the lane dtype
    p_sat = jnp.where(leak0 > Z,
                      floordiv(gap + leak0 - ONE, jnp.maximum(leak0, ONE)),
                      jnp.asarray(1 << 30, dt))

    def satA(p):
        return jnp.where(p >= p_sat, L_eff, reg.remaining + p * leak0)

    posd = pos.astype(dt)
    fh = n_lead.astype(dt)
    # balance the FIRST nonzero lane's ladder starts from (its own
    # in-transition leak included): fh leading reads + one more leak
    Rh = jnp.where(fresh0, jnp.where(over0, Z, l0), satA(fh + ONE))
    Kf = floordiv(Rh, jnp.maximum(hstar, ONE))
    kl = jnp.minimum(nzd, Kf)
    # the k-th accept is an exact drain (not generic) iff it lands on 0
    drained = (hstar > Z) & (Rh == Kf * hstar) & (kl == Kf) & (kl >= ONE)
    gen = kl - drained.astype(dt)
    phaseA = ~fresh0 & (nz == 0)
    entR_lky = jnp.where(phaseA, satA(posd), Rh - hstar * kl)
    T_lky = jnp.where(fresh0 | (nz > 0), now, reg.tstamp)
    E_lky = jnp.where(fresh0 | (gen >= ONE), now + d0, reg.expire)

    # ---- GCRA: token-shaped fold on the TAT-derived burst capacity ----
    # The capacity raw = (now+D-base)//rate drops by EXACTLY hstar per
    # accept (subtracting an exact multiple of rate commutes with the
    # floor division), so the accept count is the same greedy min as
    # token's, gated on hstar <= L (the per-hit clamp to the stored
    # limit).  Only the TAT evolves; reads and rejects freeze it, so a
    # kp == 0 non-fresh lane must see the RAW stored tstamp.
    g_rate0 = rate0
    g_base_nf = jnp.maximum(reg.tstamp, now)
    g_rawNF = jnp.maximum(floordiv(now + D_eff - g_base_nf, g_rate0), Z)
    g_rawT = jnp.where(fresh0, jnp.where(over0, Z, floordiv(D_eff, g_rate0)),
                       g_rawNF)
    g_kp = jnp.where((hstar > Z) & (hstar <= L_eff),
                     jnp.minimum(nzd, floordiv(g_rawT, jnp.maximum(hstar, ONE))),
                     Z)
    g_baset = jnp.where(fresh0,
                        jnp.where(over0, now + d0, now), g_base_nf)
    entT_gc = jnp.where((g_kp > Z) | fresh0,
                        g_baset + g_kp * hstar * g_rate0, reg.tstamp)
    entR_gc = jnp.where(fresh0, jnp.where(over0, Z, l0 - h0),
                        reg.remaining)

    # ---- sliding: the roll happens once (now is fixed per window) and
    # every accept adds hstar to the estimate, so the accept count is the
    # token greedy min over the post-roll headroom ----
    s_prev1, s_cur1, s_ws1, s_est0, s_L = _sliding_roll(
        reg.remaining, reg.tstamp, D_eff, L_eff, now)
    s_over0 = fresh0 & (h0 > s_L)
    s_est_base = jnp.where(fresh0, jnp.where(s_over0, s_L, Z), s_est0)
    s_kp = jnp.where(hstar > Z,
                     jnp.minimum(nzd, floordiv(jnp.maximum(s_L - s_est_base, Z),
                                               jnp.maximum(hstar, ONE))),
                     Z)
    s_cur_ent = (jnp.where(fresh0, jnp.where(s_over0, s_L, Z), s_cur1)
                 + s_kp * hstar)
    s_prev_ent = jnp.where(fresh0, Z, s_prev1)
    entR_sl = s_cur_ent | (s_prev_ent << SLIDING_PACK_BITS)
    entT_sl = jnp.where(fresh0, now, s_ws1)
    E_sl = jnp.where(fresh0 | (s_kp >= ONE), now + d0, reg.expire)

    # ---- concurrency: acquires fold exactly like token; releases are a
    # saturating climb toward the stored limit (monotone, so the k-th
    # release's balance is closed-form via the saturation point) ----
    c_a = -hstar  # release magnitude (valid when hstar < 0)
    c_R0 = reg.remaining
    c_gap = L_eff - c_R0
    c_ksat = jnp.where(c_gap > Z,
                       floordiv(c_gap + c_a - ONE, jnp.maximum(c_a, ONE)), Z)
    entR_rel = jnp.where(
        fresh0, l0,
        jnp.where(nzd == Z, c_R0,
                  jnp.where(nzd >= c_ksat, L_eff, c_R0 + nzd * c_a)))
    entR_cc = jnp.where(hstar < Z, entR_rel, entR_tok)
    c_applied = jnp.where(hstar < Z, nzd, kt)
    T_cc = jnp.where(fresh0 | (c_applied >= ONE), now, reg.tstamp)
    E_cc = jnp.where(fresh0 | (c_applied >= ONE), now + d0, reg.expire)

    # default = token, matching transition's out-of-range fallback
    pick = lambda lk, gc, sl, cc, tok: _chain(  # noqa: E731
        [(is_lky, lk), (is_gc, gc), (is_sl, sl), (is_cc, cc)], tok)
    return _Reg(
        limit=L_eff,
        duration=D_eff,
        remaining=pick(entR_lky, entR_gc, entR_sl, entR_cc, entR_tok),
        tstamp=pick(T_lky, entT_gc, entT_sl, T_cc, T_tok),
        expire=pick(E_lky, E_tok, E_sl, E_cc, E_tok),
        algo=a0,
    )


def segment_structure(s_slot, s_valid, s_init):
    """Segment indexing over a slot-sorted window: virtual-segment starts,
    per-lane segment start index / position / length, and the commit mask
    (the lanes whose final register may land in the arena).

    Segments are VIRTUAL: they break at slot changes AND at is_init lanes
    (see window_prep's docstring for why).  Gathers and scans only —
    shifted compares via `jnp.take`, `lax.cummax` / `lax.cummin` — no
    scatter.

    Returns (seg_start, seg_start_idx, pos, seg_len, commit_mask).
    """
    B = s_slot.shape[0]
    idx = lax.iota(I32, B)
    prev_slot = jnp.take(s_slot, jnp.maximum(idx - 1, 0))
    phys_start = (idx == 0) | (s_slot != prev_slot)
    seg_start = phys_start | (s_init & s_valid)
    seg_start_idx = lax.cummax(jnp.where(seg_start, idx, jnp.int32(0)))
    pos = idx - seg_start_idx
    # next segment start at-or-after lane i+1 (B when none): lane i's value
    # is min over j > i of {j if start[j] else B}, via a reverse cummin of
    # the shifted-start lattice
    nxt = jnp.minimum(idx + 1, B - 1)

    def _next_boundary(start):
        shifted = jnp.where(jnp.take(start, nxt) & (idx < B - 1),
                            idx + 1, jnp.int32(B))
        return lax.cummin(shifted, reverse=True)

    next_start = _next_boundary(seg_start)
    seg_len = next_start - seg_start_idx
    # a virtual segment is its slot's LAST (→ the one that commits) iff no
    # further virtual start precedes the next physical slot change
    next_phys = _next_boundary(phys_start)
    commit_mask = seg_start & s_valid & (next_start >= next_phys)
    return seg_start, seg_start_idx, pos, seg_len, commit_mask


def segment_count(flag, seg_start_idx, seg_len):
    """Per-lane: how many lanes of my segment satisfy `flag`?  Replicated
    to all lanes of the segment (i32).

    Cumsum range-count instead of a scatter-add (`.at[seg].add`): counts
    the flagged lanes inside [seg_start, seg_start+len) from an inclusive
    prefix sum — gather-only.
    """
    f = flag.astype(I32)
    csum = jnp.cumsum(f)
    seg_end = seg_start_idx + seg_len - 1
    return (jnp.take(csum, seg_end) - jnp.take(csum, seg_start_idx)
            + jnp.take(f, seg_start_idx))


def segment_all(ok, seg_start_idx, seg_len):
    """Per-lane: does EVERY lane of my segment satisfy `ok`?  Replicated to
    all lanes of the segment."""
    return segment_count(~ok, seg_start_idx, seg_len) == 0


def fold_classify(s_hits, s_limit, s_duration, s_algo, s_agg,
                  seg_start_idx, seg_len, h0, l0, d0, a0, fresh_seg, reg,
                  now):
    """Classify segments for the zero-replay fold and compute the per-lane
    prefix facts fold_entering consumes.  Returns
    (seg_fold, nz, n_lead, hstar), all replicated/aligned to lanes.

    A segment folds when one shared `transition` call per lane reproduces
    the sequential replay exactly:
      * uniform config (limit/duration/algo match the segment head), no
        AGG lanes, no negative hits;
      * every nonzero hit equals hstar (the first nonzero lane's hits) —
        reads (hits==0) may interleave anywhere;
      * leaky non-fresh registers additionally need the stored invariant
        remaining <= limit, and a non-negative read-leak whenever the
        segment has leading reads (each read re-applies leak0, which only
        telescopes when it saturates monotonically; a lone in-transition
        leak — no leading reads — is exact for any sign).
    Everything else (mixed distinct nonzero hits, mixed configs, AGG runs
    in multi-lane segments, negative hits/limits on leaky) falls back to
    the replay while_loop — rare shapes by construction, since the router
    folds duplicate identical requests into AGG singletons already.
    """
    B = s_hits.shape[0]
    dt = s_hits.dtype
    Z = jnp.asarray(0, dt)
    ONE = jnp.asarray(1, dt)
    nonzero = s_hits != 0
    nzf = nonzero.astype(I32)
    csum = jnp.cumsum(nzf)
    exc = csum - nzf
    # exclusive in-segment nonzero-lane count before each lane
    nz = exc - jnp.take(exc, seg_start_idx)
    lead = ~nonzero & (nz == 0)
    n_lead = segment_count(lead, seg_start_idx, seg_len)
    first_nz = jnp.clip(seg_start_idx + n_lead, 0, B - 1)
    hstar = jnp.where(n_lead < seg_len, jnp.take(s_hits, first_nz), Z)
    lane_ok = ((s_limit == l0) & (s_duration == d0) & (s_algo == a0)
               & ~s_agg & ((s_hits == Z) | (s_hits == hstar)))
    cfg_ok = segment_all(lane_ok, seg_start_idx, seg_len)
    fresh0 = fresh_seg | (a0 != reg.algo)
    L_eff = jnp.where(fresh0, l0, reg.limit)
    rate0 = jnp.maximum(floordiv(jnp.where(fresh0, d0, reg.duration),
                                 jnp.maximum(l0, ONE)), ONE)
    leak0 = jnp.where(fresh0, Z, floordiv(now - reg.tstamp, rate0))
    lky_ok = ((a0 != LEAKY_BUCKET) | fresh0
              | ((reg.remaining <= L_eff)
                 & ((leak0 >= Z) | (n_lead == 0))))
    # negative hits (concurrency releases) fold — the saturating climb is
    # closed-form; a negative hstar under any OTHER algorithm is an
    # engine-rejected shape and replays (exact by construction)
    hstar_ok = (hstar >= Z) | (a0 == CONCURRENCY)
    seg_fold = cfg_ok & hstar_ok & lky_ok
    return seg_fold, nz, n_lead, hstar


class WindowPrep(NamedTuple):
    """Everything window_step derives from a window before the transition
    math: sorted request lanes, segment structure, gathered registers, and
    uniform-segment classification.  Shared verbatim by window_step and
    window_step_compact32, so the two cannot drift.
    """

    order: jax.Array
    s_slot: jax.Array
    s_valid: jax.Array
    s_hits: jax.Array
    s_limit: jax.Array
    s_duration: jax.Array
    s_algo: jax.Array
    s_init: jax.Array
    seg_start: jax.Array
    seg_start_idx: jax.Array
    pos: jax.Array
    seg_len: jax.Array
    cur: _Reg          # live registers, REPLICATED at every lane
    fresh_seg: jax.Array  # segment-level miss, replicated (start lane's)
    h0: jax.Array      # segment-start request fields, replicated
    l0: jax.Array
    d0: jax.Array
    a0: jax.Array
    nz: jax.Array      # exclusive in-segment nonzero-hit lane count (i32)
    n_lead: jax.Array  # leading zero-hit lanes per segment, replicated
    hstar: jax.Array   # the segment's shared nonzero hits (0: all reads)
    seg_fold: jax.Array  # zero-replay foldable segment (fold_classify)
    max_pos: jax.Array
    commit_mask: jax.Array  # lanes whose register commits to the arena
    s_agg: jax.Array   # aggregated-run lanes (AGG_SLOT_BIT), sorted order


def gather_registers(state, g) -> _Reg:
    """The int64 registers of slots `g`, from whichever form holds the
    columns: int64 rows (BucketState: the oracle, the GLOBAL tables) or the
    resident uint32 planes (ArenaPlanes), whose halves are gathered at the
    B lanes and joined at B width — nothing of the arena's size is
    converted."""
    at = type(state)(*[col[g] for col in state])
    return _Reg(*(arena_to_rows(at) if isinstance(at, ArenaPlanes) else at))


def commit_registers(state, wslot, fin: _Reg):
    """Write registers `fin` to slots `wslot` (out-of-range lanes dropped),
    in `state`'s own form: the resident planes take each int64 register
    split at B width, as one single-operand 32-bit scatter a plane."""
    rows = BucketState(*fin)
    vals = arena_from_rows(rows) if isinstance(state, ArenaPlanes) else rows
    return type(state)(*[col.at[wslot].set(v, mode="drop")
                         for col, v in zip(state, vals)])


def window_prep(state, batch: WindowBatch, now) -> WindowPrep:
    """Sort by slot, find segments, gather registers, classify uniform
    segments (see window_step for the semantics each piece serves).

    Segments are VIRTUAL: they break at slot changes AND at is_init lanes.
    Capacity eviction can recycle a slot to a different key mid-window
    (state/arena.py + native pack assign the new tenant's first lane
    is_init); splitting there turns [old-tenant lanes][init + new-tenant
    lanes] into two independently-uniform segments, so a recycled hot slot
    keeps the closed form instead of forcing a lane-by-lane replay of the
    whole run (a 3000-duplicate Zipf head key would otherwise cost 3000
    replay rounds in one device call).  Only the LAST virtual segment of a
    slot commits to the arena (earlier tenants' counters die with the
    eviction, exactly like the reference's cache Remove)."""
    B = batch.slot.shape[0]
    C = state.algo.shape[0]

    valid = batch.slot >= 0
    # Strip the aggregated-run flag off the slot BEFORE anything keys on
    # slot values (sorting, sharding, the arena gather).
    agg = valid & ((batch.slot & jnp.int32(AGG_SLOT_BIT)) != 0)
    slot_clean = jnp.where(agg, batch.slot & jnp.int32(~AGG_SLOT_BIT),
                           batch.slot)
    # Sort by slot (stable → arrival order preserved within a slot); pads last.
    # Packed single-key sort instead of jnp.argsort: fold (key, lane) into one
    # i64 word with the lane index in the low bits.  A single-array sort of
    # that word is bit-identical to a stable argsort (ties break on lane
    # order) but avoids XLA's variadic comparator sort, which costs ~5x more
    # per window on the CPU backend.
    sort_key = jnp.where(valid, slot_clean, jnp.int32(2**31 - 1))
    lane_bits = max((B - 1).bit_length(), 1)
    packed_key = ((sort_key.astype(I64) << lane_bits)
                  | lax.iota(I64, B))
    sorted_key = lax.sort(packed_key, is_stable=False)
    order = (sorted_key & jnp.int64((1 << lane_bits) - 1)).astype(I32)
    s_slot = (sorted_key >> lane_bits).astype(I32)
    s_valid = valid[order]
    # Permute the request fields as ONE packed [B, 6] row gather instead of
    # six separate gathers: each gather/scatter is its own launch, and the
    # pack/unpack is elementwise (fused, effectively free).
    packed_req = jnp.stack(
        [batch.hits, batch.limit, batch.duration,
         batch.algo.astype(I64), batch.is_init.astype(I64),
         agg.astype(I64)], axis=-1)
    s_req = packed_req[order]
    s_hits = s_req[:, 0]
    s_limit = s_req[:, 1]
    s_duration = s_req[:, 2]
    s_algo = s_req[:, 3].astype(I32)
    s_init = s_req[:, 4].astype(jnp.bool_)
    s_agg = s_req[:, 5].astype(jnp.bool_)

    seg_start, seg_start_idx, pos, seg_len, commit_mask = segment_structure(
        s_slot, s_valid, s_init)

    # Registers: the live state of each segment's bucket.  Every lane of a
    # segment gathers the SAME slot, so these are replicated per segment.
    cur = gather_registers(state, jnp.clip(s_slot, 0, C - 1))
    # Miss conditions known before replay: fresh host allocation or lazy TTL
    # expiry (lru.go:110: expireAt < now).  Algorithm switches are detected
    # per-round against the live register.
    cur_fresh = s_init | (cur.expire < now)

    # Fold classification: a hot key's duplicates are usually identical
    # requests (same hits and config, reads interleaved anywhere); those
    # take the zero-replay closed form (fold_classify / fold_entering).
    # Only *irregular* segments (mixed distinct nonzero hits, mixed
    # config, AGG-in-multi-lane) replay — is_init lanes can't appear
    # mid-segment anymore (they start their own virtual segment above).
    # Segment-start replication: one packed row gather instead of five.
    packed_seg = jnp.stack(
        [s_hits, s_limit, s_duration, s_algo.astype(I64),
         cur_fresh.astype(I64)], axis=-1)
    seg0 = packed_seg[seg_start_idx]
    h0 = seg0[:, 0]
    l0 = seg0[:, 1]
    d0 = seg0[:, 2]
    a0 = seg0[:, 3].astype(I32)
    fresh_seg = seg0[:, 4].astype(jnp.bool_)
    seg_fold, nz, n_lead, hstar = fold_classify(
        s_hits, s_limit, s_duration, s_algo, s_agg, seg_start_idx,
        seg_len, h0, l0, d0, a0, fresh_seg, cur, now)
    # A singleton non-fold segment — an aggregated-run lane owning its
    # slot this window, or a lone irregular lane — needs no replay trips
    # either: its one round reads exactly the window-entry register, which
    # the shared pos==0 transition in window_math covers.
    seg_single = s_valid & ~seg_fold & (seg_len == 1)
    max_pos = jnp.max(jnp.where(s_valid & ~seg_fold & ~seg_single, pos,
                                jnp.int32(-1)))

    return WindowPrep(order, s_slot, s_valid, s_hits, s_limit, s_duration,
                      s_algo, s_init, seg_start, seg_start_idx, pos,
                      seg_len, cur, fresh_seg, h0, l0, d0, a0, nz, n_lead,
                      hstar, seg_fold, max_pos, commit_mask, s_agg)


def window_commit(state, prep: WindowPrep, fin: _Reg,
                  outs_sorted: WindowOutput):
    """Scatter the final segment registers back to the arena (one write per
    touched slot — the window's net effect) and un-sort the responses to
    arrival order.

    commit_mask keeps the scatter one-write-per-SLOT: when eviction recycled
    a slot mid-window the slot has several virtual segments, and only the
    last tenant's final register may land in the arena (duplicate scatter
    indices have undefined order in XLA)."""
    C = state.algo.shape[0]
    wslot = jnp.where(prep.commit_mask, prep.s_slot, jnp.int32(C))
    new_state = commit_registers(state, wslot, fin)
    # Un-sort via ONE packed row scatter instead of four per-field scatters
    # (per-op launch cost, see window_prep note); unpack is fused slices.
    B = prep.order.shape[0]
    packed_out = jnp.stack(
        [outs_sorted.status.astype(I64), outs_sorted.limit,
         outs_sorted.remaining, outs_sorted.reset_time], axis=-1)
    unpacked = jnp.zeros((B, 4), I64).at[prep.order].set(packed_out)
    unsorted = WindowOutput(
        status=unpacked[:, 0].astype(I32), limit=unpacked[:, 1],
        remaining=unpacked[:, 2], reset_time=unpacked[:, 3])
    return new_state, unsorted


def window_math(now, max_pos, s_valid, s_hits, s_limit, s_duration,
                s_algo, s_agg, pos, seg_len, seg_start_idx, seg_fold,
                h0, l0, d0, a0, fresh_seg, reg, nz, n_lead, hstar):
    """One pass over the sorted window: ONE shared transition call covers
    every lane of foldable segments (entering registers reconstructed in
    closed form by fold_entering) plus every singleton and pos-0 lane,
    then replay rounds run only for the residual irregular segments.
    Pure function of [B] lane vectors — the SAME body runs in rebased
    int32 (window_step_compact32, the engine's serving drain) and in int64
    (window_step below, the oracle), so the two cannot drift.

    Register state is REPLICATED at every lane of its segment (the arena
    gather outside already yields that), so a replay round is elementwise
    plus ONE vector gather — `computed[seg_start + p]` pulls the active
    lane's freshly-computed register back to every lane of its segment —
    with no scatters.

    Returns (out_sorted: WindowOutput, fin: _Reg) with fin already
    fold-vs-replayed selected (replicated; commit reads any lane).
    """
    B = pos.shape[0]
    valid = s_valid
    p_arr = pos
    sidx = seg_start_idx
    fresh0 = fresh_seg | (a0 != reg.algo)
    seg_single = valid & ~seg_fold & (seg_len == 1)
    covered = seg_fold | seg_single

    # ---- the shared ladder: every covered lane in ONE transition ----
    # pos-0 lanes (any segment kind) see the RAW stored register — the
    # ladder's own init/expiry paths are the ground truth there, which is
    # exactly what the old hoisted singleton call computed.
    ent = fold_entering(reg, fresh0, h0, l0, d0, a0, p_arr, nz, n_lead,
                        hstar, now)
    first = p_arr == 0
    ent = _Reg(*[jnp.where(first, r, e) for r, e in zip(reg, ent)])
    ent_fresh = first & (fresh_seg | (s_algo != reg.algo))
    new_reg, f_out = transition(ent, s_hits, s_limit, s_duration, s_algo,
                                now, ent_fresh, agg=s_agg)
    # a fold segment's committed register is its LAST lane's result
    eidx = jnp.clip(sidx + seg_len - 1, 0, B - 1)
    fin_cov = _Reg(*[jnp.take(x, eidx) for x in new_reg])

    # ---- replay rounds for residual irregular segments ----
    def body(carry):
        p, lim, dur, rem, ts, exp, alg, fr, ost, oli, ore, ors = carry
        r = _Reg(limit=lim, duration=dur, remaining=rem, tstamp=ts,
                 expire=exp, algo=alg)
        # is_init lanes start their own virtual segment, so their
        # freshness is carried by fr (fresh_seg) until their round clears
        # it — no per-lane s_init term needed
        fresh = fr | (s_algo != r.algo)
        new_r, resp = transition(
            r, s_hits, s_limit, s_duration, s_algo, now, fresh,
            agg=s_agg)
        active = (p_arr == p) & valid & ~covered
        # Propagate the active lane's result to its WHOLE segment (the
        # final commit reads replicated registers).  ai = my segment
        # start + p; active[ai] holds iff pos[ai] == p, which
        # algebraically forces sidx[ai] == my sidx — i.e. ai really is MY
        # segment's round-p lane (the clamp cannot false-positive:
        # pos[B-1] == p with a clamped ai would need sidx + p > B-1 and
        # sidx + p == B-1 at once).
        ai = jnp.clip(sidx + p, 0, B - 1)
        take = jnp.take(active, ai)

        def upd(new, old):
            return jnp.where(take, jnp.take(new, ai), old)

        lim = upd(new_r.limit, lim)
        dur = upd(new_r.duration, dur)
        rem = upd(new_r.remaining, rem)
        ts = upd(new_r.tstamp, ts)
        exp = upd(new_r.expire, exp)
        alg = jnp.where(take, jnp.take(new_r.algo, ai), alg)
        fr = jnp.where(take, False, fr)
        ost = jnp.where(active, resp.status, ost)
        oli = jnp.where(active, resp.limit, oli)
        ore = jnp.where(active, resp.remaining, ore)
        ors = jnp.where(active, resp.reset_time, ors)
        return (p + 1, lim, dur, rem, ts, exp, alg, fr, ost, oli, ore, ors)

    init = (jnp.int32(0), reg.limit, reg.duration, reg.remaining,
            reg.tstamp, reg.expire, reg.algo, fresh0,
            f_out.status, f_out.limit, f_out.remaining, f_out.reset_time)
    carry = lax.while_loop(lambda c: c[0] <= max_pos, body, init)
    (_, lim, dur, rem, ts, exp, alg, _, ost, oli, ore, ors) = carry

    # replay rounds never touch covered lanes, so the loop's output
    # buffers (seeded from the shared ladder) are already complete
    out_sorted = WindowOutput(status=ost, limit=oli, remaining=ore,
                              reset_time=ors)
    fin = _Reg(
        limit=jnp.where(covered, fin_cov.limit, lim),
        duration=jnp.where(covered, fin_cov.duration, dur),
        remaining=jnp.where(covered, fin_cov.remaining, rem),
        tstamp=jnp.where(covered, fin_cov.tstamp, ts),
        expire=jnp.where(covered, fin_cov.expire, exp),
        algo=jnp.where(covered, fin_cov.algo, alg))
    return out_sorted, fin


def window_step(state: BucketState, batch: WindowBatch, now) -> tuple[BucketState, WindowOutput]:
    """Apply one window of requests to the arena; returns (new_state, responses).

    Equivalent to the owning node draining one batched GetPeerRateLimits RPC
    item-by-item under the cache mutex (gubernator.go:210-227,236-251), but as
    one device computation.  Responses are positionally aligned with the batch
    (the reference demuxes by index, peers.go:204-207).

    This is the int64 oracle, and the body of the full-format call sites:
    prep → window_math → commit, the same three stages
    window_step_compact32 composes, in full-width arithmetic.
    """
    now = jnp.asarray(now, dtype=I64)
    prep = window_prep(state, batch, now)
    out_sorted, fin = window_math(
        now, prep.max_pos, prep.s_valid, prep.s_hits, prep.s_limit,
        prep.s_duration, prep.s_algo, prep.s_agg, prep.pos, prep.seg_len,
        prep.seg_start_idx, prep.seg_fold, prep.h0, prep.l0, prep.d0,
        prep.a0, prep.fresh_seg, prep.cur, prep.nz, prep.n_lead,
        prep.hstar)
    return window_commit(state, prep, fin, out_sorted)


def window_step_compact32(state, batch: WindowBatch, now):
    """The serving drain's window step: window_step with the window math in
    int32, times REBASED to the window's `now` (prep and commit are the same
    functions; the TPU emulates int64 arithmetic as i32-pair ops, so the
    int64 ladder pays roughly double the math for nothing inside the compact
    ranges).

    Exact iff every lane satisfies the compact wire-format ranges
    (COMPACT_MAX_*: hits < 2^28, limit < 2^31, duration < 2^31-16) AND the
    arena rows it reads were written under the same caps — both guaranteed
    on the engine's compact serving path (the engine permanently drops to
    the full-format path, window_step, the first time an out-of-range config
    appears: core/engine.py _dispatch).  Rebased time identities: every
    absolute time the ladder computes is now+X with X in (-2^31, 2^31);
    non-fresh registers satisfy |t - now| <= max request duration < 2^31-16
    (token: tstamp = expire >= now and <= write_now+duration; leaky: expire
    = last-decrement now+duration >= now) PROVIDED the window clock is
    monotonic — the engine's serving clocks are.  A clock that jumps
    backward by D ms can push a stored time up to D past the rebase range;
    the clip then bounds the resulting expiry error to D (graceful, not
    wrong-branch)."""
    now = jnp.asarray(now, dtype=I64)
    prep = window_prep(state, batch, now)
    lim = jnp.int64(REBASE_LIM)
    rel = lambda t: jnp.clip(t - now, -lim, lim).astype(I32)
    cnt = lambda x: x.astype(I32)
    cur = prep.cur
    out_sorted, fin = window_math(
        jnp.int32(0), prep.max_pos, prep.s_valid, cnt(prep.s_hits),
        cnt(prep.s_limit), cnt(prep.s_duration), prep.s_algo, prep.s_agg,
        prep.pos, prep.seg_len, prep.seg_start_idx, prep.seg_fold,
        cnt(prep.h0), cnt(prep.l0), cnt(prep.d0), prep.a0, prep.fresh_seg,
        _Reg(limit=cnt(cur.limit), duration=cnt(cur.duration),
             remaining=cnt(cur.remaining), tstamp=rel(cur.tstamp),
             expire=rel(cur.expire), algo=cur.algo),
        prep.nz, prep.n_lead, cnt(prep.hstar))
    # re-absolutize.  reset_time: leaky and concurrency use 0 as the "no
    # reset" sentinel (leaky's non-zero resets are now+rate with rate >= 1;
    # concurrency resets are ALWAYS the sentinel), so rel == 0 distinguishes
    # exactly; token/GCRA/sliding lanes always carry a real time (rel 0 ==
    # "resets at now") and never the sentinel (algorithms.go:130-141 vs
    # :69-74).
    leaky_lane = (prep.s_algo == LEAKY_BUCKET) | (prep.s_algo == CONCURRENCY)
    reset64 = jnp.where(
        leaky_lane & (out_sorted.reset_time == 0), jnp.int64(0),
        out_sorted.reset_time.astype(I64) + now)
    out_sorted = WindowOutput(
        status=out_sorted.status, limit=out_sorted.limit.astype(I64),
        remaining=out_sorted.remaining.astype(I64), reset_time=reset64)
    fin = _Reg(limit=fin.limit.astype(I64),
               duration=fin.duration.astype(I64),
               remaining=fin.remaining.astype(I64),
               tstamp=fin.tstamp.astype(I64) + now,
               expire=fin.expire.astype(I64) + now,
               algo=fin.algo)
    return window_commit(state, prep, fin, out_sorted)


def pack_outputs(out: WindowOutput, gout: WindowOutput) -> jax.Array:
    """Fuse both windows' responses into one i64[B+Bg, 4] array.

    Lane rows: the regular window's B lanes then the GLOBAL window's Bg
    lanes; columns (status, limit, remaining, reset_time).  One fused array
    means the host pays ONE device→host round trip per dispatch instead of
    eight, which cuts per-window fixed costs.
    """
    o = jnp.stack(
        [out.status.astype(I64), out.limit, out.remaining, out.reset_time],
        axis=-1)
    g = jnp.stack(
        [gout.status.astype(I64), gout.limit, gout.remaining, gout.reset_time],
        axis=-1)
    return jnp.concatenate([o, g], axis=0)


def split_outputs(fused, lanes: int) -> tuple[WindowOutput, WindowOutput]:
    """Host-side inverse of pack_outputs over [..., B+Bg, 4] numpy buffers:
    returns (regular, GLOBAL) WindowOutputs as zero-copy views."""
    def unpack(a):
        return WindowOutput(
            status=a[..., 0], limit=a[..., 1],
            remaining=a[..., 2], reset_time=a[..., 3])
    return unpack(fused[..., :lanes, :]), unpack(fused[..., lanes:, :])


# ---- compact wire format -------------------------------------------------
# The host<->device transfer is the serving path's fixed cost per window (it
# bounds small-window latency).  Eligible windows (host-checked: 0 <= hits < 2^28,
# 0 <= limit < 2^31, 0 <= duration < 2^31-16) travel packed:
#
#   request  i64[B, 2]:
#     w0: bits 0..31 slot+1 (0 = padded lane), bit 32 is_init,
#         bit 33 algorithm bit 0, bits 34..61 hits,
#         bits 62..63 algorithm bits 1..2 (zero for token/leaky, so the
#         pre-algorithm-plane encoding is bit-identical for algo 0/1;
#         concurrency hits are SIGN-EXTENDED from bit 27 of the hits
#         field, so releases travel as |hits| < 2^27)
#     w1: bits 0..31 limit, bits 32..62 duration
#   response i64[B, 2]:
#     w0: bits 0..30 remaining, bit 31 status,
#         bits 32..63 reset_enc = 0 if reset_time == 0 else reset_time - now + 1
#     w1: the response's limit, raw — it is the STORED limit on hit paths
#         (a live bucket keeps its init-time config, algorithms.go:40-65), so
#         it can exceed the request-side range checks and can't be dropped or
#         packed.
#
# Windows that fail the range checks use the full WindowBatch/pack_outputs
# path, so the compact path is lossless: remaining <= stored limit and
# reset - now <= stored duration always, and the engine permanently drops to
# the full path the first time an out-of-range config enters the arena
# (RateLimitEngine._dispatch), so compact windows only ever read state whose
# stored configs passed the same checks.

COMPACT_MAX_HITS = 1 << 28
COMPACT_MAX_LIMIT = 1 << 31
COMPACT_MAX_DURATION = REBASE_LIM


def decode_batch(packed) -> WindowBatch:
    """Device-side decode of the compact request pair (see layout above)."""
    w0 = packed[..., 0]
    w1 = packed[..., 1]
    algo = (((w0 >> 33) & 1) | (((w0 >> 62) & 3) << 1)).astype(I32)
    hits_raw = (w0 >> 34) & (COMPACT_MAX_HITS - 1)
    # concurrency releases: hits sign-extend from bit 27
    hits = jnp.where(algo == CONCURRENCY,
                     (hits_raw ^ CONC_MAX_HITS) - CONC_MAX_HITS, hits_raw)
    return WindowBatch(
        slot=(w0 & 0xFFFFFFFF).astype(I32) - 1,
        hits=hits,
        limit=w1 & 0xFFFFFFFF,
        duration=(w1 >> 32) & 0x7FFFFFFF,
        algo=algo,
        is_init=((w0 >> 32) & 1).astype(jnp.bool_),
    )


def encode_batch_host(slot, hits, limit, duration, algo, is_init):
    """Host-side (numpy) encode into the compact request pair.

    Caller must have verified the COMPACT_MAX_* ranges; padded lanes
    (slot == PAD_SLOT) encode to w0 == 0 regardless of other fields."""
    import numpy as np

    pad = slot < 0
    a64 = algo.astype(np.int64)
    w0 = ((slot.astype(np.int64) + 1)
          | (is_init.astype(np.int64) << 32)
          | ((a64 & 1) << 33)
          | ((hits & (COMPACT_MAX_HITS - 1)) << 34)
          | (((a64 >> 1) & 3) << 62))
    w0 = np.where(pad, 0, w0)
    w1 = limit | (duration << 32)
    return np.stack([w0, w1], axis=-1)


def encode_output_word(out: WindowOutput, now) -> jax.Array:
    """Device-side encode of (status, remaining, reset_time) into one i64
    word per lane.  The response's limit travels separately: the serving
    pipeline echoes the REQUEST limit host-side and fetches the device's
    limit plane only when a window's stored-vs-request mismatch flag fires
    (see engine._compiled_pipeline_step) — on hit paths the two differ only
    when a live bucket's config was changed mid-stream."""
    reset_enc = jnp.where(
        out.reset_time == 0,
        jnp.int64(0),
        jnp.clip(out.reset_time - now, 0, (1 << 31) - 2) + 1,
    )
    return ((reset_enc << 32)
            | (out.status.astype(I64) << 31)
            | jnp.clip(out.remaining, 0, (1 << 31) - 1))


def encode_output_compact(out: WindowOutput, now) -> jax.Array:
    """Device-side encode of responses into i64[B, 2] (packed word, limit)."""
    return jnp.stack([encode_output_word(out, now), out.limit], axis=-1)


def decode_output_host(packed, now) -> WindowOutput:
    """Host-side (numpy) decode of the compact response pair."""
    import numpy as np

    word = packed[..., 0]
    enc = (word >> 32) & 0xFFFFFFFF
    return WindowOutput(
        status=(word >> 31) & 1,
        limit=packed[..., 1],
        remaining=word & 0x7FFFFFFF,
        reset_time=np.where(enc == 0, 0, now + enc - 1),
    )


def global_read(state: BucketState, batch: WindowBatch, now) -> WindowOutput:
    """Answer GLOBAL-behavior requests from the local replica without mutating it.

    Mirrors the non-owner fast path (gubernator.go:173-195): a cached entry is
    returned as-is (hits are NOT applied locally — they reconcile via the
    window psum, see global_apply); a miss is answered as-if-initialized
    (the reference bootstraps its local cache the same way, :189-193 — since
    reads never decrement, recomputing limit-hits each time is
    response-identical while keeping replicas bit-exact across shards).
    """
    C = state.algo.shape[0]
    now = jnp.asarray(now, dtype=I64)
    reg = gather_registers(state, jnp.clip(batch.slot, 0, C - 1))
    fresh = batch.is_init | (reg.expire < now) | (batch.algo != reg.algo)
    # A cached read is the hit path with hits=0 (the cached status the owner
    # would broadcast, global.go:199-203 → getRateLimit with Hits cleared);
    # a miss is the init path with the request's hits.
    read_hits = jnp.where(fresh, batch.hits, jnp.int64(0))
    _, out = transition(reg, read_hits, batch.limit, batch.duration, batch.algo, now, fresh)
    return out


def global_accumulate(delta: jax.Array, batch: WindowBatch) -> jax.Array:
    """Scatter-add this shard's GLOBAL hits into the per-slot delta array.

    The device-side analog of the reference's hit aggregation map
    (global.go:81-86: `hits[key].Hits += r.Hits`).
    """
    idx = jnp.where(batch.slot >= 0, batch.slot, delta.shape[0])
    return delta.at[idx].add(batch.hits, mode="drop")


class GlobalConfig(NamedTuple):
    """Replicated per-slot config for GLOBAL limits (host-written at allocation).

    The aggregate-apply step needs limit/duration/algorithm per slot; the
    reference carries these on the queued RateLimitReq it sends to the owner
    (global.go:115-153) — here they are resident device state.
    """

    limit: jax.Array  # i64[G]
    duration: jax.Array  # i64[G]
    algo: jax.Array  # i32[G]

    @classmethod
    def zeros(cls, capacity: int) -> "GlobalConfig":
        return cls(
            limit=jnp.zeros((capacity,), I64),
            duration=jnp.zeros((capacity,), I64),
            algo=jnp.zeros((capacity,), I32),
        )


def global_apply(state: BucketState, cfg: GlobalConfig, summed_hits: jax.Array, now
                 ) -> BucketState:
    """Apply psum'd GLOBAL hit totals to the replicated arena.

    Every shard runs this on identical inputs (summed_hits is the psum over
    the mesh axis), so replicas stay bit-exact — this one collective replaces
    both the async hit send (global.go:115-156) and the owner's status
    broadcast (global.go:193-232): after it runs, the authoritative state is
    already resident on every shard.

    Matches the owner's application of the aggregated request: the reference
    sums hits per key and applies the sum as one request through the normal
    algorithm (global.go:81-86 → gubernator.go:218-226).
    """
    now = jnp.asarray(now, dtype=I64)
    reg = _Reg(
        limit=state.limit,
        duration=state.duration,
        remaining=state.remaining,
        tstamp=state.tstamp,
        expire=state.expire,
        algo=state.algo,
    )
    fresh = (reg.expire < now) | (cfg.algo != reg.algo)
    new_reg, _ = transition(reg, summed_hits, cfg.limit, cfg.duration, cfg.algo, now, fresh)
    touched = summed_hits != 0
    merged = jax.tree.map(lambda n, o: jnp.where(touched, n, o), new_reg, reg)
    return BucketState(*merged)
