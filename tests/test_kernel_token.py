"""Token-bucket kernel semantics: every branch of reference algorithms.go:24-85.

The first three tests replay the reference's functional tables
(functional_test.go:51-146) with a virtual clock.
"""

import pytest

from gubernator_tpu.api.types import Algorithm, RateLimitReq, Status, Second
from .harness import KernelHarness


def req(name="t", key="account:1234", hits=1, limit=2, duration=Second, algo=Algorithm.TOKEN_BUCKET):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=algo)


def test_over_the_limit():
    # functional_test.go:51-95: limit=2, three hits of 1
    h = KernelHarness()
    expect = [
        (1, Status.UNDER_LIMIT),
        (0, Status.UNDER_LIMIT),
        (0, Status.OVER_LIMIT),
    ]
    for remaining, status in expect:
        r = h.one(req(name="test_over_limit"))
        assert r.status == status
        assert r.remaining == remaining
        assert r.limit == 2
        assert r.reset_time != 0


def test_token_bucket_reset_after_expiry():
    # functional_test.go:97-146: 5ms duration bucket resets after expiry
    h = KernelHarness()
    r = h.one(req(name="test_token_bucket", duration=5))
    assert (r.remaining, r.status) == (1, Status.UNDER_LIMIT)
    r = h.one(req(name="test_token_bucket", duration=5))
    assert (r.remaining, r.status) == (0, Status.UNDER_LIMIT)
    h.advance(6)  # entry expires when expireAt < now (lru.go:110)
    r = h.one(req(name="test_token_bucket", duration=5))
    assert (r.remaining, r.status) == (1, Status.UNDER_LIMIT)


def test_expiry_boundary_is_strict():
    # lru.go:110: `expireAt < now` — an entry read at exactly expireAt is live
    h = KernelHarness()
    h.one(req(duration=5))
    h.advance(5)  # now == expireAt
    r = h.one(req(duration=5))
    assert r.remaining == 0  # still the old bucket


def test_limit_zero_immediately_over():
    # functional_test.go:229-238: limit=0 -> OVER_LIMIT on first hit
    h = KernelHarness()
    r = h.one(req(hits=1, limit=0, duration=10000))
    assert r.status == Status.OVER_LIMIT
    assert r.remaining == 0


def test_duration_zero_ok():
    # functional_test.go:218-227: duration=0 is accepted
    h = KernelHarness()
    r = h.one(req(hits=1, limit=10, duration=0))
    assert r.status == Status.UNDER_LIMIT
    assert r.remaining == 9
    # expireAt == now -> next window (now+1) sees it expired
    h.advance(1)
    r = h.one(req(hits=1, limit=10, duration=0))
    assert r.remaining == 9


def test_read_only_hits_zero():
    # algorithms.go:46-49: hits=0 returns status without consuming
    h = KernelHarness()
    h.one(req(hits=1, limit=5))
    r = h.one(req(hits=0, limit=5))
    assert (r.remaining, r.status) == (4, Status.UNDER_LIMIT)
    r = h.one(req(hits=0, limit=5))
    assert r.remaining == 4


def test_over_ask_does_not_mutate():
    # algorithms.go:57-62: hits > remaining -> OVER_LIMIT, current remaining
    # returned, state untouched; a smaller retry succeeds.
    h = KernelHarness()
    h.one(req(hits=2, limit=5))  # remaining 3
    r = h.one(req(hits=4, limit=5))
    assert (r.status, r.remaining) == (Status.OVER_LIMIT, 3)
    r = h.one(req(hits=3, limit=5))
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 0)


def test_exact_drain_is_under_limit():
    # algorithms.go:51-55: hits == remaining drains to 0 but returns UNDER
    h = KernelHarness()
    h.one(req(hits=1, limit=3))
    r = h.one(req(hits=2, limit=3))
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 0)
    r = h.one(req(hits=1, limit=3))
    assert r.status == Status.OVER_LIMIT


def test_first_request_over_limit_is_stored():
    # algorithms.go:77-83: first request with hits > limit stores OVER_LIMIT
    # with remaining 0 — subsequent small asks stay OVER until expiry.
    h = KernelHarness()
    r = h.one(req(hits=10, limit=3, duration=1000))
    assert (r.status, r.remaining) == (Status.OVER_LIMIT, 0)
    r = h.one(req(hits=1, limit=3, duration=1000))
    assert r.status == Status.OVER_LIMIT
    h.advance(1001)
    r = h.one(req(hits=1, limit=3, duration=1000))
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 2)


def test_stored_limit_wins_within_window():
    # token hit path ignores the request's limit/duration until reset
    # (algorithms.go:40-65 reads only cached state)
    h = KernelHarness()
    h.one(req(hits=1, limit=5, duration=1000))
    r = h.one(req(hits=1, limit=99, duration=1000))
    assert r.limit == 5
    assert r.remaining == 3


def test_reset_time_constant_within_window():
    h = KernelHarness()
    r1 = h.one(req(hits=1, limit=5, duration=1000))
    h.advance(100)
    r2 = h.one(req(hits=1, limit=5, duration=1000))
    assert r1.reset_time == r2.reset_time == 1_700_000_000_000 + 1000


def test_algorithm_switch_resets():
    # Divergence from reference bug (algorithms.go:100-104): switching
    # algorithms re-initializes under the REQUESTED algorithm.
    h = KernelHarness()
    h.one(req(hits=1, limit=5, algo=Algorithm.TOKEN_BUCKET))
    r = h.one(req(hits=1, limit=5, duration=1000, algo=Algorithm.LEAKY_BUCKET))
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 4)
    assert r.reset_time == 0  # leaky init response has reset_time 0


# ---- in-window duplicate-key sequencing (the reference serializes these
# under the cache mutex, gubernator.go:237; we replay segments in rounds) ----

def test_duplicates_in_one_window():
    h = KernelHarness()
    rs = h.window([req(), req(), req()])
    assert [(r.remaining, r.status) for r in rs] == [
        (1, Status.UNDER_LIMIT),
        (0, Status.UNDER_LIMIT),
        (0, Status.OVER_LIMIT),
    ]


def test_duplicate_over_ask_replay():
    # hit-summing would be wrong here (SURVEY.md §7 hard parts): the over-ask
    # must NOT consume, and the smaller later ask must succeed.
    h = KernelHarness()
    rs = h.window([
        req(hits=5, limit=10),   # init -> 5
        req(hits=7, limit=10),   # over-ask -> OVER, remaining 5, no mutation
        req(hits=3, limit=10),   # -> UNDER, remaining 2
    ])
    assert (rs[0].status, rs[0].remaining) == (Status.UNDER_LIMIT, 5)
    assert (rs[1].status, rs[1].remaining) == (Status.OVER_LIMIT, 5)
    assert (rs[2].status, rs[2].remaining) == (Status.UNDER_LIMIT, 2)


def test_interleaved_keys_one_window():
    h = KernelHarness()
    a = lambda hits: req(key="a", hits=hits, limit=3)
    b = lambda hits: req(key="b", hits=hits, limit=2)
    rs = h.window([a(1), b(1), a(1), b(1), a(1), b(1)])
    assert [r.remaining for r in rs] == [2, 1, 1, 0, 0, 0]
    assert rs[5].status == Status.OVER_LIMIT
    assert rs[4].status == Status.UNDER_LIMIT  # a drained exactly


def test_window_init_with_duplicates_first_over():
    # first request over-asks on a fresh key: stored remaining = 0
    # (algorithms.go:77-83), so the rest of the window is OVER.
    h = KernelHarness()
    rs = h.window([req(hits=9, limit=5), req(hits=1, limit=5)])
    assert (rs[0].status, rs[0].remaining) == (Status.OVER_LIMIT, 0)
    assert (rs[1].status, rs[1].remaining) == (Status.OVER_LIMIT, 0)


def test_many_duplicates_deep_replay():
    # uniform segment -> exercised by the closed-form fast path
    h = KernelHarness()
    rs = h.window([req(hits=1, limit=10) for _ in range(15)])
    under = [r for r in rs if r.status == Status.UNDER_LIMIT]
    over = [r for r in rs if r.status == Status.OVER_LIMIT]
    assert len(under) == 10 and len(over) == 5
    assert [r.remaining for r in rs[:11]] == [9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 0]


def test_uniform_hits_gt_one_closed_form():
    # uniform hits=3 over limit 10: two decrements then rejects with the
    # leftover remaining (algorithms.go:57-62)
    h = KernelHarness()
    rs = h.window([req(hits=3, limit=10) for _ in range(4)])
    assert [(r.status, r.remaining) for r in rs] == [
        (Status.UNDER_LIMIT, 7),
        (Status.UNDER_LIMIT, 4),
        (Status.UNDER_LIMIT, 1),
        (Status.OVER_LIMIT, 1),
    ]
    # a later smaller ask still succeeds (state kept the leftover 1)
    r = h.one(req(hits=1, limit=10))
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 0)


def test_uniform_and_irregular_segments_coexist():
    # one hot uniform key + one irregular key (zero-hit read mixed in) in the
    # same window: fast path and replay must not interfere
    h = KernelHarness()
    a = lambda hits: req(key="hot", hits=hits, limit=5)
    b = lambda hits: req(key="odd", hits=hits, limit=4)
    rs = h.window([a(1), b(2), a(1), b(0), a(1), b(1), a(1)])
    assert [r.remaining for r in rs if r.limit == 5] == [4, 3, 2, 1]
    assert [r.remaining for r in rs if r.limit == 4] == [2, 2, 1]


def test_uniform_segment_init_over_ask():
    # fresh key, uniform hits > limit: init stores remaining 0 and every
    # lane is OVER (algorithms.go:77-83)
    h = KernelHarness()
    rs = h.window([req(hits=9, limit=5) for _ in range(3)])
    assert all(r.status == Status.OVER_LIMIT for r in rs)
    assert all(r.remaining == 0 for r in rs)


def test_in_window_slot_reuse_after_eviction():
    # With more new keys than table capacity in ONE window, eviction recycles
    # a slot to a second key mid-window; its first lane must re-init rather
    # than inherit the evicted key's register.
    h = KernelHarness(capacity=4, batch=16)
    rs = h.window([
        RateLimitReq(name="ev", unique_key=f"k{i}", hits=1, limit=100 + i,
                     duration=1000, algorithm=Algorithm.TOKEN_BUCKET)
        for i in range(6)  # k4 evicts k0's slot, k5 evicts k1's
    ])
    for i, r in enumerate(rs):
        assert r.limit == 100 + i, f"lane {i} inherited a stale register"
        assert r.remaining == 100 + i - 1


def test_algo_switch_within_window():
    # same key, different algorithm mid-window -> reset at that request
    h = KernelHarness()
    rs = h.window([
        req(hits=1, limit=5, algo=Algorithm.TOKEN_BUCKET),
        req(hits=1, limit=5, duration=1000, algo=Algorithm.LEAKY_BUCKET),
    ])
    assert rs[0].remaining == 4
    assert rs[1].remaining == 4  # re-initialized as leaky
    assert rs[1].reset_time == 0


@pytest.mark.parametrize("bits_a,bits_b", [(63, 63), (63, 16), (40, 31),
                                           (31, 4), (8, 1)])
def test_floordiv_rolled_equals_floor_divide(bits_a, bits_b):
    """kernel.floordiv's TPU branch (the rolled 64-step long division that
    keeps int64 ladders compilable for the chip) is bit-identical to
    jnp.floor_divide — signs, inexact quotients, INT64_MIN and x/0
    included.  The branch lowers only for a TPU, so it is pinned here by
    calling it directly."""
    import jax
    import numpy as np

    from gubernator_tpu.ops import kernel

    rng = np.random.default_rng(bits_a * 64 + bits_b)
    lim = np.iinfo(np.int64)
    edge = np.array([0, 1, -1, 2, -2, 3, -7, lim.max, lim.min, lim.max - 1,
                     lim.min + 1, 1 << 31, -(1 << 31), (1 << 32) + 1,
                     1 << 62, -(1 << 62), 60_000, 1_790_000_000_000],
                    np.int64)
    ea, eb = (m.ravel() for m in np.meshgrid(edge, edge))
    hi_a, hi_b = (1 << bits_a) - 1, (1 << bits_b) - 1
    a = np.concatenate([ea, rng.integers(-hi_a, hi_a, 4000, dtype=np.int64)])
    b = np.concatenate([eb, rng.integers(-hi_b, hi_b, 4000, dtype=np.int64)])
    got = np.asarray(jax.jit(kernel._floordiv_rolled)(a, b))
    want = np.asarray(jax.jit(lambda x, y: x // y)(a, b))
    bad = np.nonzero(got != want)[0]
    assert bad.size == 0, [(a[i], b[i], got[i], want[i]) for i in bad[:5]]
    # the dispatcher itself: int64 in, the native op on this (CPU) backend;
    # int32 never leaves the native op
    assert np.array_equal(np.asarray(kernel.floordiv(a, b)), want)
    assert kernel.floordiv(np.int32(-7), np.int32(2)).dtype == np.int32
