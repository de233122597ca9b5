"""Aggregated-run lanes (AGG_SLOT_BIT): one lane carrying n identical
hits=1 requests must leave the arena EXACTLY as n plain lanes would, and
the host synthesis rule (status_i = i < r_start, remaining_i =
max(r_start-(i+1), 0), leaky UNDER reset 0 / OVER reset from the word)
must reproduce every per-item response.

This is the device half of the native router's duplicate collapse — the
reason a Zipf head key costs one lane instead of thousands.
"""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
from gubernator_tpu.ops import kernel

T0 = 1_700_000_000_000
AGG = kernel.AGG_SLOT_BIT


def _batch(slots, hits, limits, durations, algos, inits):
    n = len(slots)
    return kernel.WindowBatch(
        slot=np.asarray(slots, np.int32),
        hits=np.asarray(hits, np.int64),
        limit=np.asarray(limits, np.int64),
        duration=np.asarray(durations, np.int64),
        algo=np.asarray(algos, np.int32),
        is_init=np.asarray(inits, bool),
    )


def _synthesize(word_out, i, algo, now):
    """The host synthesis rule (mirrors fastpath_encode_w)."""
    r_start = int(word_out.remaining)
    under = i < r_start
    status = 0 if under else 1
    remaining = max(r_start - (i + 1), 0)
    if algo == kernel.TOKEN_BUCKET:
        reset = int(word_out.reset_time)
    else:
        reset = 0 if under else int(word_out.reset_time)
    return status, remaining, reset


CASES = {
    # plain token run, resident entry
    "token_resident": dict(slot=3, n=7, limit=5, duration=60_000, algo=0,
                           init=False, warm=True),
    # token fresh (init lane aggregated)
    "token_fresh": dict(slot=4, n=4, limit=10, duration=60_000, algo=0,
                        init=True, warm=False),
    # token run longer than the balance (OVER tail)
    "token_over": dict(slot=5, n=9, limit=3, duration=60_000, algo=0,
                       init=True, warm=False),
    # leaky resident with leak
    "leaky_resident": dict(slot=6, n=5, limit=8, duration=40_000, algo=1,
                           init=False, warm=True),
    # leaky fresh exact drain (n == limit)
    "leaky_drain": dict(slot=7, n=6, limit=6, duration=30_000, algo=1,
                        init=True, warm=False),
    # leaky over tail
    "leaky_over": dict(slot=8, n=12, limit=4, duration=30_000, algo=1,
                       init=True, warm=False),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_agg_lane_matches_expanded_run(name):
    c = CASES[name]
    state_a = kernel.BucketState.zeros(16)
    state_p = kernel.BucketState.zeros(16)
    if c["warm"]:
        warm = _batch([c["slot"]], [2], [c["limit"]], [c["duration"]],
                      [c["algo"]], [True])
        state_a, _ = kernel.window_step(state_a, warm, T0 - 5_000)
        state_p, _ = kernel.window_step(state_p, warm, T0 - 5_000)

    n = c["n"]
    # aggregated: ONE lane, hits=n, slot bit 30
    agg = _batch([c["slot"] | AGG], [n], [c["limit"]], [c["duration"]],
                 [c["algo"]], [c["init"]])
    state_a, out_a = kernel.window_step(state_a, agg, T0)

    # plain: n lanes of hits=1 (first carries is_init)
    plain = _batch([c["slot"]] * n, [1] * n, [c["limit"]] * n,
                   [c["duration"]] * n, [c["algo"]] * n,
                   [c["init"]] + [False] * (n - 1))
    state_p, out_p = kernel.window_step(state_p, plain, T0)

    # arena state identical
    for f in kernel.BucketState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state_a, f)), np.asarray(getattr(state_p, f)),
            err_msg=f"{name} state.{f}")

    # synthesized per-item responses identical to the plain lanes
    word = kernel.WindowOutput(*[np.asarray(a)[0] for a in out_a])
    for i in range(n):
        got = _synthesize(word, i, c["algo"], T0)
        want = (int(np.asarray(out_p.status)[i]),
                int(np.asarray(out_p.remaining)[i]),
                int(np.asarray(out_p.reset_time)[i]))
        assert got == want, (name, i, got, want)


def test_agg_mixed_with_plain_lanes():
    """An aggregated lane followed by a different-config plain lane of the
    same key replays sequentially (arrival order preserved)."""
    state_a = kernel.BucketState.zeros(16)
    state_p = kernel.BucketState.zeros(16)
    # agg run of 3 (init) then a hits=2 request with the same config
    batch_a = _batch([2 | AGG, 2], [3, 2], [9, 9], [60_000, 60_000],
                     [0, 0], [True, False])
    state_a, out_a = kernel.window_step(state_a, batch_a, T0)
    batch_p = _batch([2, 2, 2, 2], [1, 1, 1, 2], [9] * 4, [60_000] * 4,
                     [0] * 4, [True, False, False, False])
    state_p, out_p = kernel.window_step(state_p, batch_p, T0)
    for f in kernel.BucketState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state_a, f)), np.asarray(getattr(state_p, f)),
            err_msg=f"state.{f}")
    # the plain trailing lane's direct response matches
    assert int(np.asarray(out_a.remaining)[1]) == \
        int(np.asarray(out_p.remaining)[3])
    assert int(np.asarray(out_a.status)[1]) == \
        int(np.asarray(out_p.status)[3])


@pytest.mark.parametrize("algo", [0, 1])
def test_agg_lane_compact32(algo):
    """The aggregated branch flows through the compact32 serving body."""
    state_x = kernel.BucketState.zeros(16)
    state_p = kernel.BucketState.zeros(16)
    batch = _batch([1 | AGG, 3], [5, 1], [4, 7], [60_000, 60_000],
                   [algo, algo], [True, True])
    state_x, out_x = kernel.window_step(state_x, batch, T0)
    state_p, out_p = kernel.window_step_compact32(state_p, batch, T0)
    for f in kernel.BucketState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(state_x, f)), np.asarray(getattr(state_p, f)),
            err_msg=f"state.{f}")
    for f in kernel.WindowOutput._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_x, f)), np.asarray(getattr(out_p, f)),
            err_msg=f"out.{f}")


def test_pipeline_aggregation_end_to_end():
    """Heavy hot-key duplicate traffic through the native RPC pipeline
    (where runs aggregate into single lanes) must answer byte-for-byte
    like the plain Python engine, and must actually collapse lanes."""
    import asyncio

    from gubernator_tpu import native
    from gubernator_tpu.api import pb
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine

    if not native.available():
        pytest.skip("native router unavailable")

    eng = RateLimitEngine(capacity_per_shard=256, batch_per_shard=64,
                          global_capacity=16, global_batch_per_shard=8,
                          max_global_updates=8, use_native="on")
    ref = RateLimitEngine(capacity_per_shard=256, batch_per_shard=64,
                          global_capacity=16, global_batch_per_shard=8,
                          max_global_updates=8, use_native=False)
    b = WindowBatcher(eng, BehaviorConfig())
    assert b.pipeline is not None and b.pipeline.enabled
    b.pipeline.now_fn = lambda: T0

    rng = np.random.default_rng(7)
    # 3 hot keys + a tail; mixed algos; hits=1 (the aggregable shape)
    reqs = [RateLimitReq(name="agg", unique_key=f"k{rng.zipf(1.2) % 5}",
                        hits=1, limit=20, duration=60_000,
                        algorithm=int(rng.integers(0, 2)))
            for _ in range(120)]
    data = pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name=r.name, unique_key=r.unique_key, hits=r.hits,
                        limit=r.limit, duration=r.duration,
                        algorithm=r.algorithm) for r in reqs
    ]).SerializeToString()

    async def run():
        return await b.submit_rpc(data)

    raw = asyncio.run(run())
    b.close()
    got = pb.GetRateLimitsResp.FromString(bytes(raw)).responses
    want = ref.process(reqs, now=T0)
    assert len(got) == len(want)
    for j, (g, w) in enumerate(zip(got, want)):
        assert (g.status, g.limit, g.remaining, g.reset_time) == \
            (int(w.status), w.limit, w.remaining, w.reset_time), \
            (j, reqs[j].unique_key)


def test_plain_lane_invalidates_aggregation_target():
    """[h1, h2, h1, h1...] to one key: after the h=2 plain lane, later
    h=1 items must NOT fold into the run staged BEFORE it (review-caught
    ordering bug: folding would replay them ahead of the h=2 consume).
    Pinned by exact sequential equality with the plain engine — including
    with a tiny replay cap, whose pass-1 reset clears the cell's
    nonuniform flag (the trigger)."""
    import asyncio

    from gubernator_tpu import native
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine

    if not native.available():
        pytest.skip("native router unavailable")

    for cap in (128, 2):  # default and a cap small enough to reset mid-run
        eng = RateLimitEngine(capacity_per_shard=256, batch_per_shard=64,
                              global_capacity=16, global_batch_per_shard=8,
                              max_global_updates=8, use_native="on")
        ref = RateLimitEngine(capacity_per_shard=256, batch_per_shard=64,
                              global_capacity=16, global_batch_per_shard=8,
                              max_global_updates=8, use_native=False)
        eng.native.set_replay_cap(cap)
        b = WindowBatcher(eng, BehaviorConfig())
        assert b.pipeline is not None and b.pipeline.enabled
        b.pipeline.now_fn = lambda: T0

        mk = lambda h: RateLimitReq(name="ord", unique_key="A", hits=h,
                                    limit=3, duration=60_000)
        reqs = [mk(1), mk(2), mk(1), mk(1), mk(1), mk(1), mk(1)]

        async def run():
            return await asyncio.gather(*(b.submit(r) for r in reqs))

        got = asyncio.run(run())
        b.close()
        want = ref.process(reqs, now=T0)
        for j, (g, w) in enumerate(zip(got, want)):
            assert (int(g.status), g.remaining) == \
                (int(w.status), w.remaining), (cap, j)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pipeline_fuzz_differential(seed):
    """Randomized multi-drain differential through the aggregating
    pipeline: Zipf-hot keys, mostly hits=1 (the aggregable shape) mixed
    with reads/bursts, both algorithms, a small arena (eviction pressure)
    and a tiny replay cap (window splits + pass-1 resets) — every
    response must equal the plain Python engine's, lane for lane."""
    import asyncio

    from gubernator_tpu import native
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine

    if not native.available():
        pytest.skip("native router unavailable")

    rng = np.random.default_rng(100 + seed)
    eng = RateLimitEngine(capacity_per_shard=64, batch_per_shard=32,
                          global_capacity=16, global_batch_per_shard=8,
                          max_global_updates=8, use_native="on")
    ref = RateLimitEngine(capacity_per_shard=64, batch_per_shard=32,
                          global_capacity=16, global_batch_per_shard=8,
                          max_global_updates=8, use_native=False)
    eng.native.set_replay_cap(4)

    now = T0
    for drain in range(6):
        now += int(rng.integers(0, 40_000))  # cross expiry boundaries
        b = WindowBatcher(eng, BehaviorConfig())
        assert b.pipeline is not None and b.pipeline.enabled
        t = now
        b.pipeline.now_fn = lambda t=t: t
        b.now_fn = b.pipeline.now_fn  # keep any fallback on the same clock
        reqs = []
        for _ in range(60):
            key = f"z{(rng.zipf(1.3) - 1) % 7}"
            hits = int(rng.choice([1, 1, 1, 1, 0, 2]))
            lim = int(rng.choice([5, 5, 9]))
            reqs.append(RateLimitReq(
                name="fz", unique_key=key, hits=hits, limit=lim,
                duration=int(rng.choice([1_000, 30_000])),
                algorithm=int(rng.integers(0, 2))))

        async def run():
            return await asyncio.gather(*(b.submit(r) for r in reqs))

        got = asyncio.run(run())
        b.close()
        want = ref.process(reqs, now=now)
        for j, (g, w) in enumerate(zip(got, want)):
            assert (int(g.status), g.limit, g.remaining, g.reset_time) == \
                (int(w.status), w.limit, w.remaining, w.reset_time), \
                (seed, drain, j, reqs[j])
