"""The serving window through the packed wire, pinned bit-exact against the
int64 oracle (ops/kernel.window_step).

The differential contract: for any compact-encoded window (pads, hot
duplicates, folds, recycling inits, zero-reads, cap-edge configs) and any
arena whose rows were written under the compact caps,

    decode_batch -> window_step -> encode_output_word   (the oracle, on
                                                         int64 rows)

and the body the chip runs (decode_batch -> window_step_compact32 ->
encode_output_word on the resident uint32 planes: tests/harness.py
wire_window, the scan body of engine._drain_scan) must agree on every
response word, every limit lane, the mismatch flag, and every column of the
new state.
"""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import lax

from gubernator_tpu.ops import kernel

from .harness import wire_window

T0 = 1_754_000_000_000  # ms epoch, like the engine's serving clocks


def _random_state(rng, C, now):
    """Arena rows as the compact serving path would have written them:
    values inside the compact caps, times within a duration of now."""
    return kernel.BucketState(
        limit=jnp.asarray(rng.integers(1, 1000, C), jnp.int64),
        duration=jnp.asarray(rng.integers(1, 600_000, C), jnp.int64),
        remaining=jnp.asarray(rng.integers(0, 1000, C), jnp.int64),
        tstamp=jnp.asarray(now + rng.integers(-500_000, 500_000, C)),
        expire=jnp.asarray(now + rng.integers(-500_000, 500_000, C)),
        algo=jnp.asarray(rng.integers(0, 2, C), jnp.int32),
    )


def _random_packed(rng, B, C, hot=6, agg_frac=0.1, init_frac=0.15,
                   pad_frac=0.2, cap_edges=False):
    """A compact-encoded window: pads, duplicate-heavy slots, folds
    (AGG_SLOT_BIT lanes), recycling inits, zero-read peeks."""
    slot = rng.integers(0, C, B).astype(np.int32)
    dup = rng.random(B) < 0.5
    hotslots = rng.integers(0, C, hot)
    slot[dup] = hotslots[rng.integers(0, hot, int(dup.sum()))]
    slot[rng.random(B) < pad_frac] = kernel.PAD_SLOT
    hits = rng.choice([0, 0, 1, 1, 2, 7], B).astype(np.int64)
    limit = rng.integers(1, 1000, B).astype(np.int64)
    duration = rng.integers(1, 600_000, B).astype(np.int64)
    if cap_edges:
        edge = rng.random(B) < 0.2
        hits[rng.random(B) < 0.1] = int(kernel.COMPACT_MAX_HITS - 1)
        limit[edge] = int(kernel.COMPACT_MAX_LIMIT - 1)
        duration[edge] = int(kernel.COMPACT_MAX_DURATION - 1)
    algo = rng.integers(0, 2, B).astype(np.int32)
    is_init = rng.random(B) < init_frac
    agg = (rng.random(B) < agg_frac) & (slot >= 0)
    eslot = np.where(agg, slot | kernel.AGG_SLOT_BIT, slot)
    return jnp.asarray(kernel.encode_batch_host(
        eslot, hits, limit, duration, algo, is_init))


_wire_window = jax.jit(wire_window)
_oracle_step = jax.jit(kernel.window_step)


def _assert_window_exact(st, packed, now, tag=""):
    """One window through the oracle and the serving body; assert full
    agreement.  Returns the (identical) new state for chaining."""
    bt = kernel.decode_batch(packed)
    st_ref, out_ref = _oracle_step(st, bt, now)
    words_ref = kernel.encode_output_word(out_ref, now)
    mism_ref = bool(np.any(
        (np.asarray(out_ref.limit) != np.asarray(bt.limit))
        & (np.asarray(bt.slot) >= 0)))

    planes_f, words_f, limits_f, mism_f = _wire_window(
        kernel.arena_from_rows(st), packed, now)
    st_f = kernel.arena_to_rows(planes_f)

    np.testing.assert_array_equal(
        np.asarray(words_ref), np.asarray(words_f),
        err_msg=f"{tag} response words")
    np.testing.assert_array_equal(
        np.asarray(out_ref.limit), np.asarray(limits_f),
        err_msg=f"{tag} limit lanes")
    assert mism_ref == bool(mism_f), f"{tag} mismatch flag"
    for name, a, b in zip(kernel.BucketState._fields, st_ref, st_f):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{tag} state.{name}")
    return st_ref


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("edges", ["alternate", "every"])
def test_wire_fuzz_chained_windows(edges, seed):
    """Property fuzz: chained windows over a live arena (state carries,
    time advances across expiry boundaries), duplicates + folds + inits +
    pads + zero-reads, with cap-edge configs (lanes at COMPACT_MAX_* - 1,
    which press the rebase range) in every other window or in every one."""
    rng = np.random.default_rng((300 if edges == "alternate" else 400) + seed)
    B, C = 64, 128
    st = kernel.BucketState.zeros(C)
    now = T0
    for w in range(6):
        now += int(rng.integers(1, 400_000))
        packed = _random_packed(rng, B, C,
                                cap_edges=edges == "every" or w % 2 == 1)
        st = _assert_window_exact(st, packed, now,
                                  tag=f"{edges} seed{seed} w{w}")


def test_wire_window_recycle():
    """Mid-window slot recycling: duplicate runs on one slot where a later
    lane is is_init (capacity eviction handed the slot to a new tenant).
    The init must start a fresh virtual segment and ONLY the last tenant's
    register may commit."""
    B, C = 16, 8
    slot = np.full(B, kernel.PAD_SLOT, np.int32)
    hits = np.zeros(B, np.int64)
    limit = np.full(B, 10, np.int64)
    duration = np.full(B, 60_000, np.int64)
    algo = np.zeros(B, np.int32)
    is_init = np.zeros(B, bool)
    # old tenant: lanes 0-2 on slot 3; new tenant: lanes 3-5 (lane 3 init)
    slot[0:6] = 3
    hits[0:6] = 1
    is_init[3] = True
    limit[3:6] = 7  # new tenant's config differs
    packed = jnp.asarray(kernel.encode_batch_host(
        slot, hits, limit, duration, algo, is_init))
    rng = np.random.default_rng(5)
    st = _random_state(rng, C, T0)
    _assert_window_exact(st, packed, T0 + 50, tag="recycle")


def test_wire_duplicate_run_folds():
    """Aggregated-run lanes (AGG_SLOT_BIT): a fold owning its slot alone
    (replay-free closed form) and a fold mixed into a duplicate run."""
    B, C = 16, 8
    slot = np.full(B, kernel.PAD_SLOT, np.int32)
    hits = np.zeros(B, np.int64)
    limit = np.full(B, 100, np.int64)
    duration = np.full(B, 60_000, np.int64)
    algo = np.zeros(B, np.int32)
    is_init = np.zeros(B, bool)
    slot[0] = 2            # lone fold on slot 2
    hits[0] = 37
    slot[1:4] = 5          # slot 5: plain, fold, plain
    hits[1:4] = (1, 12, 1)
    eslot = slot.copy()
    eslot[0] |= kernel.AGG_SLOT_BIT
    eslot[2] |= kernel.AGG_SLOT_BIT
    packed = jnp.asarray(kernel.encode_batch_host(
        eslot, hits, limit, duration, algo, is_init))
    rng = np.random.default_rng(6)
    st = _random_state(rng, C, T0)
    _assert_window_exact(st, packed, T0 + 9, tag="folds")


def test_wire_all_init_zipf():
    """Every lane is_init on a Zipf-skewed slot distribution: maximal
    virtual-segment splitting (every lane starts a segment)."""
    rng = np.random.default_rng(7)
    B, C = 64, 32
    slot = np.minimum(rng.zipf(1.5, B) - 1, C - 1).astype(np.int32)
    packed = jnp.asarray(kernel.encode_batch_host(
        slot, np.ones(B, np.int64), np.full(B, 50, np.int64),
        np.full(B, 30_000, np.int64), rng.integers(0, 2, B).astype(np.int32),
        np.ones(B, bool)))
    st = _random_state(rng, C, T0)
    _assert_window_exact(st, packed, T0 + 123, tag="all-init zipf")


def _drain_vs_oracle(eng, rows, stack, nows, tag):
    """One K-window stack through engine.pipeline_dispatch (one shard) vs
    the int64 oracle chained window by window over `rows`; returns the
    oracle's new rows (the engine's arena is asserted equal to them)."""
    words, limits, mism = eng.pipeline_dispatch(stack, nows)
    for k in range(stack.shape[0]):
        bt = kernel.decode_batch(jnp.asarray(stack[k, 0]))
        rows, out = _oracle_step(rows, bt, nows[k])
        np.testing.assert_array_equal(
            np.asarray(kernel.encode_output_word(out, nows[k])),
            np.asarray(words)[k, 0], err_msg=f"{tag} window {k} words")
        np.testing.assert_array_equal(
            np.asarray(out.limit), np.asarray(limits)[k, 0],
            err_msg=f"{tag} window {k} limits")
        want = bool(np.any((np.asarray(out.limit) != np.asarray(bt.limit))
                           & (np.asarray(bt.slot) >= 0)))
        assert want == bool(np.asarray(mism)[k, 0]), f"{tag} window {k} mism"
    got = kernel.arena_to_rows(jax.tree.map(lambda a: np.asarray(a[0]),
                                            eng.state))
    for name, a, b in zip(kernel.BucketState._fields, rows, got):
        np.testing.assert_array_equal(np.asarray(a), b,
                                      err_msg=f"{tag} state.{name}")
    return rows


def _one_shard_engine(dev, B, C):
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.mesh import make_mesh
    return RateLimitEngine(
        mesh=make_mesh(jax.devices("cpu")[dev:dev + 1]),
        capacity_per_shard=C, batch_per_shard=B, global_capacity=16,
        global_batch_per_shard=8, max_global_updates=8)


def test_wire_multi_window_drain():
    """Several windows stacked into ONE drain (engine.pipeline_dispatch: the
    lax.scan of the serving body, the arena carried in its resident plane
    form) agree with chaining the int64 oracle window by window, and so do
    two drains in a row."""
    rng = np.random.default_rng(8)
    K, B, C = 4, 32, 64
    eng = _one_shard_engine(6, B, C)
    rows = kernel.BucketState.zeros(C)
    now = T0
    for d in range(2):
        nows = now + np.cumsum(rng.integers(1, 1000, K)).astype(np.int64)
        now = int(nows[-1])
        stack = np.stack([np.asarray(_random_packed(rng, B, C))[None]
                          for _ in range(K)])
        rows = _drain_vs_oracle(eng, rows, stack, nows, f"drain {d}")


@pytest.mark.parametrize("width", ["narrow", "quarter", "full"])
def test_wire_fuzz_drain_lane_widths(width):
    """The chained-fuzz wire streams through engine.pipeline_dispatch at
    each lane width the engine serves (B/16, B/4 and B: the occupied-prefix
    buckets a drain's executable is picked from), one single-window drain
    after another over the same arena, cap-edge configs in every other."""
    B, C = 1024, 256
    eng = _one_shard_engine(7, B, C)
    lanes = dict(zip(("narrow", "quarter", "full"), eng._lane_bucket_list))
    assert sorted(lanes.values()) == [B // 16, B // 4, B]
    L = lanes[width]
    rng = np.random.default_rng(500 + L)
    rows = kernel.BucketState.zeros(C)
    now = T0
    for w in range(4):
        now += int(rng.integers(1, 400_000))
        packed = _random_packed(rng, L, C, cap_edges=w % 2 == 1)
        rows = _drain_vs_oracle(eng, rows, np.asarray(packed)[None, None],
                                np.asarray([now], np.int64),
                                f"{width} w{w}")


def test_pair_arithmetic_exact():
    """The (lo, hi) i32 pair rebase/re-absolutize helpers are exact images
    of the int64 clip-subtract and add for random i64s and edge values."""
    rng = np.random.default_rng(9)
    t = np.concatenate([
        rng.integers(-2**62, 2**62, 2000),
        np.array([0, 1, -1, 2**31 - 16, -(2**31 - 16), 2**31, -(2**31),
                  T0, T0 + 2**31], np.int64),
    ]).astype(np.int64)
    for now in (np.int64(T0), np.int64(0), np.int64(5), np.int64(2**33 + 7)):
        tp = lax.bitcast_convert_type(jnp.asarray(t), jnp.int32)
        npair = lax.bitcast_convert_type(
            jnp.asarray(now).reshape((1,)), jnp.int32).reshape((2,))
        rel = kernel.pair_rebase(tp[:, 0], tp[:, 1], npair[0], npair[1])
        want = np.clip(t - now, -kernel.REBASE_LIM,
                       kernel.REBASE_LIM).astype(np.int32)
        np.testing.assert_array_equal(np.asarray(rel), want,
                                      err_msg=f"rebase now={now}")
        a_lo, a_hi = kernel.pair_reabs(rel, npair[0], npair[1])
        back = lax.bitcast_convert_type(
            jnp.stack([a_lo, jnp.broadcast_to(a_hi, a_lo.shape)], -1),
            jnp.int64)
        np.testing.assert_array_equal(
            np.asarray(back), now + np.asarray(rel).astype(np.int64),
            err_msg=f"reabs now={now}")
