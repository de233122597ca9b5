"""The fused serving-window megakernel (ops/pallas_kernel.window_step_fused)
pinned bit-exact against the int64 oracle (ops/kernel.window_step) in
interpret mode, plus the executed-kernel census that justifies its
existence.

The differential contract: for any compact-encoded window (pads, hot
duplicates, folds, recycling inits, zero-reads, cap-edge configs) and any
arena whose rows were written under the compact caps,

    decode_batch -> window_step -> encode_output_word   (the oracle)

and one window_step_fused pallas_call must agree on every response word,
every limit lane, the mismatch flag, and every plane of the new state.
"""

import subprocess
import sys

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import lax

from gubernator_tpu.ops import kernel
from gubernator_tpu.ops import pallas_kernel as pk

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


def _assert_window_exact(st, packed, now, tag=""):
    """One window through oracle and megakernel; assert full agreement.
    Returns the (identical) new state for chaining."""
    bt = kernel.decode_batch(packed)
    st_ref, out_ref = jax.jit(kernel.window_step)(st, bt, now)
    words_ref = kernel.encode_output_word(out_ref, now)
    mism_ref = bool(np.any(
        (np.asarray(out_ref.limit) != np.asarray(bt.limit))
        & (np.asarray(bt.slot) >= 0)))

    st_f, words_f, limits_f, mism_f = pk.window_step_fused(
        st, packed, now, interpret=True)

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
def test_fused_fuzz_chained_windows(seed):
    """Property fuzz: chained windows over a live arena (state carries,
    time advances across expiry boundaries), duplicates + folds + inits +
    pads + zero-reads, with cap-edge configs mixed in."""
    rng = np.random.default_rng(300 + seed)
    B, C = 64, 128
    st = kernel.BucketState.zeros(C)
    now = T0
    for w in range(6):
        now += int(rng.integers(1, 400_000))
        packed = _random_packed(rng, B, C, cap_edges=(w % 2 == 1))
        st = _assert_window_exact(st, packed, now, tag=f"seed{seed} w{w}")


def test_fused_window_recycle():
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


def test_fused_duplicate_run_folds():
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


def test_fused_all_init_zipf():
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


def test_fused_multi_window_drain_shapes():
    """Several fused windows chained through the plane form (the pipeline
    drain's carry) agree with chaining through BucketState round trips —
    the conversion is exact both ways."""
    rng = np.random.default_rng(8)
    B, C = 32, 64
    st = _random_state(rng, C, T0)
    st32 = pk.fused_state_to_planes(st)
    st_rt = st
    now = T0
    for w in range(4):
        now += int(rng.integers(1, 1000))
        packed = _random_packed(rng, B, C)
        st32, w1, l1, m1 = pk.window_step_fused_planes(
            st32, packed, now, interpret=True)
        st_rt, w2, l2, m2 = pk.window_step_fused(
            st_rt, packed, now, interpret=True)
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        assert bool(m1) == bool(m2)
    for name, a, b in zip(kernel.BucketState._fields,
                          pk.fused_state_from_planes(st32), st_rt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"state.{name}")


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
        rel = pk._pair_rebase(tp[:, 0], tp[:, 1], npair[0], npair[1])
        want = np.clip(t - now, -(2**31 - 16), 2**31 - 16).astype(np.int32)
        np.testing.assert_array_equal(np.asarray(rel), want,
                                      err_msg=f"rebase now={now}")
        a_lo, a_hi = pk._pair_reabs(rel, npair[0], npair[1])
        back = lax.bitcast_convert_type(
            jnp.stack([a_lo, jnp.broadcast_to(a_hi, a_lo.shape)], -1),
            jnp.int64)
        np.testing.assert_array_equal(
            np.asarray(back), now + np.asarray(rel).astype(np.int64),
            err_msg=f"reabs now={now}")


def test_bitonic_sort_is_stable_argsort():
    """The in-kernel bitonic network must reproduce jnp.argsort exactly
    (stability is semantic: duplicate hits apply in arrival order)."""
    rng = np.random.default_rng(10)
    for B in (2, 8, 64, 256):
        key = jnp.asarray(rng.integers(0, max(2, B // 4), B), jnp.int32)
        s_key, order = pk._bitonic_sort_by_slot(key)
        want = jnp.argsort(key)
        np.testing.assert_array_equal(np.asarray(order), np.asarray(want),
                                      err_msg=f"B={B}")
        np.testing.assert_array_equal(np.asarray(s_key),
                                      np.asarray(key)[np.asarray(want)])


# the shared executed-kernel proxy (also used by bench.py's per-arm census
# and the mesh-fused drain suite)
_census = pk.kernel_census


def test_fused_kernel_census():
    """The point of the megakernel: >= 5x fewer executed ops per serving
    window than the compact32-XLA drain body (ISSUE acceptance bar; the
    measured ratio is ~20x)."""
    B, C = 64, 128
    state = kernel.BucketState.zeros(C)
    packed = jnp.zeros((B, 2), jnp.int64)
    now = jnp.int64(T0)

    def xla_window(state, packed, now):
        bt = kernel.decode_batch(packed)
        st, out = pk.window_step_compact32_xla(state, bt, now)
        word = kernel.encode_output_word(out, now)
        mism = jnp.any((out.limit != bt.limit) & (bt.slot >= 0))
        return st, word, out.limit, mism

    def fused_window(state, packed, now):
        return pk.window_step_fused(state, packed, now, interpret=False)

    cx = _census(jax.make_jaxpr(xla_window)(state, packed, now))
    cf = _census(jax.make_jaxpr(fused_window)(state, packed, now))
    assert cf * 5 <= cx, (
        f"fused window census {cf} not >=5x below XLA census {cx}")


def test_fused_rejects_non_power_of_two():
    rng = np.random.default_rng(11)
    st = _random_state(rng, 16, T0)
    packed = _random_packed(rng, 12, 16)  # B=12: not a power of two
    with pytest.raises(AssertionError):
        pk.window_step_fused(st, packed, T0, interpret=True)


def test_engine_serves_with_fused(monkeypatch):
    """GUBER_PALLAS_FUSED=1 must cover the engine's compact serving
    dispatch end to end and match a flag-free engine response for
    response.  The flag is read at dispatch time (part of the compiled
    builder's cache key), so it is toggled around each engine's calls."""
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices("cpu")[6:7])
    kw = dict(capacity_per_shard=64, batch_per_shard=16, global_capacity=16,
              global_batch_per_shard=8, max_global_updates=8)
    eng = RateLimitEngine(mesh=mesh, **kw)
    plain = RateLimitEngine(**kw)
    assert eng._compact_enabled
    for i in range(6):
        reqs = [RateLimitReq(name="fz", unique_key=f"k{j % 3}", hits=1,
                             limit=4, duration=60_000) for j in range(6)]
        monkeypatch.setenv("GUBER_PALLAS_FUSED", "1")
        a = eng.process(reqs, now=T0 + i)
        monkeypatch.delenv("GUBER_PALLAS_FUSED")
        b = plain.process(reqs, now=T0 + i)
        assert [(int(x.status), x.remaining, x.reset_time) for x in a] == \
            [(int(y.status), y.remaining, y.reset_time) for y in b], i


def test_pipeline_drain_fused_parity(monkeypatch):
    """The stacked drain (pipeline_dispatch) under GUBER_PALLAS_FUSED=1:
    words, limits, mismatch flags and the final arena must match the
    default compact32-XLA drain bit for bit."""
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(12)
    K, B, C = 4, 16, 64
    stack = np.zeros((K, 1, B, 2), np.int64)
    for k in range(K):
        stack[k, 0] = np.asarray(_random_packed(rng, B, C, hot=3))
    nows = np.asarray([T0 + 10 * i for i in range(K)], np.int64)

    kw = dict(capacity_per_shard=C, batch_per_shard=B, global_capacity=16,
              global_batch_per_shard=8, max_global_updates=8)
    ef = RateLimitEngine(mesh=make_mesh(jax.devices("cpu")[6:7]), **kw)
    ex = RateLimitEngine(mesh=make_mesh(jax.devices("cpu")[7:8]), **kw)

    monkeypatch.setenv("GUBER_PALLAS_FUSED", "1")
    wf, lf, mf = ef.pipeline_dispatch(stack, nows)
    monkeypatch.delenv("GUBER_PALLAS_FUSED")
    wx, lx, mx = ex.pipeline_dispatch(stack, nows)
    np.testing.assert_array_equal(np.asarray(wf), np.asarray(wx))
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lx))
    np.testing.assert_array_equal(np.asarray(mf), np.asarray(mx))
    for n, a, b in zip(kernel.ArenaPlanes._fields, ef.state, ex.state):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]),
                                      err_msg=f"state.{n}")


def test_fused_fresh_interpreter_no_recursion_leak():
    """Running the fused megakernel (interpret mode) must not leave a
    raised recursion limit behind: the mosaic_recursion_guard scoping is
    per lowering call, never process-global.  Fresh
    interpreter so the check sees exactly this code path's side effects."""
    code = (
        "import sys; base = sys.getrecursionlimit()\n"
        "import numpy as np\n"
        "import gubernator_tpu\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import jax.numpy as jnp\n"
        "from gubernator_tpu.ops import kernel\n"
        "from gubernator_tpu.ops.pallas_kernel import window_step_fused\n"
        "st = kernel.BucketState.zeros(16)\n"
        "packed = jnp.asarray(kernel.encode_batch_host(\n"
        "    np.array([0, 1, -1, 1], np.int32), np.ones(4, np.int64),\n"
        "    np.full(4, 5, np.int64), np.full(4, 1000, np.int64),\n"
        "    np.zeros(4, np.int32), np.zeros(4, bool)))\n"
        "window_step_fused(st, packed, 1_754_000_000_000, interpret=True)\n"
        "print(int(sys.getrecursionlimit() == base))\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1", "recursion limit leaked"
