"""Adversarial-segment fuzz for the generalized zero-replay fold.

window_step evaluates each same-slot lane run (segment) either
CLOSED-FORM — when fold_classify admits it — or through the per-segment
replay; both must reproduce the sequential contract exactly: lanes
applied one at a time in lane order, each seeing its predecessors'
committed register.  The oracle here IS that contract: the same lanes
re-dispatched as single-lane windows, where every segment has length 1
and the fold prefix machinery is inert by construction.  Any
fold-vs-sequential disagreement shows up bit for bit in the responses
or the committed arena.

Segments are built adversarially, every class fold_classify must either
fold exactly or reject to the replay:

  * long hot runs (3 hot slots over a tiny arena);
  * hstar violations — mixed distinct nonzero hits in one run;
  * config flips mid-segment (limit / duration / algorithm);
  * AGG lanes inside multi-lane runs (fold must reject);
  * leading and interleaved zero-hit reads (the read-leak telescoping
    edge on leaky buckets);
  * recycle inits mid-run (is_init starts a fresh virtual segment);
  * arena rows violating the leaky invariant (remaining > limit).

Both window bodies are pinned: the int64 oracle path against the serial
contract, and the compact32 serving body against the int64 path on the same
windows (all values inside the compact caps by construction).

The drain seeds push the SAME adversarial windows through the packed wire
— compact-encoded requests in, response words out — as ONE K-window stack
through the serving drain executable (engine._compiled_pipeline_step, the
arena carried in its resident plane form), against the host decode →
oracle → encode path.  The replay fallback inside the body is exercised by
construction (hstar violations and AGG lanes inside multi-lane runs force
fold_classify to bail).
"""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.ops import kernel
from gubernator_tpu.parallel.mesh import make_mesh

T0 = 1_754_000_000_000


def _adversarial_state(rng, C, now, algo_hi=2):
    """Arena rows inside the compact caps, with deliberate leaky-invariant
    violations (remaining > limit) and times straddling now.  algo_hi=5
    seeds rows under every wire algorithm (GCRA TAT times, sliding packed
    two-bucket remainders, concurrency free-slot counters) — any int is a
    structurally valid stored value for each ladder."""
    limit = rng.integers(1, 900, C).astype(np.int64)
    remaining = rng.integers(0, 1000, C).astype(np.int64)  # may exceed limit
    return kernel.BucketState(
        limit=jnp.asarray(limit),
        duration=jnp.asarray(rng.integers(1, 500_000, C), jnp.int64),
        remaining=jnp.asarray(remaining),
        tstamp=jnp.asarray(now + rng.integers(-400_000, 400_000, C)),
        expire=jnp.asarray(now + rng.integers(-400_000, 400_000, C)),
        algo=jnp.asarray(rng.integers(0, algo_hi, C), jnp.int32),
    )


def _adversarial_batch(rng, B, C, algo_hi=2):
    slot = rng.integers(0, C, B).astype(np.int32)
    hot = rng.integers(0, C, 3)
    dup = rng.random(B) < 0.7
    slot[dup] = hot[rng.integers(0, 3, int(dup.sum()))]
    slot[rng.random(B) < 0.1] = kernel.PAD_SLOT

    hstar = int(rng.integers(1, 4))
    hits = np.where(rng.random(B) < 0.5, hstar, 0).astype(np.int64)
    mix = rng.random(B) < 0.25  # distinct nonzero hits: hstar violations
    hits[mix] = rng.integers(1, 9, int(mix.sum()))

    limit = np.full(B, int(rng.integers(2, 12)), np.int64)
    flip = rng.random(B) < 0.2  # config flips mid-segment
    limit[flip] = rng.integers(2, 900, int(flip.sum()))
    duration = np.full(B, int(rng.integers(1_000, 90_000)), np.int64)
    dflip = rng.random(B) < 0.2
    duration[dflip] = rng.integers(1_000, 500_000, int(dflip.sum()))
    algo = np.full(B, int(rng.integers(0, algo_hi)), np.int32)
    aflip = rng.random(B) < 0.15
    algo[aflip] = rng.integers(0, algo_hi, int(aflip.sum())).astype(np.int32)
    if algo_hi > kernel.CONCURRENCY:
        # concurrency releases: negative hits, ONLY on conc lanes (the
        # compact wire sign-extends hits solely for algo 4)
        rel = (algo == kernel.CONCURRENCY) & (rng.random(B) < 0.4)
        hits[rel] = -rng.integers(1, 9, int(rel.sum()))

    is_init = (rng.random(B) < 0.1) & (slot >= 0)
    # the native router only synthesizes AGG runs for algo <= 1, so AGG
    # lanes with higher algorithms never reach a window in production
    agg = ((rng.random(B) < 0.15) & (slot >= 0) & (hits > 0)
           & (algo <= kernel.LEAKY_BUCKET))
    eslot = np.where(agg, slot | kernel.AGG_SLOT_BIT, slot).astype(np.int32)
    return kernel.WindowBatch(slot=eslot, hits=hits, limit=limit,
                              duration=duration, algo=algo, is_init=is_init)


def _serial_oracle(step1, st, batch, now):
    """The sequential contract: one lane per dispatch, in lane order."""
    outs = []
    for i in range(batch.slot.shape[0]):
        one = kernel.WindowBatch(*[np.asarray(a)[i:i + 1] for a in batch])
        st, out = step1(st, one, now)
        outs.append(out)
    cat = lambda f: np.concatenate(  # noqa: E731
        [np.asarray(getattr(o, f)) for o in outs])
    return st, kernel.WindowOutput(*[cat(f)
                                     for f in kernel.WindowOutput._fields])


@pytest.mark.parametrize("seed", list(range(12)))
def test_fold_adversarial_segments_match_serial(seed):
    B, C = 32, 24
    rng = np.random.default_rng(7000 + seed)
    now = T0
    st_batch = _adversarial_state(rng, C, now)
    st_c32 = kernel.BucketState(*[jnp.asarray(np.asarray(a))
                                  for a in st_batch])
    st_serial = kernel.BucketState(*[jnp.asarray(np.asarray(a))
                                     for a in st_batch])
    step = jax.jit(kernel.window_step)
    step_c32 = jax.jit(kernel.window_step_compact32)
    for w in range(4):
        now += int(rng.integers(1, 300_000))  # cross expiry boundaries
        batch = _adversarial_batch(rng, B, C)
        nj = jnp.int64(now)

        # PAD lanes carry unspecified outputs (the engine masks them on
        # slot >= 0 before any response leaves the device) — compare
        # occupied lanes only; the committed arena must agree everywhere
        valid = np.asarray(batch.slot) >= 0

        st_batch, out = step(st_batch, batch, nj)
        st_serial, want = _serial_oracle(step, st_serial, batch, nj)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f))[valid],
                np.asarray(getattr(want, f))[valid],
                err_msg=f"seed {seed} window {w} out.{f}")
        for name, a, b in zip(kernel.BucketState._fields,
                              st_batch, st_serial):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"seed {seed} window {w} state.{name}")

        st_c32, out32 = step_c32(st_c32, batch, nj)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out32, f))[valid],
                np.asarray(getattr(out, f))[valid],
                err_msg=f"seed {seed} window {w} compact32 out.{f}")
        for name, a, b in zip(kernel.BucketState._fields, st_c32, st_batch):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"seed {seed} window {w} compact32 state.{name}")


def _run_fold_vs_serial(st0, windows, tag):
    """Pin fold (window_step) vs the serial single-lane contract vs the
    compact32-XLA lowering on explicit (batch, now) windows, bit for bit."""
    st_batch = kernel.BucketState(*[jnp.asarray(np.asarray(a)) for a in st0])
    st_c32 = kernel.BucketState(*[jnp.asarray(np.asarray(a)) for a in st0])
    st_serial = kernel.BucketState(*[jnp.asarray(np.asarray(a))
                                     for a in st0])
    step = jax.jit(kernel.window_step)
    step_c32 = jax.jit(kernel.window_step_compact32)
    for w, (batch, now) in enumerate(windows):
        nj = jnp.int64(now)
        valid = np.asarray(batch.slot) >= 0
        st_batch, out = step(st_batch, batch, nj)
        st_serial, want = _serial_oracle(step, st_serial, batch, nj)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out, f))[valid],
                np.asarray(getattr(want, f))[valid],
                err_msg=f"{tag} window {w} out.{f}")
        for name, a, b in zip(kernel.BucketState._fields,
                              st_batch, st_serial):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{tag} window {w} state.{name}")
        st_c32, out32 = step_c32(st_c32, batch, nj)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out32, f))[valid],
                np.asarray(getattr(out, f))[valid],
                err_msg=f"{tag} window {w} compact32 out.{f}")
        for name, a, b in zip(kernel.BucketState._fields, st_c32, st_batch):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{tag} window {w} compact32 state.{name}")


@pytest.mark.algorithms
@pytest.mark.parametrize("seed", list(range(8)))
def test_fold_adversarial_all_algorithms_match_serial(seed):
    """The 12-seed fuzz above, re-run over the FULL wire algorithm range
    (token, leaky, GCRA, sliding-window, concurrency) with negative-hits
    concurrency releases in the mix: segments now flip between all five
    ladders mid-run, and fold_classify must still either fold exactly or
    reject to the replay on every lowering."""
    B, C = 32, 24
    rng = np.random.default_rng(11_000 + seed)
    now = T0
    st0 = _adversarial_state(rng, C, now, algo_hi=5)
    windows = []
    for _ in range(4):
        now += int(rng.integers(1, 300_000))
        windows.append((_adversarial_batch(rng, B, C, algo_hi=5), now))
    _run_fold_vs_serial(st0, windows, f"algos seed {seed}")


def _one_slot_batch(B, slot, hits, limit, duration, algo, is_init=None):
    mk = lambda v, dt: np.full(B, v, dt) if np.isscalar(v) \
        else np.asarray(v, dt)  # noqa: E731
    return kernel.WindowBatch(
        slot=mk(slot, np.int32), hits=mk(hits, np.int64),
        limit=mk(limit, np.int64), duration=mk(duration, np.int64),
        algo=mk(algo, np.int32),
        is_init=np.zeros(B, bool) if is_init is None
        else mk(is_init, bool))


def _fresh_state(C):
    z = jnp.zeros(C, jnp.int64)
    return kernel.BucketState(limit=z, duration=z, remaining=z,
                              tstamp=z, expire=z,
                              algo=jnp.zeros(C, jnp.int32))


@pytest.mark.algorithms
def test_fold_algorithm_switch_mid_stream():
    """One slot touched under every algorithm value in one run (config
    flips force the replay) and across windows (each switch re-inits the
    register): the sequential contract holds bit for bit."""
    # all four targeted tests share the B=8/C=8 shape so the fold and
    # compact32 lowerings compile ONCE for the whole group (1-core box)
    algos = [0, 1, 2, 3, 4, 2, 3, 0]
    b1 = _one_slot_batch(8, 3, 1, 10, 60_000, algos)
    b2 = _one_slot_batch(8, 3, 1, 10, 60_000, [4, 4, 0, 4, 1, 2, 3, 0])
    hits2 = np.asarray(b2.hits).copy()
    hits2[1] = -1  # a release inside the switch storm
    b2 = b2._replace(hits=hits2)
    _run_fold_vs_serial(_fresh_state(8),
                        [(b1, T0), (b2, T0 + 30_000)], "algo switch")


@pytest.mark.algorithms
def test_fold_concurrency_release_saturates():
    """Negative-hits releases past the held count: the device counter
    saturates at limit, over-release never mints free slots."""
    st = _fresh_state(8)
    acq = _one_slot_batch(8, 2, [3, 2, 0, 1, 0, 0, 0, 0], 5, 60_000, 4)
    rel = _one_slot_batch(8, 2, [-10, -1, 2, -4, 0, 0, 0, 0], 5, 60_000, 4)
    _run_fold_vs_serial(st, [(acq, T0), (rel, T0 + 1_000),
                             (acq, T0 + 2_000)], "conc release")


@pytest.mark.algorithms
def test_fold_gcra_burst_boundary():
    """GCRA at the exact emission interval: a full-burst drain followed by
    touches at TAT-aligned instants (now == stored TAT, one tick before,
    one after) — the closed-form fold and the replay must agree on the
    conforming/non-conforming edge."""
    L, D = 5, 5_000
    rate = D // L  # 1000ms emission interval
    st = _fresh_state(8)
    burst = _one_slot_batch(8, 1, [L, 1, 0, 1, 1, 1, 0, 0], L, D, 2)
    edge = _one_slot_batch(8, 1, 1, L, D, 2)
    windows = [(burst, T0),
               (edge, T0 + rate),          # exactly one interval later
               (edge, T0 + 2 * rate - 1),  # one tick before the boundary
               (edge, T0 + 2 * rate),      # exactly on it
               (edge, T0 + D)]             # TAT horizon
    _run_fold_vs_serial(st, windows, "gcra boundary")


@pytest.mark.algorithms
def test_fold_sliding_boundary_straddle():
    """Sliding-window touches straddling the bucket boundary: at window
    start + D - 1, exactly + D (previous weight hits zero), and + 2D (the
    previous bucket ages out entirely)."""
    L, D = 100, 10_000
    st = _fresh_state(8)
    fill = _one_slot_batch(8, 0, [60, 0, 30, 0, 0, 0, 0, 0], L, D, 3)
    touch = _one_slot_batch(8, 0, 1, L, D, 3)
    windows = [(fill, T0),
               (touch, T0 + D - 1),
               (touch, T0 + D),
               (touch, T0 + 2 * D),
               (fill, T0 + 3 * D + 1)]
    _run_fold_vs_serial(st, windows, "sliding straddle")


def _has_replay_shape(batch):
    """True iff some duplicate run carries distinct nonzero hits (an hstar
    violation) or an AGG lane inside a multi-lane run — the shapes
    fold_classify must reject to the per-segment replay."""
    slot = np.asarray(batch.slot)
    hits = np.asarray(batch.hits)
    valid = slot >= 0
    clean = np.where(valid, slot & ~kernel.AGG_SLOT_BIT, -1)
    agg = valid & ((slot & kernel.AGG_SLOT_BIT) != 0)
    for s in np.unique(clean[valid]):
        lanes = clean == s
        nz = hits[lanes][hits[lanes] > 0]
        if np.unique(nz).size > 1:
            return True
        if lanes.sum() > 1 and agg[lanes].any():
            return True
    return False


@pytest.mark.parametrize("seed", list(range(6)))
def test_drain_matches_host_oracle(seed):
    """Drain differential: packed wire in / packed wire out through the
    serving drain executable vs the host decode → int64 oracle → encode
    path, on the fold fuzz's adversarial windows (replay-fallback shapes
    guaranteed by construction)."""
    _run_drain_vs_host(np.random.default_rng(9000 + seed), seed, algo_hi=2)


@pytest.mark.algorithms
# two seeds in the per-commit run; the deeper sweep rides the slow lane
# (tier-1 wall budget on a 1-core box)
@pytest.mark.parametrize("seed", [0, 1,
                                  pytest.param(2, marks=pytest.mark.slow),
                                  pytest.param(3, marks=pytest.mark.slow)])
def test_drain_all_algorithms(seed):
    """The drain differential over the full algorithm range: GCRA /
    sliding / concurrency lanes (negative conc hits sign-extended through
    the 28-bit compact hits field) through the same packed wire."""
    _run_drain_vs_host(np.random.default_rng(10_000 + seed), seed,
                       algo_hi=5)


def _run_drain_vs_host(rng, seed, algo_hi):
    K, B, C = 4, 32, 24
    st0 = _adversarial_state(rng, C, T0, algo_hi)

    now = T0
    nows, packs = [], []
    saw_replay = False
    for _ in range(K):
        now += int(rng.integers(1, 300_000))
        bt = _adversarial_batch(rng, B, C, algo_hi)
        saw_replay |= _has_replay_shape(bt)
        nows.append(now)
        packs.append(np.asarray(kernel.encode_batch_host(
            np.asarray(bt.slot), np.asarray(bt.hits),
            np.asarray(bt.limit), np.asarray(bt.duration),
            np.asarray(bt.algo), np.asarray(bt.is_init))))
    assert saw_replay, "adversarial windows lost their replay shapes"
    packed = jnp.asarray(np.stack(packs))
    nows_j = jnp.asarray(np.asarray(nows, np.int64))

    # host path: wire decode -> int64 oracle -> wire encode, per window
    step = jax.jit(kernel.window_step)
    st_ref = st0
    ref_words, ref_limits, ref_mism = [], [], []
    for k in range(K):
        nj = jnp.int64(nows[k])
        bt = kernel.decode_batch(packed[k])
        st_ref, out = step(st_ref, bt, nj)
        ref_words.append(np.asarray(kernel.encode_output_word(out, nj)))
        ref_limits.append(np.asarray(out.limit))
        ref_mism.append(bool(np.any(
            (np.asarray(out.limit) != np.asarray(bt.limit))
            & (np.asarray(bt.slot) >= 0))))

    # the serving drain: one K-window stack, one shard, resident planes
    drain = engine_mod._compiled_pipeline_step(
        make_mesh(jax.devices("cpu")[:1]))
    planes = jax.tree.map(lambda a: a[None], kernel.arena_from_rows(st0))
    planes, words, limits, mism = drain(planes, packed[:, None], nows_j)
    np.testing.assert_array_equal(
        np.asarray(words)[:, 0], np.stack(ref_words),
        err_msg=f"seed {seed} drain response words")
    np.testing.assert_array_equal(
        np.asarray(limits)[:, 0], np.stack(ref_limits),
        err_msg=f"seed {seed} drain limit lanes")
    np.testing.assert_array_equal(
        np.asarray(mism)[:, 0], np.asarray(ref_mism),
        err_msg=f"seed {seed} drain mismatch flags")
    got = kernel.arena_to_rows(jax.tree.map(lambda a: a[0], planes))
    for name, a, b in zip(kernel.BucketState._fields, got, st_ref):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"seed {seed} drain state.{name}")
