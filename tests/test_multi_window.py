"""step_windows (K windows per dispatch) must equal K sequential step() calls.

The scan-of-windows dispatch is the high-load throughput path; this pins its
semantics to the single-window step on an 8-device CPU mesh, including GLOBAL
psum traffic and mid-stack expiry.
"""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.ops import kernel

T0 = 1_700_000_000_000
S, C, B = 8, 64, 16
BG, KG = 8, 8
K = 5


def make_engine():
    return RateLimitEngine(
        capacity_per_shard=C,
        batch_per_shard=B,
        global_capacity=32,
        global_batch_per_shard=BG,
        max_global_updates=KG,
        use_native=False,
    )


def random_windows(rng):
    """K windows of synthetic per-shard lanes: mixed algos, duplicate slots,
    some padded lanes, plus GLOBAL lanes with psum contributions."""
    batches, gbatches, gaccs = [], [], []
    for _ in range(K):
        slot = rng.integers(0, C, size=(S, B)).astype(np.int32)
        pad = rng.random((S, B)) < 0.2
        slot[pad] = kernel.PAD_SLOT
        batches.append(kernel.WindowBatch(
            slot=slot,
            hits=rng.integers(0, 3, size=(S, B)).astype(np.int64),
            limit=rng.integers(1, 8, size=(S, B)).astype(np.int64),
            duration=np.full((S, B), 10_000, np.int64),
            algo=rng.integers(0, 2, size=(S, B)).astype(np.int32),
            is_init=np.zeros((S, B), bool),
        ))
        gslot = rng.integers(0, 16, size=(S, BG)).astype(np.int32)
        gpad = rng.random((S, BG)) < 0.5
        gslot[gpad] = kernel.PAD_SLOT
        ghits = rng.integers(0, 2, size=(S, BG)).astype(np.int64)
        gbatches.append(kernel.WindowBatch(
            slot=gslot,
            hits=ghits,
            limit=np.full((S, BG), 20, np.int64),
            duration=np.full((S, BG), 10_000, np.int64),
            algo=np.zeros((S, BG), np.int32),
            is_init=np.zeros((S, BG), bool),
        ))
        gaccs.append(np.where(gslot >= 0, ghits, 0).astype(np.int64))
    return batches, gbatches, gaccs


@pytest.mark.parametrize("seed", [0, 1])
def test_stacked_equals_sequential(seed):
    rng = np.random.default_rng(seed)
    batches, gbatches, gaccs = random_windows(rng)
    nows = [T0 + 100 * i for i in range(K)]

    # engine A: K sequential single-window dispatches
    ea = make_engine()
    gbatch0, gacc0, upd, ups = ea.empty_control()
    # exercise the control plane identically on both paths: configure two
    # GLOBAL slots before window 0
    upd[0][:2] = [3, 7]
    upd[1][:2] = 20
    upd[2][:2] = 10_000
    upd[4][:2] = [3, 7]
    seq_fused = []
    for i in range(K):
        u = upd if i == 0 else (np.full_like(upd[0], ea.global_capacity),
                                upd[1] * 0, upd[2] * 0, upd[3] * 0,
                                np.full_like(upd[4], ea.global_capacity))
        ea.state, fused, ea.gstate, ea.gcfg = ea._step_fn(
            ea.state, ea.gstate, ea.gcfg, batches[i], gbatches[i], gaccs[i],
            u, ups, jnp.int64(nows[i]),
        )
        seq_fused.append(jax.device_get(fused))

    # engine B: one stacked dispatch
    eb = make_engine()
    stack = lambda ws: type(ws[0])(*[
        np.stack([getattr(w, f) for w in ws]) for f in ws[0]._fields])
    fused = eb.step_windows(
        stack(batches), stack(gbatches), np.stack(gaccs),
        upd, ups, np.asarray(nows, np.int64),
    )
    fused = jax.device_get(fused)

    for i in range(K):
        outs, gouts = kernel.split_outputs(fused[i], B)
        seq_out, seq_gout = kernel.split_outputs(seq_fused[i], B)
        for f in kernel.WindowOutput._fields:
            np.testing.assert_array_equal(
                getattr(outs, f), getattr(seq_out, f),
                err_msg=f"window {i} field {f}")
            np.testing.assert_array_equal(
                getattr(gouts, f), getattr(seq_gout, f),
                err_msg=f"window {i} GLOBAL field {f}")

    # final arena state identical
    for f in kernel.ArenaPlanes._fields:
        np.testing.assert_array_equal(
            jax.device_get(getattr(ea.state, f)),
            jax.device_get(getattr(eb.state, f)), err_msg=f"state.{f}")
    for f in kernel.BucketState._fields:
        np.testing.assert_array_equal(
            jax.device_get(getattr(ea.gstate, f)),
            jax.device_get(getattr(eb.gstate, f)), err_msg=f"gstate.{f}")
