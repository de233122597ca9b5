"""The serving window body (ops/kernel.window_step_compact32: the window
math in int32, times rebased to the window's `now`) pinned bit-exact against
the int64 oracle (kernel.window_step) on compact-range workloads."""

import numpy as np
import pytest

import gubernator_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from gubernator_tpu.ops import kernel

T0 = 1_700_000_000_000

_step_int64 = jax.jit(kernel.window_step)
_step_c32 = jax.jit(kernel.window_step_compact32)


def _random_window(rng, B, C, hot=6):
    """Windows mixing pads, hot duplicate keys, uniform and irregular
    segments (mixed hits incl. zero-reads, config changes, mid-window
    is_init recycling)."""
    slot = rng.integers(0, hot, B).astype(np.int32)  # heavy duplicates
    spread = rng.random(B) < 0.3  # some lanes spread over the whole arena
    slot[spread] = rng.integers(0, C, int(spread.sum())).astype(np.int32)
    pad = rng.random(B) < 0.15
    slot[pad] = kernel.PAD_SLOT
    return kernel.WindowBatch(
        slot=jnp.asarray(slot),
        hits=jnp.asarray(rng.choice([0, 0, 1, 1, 2, 7], B), jnp.int64),
        limit=jnp.asarray(rng.choice([5, 5, 5, 9], B), jnp.int64),
        duration=jnp.asarray(rng.choice([1_000, 1_000, 50], B), jnp.int64),
        algo=jnp.asarray(rng.integers(0, 2, B), jnp.int32),
        is_init=jnp.asarray(rng.random(B) < 0.05),
    )


# (rng seed, share of lanes at the limit/duration caps, share at the hits
# cap).  "edges-*" put MOST lanes at COMPACT_MAX_* - 1, so nearly every
# register the body rebases sits at the edge of the int32 range.
_STREAMS = {
    **{f"capped-{s}": (180 + s, 0.2, 0.1) for s in range(2)},
    **{f"chained-{s}": (90 + s, 0.2, 0.1) for s in range(4)},
    **{f"edges-{s}": (270 + s, 0.8, 0.5) for s in range(4)},
}


@pytest.mark.parametrize("stream", sorted(_STREAMS))
def test_compact32_matches_int64(stream):
    """Chained windows (state carries, the clock crosses expiries), hot
    duplicates, recycling inits, zero-reads and near-cap configs: every
    response and every state column equals the int64 kernel's."""
    seed, cap_share, hits_share = _STREAMS[stream]
    rng = np.random.default_rng(seed)
    B, C = 128, 32
    state_x = kernel.BucketState.zeros(C)
    state_c = kernel.BucketState.zeros(C)
    big_l = int(kernel.COMPACT_MAX_LIMIT - 1)
    big_d = int(kernel.COMPACT_MAX_DURATION - 1)
    big_h = int(kernel.COMPACT_MAX_HITS - 1)
    now = T0
    for w in range(6):
        # MONOTONIC clock: i32 exactness needs |stored time - now| <=
        # max duration, which a backward-jumping clock can break by the
        # jump size (the clip then bounds the error to the jump) — the
        # engine's serving clocks are monotonic by construction
        now += int(rng.integers(1, 400))
        batch = _random_window(rng, B, C)
        capped = rng.random(B) < cap_share
        batch = kernel.WindowBatch(
            slot=batch.slot,
            hits=jnp.where(jnp.asarray(rng.random(B) < hits_share),
                           jnp.int64(big_h), batch.hits),
            limit=jnp.where(jnp.asarray(capped), jnp.int64(big_l),
                            batch.limit),
            duration=jnp.where(jnp.asarray(capped), jnp.int64(big_d),
                               batch.duration),
            algo=batch.algo,
            is_init=batch.is_init,
        )
        state_x, out_x = _step_int64(state_x, batch, now)
        state_c, out_c = _step_c32(state_c, batch, now)
        valid = np.asarray(batch.slot) >= 0
        for name, x, c in zip(kernel.WindowOutput._fields, out_x, out_c):
            np.testing.assert_array_equal(
                np.asarray(x)[valid], np.asarray(c)[valid],
                err_msg=f"window {w} out.{name}")
        for name, x, c in zip(kernel.BucketState._fields, state_x, state_c):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(c),
                err_msg=f"window {w} state.{name}")


def test_engine_compact_serving_uses_compact32():
    """The engine's compact serving path answers as a second engine on a
    mesh of its own does."""
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices("cpu")[5:6])
    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=64,
                          batch_per_shard=16, global_capacity=16,
                          global_batch_per_shard=8, max_global_updates=8)
    plain = RateLimitEngine(capacity_per_shard=64, batch_per_shard=16,
                            global_capacity=16, global_batch_per_shard=8,
                            max_global_updates=8)
    assert eng._compact_enabled
    for i in range(5):
        reqs = [RateLimitReq(name="c32", unique_key=f"k{j % 3}", hits=1,
                             limit=4, duration=60_000) for j in range(6)]
        a = eng.process(reqs, now=T0 + i)
        b = plain.process(reqs, now=T0 + i)
        assert [(int(x.status), x.remaining, x.reset_time) for x in a] == \
            [(int(y.status), y.remaining, y.reset_time) for y in b], i


# ------------------------------------------------ one body, no lowering flag

# spelled in two parts so that a grep for a flag's name finds readers only
_LOWERING_FLAGS = tuple("GUBER_" + name for name in (
    "PALLAS", "PALLAS_FUSED", "PALLAS_STAGED", "COMPACT32_XLA"))


def _package_sources():
    import pathlib
    root = pathlib.Path(gubernator_tpu.__file__).parent
    return {p: p.read_text() for p in root.rglob("*.py")}


def test_no_module_imports_pallas():
    """The package holds no Pallas kernel: the window body is XLA, and a
    Pallas drain, if one is ever wanted, is written new over ArenaPlanes."""
    hits = [str(p) for p, src in _package_sources().items()
            if "jax.experimental.pallas" in src
            or "jax.experimental import pallas" in src]
    assert not hits, hits


def test_no_source_reads_a_lowering_flag():
    hits = [(str(p), f) for p, src in _package_sources().items()
            for f in _LOWERING_FLAGS if f in src]
    assert not hits, hits


@pytest.mark.parametrize("builder,args", [
    ("_compiled_step", ()),
    ("_compiled_step_compact", ()),
    ("_compiled_pipeline_step", ()),
    ("_compiled_pipeline_step_global", ()),
    ("_compiled_multi_step", (False,)),
])
def test_builder_keyed_on_mesh_alone(monkeypatch, builder, args):
    """Each drain builder hands back the same executable for the same mesh
    whatever the old lowering variables are set to: nothing in the
    environment selects a window body."""
    from gubernator_tpu.core import engine as engine_mod
    from gubernator_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices("cpu")[5:6])
    build = getattr(engine_mod, builder)
    for flag in _LOWERING_FLAGS:
        monkeypatch.delenv(flag, raising=False)
    before = build(mesh, *args)
    assert hasattr(before, "lower")  # the jitted executable, no wrapper
    for value in ("1", "0"):
        for flag in _LOWERING_FLAGS:
            monkeypatch.setenv(flag, value)
        assert build(mesh, *args) is before, (builder, value)
