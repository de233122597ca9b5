"""Ask the chip's compiler: AOT compiles of the engine executables for a
described (not attached) TPU v5e, at the widths chip_smoke.py serves.

Nothing runs — a pass says the TPU compiler accepts the program and it
fits the device, never that it is fast or right.  The default-path
executables must compile; each Pallas lowering the compiler still refuses
is a strict xfail carrying the compiler's own words, so the PR that makes
it lower has to remove the mark.

The topology is described inside a module-scoped fixture (only one
process may load the TPU library, so never at import), everything built
from it is built in the tests, and the persistent compile cache is off
around them (a described-device executable cannot be read back).
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.config import AnalyticsConfig, EngineConfig
from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.ops.kernel import (ArenaPlanes, BucketState,
                                       GlobalConfig, WindowBatch)
from gubernator_tpu.parallel.mesh import SHARD_AXIS

# chip_smoke.py's one-chip widths (BASELINE.json config 3) and K
SMOKE_C, SMOKE_B, SMOKE_K = 10_485_760, 16_384, 8
_D = EngineConfig()
DEF_C, DEF_B = _D.capacity_per_shard, _D.batch_per_shard
BG, G, KG = (_D.global_batch_per_shard, _D.global_capacity,
             _D.max_global_updates)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return Mesh(np.asarray(topo.devices[:1]), (SHARD_AXIS,))


@pytest.fixture(scope="module")
def four_chips(topo, no_persistent_cache):
    assert len(topo.devices) == 4
    return Mesh(np.asarray(topo.devices), (SHARD_AXIS,))


class _Shapes:
    """Abstract arguments of the engine executables on `mesh`, in the
    layouts core/engine.py's builders document."""

    def __init__(self, mesh: Mesh, C: int, B: int, K: int):
        self.S = S = mesh.devices.size
        self.C, self.B, self.K = C, B, K
        sh = NamedSharding(mesh, P(SHARD_AXIS))
        rep = NamedSharding(mesh, P())
        stk = NamedSharding(mesh, P(None, SHARD_AXIS))
        i32, i64 = jnp.int32, jnp.int64
        sds = jax.ShapeDtypeStruct

        def batch(shape, s):
            return WindowBatch(
                slot=sds(shape, i32, sharding=s),
                hits=sds(shape, i64, sharding=s),
                limit=sds(shape, i64, sharding=s),
                duration=sds(shape, i64, sharding=s),
                algo=sds(shape, i32, sharding=s),
                is_init=sds(shape, jnp.bool_, sharding=s))

        self.state = ArenaPlanes(
            *[sds((S, C), jnp.uint32, sharding=sh)] * 10,
            sds((S, C), i32, sharding=sh))
        self.gstate = BucketState(*[sds((G,), i64, sharding=rep)] * 5,
                                  sds((G,), i32, sharding=rep))
        self.gcfg = GlobalConfig(sds((G,), i64, sharding=rep),
                                 sds((G,), i64, sharding=rep),
                                 sds((G,), i32, sharding=rep))
        self.batch = batch((S, B), sh)
        self.batches = batch((K, S, B), stk)
        self.packed1 = sds((S, B, 2), i64, sharding=sh)
        self.packed = sds((K, S, B, 2), i64, sharding=stk)
        self.words = sds((K, S, B), i64, sharding=stk)
        self.tenants = sds((K, S, B), i32, sharding=stk)
        self.gbatch = batch((S, BG), sh)
        self.gbatches = batch((K, S, BG), stk)
        self.gacc = sds((S, BG), i64, sharding=sh)
        self.gaccs = sds((K, S, BG), i64, sharding=stk)
        kg = lambda dt: sds((KG,), dt, sharding=rep)
        self.upd = (kg(i32), kg(i64), kg(i64), kg(i32), kg(i32))
        self.ups = (kg(i32),) + (kg(i64),) * 5 + (kg(i32),)
        self.now = sds((), i64, sharding=rep)
        self.nows = sds((K,), i64, sharding=rep)

    def sketch(self, conf):
        return jax.ShapeDtypeStruct(
            (self.S, conf.sketch_depth, conf.sketch_width), jnp.int64,
            sharding=self.state.algo.sharding)


def _compile(fn, *args):
    if not hasattr(fn, "lower"):
        fn = fn.__wrapped__  # past engine._recursion_guarded
    # Room for a deep-but-finite Mosaic lowering, yet shallow enough that a
    # lowering that recurses without end fails in seconds (the engine's own
    # 20000-frame guard takes minutes to get there).  The error is re-raised
    # without its thousands of frames: pytest prunes a recursive traceback
    # in time quadratic in its depth.
    from gubernator_tpu.ops.pallas_kernel import mosaic_recursion_guard
    try:
        with mosaic_recursion_guard(4000):
            return fn.lower(*args).compile()
    except RecursionError as e:
        msg = str(e)
    raise RecursionError(msg)


def _fits(compiled, budget_bytes: int = 16 * 1024 ** 3) -> None:
    """The program alone fits one v5e chip's 16 GB."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < budget_bytes, m


def _arena_stays_in_place(compiled, C: int, staged_ok: bool) -> None:
    """The optimised program converts and copies nothing of the arena's
    size: the resident planes are uint32 (ops/kernel.py ArenaPlanes), so
    no X64SplitLow / X64SplitHigh / X64Combine custom-call has an operand
    of C elements (with int64 planes there were fifteen, 4.7 ms of every
    drain at C = 10,485,760: ledger, PR 32), every plane parameter is
    aliased to its output (a plane that is not donated is copied, 42 MB a
    drain), and the only instructions that produce an array of C elements
    are the commit scatters.

    `staged_ok`: from 4,096 lanes up, and for small arenas, the compiler's
    memory-space assignment prefetches some planes into S(1) around their
    scatter (slice-start / ConcatBitcast in, copy-start out: asynchronous
    moves the compiler chooses, and a 16,384-lane drain compiled without
    them measured 1 ms SLOWER: PERF.md section 6, PR 33).  They are its
    to choose; a conversion or a synchronous copy is not."""
    text = compiled.as_text()
    head = text.split("\n", 1)[0]
    pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)
    assert (sum(o == i for o, i in pairs)
            >= len(ArenaPlanes._fields)), head[:400]

    sized = re.compile(r"\[(?:1,)?%d\]" % C)
    instr = re.compile(r"^(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(")
    no_data = {"parameter", "bitcast", "get-tuple-element", "tuple", "while"}
    prefetch = {"slice-start", "slice-done", "copy-start", "copy-done"}
    fused = False       # inside a fusion's own computation: judged by its
    for line in text.splitlines():      # caller's `fusion(` line instead
        line = line.strip()
        if line.endswith("{") and " = " not in line:
            fused = line.startswith("%fused_computation")
            continue
        m = instr.match(line)
        if fused or m is None:
            continue
        shape, op = m.groups()
        if "X64" in line:
            assert not sized.search(line), (
                f"arena-sized x64 conversion: {line[:240]}")
        if not sized.search(shape) or op in no_data:
            continue    # gathers read a plane and produce B lanes
        if op == "fusion":
            assert "/scatter" in line, (
                f"arena-sized fusion that is no commit scatter: {line[:300]}")
            continue
        assert staged_ok and (op in prefetch or '"ConcatBitcast"' in line), (
            f"arena-sized instruction that is neither a commit scatter nor "
            f"a prefetch: {line[:300]}")


# ----------------------------------------------------- default path: compiles


@pytest.mark.parametrize("C,B,K", [(SMOKE_C, SMOKE_B, SMOKE_K),
                                   (DEF_C, DEF_B, SMOKE_K),
                                   (SMOKE_C, SMOKE_B // 16, 1),
                                   (SMOKE_C, SMOKE_B // 4, 1)],
                         ids=["smoke-10M", "daemon-default",
                              "smoke-10M-1024-lanes", "smoke-10M-4096-lanes"])
def test_default_drain_compiles(one_chip, C, B, K):
    """The serving drain every default deployment runs: K compact windows,
    compact32-XLA body (GUBER_* lowering flags all at their defaults); and
    the single-window drain at the two narrower lane buckets, which the
    benchmark's edge cells run."""
    s = _Shapes(one_chip, C, B, K)
    fn = engine_mod._compiled_pipeline_step_impl(
        one_chip, False, True, False, True)
    c = _compile(fn, s.state, s.packed, s.nows)
    _fits(c)
    assert "tpu_custom_call" not in c.as_text()  # the XLA body, no Mosaic
    # at 1,024 lanes over 10M slots (the edge cells' drain) the compiler
    # stages nothing: gathers and commit scatters alone touch the planes
    _arena_stays_in_place(c, C, staged_ok=(C, B) != (SMOKE_C, SMOKE_B // 16))


def test_global_drain_compiles_one_chip(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global_impl(
        one_chip, False, True, False, True)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                   s.gacc, s.upd, s.nows))


def test_global_drain_compiles_four_chips(four_chips):
    """The lockstep mesh drain: four arena shards, ONE all-reduce (the
    GLOBAL hit-delta psum) in the whole K-window program."""
    s = _Shapes(four_chips, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global_impl(
        four_chips, False, True, False, True)
    c = _compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                 s.gacc, s.upd, s.nows)
    _fits(c)
    text = c.as_text()
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1
    _arena_stays_in_place(c, DEF_C, staged_ok=True)


def test_global_drain_with_analytics_compiles(one_chip):
    conf = AnalyticsConfig()
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global_impl(
        one_chip, False, True, False, True,
        (conf.sketch_depth, conf.sketch_width, conf.tenant_slots,
         conf.topk, conf.over_weight))
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                   s.gacc, s.upd, s.nows, s.sketch(conf), s.tenants,
                   s.now))


def test_legacy_step_compiles(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, 1)
    fn = engine_mod._compiled_step_impl(one_chip, False)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.batch, s.gbatch,
                   s.gacc, s.upd, s.ups, s.now))


def test_compact_step_compiles(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, 1)
    fn = engine_mod._compiled_step_compact_impl(one_chip, False, True, False)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed1, s.gbatch,
                   s.gacc, s.upd, s.ups, s.now))


@pytest.mark.parametrize("with_global", [True, False])
def test_multi_step_compiles(one_chip, with_global):
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_multi_step_impl(one_chip, False, with_global)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.batches, s.gbatches,
                   s.gaccs, s.upd, s.ups, s.nows))


def test_analytics_reduce_compiles(one_chip):
    conf = AnalyticsConfig()
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_analytics_reduce(
        one_chip, conf.sketch_depth, conf.sketch_width, conf.tenant_slots,
        conf.topk, conf.over_weight)
    _fits(_compile(fn, s.sketch(conf), s.state.expire_lo, s.state.expire_hi,
                   s.packed, s.words, s.tenants, s.now, s.now))


# ------------------------------------------- opt-in Pallas lowerings: refused
#
# Each case is the drain executable one GUBER_PALLAS* flag selects, at the
# daemon's default widths.  The reasons are the chip compiler's own words
# (PR 24).  A strict xfail: the PR that makes one lower must delete its mark
# AND the matching entry of engine._MOSAIC_REFUSED, which is what makes the
# engine raise at construction when the flag is set on a TPU mesh.

_PALLAS_CASES = {
    # name: ((pallas, c32xla, fused, staged), raises, the compiler's words)
    "GUBER_PALLAS": (
        (True, True, False, True), RecursionError,
        "RecursionError: maximum recursion depth exceeded (Mosaic's lowering "
        "of _window_math_kernel recurses without end on a 64-bit to 32-bit "
        "convert_element_type: python-int operands of jnp.clip/jnp.where "
        "trace as weak int64 under x64)"),
    "GUBER_PALLAS_FUSED+STAGED=0": (
        (False, True, True, False), NotImplementedError,
        "NotImplementedError: Only 2D gather is supported (the fused body's "
        "bitonic sort gathers 1-D (B,) lanes with jnp.take)"),
    "GUBER_PALLAS_FUSED": (
        (False, True, True, True), ValueError,
        "ValueError: The Pallas TPU lowering currently requires that the last "
        "two dimensions of your block shape are divisible by 8 and 128 "
        "respectively, or be equal to the respective dimensions of the "
        "overall array. Block spec for args[0] in pallas_call drain_kernel "
        "has block shape (1, 2), array shape (8, 2)"),
}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.xfail(strict=True, raises=exc,
                                            reason=why))
    for n, (_flags, exc, why) in _PALLAS_CASES.items()])
def test_pallas_drain_lowering(one_chip, name):
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_impl(one_chip,
                                                 *_PALLAS_CASES[name][0])
    c = _compile(fn, s.state, s.packed, s.nows)
    assert "tpu_custom_call" in c.as_text()


def test_refused_flags_raise_at_engine_construction(one_chip, monkeypatch):
    """A refused lowering's flag on a TPU mesh is an error before any
    executable is built — never a silent XLA body."""
    for flag in engine_mod._MOSAIC_REFUSED:
        monkeypatch.setenv(flag, "1")
        with pytest.raises(RuntimeError, match=flag):
            engine_mod._check_lowering_flags(one_chip)
        monkeypatch.delenv(flag)
    engine_mod._check_lowering_flags(one_chip)  # defaults: accepted
