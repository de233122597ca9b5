"""Ask the chip's compiler: AOT compiles of the engine executables for a
described (not attached) TPU v5e, at the widths chip_smoke.py serves.

Nothing runs — a pass says the TPU compiler accepts the program and it
fits the device, never that it is fast or right.

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
    return fn.lower(*args).compile()


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


# ------------------------------------------------- the executables: compile


@pytest.mark.parametrize("C,B,K", [(SMOKE_C, SMOKE_B, SMOKE_K),
                                   (DEF_C, DEF_B, SMOKE_K),
                                   (SMOKE_C, SMOKE_B // 16, 1),
                                   (SMOKE_C, SMOKE_B // 4, 1)],
                         ids=["smoke-10M", "daemon-default",
                              "smoke-10M-1024-lanes", "smoke-10M-4096-lanes"])
def test_default_drain_compiles(one_chip, C, B, K):
    """The serving drain every deployment runs: K compact windows of the
    compact32 body; and the single-window drain at the two narrower lane
    buckets, which the benchmark's edge cells run."""
    s = _Shapes(one_chip, C, B, K)
    fn = engine_mod._compiled_pipeline_step(one_chip)
    c = _compile(fn, s.state, s.packed, s.nows)
    _fits(c)
    assert "tpu_custom_call" not in c.as_text()  # the XLA body, no Mosaic
    # at 1,024 lanes over 10M slots (the edge cells' drain) the compiler
    # stages nothing: gathers and commit scatters alone touch the planes
    _arena_stays_in_place(c, C, staged_ok=(C, B) != (SMOKE_C, SMOKE_B // 16))


def test_global_drain_compiles_one_chip(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global(one_chip)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                   s.gacc, s.upd, s.nows))


def test_global_drain_compiles_four_chips(four_chips):
    """The lockstep mesh drain: four arena shards, ONE all-reduce (the
    GLOBAL hit-delta psum) in the whole K-window program."""
    s = _Shapes(four_chips, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global(four_chips)
    c = _compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                 s.gacc, s.upd, s.nows)
    _fits(c)
    text = c.as_text()
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1
    _arena_stays_in_place(c, DEF_C, staged_ok=True)


def test_global_drain_with_analytics_compiles(one_chip):
    conf = AnalyticsConfig()
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_pipeline_step_global(
        one_chip, (conf.sketch_depth, conf.sketch_width, conf.tenant_slots,
                   conf.topk, conf.over_weight))
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed, s.gbatch,
                   s.gacc, s.upd, s.nows, s.sketch(conf), s.tenants,
                   s.now))


def test_legacy_step_compiles(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, 1)
    fn = engine_mod._compiled_step(one_chip)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.batch, s.gbatch,
                   s.gacc, s.upd, s.ups, s.now))


def test_compact_step_compiles(one_chip):
    s = _Shapes(one_chip, DEF_C, DEF_B, 1)
    fn = engine_mod._compiled_step_compact(one_chip)
    _fits(_compile(fn, s.state, s.gstate, s.gcfg, s.packed1, s.gbatch,
                   s.gacc, s.upd, s.ups, s.now))


@pytest.mark.parametrize("with_global", [True, False])
def test_multi_step_compiles(one_chip, with_global):
    s = _Shapes(one_chip, DEF_C, DEF_B, SMOKE_K)
    fn = engine_mod._compiled_multi_step(one_chip, with_global)
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
