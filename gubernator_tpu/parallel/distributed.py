"""Multi-host mesh mode: one SPMD device mesh spanning daemon processes.

The reference scales out as N independent nodes exchanging gRPC
(peers.go:130-172).  This framework supports that same topology ("node
mode": every daemon owns its chips and its slice of the keyspace, peer plane
over gRPC — see net/peers.py), and additionally a TPU-native topology this
module enables:

  MESH MODE — all hosts join one `jax.sharding.Mesh` via
  `jax.distributed.initialize`; the bucket arena is one global array sharded
  over every chip of every host; each host packs request lanes for its local
  shards and all hosts dispatch the SAME compiled window step in lockstep.
  Cross-shard traffic inside the mesh needs no RPCs at all, and the GLOBAL
  reconciliation psum rides ICI within a slice / DCN across slices — the
  collective replaces the reference's async-hits + broadcast gRPC dance
  entirely (global.go:72-232).

Lockstep is a hard requirement: every process must issue the same sequence
of engine dispatches (the collectives inside the step otherwise deadlock).
The serving layer guarantees this by flushing windows on a fixed clock
(tick even when empty) rather than on demand.

Env surface (daemon wiring):
  GUBER_MESH_COORDINATOR   host:port of process 0 (enables mesh mode)
  GUBER_MESH_NUM_PROCESSES total process count
  GUBER_MESH_PROCESS_ID    this process's rank
"""

from __future__ import annotations

import os
import time
from functools import lru_cache as _functools_lru_cache
from typing import Optional

import jax
import numpy as np

from gubernator_tpu.parallel.mesh import (SHARD_AXIS, make_mesh, shard_spec,
                                          stacked_spec)


def initialize_from_env() -> bool:
    """Join the distributed runtime if GUBER_MESH_COORDINATOR is set.

    Returns True when mesh mode is active.  Must run before any other JAX
    call in the process (jax.distributed.initialize constraint)."""
    coord = os.environ.get("GUBER_MESH_COORDINATOR", "")
    if not coord:
        return False
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["GUBER_MESH_NUM_PROCESSES"]),
        process_id=int(os.environ["GUBER_MESH_PROCESS_ID"]),
    )
    return True


def global_mesh():
    """The mesh over every device of every process (shard axis)."""
    return make_mesh(jax.devices())


@_functools_lru_cache(maxsize=None)
def shard_sharding(mesh):
    """NamedSharding for [S, ...] per-shard arrays (cached per mesh).

    Staging rebuilds the same placement for every dispatch; meshes are
    long-lived and hashable, so cache the NamedSharding objects instead of
    re-deriving them on the hot path."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, shard_spec())


@_functools_lru_cache(maxsize=None)
def stacked_sharding(mesh):
    """NamedSharding for [K, S, ...] drain stacks (cached per mesh)."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, stacked_spec())


def local_device_indices(mesh) -> list[int]:
    """Flat mesh-device indices owned by this process (its shard ids)."""
    devs = mesh.devices.reshape(-1)
    return [i for i, d in enumerate(devs)
            if d.process_index == jax.process_index()]


def owning_process(shard: int, mesh) -> int:
    """Which process owns a global shard index (for host-side routing)."""
    return int(mesh.devices.reshape(-1)[shard].process_index)


def agree_max(mesh, value: int) -> int:
    """Every process learns the largest of the processes' `value`s via one
    tiny collective.

    What the lockstep clock agrees through: a window's `now` is a replicated
    step input that must be bit-identical on every process
    (engine._resolve_now), so each host proposes what its own wall clock
    says and all take the latest.  With one process there is nobody to
    agree with and nothing is dispatched."""
    if jax.process_count() == 1:
        return int(value)
    from jax.sharding import NamedSharding, PartitionSpec as P

    local = np.full((len(local_device_indices(mesh)),), value, np.int64)
    sh = NamedSharding(mesh, P(SHARD_AXIS))
    gv = jax.make_array_from_process_local_data(sh, local,
                                                (mesh.devices.size,))
    out = _agree_max_fn(mesh)(gv)
    return int(np.asarray(out.addressable_shards[0].data)[0])


@_functools_lru_cache(maxsize=None)
def _agree_max_fn(mesh):
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def fn(v):
        return lax.pmax(v[0], SHARD_AXIS)[None]

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(SHARD_AXIS),
                                 out_specs=P(SHARD_AXIS)))


def _wall_ms() -> float:
    return time.time() * 1000.0


class LockstepClock:
    """The mesh's tick clock: wall-clock deadlines, one timestamp a tick,
    the same on every process by rule.

    Tick `i` is due at epoch + i * interval and tells that time, in ms, as
    its window's `now`.  The epoch is taken at the first tick (`start`),
    not when the clock is built: start-up, warm-up and an arena fill lie
    between the two.  A host sleeps to a tick's deadline (`until_next`)
    and never runs ahead of it; when it wakes late, whole periods are
    skipped, so that the tick it runs is the newest one whose deadline has
    passed and its timestamp lies within one interval of the wall clock.
    Timestamps never decrease.

    Across processes: `agree` (agree_max over the mesh) makes the epoch and
    each tick's index the largest any host proposes, so every host derives
    the identical timestamp from the agreed epoch and the agreed count of
    periods; with one process nothing is agreed and nothing dispatched.

    `epoch_ms` given (tests, chip_smoke.py): the timeline starts there
    instead of at the wall clock's reading, and runs at the wall clock's
    pace from the first tick."""

    def __init__(self, epoch_ms: Optional[int] = None,
                 interval_s: float = 0.0005, *, wall_ms=_wall_ms,
                 agree=None):
        self.interval_ms = interval_s * 1000.0
        self._wall_ms = wall_ms
        self._agree = agree
        # a given epoch is usable before the first tick (warm-up reads it)
        self.epoch_ms = epoch_ms
        self._offset_ms = None   # timeline - wall clock; set by start()
        self._t0 = None          # the epoch on the wall clock; by start()
        self.tick = 0            # index of the next tick to run
        self.skipped = 0         # whole periods skipped so far
        self.lag_s = 0.0         # the last tick's start behind its deadline

    @property
    def agrees(self) -> bool:
        """Does a tick's index go through a collective (several hosts)?"""
        return self._agree is not None

    def now_ms(self) -> int:
        """The timeline's reading now, agreed across processes: for work
        that needs one timestamp outside a tick (warm-up, a key preload)."""
        if self._offset_ms is None and self.epoch_ms is not None:
            t = self.epoch_ms        # a given epoch, before its first tick
        else:
            t = int(self._wall_ms() + (self._offset_ms or 0.0))
        return self._agree(t) if self._agree else t

    def start(self) -> None:
        """The first tick is about to run: fix the epoch (once)."""
        if self._offset_ms is not None:
            return
        wall = self._wall_ms()
        if self.epoch_ms is None:
            self.epoch_ms = (self._agree(int(wall)) if self._agree
                             else int(wall))
            self._offset_ms = 0.0
        else:
            self._offset_ms = self.epoch_ms - wall
        self._t0 = self.epoch_ms - self._offset_ms

    def until_next(self) -> float:
        """Seconds until the next tick's deadline (<= 0: it has passed)."""
        due = self._t0 + self.tick * self.interval_ms
        return (due - self._wall_ms()) / 1000.0

    def next_now(self) -> int:
        """Run the newest tick whose deadline has passed: its timestamp.
        Called at or after `until_next() <= 0`; a call before the deadline
        still tells the tick's own time (never the wall clock's)."""
        wall = self._wall_ms()
        due = self._t0 + self.tick * self.interval_ms
        self.lag_s = max(0.0, (wall - due) / 1000.0)
        idx = max(self.tick, int((wall - self._t0) // self.interval_ms))
        if self._agree is not None:
            idx = self._agree(idx)
        self.skipped += idx - self.tick
        self.tick = idx + 1
        # from the exact float interval, tick by tick, so that
        # sub-millisecond ticks do not drift; rounded down: never ahead
        return self.epoch_ms + int(idx * self.interval_ms)
