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
from functools import lru_cache as _functools_lru_cache

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


def agree_epoch_ms(mesh) -> int:
    """Every process learns process 0's wall clock via one tiny collective.

    The lockstep window clock derives each tick's timestamp from this agreed
    epoch, because the window `now` is a replicated step input that must be
    bit-identical on every process (engine._resolve_now)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.api.types import millisecond_now

    local = np.full(
        (len(local_device_indices(mesh)),),
        millisecond_now() if jax.process_index() == 0 else 0,
        np.int64,
    )
    sh = NamedSharding(mesh, P(SHARD_AXIS))
    gv = jax.make_array_from_process_local_data(sh, local,
                                                (mesh.devices.size,))

    def fn(v):
        first = lax.axis_index(SHARD_AXIS) == 0
        return lax.psum(jnp.where(first, v[0], jnp.int64(0)), SHARD_AXIS)[None]

    out = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(SHARD_AXIS),
                                out_specs=P(SHARD_AXIS)))(gv)
    return int(np.asarray(out.addressable_shards[0].data)[0])


class LockstepClock:
    """Deterministic per-tick timestamps shared by every mesh process.

    Tick i's window timestamp is epoch + i*interval — identical everywhere
    by construction.  Hosts pace ticks with their local clocks; the
    collectives inside each window act as the rendezvous, so skew shows up
    as backpressure, never as divergent state."""

    def __init__(self, epoch_ms: int, interval_s: float):
        self.epoch_ms = epoch_ms
        self.interval_s = interval_s
        self.tick = 0

    def next_now(self) -> int:
        # rounded per tick from the exact float interval, so logical time
        # never drifts from wall time even for sub-millisecond ticks
        now = self.epoch_ms + round(self.tick * self.interval_s * 1000)
        self.tick += 1
        return now
