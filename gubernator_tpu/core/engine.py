"""The rate-limit engine: host routing + one sharded device step per window.

This is the TPU-native collapse of three reference components:

  * the owner's batch drain (gubernator.go:210-227) → `window_step` per shard;
  * the consistent-hash peer routing (hash.go:80-96, gubernator.go:114) →
    `crc32(key) % num_shards` choosing the mesh-axis shard, resolved on the
    host while packing the window;
  * the GLOBAL async-hits + broadcast dance (global.go:72-232) → one
    `lax.psum` of per-slot hit deltas over the mesh axis, after which the
    authoritative state is already resident on every shard.

One call to `step()` plays the role of one 500µs batching window being shipped
to the owner (peers.go:176-207): the host packs per-shard request lanes into
dense arrays, the device applies them in a single jitted shard_map step, and
the responses demux back by lane index.

State layout: regular (sharded) keys live in ArenaPlanes arrays of shape
[S, C] partitioned over the "shard" mesh axis (each int64 column as a
(lo, hi) pair of uint32 planes: the executables' parameters hold no int64
of the arena's size); GLOBAL keys live in a replicated [G] int64
BucketState whose updates flow only through the psum so replicas stay
bit-exact.  Host-side key→slot tables (state/arena.py) are per shard.
"""

from __future__ import annotations

import logging
import zlib
from functools import lru_cache
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    millisecond_now,
)
from gubernator_tpu.ops import kernel
from gubernator_tpu.ops.kernel import (
    ArenaPlanes,
    BucketState,
    GlobalConfig,
    WindowBatch,
    WindowOutput,
)
from gubernator_tpu.parallel.mesh import (SHARD_AXIS, make_mesh, shard_spec,
                                          stacked_spec)
from gubernator_tpu.state.arena import SlotTable

log = logging.getLogger("gubernator.engine")


# Stacked-window buckets for the serving pipeline (core/pipeline.py): a
# drain dispatches its windows padded up to the nearest bucket, and
# warmup() pre-compiles exactly these shapes.  Single source of truth —
# a bucket missing here would compile mid-serving on the engine thread.
# Stacked-drain depth ladder: each bucket is one compiled executable (the
# scan body is K-independent, so deeper stacks amortize the per-dispatch
# cost linearly — the decisions-per-dispatch lever).  GUBER_PIPELINE_KMAX
# extends the ladder without code changes.
def _k_buckets_from_env():
    from gubernator_tpu.config import env_int
    kmax = env_int("GUBER_PIPELINE_KMAX", 8)
    # dense through 8, sparse above: dispatch cost is linear in the PADDED
    # bucket, so a k=3 drain padded to kb=4 wastes a third of its device
    # time — and k in [1, 8] is exactly where the overlapped pipeline's
    # occupancy gate lands under steady load.  Above 8 every bucket is one
    # warmup compile (seconds each for a TPU), so the extended ladder
    # keeps trading shape fit for boot time.
    buckets = list(range(1, min(kmax, 8) + 1))
    buckets += [b for b in (32, 128, 512) if buckets[-1] < b < kmax]
    if kmax > buckets[-1]:
        buckets.append(kmax)
    return tuple(buckets)


PIPELINE_K_BUCKETS = _k_buckets_from_env()


def shard_of(key: str, num_shards: int) -> int:
    """Map a hash key to its owning shard.

    Same hash family as the reference's ring (crc32 IEEE, hash.go:41) but a
    plain modulus: mesh shards are homogeneous and resize by re-sharding the
    arena, so ring semantics (minimal movement on membership change) buy
    nothing inside a mesh.
    """
    return zlib.crc32(key.encode("utf-8")) % num_shards


class _PackedWindow:
    """Host-side staging buffers for one window (numpy, reused per step)."""

    def __init__(self, S: int, B: int, Bg: int, Kg: int):
        self.slot = np.full((S, B), kernel.PAD_SLOT, dtype=np.int32)
        self.hits = np.zeros((S, B), dtype=np.int64)
        self.limit = np.zeros((S, B), dtype=np.int64)
        self.duration = np.zeros((S, B), dtype=np.int64)
        self.algo = np.zeros((S, B), dtype=np.int32)
        self.is_init = np.zeros((S, B), dtype=bool)
        self.gslot = np.full((S, Bg), kernel.PAD_SLOT, dtype=np.int32)
        self.ghits = np.zeros((S, Bg), dtype=np.int64)
        # hits contributed to the psum (0 for lanes whose hits reconcile via
        # the cross-host path instead — see RateLimitEngine.step(accumulate))
        self.ghits_acc = np.zeros((S, Bg), dtype=np.int64)
        self.glimit = np.zeros((S, Bg), dtype=np.int64)
        self.gduration = np.zeros((S, Bg), dtype=np.int64)
        self.galgo = np.zeros((S, Bg), dtype=np.int32)
        self.gis_init = np.zeros((S, Bg), dtype=bool)
        self.uslot = np.zeros((Kg,), dtype=np.int32)
        self.ulimit = np.zeros((Kg,), dtype=np.int64)
        self.uduration = np.zeros((Kg,), dtype=np.int64)
        self.ualgo = np.zeros((Kg,), dtype=np.int32)
        self.rslot = np.zeros((Kg,), dtype=np.int32)
        # owner-broadcast upsert lanes (cross-host GLOBAL replicas)
        self.pslot = np.zeros((Kg,), dtype=np.int32)
        self.plimit = np.zeros((Kg,), dtype=np.int64)
        self.pduration = np.zeros((Kg,), dtype=np.int64)
        self.premaining = np.zeros((Kg,), dtype=np.int64)
        self.ptstamp = np.zeros((Kg,), dtype=np.int64)
        self.pexpire = np.zeros((Kg,), dtype=np.int64)
        self.palgo = np.zeros((Kg,), dtype=np.int32)

    def reset(self, G: int):
        self.slot.fill(kernel.PAD_SLOT)
        self.gslot.fill(kernel.PAD_SLOT)
        self.ghits.fill(0)
        self.ghits_acc.fill(0)
        # pad config-update/reset lanes point one past the global arena → dropped
        self.uslot.fill(G)
        self.rslot.fill(G)
        self.pslot.fill(G)


class RateLimitEngine:
    """Dense sharded rate-limit state + one jitted device step per window.

    capacity_per_shard: slots per shard (reference default cache size is
        50k per node, cache/lru.go:50; ours defaults to 64k per shard).
    batch_per_shard: max regular-key request lanes per shard per window.
    global_capacity: slots in the replicated GLOBAL arena.
    global_batch_per_shard: max GLOBAL request lanes per shard per window.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        capacity_per_shard: int = 65536,
        batch_per_shard: int = 1024,
        global_capacity: int = 4096,
        global_batch_per_shard: int = 256,
        max_global_updates: int = 256,
        use_native: str = "auto",
        exact_keys: bool = False,
        replay_cap: "Optional[int]" = None,
        skip_global: bool = False,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.num_shards = int(np.prod(list(self.mesh.shape.values())))
        self.capacity_per_shard = capacity_per_shard
        self.batch_per_shard = batch_per_shard
        self.global_capacity = global_capacity
        self.global_batch_per_shard = global_batch_per_shard
        self.max_global_updates = max_global_updates
        # Config-level promise of zero GLOBAL traffic (EngineConfig
        # .skip_global / GUBER_SKIP_GLOBAL): stacked dispatches always
        # lower to the GLOBAL-skipping twin.  Being config-driven it is
        # identical on every mesh process, which is what makes the skip
        # legal under the mesh collective contract — unlike the
        # single-process per-stack inertness gate in step_windows.
        self._skip_global = bool(skip_global)

        # Mesh mode (parallel/distributed.py): the mesh spans processes;
        # this host stages lanes only for its contiguous run of shards and
        # reads back only its addressable output blocks.  All processes must
        # dispatch in lockstep.
        from gubernator_tpu.parallel.distributed import local_device_indices
        local_ids = local_device_indices(self.mesh)
        self.multiprocess = len(local_ids) != self.mesh.devices.size
        self.num_local_shards = len(local_ids)
        self.local_shard_offset = min(local_ids) if local_ids else 0
        if self.multiprocess:
            if local_ids != list(range(self.local_shard_offset,
                                       self.local_shard_offset + len(local_ids))):
                raise ValueError(
                    "mesh mode needs each process's devices contiguous on the "
                    "shard axis (default jax.devices() order satisfies this)")
            # dynamic GLOBAL registration and gRPC upserts would diverge the
            # replicated arena across processes — see step()/register_global_keys
            self._dynamic_global = False
        else:
            self._dynamic_global = True

        S, C, G = self.num_shards, capacity_per_shard, global_capacity
        shard_sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        repl_sharding = NamedSharding(self.mesh, P())
        self._shard_sharding = shard_sharding
        self._repl_sharding = repl_sharding

        def sharded_zeros(shape, dtype, sharding):
            # compiled constant: works when the sharding spans non-addressable
            # devices (multi-host), unlike device_put of a host array
            return jax.jit(lambda: jnp.zeros(shape, dtype),
                           out_shardings=sharding)()

        self.state = ArenaPlanes(
            *[sharded_zeros((S, C), jnp.uint32, shard_sharding)
              for _ in ArenaPlanes._fields[:-1]],
            algo=sharded_zeros((S, C), jnp.int32, shard_sharding))
        self.gstate = BucketState(
            limit=sharded_zeros((G,), jnp.int64, repl_sharding),
            duration=sharded_zeros((G,), jnp.int64, repl_sharding),
            remaining=sharded_zeros((G,), jnp.int64, repl_sharding),
            tstamp=sharded_zeros((G,), jnp.int64, repl_sharding),
            expire=sharded_zeros((G,), jnp.int64, repl_sharding),
            algo=sharded_zeros((G,), jnp.int32, repl_sharding),
        )
        self.gcfg = GlobalConfig(
            limit=sharded_zeros((G,), jnp.int64, repl_sharding),
            duration=sharded_zeros((G,), jnp.int64, repl_sharding),
            algo=sharded_zeros((G,), jnp.int32, repl_sharding),
        )

        # host routing state covers local shards only (all of them when
        # single-process)
        self.tables = [SlotTable(C) for _ in range(self.num_local_shards)]
        self.gtable = SlotTable(G)
        # dynamic mesh registrations applied (phase 1) but not yet activated
        # mesh-wide (phase 2) — not servable until then
        self._gpending: set = set()
        # step_stacked staging, cached per stack depth K
        self._stacked_bufs: dict = {}
        self._buf = _PackedWindow(self.num_local_shards, batch_per_shard,
                                  global_batch_per_shard, max_global_updates)
        self._step_fn = self._build_step()
        self._multi_fn = _compiled_multi_step(self.mesh)
        self._compact_fn = _compiled_step_compact(self.mesh)
        # Sound-saturation guard for the compact wire format: once any
        # out-of-range config enters the arena via the full path, stored
        # limits/durations may exceed what the compact response can carry, so
        # compact dispatch is disabled for the engine's lifetime (see the
        # format note in ops/kernel.py).  Mesh mode's LEGACY step paths
        # always use the full format: per-window compact eligibility is a
        # per-host data-dependent choice, and hosts picking different
        # executables for the same lockstep window would wedge the
        # collectives.  The lockstep pipeline drain instead keeps the
        # EXECUTABLE fixed every tick and moves the data-dependence into
        # STAGING (_compact_sound gates which lanes enter the compact
        # stack; the drain dispatches either way), so mesh serving gets
        # the compact wire + fold without executable divergence.
        self._compact_enabled = not self.multiprocess
        self._compact_sound = True
        self.windows_processed = 0
        self.decisions_processed = 0
        # occupied-prefix lane buckets (see _lane_bucket): powers-of-4 steps
        # down from B, floored at 64 — at most 3 shapes per executable family
        B = batch_per_shard
        self._lane_bucket_list = sorted(
            {b for b in (max(64, B // 16), max(64, B // 4)) if b < B} | {B})

        # Tiered key state (state/tiers.py): installed by enable_tiers on
        # Python-routed single-process engines; None = single-tier seed
        # behavior, byte-identical hot path
        self._tiers = None

        # Native C++ window router (gubernator_tpu/native): batch key hashing,
        # shard routing, slot lookup + LRU in one C call per window, replacing
        # the per-key Python dict path.  The two backends are exclusive —
        # regular-key routing state lives in exactly one of them.
        # replay-bound guard (GUBER_REPLAY_CAP overrides the param/config
        # unconditionally, like GUBER_EXACT_KEYS; default 128, 0 disables)
        import os as _os
        _env_cap = _os.environ.get("GUBER_REPLAY_CAP")
        if _env_cap is not None:
            try:
                self.replay_cap = int(_env_cap)
            except ValueError:
                raise ValueError(
                    f"GUBER_REPLAY_CAP must be an integer (lanes; 0 "
                    f"disables the replay-bound guard), got {_env_cap!r}"
                ) from None
        else:
            self.replay_cap = 128 if replay_cap is None else replay_cap
        self.native = None
        if use_native in ("auto", True, "on"):
            from gubernator_tpu import native as native_mod
            if native_mod.available():
                self.native = native_mod.NativeRouter(
                    self.num_local_shards, C,
                    num_global_shards=S,
                    shard_offset=self.local_shard_offset)
                # opt-in exact-key guard (GUBER_EXACT_KEYS=1 or
                # EngineConfig.exact_keys): store full keys so a 64-bit
                # fingerprint collision probes onward instead of silently
                # merging two keys' counters
                import os
                if exact_keys or os.environ.get("GUBER_EXACT_KEYS") == "1":
                    self.native.set_exact_keys()
                self.native.set_replay_cap(self.replay_cap)
            elif use_native != "auto":
                raise RuntimeError("native router requested but unavailable")

    # ------------------------------------------------------------------ device

    def _build_step(self):
        # All engines with the same mesh geometry share one compiled
        # executable — a 4-node in-process cluster compiles once, not four
        # times (each Instance owns an engine but the computation is pure).
        return _compiled_step(self.mesh)



    def step(
        self,
        requests: Sequence[RateLimitReq],
        now: Optional[int] = None,
        accumulate: Optional[Sequence[bool]] = None,
        upserts: Optional[Sequence] = None,
    ) -> List[RateLimitResp]:
        """Process one window of requests synchronously.

        accumulate[i]=False keeps request i's GLOBAL hits out of the psum:
        used by a non-owner *host* in a multi-host cluster, which answers
        from its replica and reconciles hits with the owner over gRPC
        (reference gubernator.go:173-195) rather than over the mesh.
        upserts: UpdatePeerGlobal-shaped records (key, status, algorithm,
        duration) from an owner broadcast, written into the replica arena
        before this window's reads.

        Caller must respect the window caps (use `process` for auto-chunking):
        per-shard regular lanes <= batch_per_shard, total GLOBAL lanes <=
        num_local_shards * global_batch_per_shard (they spread round-robin
        over local shards), distinct GLOBAL keys + upserts <=
        max_global_updates.
        """
        if self.native is not None:
            return self._process_native(requests, now, accumulate, upserts)
        now = self._resolve_now(now)
        S = self.num_shards
        buf = self._buf
        buf.reset(self.global_capacity)
        # init-pending protocol (state/arena.py): fresh allocations keep
        # reporting is_init until the dispatch below commits this window
        for t in self.tables:
            t.begin_window()
        self.gtable.begin_window()

        if upserts and not self._dynamic_global:
            # gRPC-broadcast upserts are host-local writes; in mesh mode they
            # would diverge the replicated arena across processes
            raise ValueError("upserts are not supported in mesh mode "
                             "(GLOBAL state replicates via the in-mesh psum)")
        if upserts:
            for i, u in enumerate(upserts):
                slot, _ = self.gtable.lookup(u.key, now, u.duration)
                st = u.status
                buf.pslot[i] = slot
                buf.plimit[i] = st.limit
                buf.pduration[i] = u.duration
                buf.premaining[i] = st.remaining
                is_token = u.algorithm == Algorithm.TOKEN_BUCKET
                # token: tstamp/expire are the bucket's reset_time; leaky: the
                # timestamp restarts here and the entry lives a full duration
                # (the reference's Add(key, status, status.ResetTime) leaves
                # leaky replicas instantly expired — divergence documented in
                # api/proto/peers.proto)
                buf.ptstamp[i] = st.reset_time if is_token else now
                buf.pexpire[i] = st.reset_time if is_token else now + u.duration
                buf.palgo[i] = u.algorithm

        lanes, gcfg_upd, greset, max_fill, g_count = self._stage_requests(
            buf, requests, now, accumulate)

        for i, (slot, cfg) in enumerate(gcfg_upd.items()):
            buf.uslot[i] = slot
            buf.ulimit[i], buf.uduration[i], buf.ualgo[i] = cfg
        for i, slot in enumerate(greset):
            buf.rslot[i] = slot

        if self._tiers is not None:
            self._tier_fence(now)
        out, gout = self._dispatch(
            now, reg_fill=max_fill, fetch_global=g_count > 0)
        for t in self.tables:
            t.commit_window()
        self.gtable.commit_window()

        self.decisions_processed += len(requests)

        responses = []
        for s, lane, is_global in lanes:
            o = gout if is_global else out
            responses.append(
                RateLimitResp(
                    status=int(o.status[s, lane]),
                    limit=int(o.limit[s, lane]),
                    remaining=int(o.remaining[s, lane]),
                    reset_time=int(o.reset_time[s, lane]),
                )
            )
        return responses

    def _stage_requests(self, buf, requests, now, accumulate):
        """Stage one window's requests into `buf` (the engine's
        _PackedWindow, or a per-window view over stacked staging arrays —
        anything exposing the same lane arrays).

        Returns (lanes, gcfg_upd, greset, max_reg_fill, g_count) where
        lanes is [(shard, lane, is_global)] per request for demux."""
        S = self.num_shards
        reg_fill = [0] * self.num_local_shards
        glob_fill = [0] * self.num_local_shards
        # slot -> (limit, duration, algo): latest request's config wins within
        # the window (deduped host-side — a device scatter with duplicate
        # indices has no ordering guarantee)
        gcfg_upd = {}
        greset: List[int] = []
        lanes: List[tuple] = []

        g_count = 0
        for i, r in enumerate(requests):
            key = r.hash_key()
            if r.behavior == Behavior.GLOBAL:
                if not self._dynamic_global and not self.global_ready(key):
                    raise ValueError(
                        f"GLOBAL key {key!r} is not registered; mesh mode "
                        "registers GLOBAL keys through the registrar "
                        "(core/service.py) before serving them")
                slot, is_init = self.gtable.lookup(key, now, r.duration)
                contribute = accumulate is None or accumulate[i]
                if contribute and self._dynamic_global:
                    # per-request config refresh diverges replicas in mesh
                    # mode; there configs are fixed at registration
                    gcfg_upd[slot] = (r.limit, r.duration, r.algorithm)
                    if is_init:
                        greset.append(slot)
                # GLOBAL lanes are shard-agnostic (the psum covers every
                # shard), so spread them round-robin over LOCAL shards
                if g_count >= self.num_local_shards * self.global_batch_per_shard:
                    raise ValueError(
                        "window exceeds the GLOBAL lane cap "
                        f"({self.num_local_shards} local shards x "
                        f"{self.global_batch_per_shard}); use process() for "
                        "auto-chunking")
                s = g_count % self.num_local_shards
                g_count += 1
                lane = glob_fill[s]
                glob_fill[s] += 1
                buf.gslot[s, lane] = slot
                buf.ghits[s, lane] = r.hits
                buf.ghits_acc[s, lane] = r.hits if contribute else 0
                buf.glimit[s, lane] = r.limit
                buf.gduration[s, lane] = r.duration
                buf.galgo[s, lane] = r.algorithm
                buf.gis_init[s, lane] = is_init
                lanes.append((s, lane, True))
            else:
                s = shard_of(key, S) - self.local_shard_offset
                if not 0 <= s < self.num_local_shards:
                    raise ValueError(
                        f"key {key!r} belongs to shard "
                        f"{shard_of(key, S)}, not owned by this process — "
                        "the serving layer must route it to the owning host")
                slot = None
                is_init = False
                if self._tiers is not None and key not in self.tables[s]:
                    # warm-tier rehydration: a demoted key re-enters the hot
                    # arena with its LIVE row (scattered at the pre-dispatch
                    # fence), so the decision matches the infinite-arena
                    # oracle bit for bit; a miss in warm too falls through
                    # to the ordinary cold-init lookup
                    slot = self._tiers.stage_promote(
                        s, self.tables[s], key, now, r.duration)
                if slot is None:
                    slot, is_init = self.tables[s].lookup(
                        key, now, r.duration)
                lane = reg_fill[s]
                reg_fill[s] += 1
                buf.slot[s, lane] = slot
                buf.hits[s, lane] = r.hits
                buf.limit[s, lane] = r.limit
                buf.duration[s, lane] = r.duration
                buf.algo[s, lane] = r.algorithm
                buf.is_init[s, lane] = is_init
                lanes.append((s, lane, False))
        return lanes, gcfg_upd, greset, max(reg_fill, default=0), g_count

    def step_stacked(
        self,
        windows: Sequence[Sequence[RateLimitReq]],
        now: Optional[int] = None,
        accumulates: Optional[Sequence[Optional[Sequence[bool]]]] = None,
        k_stack: Optional[int] = None,
    ) -> List[List[RateLimitResp]]:
        """K serving windows in ONE device dispatch — the lockstep
        saturation path (the mesh analog of the reference's back-to-back
        queue drain, peers.go:143-172).

        Semantics equal K sequential step() calls at the same `now`, with
        one documented divergence in single-process dynamic-GLOBAL mode:
        per-request GLOBAL config refreshes from ALL windows merge
        (last-wins) and apply once before window 0, because the stacked
        executable applies the control plane only there
        (_compiled_multi_step).  Mesh mode has no dynamic GLOBAL config, so
        its semantics are exact.

        Mesh mode: every process must call this in lockstep with the SAME
        `k_stack` (the executable's shape is part of the collective
        contract), the same cluster-agreed `now`, and its own local
        windows.  `k_stack` pads the stack with empty windows so a fixed
        tick shape can carry a variable backlog.
        """
        now = self._resolve_now(now)
        K = k_stack if k_stack is not None else max(len(windows), 1)
        if len(windows) > K:
            raise ValueError(f"{len(windows)} windows exceed k_stack={K}")
        SL, B = self.num_local_shards, self.batch_per_shard
        Bg, Kg = self.global_batch_per_shard, self.max_global_updates
        G = self.global_capacity

        # Per-K cached stacked staging (the hot lockstep path ticks every
        # batch_wait; reuse is safe because this method fetches the
        # responses before returning, so the previous tick's transfer is
        # complete).  Reset like _PackedWindow.reset: PAD slots drop lanes;
        # other fields only matter on non-PAD lanes except ghits_acc, whose
        # stale values would leak into the psum via jnp.zeros scatter-add.
        st = self._stacked_bufs.get(K)
        if st is None:
            st = _PackedWindow.__new__(_PackedWindow)
            st.slot = np.empty((K, SL, B), np.int32)
            st.hits = np.empty((K, SL, B), np.int64)
            st.limit = np.empty((K, SL, B), np.int64)
            st.duration = np.empty((K, SL, B), np.int64)
            st.algo = np.empty((K, SL, B), np.int32)
            st.is_init = np.empty((K, SL, B), bool)
            st.gslot = np.empty((K, SL, Bg), np.int32)
            st.ghits = np.empty((K, SL, Bg), np.int64)
            st.ghits_acc = np.empty((K, SL, Bg), np.int64)
            st.glimit = np.empty((K, SL, Bg), np.int64)
            st.gduration = np.empty((K, SL, Bg), np.int64)
            st.galgo = np.empty((K, SL, Bg), np.int32)
            st.gis_init = np.empty((K, SL, Bg), bool)
            self._stacked_bufs[K] = st
        st.slot.fill(kernel.PAD_SLOT)
        st.gslot.fill(kernel.PAD_SLOT)
        st.ghits_acc.fill(0)

        class _View:
            """One window's writable slice of the stacked staging arrays."""
            def __init__(self, k):
                for f in ("slot", "hits", "limit", "duration", "algo",
                          "is_init", "gslot", "ghits", "ghits_acc",
                          "glimit", "gduration", "galgo", "gis_init"):
                    setattr(self, f, getattr(st, f)[k])

        for t in self.tables:
            t.begin_window()
        self.gtable.begin_window()
        if self.native is not None:
            self.native.drain_begin()
        all_lanes: List[List[tuple]] = []
        merged_upd: dict = {}
        merged_reset: List[int] = []
        try:
            for k, reqs in enumerate(windows):
                acc = accumulates[k] if accumulates is not None else None
                if self.native is None:
                    lanes, gcfg_upd, greset, _, _ = self._stage_requests(
                        _View(k), reqs, now, acc)
                else:
                    lanes, gcfg_upd, greset = self._stage_window_native(
                        _View(k), reqs, now, acc)
                all_lanes.append(lanes)
                merged_upd.update(gcfg_upd)
                merged_reset.extend(greset)
            if len(merged_upd) > Kg or len(merged_reset) > Kg:
                raise ValueError("stacked windows carry more GLOBAL config "
                                 f"updates than max_global_updates ({Kg})")
        except Exception:
            # staging failed before dispatch: keep the drain's fresh
            # allocations pending (their slots were never initialized on
            # device; the next touch must re-init them)
            if self.native is not None:
                self.native.abort()
            raise

        uslot = np.full((Kg,), G, np.int32)
        ulimit = np.zeros((Kg,), np.int64)
        uduration = np.zeros((Kg,), np.int64)
        ualgo = np.zeros((Kg,), np.int32)
        rslot = np.full((Kg,), G, np.int32)
        for i, (slot, cfg) in enumerate(merged_upd.items()):
            uslot[i] = slot
            ulimit[i], uduration[i], ualgo[i] = cfg
        for i, slot in enumerate(merged_reset):
            rslot[i] = slot
        _, _, _, ups = self.empty_control()

        batches = WindowBatch(slot=st.slot, hits=st.hits, limit=st.limit,
                              duration=st.duration, algo=st.algo,
                              is_init=st.is_init)
        gbatches = WindowBatch(slot=st.gslot, hits=st.ghits, limit=st.glimit,
                               duration=st.gduration, algo=st.galgo,
                               is_init=st.gis_init)
        nows = np.full((K,), now, np.int64)

        if self._tiers is not None:
            # one fence covers the whole stack: begin_window ran ONCE above,
            # so every spill/promotion staged across the K windows resolves
            # here, before the single fused dispatch reads the arena
            self._tier_fence(now)
        try:
            fused = self.step_windows(
                batches, gbatches, st.ghits_acc,
                (uslot, ulimit, uduration, ualgo, rslot), ups, nows,
                n_decisions=sum(len(w) for w in windows))
        except Exception:
            if self.native is not None:
                self.native.abort()
            raise
        for t in self.tables:
            t.commit_window()
        self.gtable.commit_window()
        if self.native is not None:
            self.native.commit()

        fused = self._fetch_local_stacked(fused)
        responses: List[List[RateLimitResp]] = []
        for k, lanes in enumerate(all_lanes):
            out, gout = kernel.split_outputs(fused[k], B)
            resp = []
            for s, lane, is_global in lanes:
                o = gout if is_global else out
                resp.append(RateLimitResp(
                    status=int(o.status[s, lane]),
                    limit=int(o.limit[s, lane]),
                    remaining=int(o.remaining[s, lane]),
                    reset_time=int(o.reset_time[s, lane]),
                ))
            responses.append(resp)
        return responses

    def _stage_window_native(self, view, requests, now, accumulate):
        """step_stacked staging with the C router resolving regular keys
        (the native sibling of _stage_requests; must run inside a
        native drain_begin .. commit/abort bracket).  GLOBAL lanes keep the
        Python gtable path as everywhere else."""
        B = self.batch_per_shard
        reg_idx, glob_idx = [], []
        for i, r in enumerate(requests):
            (glob_idx if r.behavior == Behavior.GLOBAL else reg_idx).append(i)
        lanes: List[Optional[tuple]] = [None] * len(requests)

        if reg_idx:
            # single pass over the window: one walk fills the key blob and
            # all four numeric columns (the old per-field list
            # comprehensions re-touched every request object five times)
            n = len(reg_idx)
            keys_b = []
            rhits, rlim, rdur, ralgo = [], [], [], []
            for i in reg_idx:
                r = requests[i]
                keys_b.append(r.hash_key().encode("utf-8"))
                rhits.append(r.hits)
                rlim.append(r.limit)
                rdur.append(r.duration)
                ralgo.append(r.algorithm)
            key_bytes = np.frombuffer(b"".join(keys_b), dtype=np.uint8)
            key_ends = np.cumsum([len(k) for k in keys_b]).astype(np.int64)
            out_shard = np.empty(n, np.int32)
            out_lane = np.empty(n, np.int32)
            shard_fill = np.zeros(self.num_local_shards, np.int32)
            packed = self.native.pack_window(
                key_bytes, key_ends,
                np.asarray(rhits, np.int64),
                np.asarray(rlim, np.int64),
                np.asarray(rdur, np.int64),
                np.asarray(ralgo, np.int32),
                now, B,
                view.slot, view.hits, view.limit, view.duration, view.algo,
                view.is_init.view(np.uint8),
                out_shard, out_lane, shard_fill,
            )
            if packed < n:
                raise ValueError(
                    "stacked window overflows batch_per_shard — size "
                    "windows with max_window_prefix before step_stacked")
            bad = out_shard < 0
            if bad.any():
                r_bad = requests[reg_idx[int(np.argmax(bad))]]
                raise ValueError(
                    f"key {r_bad.hash_key()!r} belongs to shard "
                    f"{shard_of(r_bad.hash_key(), self.num_shards)}, "
                    "not owned by this process")
            for j, i in enumerate(reg_idx):
                lanes[i] = (int(out_shard[j]), int(out_lane[j]), False)

        gcfg_upd: dict = {}
        greset: List[int] = []
        if glob_idx:
            greqs = [requests[i] for i in glob_idx]
            gacc = ([accumulate[i] for i in glob_idx]
                    if accumulate is not None else None)
            glanes, gcfg_upd, greset, _, _ = self._stage_requests(
                view, greqs, now, gacc)
            for (s, lane, is_global), i in zip(glanes, glob_idx):
                lanes[i] = (s, lane, is_global)
        return lanes, gcfg_upd, greset

    def _process_native(
        self,
        requests: Sequence[RateLimitReq],
        now: Optional[int] = None,
        accumulate: Optional[Sequence[bool]] = None,
        upserts: Optional[Sequence] = None,
        columns: Optional[tuple] = None,
    ) -> List[RateLimitResp]:
        """Window processing with the C++ router resolving regular keys.

        One `router_pack` call hashes, routes, and slot-allocates a whole
        window directly into the staging buffers; lane overflow returns a
        partial pack and the loop ships what fit (built-in chunking).  GLOBAL
        keys and upserts are rare control-plane traffic and keep the Python
        gtable path, packed into the same device dispatch.
        """
        now = self._resolve_now(now)
        if upserts and not self._dynamic_global:
            raise ValueError("upserts are not supported in mesh mode "
                             "(GLOBAL state replicates via the in-mesh psum)")
        S = self.num_shards
        B = self.batch_per_shard
        buf = self._buf
        responses: List[Optional[RateLimitResp]] = [None] * len(requests)

        single_chunk_cap = min(
            self.batch_per_shard,
            self.num_local_shards * self.global_batch_per_shard)
        if self.multiprocess and len(requests) > single_chunk_cap:
            # The call may need multiple chunks (worst case: every key lands
            # on one shard), so validate EVERY request's routing before the
            # first dispatch — a mis-routed key discovered in a later chunk
            # would raise after earlier chunks already committed hits
            # (double-count on client retry).  Windows that provably fit one
            # chunk skip this: the C router marks bad keys and the GLOBAL
            # loop checks registration BEFORE that chunk's (only) dispatch,
            # so the lockstep hot path — pre-validated by _take_window —
            # pays no second hashing pass.
            for r in requests:
                err = self.routing_error(r)
                if err is not None:
                    raise ValueError(err)

        # split into regular (columnar) and global (listed) requests —
        # unless the caller already accumulated the window columnarly
        # (RequestColumns), in which case the split is known to be trivial
        # (no GLOBAL lanes) and the columns arrive as zero-copy slices
        glob: List[tuple] = []
        if columns is not None:
            key_bytes, key_ends, c_hits, c_lim, c_dur, c_algo = columns
            nreg = len(key_ends)
            if nreg != len(requests):
                raise ValueError("prebuilt columns must cover every request")
            reg_idx: Sequence[int] = range(nreg)
        else:
            reg_idx = []
            keys_b: List[bytes] = []
            rhits: List[int] = []
            rlim: List[int] = []
            rdur: List[int] = []
            ralgo: List[int] = []
            for i, r in enumerate(requests):
                if r.behavior == Behavior.GLOBAL:
                    glob.append((i, r, accumulate is None or accumulate[i]))
                else:
                    reg_idx.append(i)
                    keys_b.append(r.hash_key().encode("utf-8"))
                    rhits.append(r.hits)
                    rlim.append(r.limit)
                    rdur.append(r.duration)
                    ralgo.append(r.algorithm)
            nreg = len(reg_idx)
            if nreg:
                key_bytes = np.frombuffer(b"".join(keys_b), dtype=np.uint8)
                key_ends = np.cumsum([len(k) for k in keys_b]).astype(np.int64)
                c_hits = np.asarray(rhits, dtype=np.int64)
                c_lim = np.asarray(rlim, dtype=np.int64)
                c_dur = np.asarray(rdur, dtype=np.int64)
                c_algo = np.asarray(ralgo, dtype=np.int32)
        if nreg:
            out_shard = np.zeros(nreg, np.int32)
            out_lane = np.zeros(nreg, np.int32)
        shard_fill = np.zeros(self.num_local_shards, np.int32)

        pending_upserts = list(upserts) if upserts else []
        pos = 0
        gpos = 0
        # Dispatch parity with the Python path: step() always issues exactly
        # one device dispatch per call — including for an EMPTY window.  In
        # mesh mode every process must issue an identical dispatch sequence
        # per lockstep tick (core/batcher.py), so a zero-dispatch empty tick
        # on one host would wedge the collectives cluster-wide.
        first = True
        while first or pos < nreg or gpos < len(glob) or pending_upserts:
            first = False
            buf.reset(self.global_capacity)
            shard_fill[:] = 0
            self.gtable.begin_window()

            ups_chunk = pending_upserts[: self.max_global_updates]
            pending_upserts = pending_upserts[self.max_global_updates:]
            for i, u in enumerate(ups_chunk):
                slot, _ = self.gtable.lookup(u.key, now, u.duration)
                st = u.status
                buf.pslot[i] = slot
                buf.plimit[i] = st.limit
                buf.pduration[i] = u.duration
                buf.premaining[i] = st.remaining
                is_token = u.algorithm == Algorithm.TOKEN_BUCKET
                buf.ptstamp[i] = st.reset_time if is_token else now
                buf.pexpire[i] = st.reset_time if is_token else now + u.duration
                buf.palgo[i] = u.algorithm

            packed = 0
            if pos < nreg:
                base = 0 if pos == 0 else int(key_ends[pos - 1])
                packed = self.native.pack(
                    key_bytes[base:], key_ends[pos:] - base,
                    c_hits[pos:], c_lim[pos:], c_dur[pos:], c_algo[pos:],
                    now, B,
                    buf.slot, buf.hits, buf.limit, buf.duration, buf.algo,
                    buf.is_init.view(np.uint8),
                    out_shard[pos:], out_lane[pos:], shard_fill,
                )
                # mesh mode: the C router marks keys hashing to remote
                # shards; reject BEFORE dispatch (no hits committed)
                bad = out_shard[pos:pos + packed] < 0
                if bad.any():
                    r_bad = requests[reg_idx[pos + int(np.argmax(bad))]]
                    raise ValueError(
                        f"key {r_bad.hash_key()!r} belongs to shard "
                        f"{shard_of(r_bad.hash_key(), S)}, not owned by "
                        "this process")

            # global lanes (python table), bounded by caps; spread
            # round-robin over LOCAL shards (the psum is shard-agnostic)
            glanes: List[tuple] = []
            g_count = 0
            gcfg_upd = {}
            greset: List[int] = []
            while gpos + len(glanes) < len(glob):
                i, r, contribute = glob[gpos + len(glanes)]
                key = r.hash_key()
                if not self._dynamic_global and not self.global_ready(key):
                    raise ValueError(
                        f"GLOBAL key {key!r} is not registered; mesh mode "
                        "registers GLOBAL keys through the registrar")
                if g_count + 1 > self.num_local_shards * self.global_batch_per_shard:
                    break
                if len(gcfg_upd) + 1 > self.max_global_updates:
                    break
                slot, is_init = self.gtable.lookup(key, now, r.duration)
                if contribute and self._dynamic_global:
                    gcfg_upd[slot] = (r.limit, r.duration, r.algorithm)
                    if is_init:
                        greset.append(slot)
                s = g_count % self.num_local_shards
                lane = g_count // self.num_local_shards
                g_count += 1
                buf.gslot[s, lane] = slot
                buf.ghits[s, lane] = r.hits
                buf.ghits_acc[s, lane] = r.hits if contribute else 0
                buf.glimit[s, lane] = r.limit
                buf.gduration[s, lane] = r.duration
                buf.galgo[s, lane] = r.algorithm
                buf.gis_init[s, lane] = is_init
                glanes.append((i, s, lane))
            for j, (slot, cfg) in enumerate(gcfg_upd.items()):
                buf.uslot[j] = slot
                buf.ulimit[j], buf.uduration[j], buf.ualgo[j] = cfg
            for j, slot in enumerate(greset):
                buf.rslot[j] = slot

            if (packed == 0 and not glanes and not ups_chunk
                    and (pos < nreg or gpos < len(glob))):
                raise RuntimeError("window packing made no progress")

            out, gout = self._dispatch(
                now, reg_fill=int(shard_fill.max()) if packed else 0,
                fetch_global=bool(glanes))
            self.native.commit()
            self.gtable.commit_window()
            if packed:
                # vectorized demux: one fancy-indexed gather per field, then
                # plain-python scalars (per-item numpy indexing is ~10x slower)
                sh = out_shard[pos:pos + packed]
                ln = out_lane[pos:pos + packed]
                sts = out.status[sh, ln].tolist()
                lims = out.limit[sh, ln].tolist()
                rems = out.remaining[sh, ln].tolist()
                rsts = out.reset_time[sh, ln].tolist()
                for j, i in enumerate(reg_idx[pos:pos + packed]):
                    responses[i] = RateLimitResp(
                        status=sts[j], limit=lims[j],
                        remaining=rems[j], reset_time=rsts[j],
                    )
            for i, s, lane in glanes:
                responses[i] = RateLimitResp(
                    status=int(gout.status[s, lane]),
                    limit=int(gout.limit[s, lane]),
                    remaining=int(gout.remaining[s, lane]),
                    reset_time=int(gout.reset_time[s, lane]),
                )
            pos += packed
            gpos += len(glanes)
            self.decisions_processed += packed + len(glanes)

        return responses  # type: ignore[return-value]

    def step_windows(
        self,
        batches: WindowBatch,
        gbatches: WindowBatch,
        gaccs,
        upd,
        ups,
        nows,
        compact_safe: bool = False,
        n_decisions: Optional[int] = None,
    ) -> jax.Array:
        """Apply K stacked windows in one device dispatch (see
        _compiled_multi_step).  All arguments carry a leading K dimension
        except upd/ups (control plane, applied ONCE, before window 0) — so
        this equals K sequential step() calls whose first window carries all
        the control-plane writes; callers with upserts destined for a later
        window must split the dispatch at that window.

        Inputs may be numpy or device arrays.  Returns the fused response
        array (i64[K, S, B+Bg, 4], see kernel.pack_outputs) left un-fetched
        so callers can overlap demux with the next dispatch; split it with
        kernel.split_outputs(jax.device_get(fused), batch_per_shard).

        This path performs NO range checks on the stacked lanes (they may be
        resident device arrays), so unless the caller asserts
        `compact_safe=True` — promising every lane satisfies the
        COMPACT_MAX_* ranges — compact dispatch is permanently disabled to
        keep the saturation guard sound (see ops/kernel.py format note).

        Mesh mode: inputs are this process's LOCAL staging blocks
        ([K, S_local, ...]); every process must dispatch in lockstep with
        the SAME K (the stacked executable's shape is part of the
        collective contract) and identical replicated upd/ups/nows.
        """
        if not compact_safe:
            # legacy contract: unscanned stacks conservatively disable
            # compact dispatch for the engine (test_compact_wire pins it)
            self._compact_enabled = False
            if self._compact_sound:
                if isinstance(batches.slot, np.ndarray):
                    # host staging: the real cfg-range scan (occupied
                    # lanes only — the reused stacked buffers carry stale
                    # values in padded lanes).  Keeps _compact_sound
                    # accurate on the mesh lockstep tick path so the
                    # pipeline drain may keep staging compact lanes.
                    occ = batches.slot >= 0
                    dur_cap = np.where(
                        batches.algo == kernel.SLIDING_WINDOW,
                        kernel.SLIDING_MAX_DURATION,
                        kernel.COMPACT_MAX_DURATION)
                    ok = bool((((batches.limit >= 0)
                                & (batches.limit < kernel.COMPACT_MAX_LIMIT)
                                & (batches.duration >= 0)
                                & (batches.duration < dur_cap))
                               | ~occ).all())
                else:
                    ok = False  # resident arrays: unscannable
                if not ok:
                    self._compact_sound = False
        k = int(batches.slot.shape[0])
        if n_decisions is None:
            if (isinstance(batches.slot, np.ndarray)
                    and isinstance(gbatches.slot, np.ndarray)):
                # host staging (counted BEFORE any mesh rebind to sharded
                # arrays): occupied regular + GLOBAL lanes, exactly —
                # matching what process()/step() count for the same traffic
                n_decisions = (int((batches.slot >= 0).sum())
                               + int((gbatches.slot >= 0).sum()))
            else:
                # resident device arrays: the real count isn't host-visible
                # without a fetch — callers with partially-filled resident
                # stacks should pass n_decisions to keep the counter honest
                n_decisions = k * int(np.prod(batches.slot.shape[1:]))
        # Empty-GLOBAL skip: when this stack carries no GLOBAL lanes and
        # the control plane is inert (every slot points one past the
        # arena), dispatch the GLOBAL-skipping twin — same output shape,
        # minus the per-window GLOBAL gathers/scatters/psum.  Two gates:
        #
        #   * static (mesh-legal): the engine was configured skip_global —
        #     a config-level promise of zero GLOBAL traffic, identical on
        #     every process, so the twin IS the collective sequence.
        #     Active GLOBAL lanes under the promise are a caller bug and
        #     raise (host-staged stacks only; resident are unscannable).
        #   * dynamic (single-process only): host-staged inertness picks
        #     the twin per stack.  In mesh mode this choice would depend
        #     on per-process staging and break the collective contract.
        fn = self._multi_fn
        G = self.global_capacity
        inert = (isinstance(gbatches.slot, np.ndarray)
                 and not (gbatches.slot >= 0).any()
                 and (np.asarray(upd[0]) >= G).all()
                 and (np.asarray(upd[4]) >= G).all()
                 and (np.asarray(ups[0]) >= G).all())
        if self._skip_global:
            if isinstance(gbatches.slot, np.ndarray) and not inert:
                raise ValueError(
                    "engine configured skip_global=True received GLOBAL "
                    "lanes or control-plane writes")
            fn = _compiled_multi_step(self.mesh, with_global=False)
        elif not self.multiprocess and inert:
            fn = _compiled_multi_step(self.mesh, with_global=False)
        if self.multiprocess:
            batches = WindowBatch(*[self._sharded_in_stacked(np.asarray(a))
                                    for a in batches])
            gbatches = WindowBatch(*[self._sharded_in_stacked(np.asarray(a))
                                     for a in gbatches])
            gaccs = self._sharded_in_stacked(np.asarray(gaccs))
            upd = tuple(self._repl_in(a) for a in upd)
            ups = tuple(self._repl_in(a) for a in ups)
            nows = self._repl_in(np.asarray(nows, np.int64))
        self.state, fused, self.gstate, self.gcfg = fn(
            self.state, self.gstate, self.gcfg, batches, gbatches, gaccs,
            upd, ups, nows,
        )
        self.windows_processed += k
        self.decisions_processed += n_decisions
        return fused

    def empty_control(self):
        """(gbatch, gacc, upd, ups) padding values for windows that carry no
        GLOBAL traffic — lanes point one past the arena and are dropped."""
        S, Bg, G, Kg = (self.num_shards, self.global_batch_per_shard,
                        self.global_capacity, self.max_global_updates)
        gbatch = WindowBatch(
            slot=np.full((S, Bg), kernel.PAD_SLOT, np.int32),
            hits=np.zeros((S, Bg), np.int64),
            limit=np.zeros((S, Bg), np.int64),
            duration=np.zeros((S, Bg), np.int64),
            algo=np.zeros((S, Bg), np.int32),
            is_init=np.zeros((S, Bg), bool),
        )
        gacc = np.zeros((S, Bg), np.int64)
        upd = (np.full((Kg,), G, np.int32), np.zeros((Kg,), np.int64),
               np.zeros((Kg,), np.int64), np.zeros((Kg,), np.int32),
               np.full((Kg,), G, np.int32))
        ups = (np.full((Kg,), G, np.int32), np.zeros((Kg,), np.int64),
               np.zeros((Kg,), np.int64), np.zeros((Kg,), np.int64),
               np.zeros((Kg,), np.int64), np.zeros((Kg,), np.int64),
               np.zeros((Kg,), np.int32))
        return gbatch, gacc, upd, ups

    def empty_drain_control(self):
        """(gbatch, gacc, upd) padding for a pipeline drain that carries no
        GLOBAL lanes — LOCAL block shapes ([S_local, Bg]), unlike
        empty_control's global ones, because the drain stages per-process
        blocks (pipeline_dispatch_global reshards them).  Lanes point one
        past the arena and are dropped."""
        SL, Bg, G, Kg = (self.num_local_shards, self.global_batch_per_shard,
                         self.global_capacity, self.max_global_updates)
        gbatch = WindowBatch(
            slot=np.full((SL, Bg), kernel.PAD_SLOT, np.int32),
            hits=np.zeros((SL, Bg), np.int64),
            limit=np.zeros((SL, Bg), np.int64),
            duration=np.zeros((SL, Bg), np.int64),
            algo=np.zeros((SL, Bg), np.int32),
            is_init=np.zeros((SL, Bg), bool),
        )
        gacc = np.zeros((SL, Bg), np.int64)
        upd = (np.full((Kg,), G, np.int32), np.zeros((Kg,), np.int64),
               np.zeros((Kg,), np.int64), np.zeros((Kg,), np.int32),
               np.full((Kg,), G, np.int32))
        return gbatch, gacc, upd

    def register_global_keys(self, specs: Sequence[tuple],
                             now: Optional[int] = None,
                             pending: bool = False) -> None:
        """Register GLOBAL limits: (key, limit, duration, algorithm).

        Runs through a COLLECTIVE-FREE replicated executable
        (_compiled_global_register): it only scatters into the replicated
        gstate/gcfg arrays, so in mesh mode each process may run it at its
        own wall time — no lockstep tick needed — provided every process
        applies the IDENTICAL ordered batches with the identical `now`
        (boot preload, or registrar-ordered dynamic batches; see
        core/service.py register_globals).  Until a batch is applied on a
        process, that process has no lanes for the keys, so the slots'
        psum deltas are zero everywhere and replicas cannot diverge.

        pending=True (dynamic mesh registration, phase 1): the keys are
        allocated and configured but NOT yet servable — routing_error keeps
        rejecting them until activate_global_keys (phase 2, issued by the
        registrar only after EVERY process applied phase 1, so no host
        contributes hits to a slot some replica hasn't configured).

        Mesh-determinism guard: in mesh mode registration only ever
        allocates from the free list — when the arena is full it FAILS
        instead of reclaiming, because reclaim/LRU order depends on each
        host's local serving history and would diverge the replicated slot
        assignment.
        """
        now = self._resolve_now(now)
        K = self.max_global_updates
        G = self.global_capacity
        # last-wins dedupe BEFORE staging: duplicate keys would put duplicate
        # indices in one device scatter, whose ordering XLA does not define
        deduped = {key: (key, limit, duration, algorithm)
                   for key, limit, duration, algorithm in specs}
        specs = list(deduped.values())
        if self.multiprocess:
            new = sum(1 for s in specs if s[0] not in self.gtable)
            if len(self.gtable) + new > G:
                raise ValueError(
                    f"GLOBAL arena full ({G} slots): mesh-mode registration "
                    "never reclaims (host-local LRU order would diverge the "
                    "replicated slot assignment); raise global_capacity")
        fn = _compiled_global_register(self.mesh)
        for base in range(0, len(specs), K):
            chunk = specs[base:base + K]
            self.gtable.begin_window()
            uslot = np.full((K,), G, np.int32)
            ulimit = np.zeros((K,), np.int64)
            uduration = np.zeros((K,), np.int64)
            ualgo = np.zeros((K,), np.int32)
            rslot = np.full((K,), G, np.int32)
            r = 0
            for i, (key, limit, duration, algorithm) in enumerate(chunk):
                slot, is_init = self.gtable.lookup(key, now, duration)
                uslot[i] = slot
                ulimit[i] = limit
                uduration[i] = duration
                ualgo[i] = algorithm
                if is_init:
                    rslot[r] = slot
                    r += 1
                if pending:
                    self._gpending.add(key)
            upd = tuple(self._repl_in(a) for a in
                        (uslot, ulimit, uduration, ualgo, rslot))
            self.gstate, self.gcfg = fn(self.gstate, self.gcfg, upd)
            self.gtable.commit_window()

    def activate_global_keys(self, keys: Sequence[str]) -> None:
        """Phase 2 of dynamic mesh registration: begin serving the keys
        (every process has applied their phase-1 arena writes)."""
        self._gpending.difference_update(keys)

    def global_ready(self, key: str) -> bool:
        """Is this GLOBAL hash key servable on this engine right now?"""
        return key in self.gtable and key not in self._gpending

    def warmup(self, now: Optional[int] = None,
               k_stack: Optional[int] = None) -> None:
        """Compile and execute one empty window per serving executable —
        every lane bucket of both wire formats, plus the pipeline's
        stacked-window buckets and its single-window drain at each
        narrower lane bucket — so serving never pays a jit stall (a
        cluster's 500ms peer deadline does not survive a mid-serving
        compile).  Mesh mode: pass the cluster-agreed timestamp (every
        process must warm up in lockstep), and the tick's lockstep_stack as
        `k_stack` so the stacked tick executable compiles here too.

        (An empty `process()` call is a no-op on the native path, so callers
        that need the compile — cluster boot, daemon start — use this.)"""
        now = self._resolve_now(now)
        if k_stack is not None and k_stack > 1:
            self.step_stacked([[]], now, k_stack=k_stack)
            # skip_global engines never dispatch the GLOBAL-carrying
            # variant, so there is nothing extra to warm
            if not self.multiprocess and not self._skip_global:
                # the empty warm stack above lowers to the GLOBAL-skipping
                # twin (step_windows inertness gate); execute the
                # GLOBAL-carrying variant on the same inert stack too —
                # identical to the pre-skip warmup dispatch — so the first
                # stacked window with real GLOBAL lanes never pays a
                # mid-serving compile
                K = k_stack
                SL, B = self.num_local_shards, self.batch_per_shard
                gb, ga, upd, ups = self.empty_control()
                stk = lambda a: np.stack([a] * K)  # noqa: E731
                batches = WindowBatch(
                    slot=np.full((K, SL, B), kernel.PAD_SLOT, np.int32),
                    hits=np.zeros((K, SL, B), np.int64),
                    limit=np.zeros((K, SL, B), np.int64),
                    duration=np.zeros((K, SL, B), np.int64),
                    algo=np.zeros((K, SL, B), np.int32),
                    is_init=np.zeros((K, SL, B), bool))
                self.state, _, self.gstate, self.gcfg = \
                    _compiled_multi_step(self.mesh)(
                        self.state, self.gstate, self.gcfg, batches,
                        WindowBatch(*[stk(a) for a in gb]), stk(ga),
                        upd, ups, np.full((K,), now, np.int64))
        # full format compiles only at full width (it is the rare fallback
        # once compact serving is up; each extra shape is a whole XLA
        # compile of the int64 ladder)
        saved = self._compact_enabled
        self._compact_enabled = False
        self._buf.reset(self.global_capacity)
        self._dispatch(now)
        self._compact_enabled = saved
        if saved:
            for lanes in self._lane_bucket_list:
                self._buf.reset(self.global_capacity)
                self._dispatch(now, reg_fill=lanes)
        if self.native is not None and not self.multiprocess:
            # every K bucket at full width, then the single-window drain
            # at each narrower lane bucket (core/pipeline.py _drain_lanes)
            B = self.batch_per_shard
            shapes = [(kb, B) for kb in PIPELINE_K_BUCKETS]
            shapes += [(1, b) for b in self._lane_bucket_list if b < B]
            for kb, lanes in shapes:
                packed = np.zeros((kb, self.num_shards, lanes, 2), np.int64)
                _, _, mism = self.pipeline_dispatch(
                    packed, np.full(kb, now, np.int64), n_windows=0)
            jax.device_get(mism)
            if k_stack is not None:
                # lockstep serving (single-process mesh behind a tick
                # clock): the tick's drain is the GLOBAL-composed variant
                # at the tick's fixed shape — the analytics-composed
                # flavor when analytics is wired (that IS the tick
                # executable then; the plain one would never run)
                kb = max(k_stack, 1)
                packed = np.zeros(
                    (kb, self.num_shards, self.batch_per_shard, 2), np.int64)
                gbatch, gacc, upd = self.empty_drain_control()
                out = self.pipeline_dispatch_global(
                    packed, np.full(kb, now, np.int64), gbatch, gacc, upd,
                    n_windows=0,
                    analytics_args=self._warm_analytics_args(kb))
                jax.device_get(out[3])
        elif self.native is not None and self.multiprocess:
            # mesh lockstep drain: ONE fixed shape (the tick's k_stack),
            # dispatched collectively — every process warms it together.
            # The tick drain is the GLOBAL-composed variant (one psum per
            # drain, core/pipeline.py lockstep mode), analytics-composed
            # when analytics is wired.
            kb = max(k_stack or 1, 1)
            packed = np.zeros(
                (kb, self.num_local_shards, self.batch_per_shard, 2),
                np.int64)
            gbatch, gacc, upd = self.empty_drain_control()
            out = self.pipeline_dispatch_global(
                packed, np.full(kb, now, np.int64), gbatch, gacc, upd,
                n_windows=0, analytics_args=self._warm_analytics_args(kb))
            self._fetch_local_stacked(out[2])

    def _warm_analytics_args(self, kb: int):
        """Inert analytics_args for warmup's composed-drain dispatch, or
        None when analytics is not wired (matching the executable the
        lockstep tick will actually use).  Zero tenants + decay=0 leave
        the fresh sketch all-zero."""
        if self._an_conf is None:
            return None
        return (np.zeros((kb, self.num_local_shards, self.batch_per_shard),
                         np.int32), 0)

    def _resolve_now(self, now: Optional[int]) -> int:
        """Default `now` to wall clock — except in mesh mode, where the
        window timestamp is a REPLICATED input: every process must pass the
        same agreed value (e.g. the lockstep clock's tick time), so a
        per-host wall-clock default would silently diverge the replicas."""
        if now is not None:
            return now
        if self.multiprocess:
            raise ValueError(
                "mesh mode requires an explicit, cluster-agreed `now` "
                "per window (the lockstep clock provides one)")
        return millisecond_now()

    def _compact_eligible(self, buf) -> bool:
        """May this window travel in the compact wire format?  Vectorized
        range checks over the staged buffers (padded lanes are zeros and
        always pass).

        A limit/duration violation disables compact dispatch permanently —
        those values persist in the arena and could later saturate a compact
        response.  A hits violation only routes THIS window to the full
        path: hits are consumed, not stored.

        The cfg scan runs even when compact dispatch is already off (mesh
        legacy path): it maintains _compact_sound, which gates what the
        lockstep pipeline drain may STAGE in compact form."""
        if self._compact_sound:
            # sliding-window rows halve the duration cap: the compact
            # lowering's rebased-i32 exactness proof needs
            # now - window_start < 2*duration (ops/kernel.py)
            dur_cap = np.where(buf.algo == kernel.SLIDING_WINDOW,
                               kernel.SLIDING_MAX_DURATION,
                               kernel.COMPACT_MAX_DURATION)
            cfg_ok = (
                bool((buf.limit >= 0).all())
                and bool((buf.limit < kernel.COMPACT_MAX_LIMIT).all())
                and bool((buf.duration >= 0).all())
                and bool((buf.duration < dur_cap).all())
            )
            if not cfg_ok:
                self._compact_enabled = False
                self._compact_sound = False
        if not self._compact_enabled or not self._compact_sound:
            return False
        # concurrency releases carry negative hits, sign-extended through
        # bit 27 of the compact hits field; every other algorithm keeps the
        # full non-negative 28-bit range.  Algorithms outside the 3-bit wire
        # alphabet (0..4) take the full path, where the token fallback is
        # applied without re-encoding.
        conc = buf.algo == kernel.CONCURRENCY
        h_lo = np.where(conc, 1 - kernel.CONC_MAX_HITS, 0)
        h_hi = np.where(conc, kernel.CONC_MAX_HITS, kernel.COMPACT_MAX_HITS)
        return (
            bool(((buf.hits >= h_lo) & (buf.hits < h_hi)).all())
            and bool(((buf.algo >= 0)
                      & (buf.algo <= kernel.CONCURRENCY)).all())
        )

    def _sharded_in(self, local_np):
        """Local [S_local, ...] staging block -> global [S, ...] array."""
        if not self.multiprocess:
            return local_np
        gshape = (self.num_shards,) + local_np.shape[1:]
        return jax.make_array_from_process_local_data(
            self._shard_sharding, local_np, gshape)

    def _sharded_in_stacked(self, local_np):
        """Local [K, S_local, ...] stacked staging -> global [K, S, ...]."""
        if not self.multiprocess:
            return local_np
        from gubernator_tpu.parallel.distributed import stacked_sharding
        gshape = ((local_np.shape[0], self.num_shards) + local_np.shape[2:])
        return jax.make_array_from_process_local_data(
            stacked_sharding(self.mesh), local_np, gshape)

    def _repl_in(self, arr):
        """Replicated input: every process MUST pass identical values."""
        if not self.multiprocess:
            return arr
        arr = np.asarray(arr)
        return jax.make_array_from_process_local_data(
            self._repl_sharding, arr, arr.shape)

    def _fetch_local(self, arr):
        """device_get of this process's shard blocks, in shard order:
        [S_local, ...] (the whole array when single-process)."""
        if not self.multiprocess:
            return jax.device_get(arr)
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    def _fetch_local_stacked(self, arr):
        """Like _fetch_local for a stacked output [K, S, ...]: this
        process's blocks along the shard axis -> [K, S_local, ...]."""
        if not self.multiprocess:
            return jax.device_get(arr)
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[1].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards], axis=1)

    def fetch_stacked_many(self, arrs):
        """Fetch several stacked outputs of ONE drain in a single
        device_get.  The pipeline's fetch stage previously issued one
        blocking device_get per plane (words, then mismatch flag, then
        stats) — each is a separate host sync point on the transfer stream;
        batching them into one call lets the runtime coalesce the copies
        (core/pipeline.py `_complete_sync`).

        The guber_fetch annotation is a devprof classification anchor
        (observability/devprof.py): kernels inside it are the D2H copy
        cost, not drain-body time."""
        with jax.profiler.TraceAnnotation("guber_fetch"):
            if not self.multiprocess:
                return jax.device_get(list(arrs))
            return [self._fetch_local_stacked(a) for a in arrs]

    def _lane_bucket(self, max_fill: int) -> int:
        """Occupied-prefix lane width: the smallest compiled lane-bucket
        >= max_fill.  Slicing the staged window to the occupied prefix makes
        the host<->device transfer proportional to occupancy instead of to
        batch_per_shard (a 1000-request window in a 32k-lane engine otherwise
        moves 32x more bytes than it has lanes).  Buckets are powers-of-4
        steps of B so at most 3 executables exist per step family.

        The buckets serve the legacy step (_dispatch) and the pipeline's
        single-window drain (core/pipeline.py _drain_lanes): the drain
        executable's device time is set by its lane count, not by how
        many lanes hold a request, so a 20-decision drain runs the
        B/16 shape.

        Mesh mode always uses the full width: the bucket choice is
        per-host data-dependent, and hosts picking different executables
        for the same lockstep tick would wedge the collectives."""
        if self.multiprocess:
            return self.batch_per_shard
        for b in self._lane_bucket_list:
            if b >= max_fill:
                return b
        return self.batch_per_shard

    def _dispatch(self, now: int, reg_fill: Optional[int] = None,
                  fetch_global: bool = True):
        """Run the staged buffers through the device step; returns host copies
        of the (regular, global) outputs.

        The transfer is a per-window fixed cost, so eligible windows use
        the compact wire format (_compiled_step_compact), slice the regular
        lanes to the occupied-prefix bucket (reg_fill = max per-shard fill;
        None = full width), and skip fetching the GLOBAL output block when the
        window carries no GLOBAL lanes (fetch_global=False -> gout is None).

        `windows_processed` increments immediately after the device call is
        issued — before any fetch/demux — so it counts exactly the dispatches
        the device saw (the lockstep batcher's parity accounting relies on
        this, core/batcher.py).

        In mesh mode every process must call this in lockstep (same dispatch
        sequence), staging its own local lanes; replicated control inputs
        (upd/ups/now) must be identical everywhere."""
        buf = self._buf
        if self._skip_global:
            # same config-level promise as step_stacked's static gate:
            # zero GLOBAL traffic ever reaches a skip_global engine.
            # (warmup dispatches inert buffers with fetch_global=True, so
            # the check scans the staged lanes, not the fetch flag)
            G = self.global_capacity
            if ((buf.gslot >= 0).any() or (buf.uslot < G).any()
                    or (buf.rslot < G).any() or (buf.pslot < G).any()):
                raise ValueError(
                    "engine configured skip_global=True received GLOBAL "
                    "lanes or control-plane writes")
        compact = self._compact_eligible(buf)
        # Occupied-prefix buckets apply only to the compact path: the full
        # format is the rare fallback and warmup compiles it only at full
        # width, so slicing it would trigger a mid-serving XLA compile per
        # bucket shape.
        lanes = (self._lane_bucket(reg_fill)
                 if compact and reg_fill is not None
                 else self.batch_per_shard)
        gbatch = WindowBatch(
            slot=self._sharded_in(buf.gslot), hits=self._sharded_in(buf.ghits),
            limit=self._sharded_in(buf.glimit),
            duration=self._sharded_in(buf.gduration),
            algo=self._sharded_in(buf.galgo),
            is_init=self._sharded_in(buf.gis_init),
        )
        gacc = self._sharded_in(buf.ghits_acc)
        upd = tuple(self._repl_in(a) for a in (
            buf.uslot, buf.ulimit, buf.uduration, buf.ualgo, buf.rslot))
        ups = tuple(self._repl_in(a) for a in (
            buf.pslot, buf.plimit, buf.pduration, buf.premaining,
            buf.ptstamp, buf.pexpire, buf.palgo))
        now_in = self._repl_in(np.int64(now)) if self.multiprocess \
            else jnp.int64(now)
        # SURVEY §5 tracing analog: window dispatches show up as named steps
        # in a jax.profiler trace (any profiler session); no-op otherwise
        with jax.profiler.StepTraceAnnotation(
                "guber_window", step_num=self.windows_processed):
            return self._dispatch_inner(buf, compact, lanes, gbatch, gacc,
                                        upd, ups, now, now_in, fetch_global)

    def _dispatch_inner(self, buf, compact, lanes, gbatch, gacc, upd, ups,
                        now, now_in, fetch_global):
        if compact:
            packed = self._sharded_in(kernel.encode_batch_host(
                buf.slot[:, :lanes], buf.hits[:, :lanes],
                buf.limit[:, :lanes], buf.duration[:, :lanes],
                buf.algo[:, :lanes], buf.is_init[:, :lanes]))
            self.state, cword, gfused, self.gstate, self.gcfg = self._compact_fn(
                self.state, self.gstate, self.gcfg, packed, gbatch,
                gacc, upd, ups, now_in,
            )
            self.windows_processed += 1
            out = kernel.decode_output_host(self._fetch_local(cword), now)
            if not fetch_global:
                return out, None
            gfused = self._fetch_local(gfused)
            gout = WindowOutput(
                status=gfused[..., 0], limit=gfused[..., 1],
                remaining=gfused[..., 2], reset_time=gfused[..., 3])
            return out, gout
        batch = WindowBatch(
            slot=self._sharded_in(buf.slot[:, :lanes]),
            hits=self._sharded_in(buf.hits[:, :lanes]),
            limit=self._sharded_in(buf.limit[:, :lanes]),
            duration=self._sharded_in(buf.duration[:, :lanes]),
            algo=self._sharded_in(buf.algo[:, :lanes]),
            is_init=self._sharded_in(buf.is_init[:, :lanes]),
        )
        self.state, fused, self.gstate, self.gcfg = self._step_fn(
            self.state, self.gstate, self.gcfg, batch, gbatch, gacc,
            upd, ups, now_in,
        )
        self.windows_processed += 1
        return kernel.split_outputs(self._fetch_local(fused), lanes)

    # per-engine cache of the compiled stacked-drain executable (the mesh
    # never changes after construction)
    _pipeline_fn = None

    def pipeline_dispatch(self, packed, nows, n_windows: Optional[int] = None):
        """Dispatch a stacked compact drain (core/pipeline.py) WITHOUT
        fetching: K serving windows in one device call, regular keys only
        (GLOBAL traffic needs the control plane + psum and rides the legacy
        step path, serialized on the same executor thread).

        packed: i64[K, S_local, B, 2] compact request stack (numpy or
        resident), B any warmed lane bucket (jit keys the executable on
        the shape); nows: i64[K] per-window timestamps.  Returns un-fetched
        device arrays (words i64[K, S, B], limits i64[K, S, B], mism
        bool[K, S]; fetch the local blocks with _fetch_local_stacked):
        the caller overlaps their fetch with the next drain's dispatch and
        reads `limits` only when a mismatch flag fired (see
        kernel.encode_output_word).

        Mesh mode: the drain is part of the lockstep collective contract —
        every process must dispatch it at the same sequence position with
        the SAME K and identical `nows`, every tick, even when its own
        stack is empty (an all-zero stack stages no lanes and is inert).
        Per-host compact ELIGIBILITY never changes the executable: an
        unsound host just stops staging lanes (core/pipeline.py
        lockstep mode) while still issuing the dispatch.

        The `guber_drain` annotation (here and in
        pipeline_dispatch_global) covers this call alone: the host's
        enqueue of the executable.  It closes when the call returns,
        long before the device has run the drain; the device's own time
        is the trace's `XLA Modules` line.  The host stages around it
        are `guber_pack`, `guber_fetch`, `guber_decode`, `guber_commit`
        (core/pipeline.py) and `guber_rpc_in` / `guber_rpc_out`
        (server.py).
        """
        if self.multiprocess:
            packed = self._sharded_in_stacked(np.ascontiguousarray(packed))
            nows = self._repl_in(np.asarray(nows, np.int64))
        # cache the compiled step on the engine: the lru_cache lookup in
        # _compiled_pipeline_step hashes the mesh on EVERY drain, which is
        # measurable at sub-ms dispatch cadence
        fn = self._pipeline_fn
        if fn is None:
            fn = self._pipeline_fn = _compiled_pipeline_step(self.mesh)
        with jax.profiler.StepTraceAnnotation(
                "guber_drain", step_num=self.windows_processed):
            self.state, words, limits, mism = fn(self.state, packed, nows)
        self.windows_processed += (int(packed.shape[0]) if n_windows is None
                                   else n_windows)
        return words, limits, mism

    def pipeline_dispatch_global(self, packed, nows, gbatch, gacc, upd,
                                 n_windows: Optional[int] = None,
                                 analytics_args=None):
        """The mesh serving drain: pipeline_dispatch's K-window compact
        stack PLUS one GLOBAL window (replica reads + the reconciliation
        psum + config writes), all in ONE device call with ONE collective
        (_compiled_pipeline_step_global).  This is the lockstep tick's
        drain executable — GLOBAL traffic no longer needs the legacy step
        path to reach the mesh.

        packed/nows: as pipeline_dispatch.  gbatch: full-format GLOBAL
        WindowBatch [S_local, Bg] (PAD_SLOT lanes drop); gacc: the psum
        hit contributions i64[S_local, Bg]; upd: the 5-tuple of replicated
        config-update/reset lanes (engine.empty_drain_control provides
        inert padding for all three).  Returns un-fetched (words, limits,
        mism, gfused) — gfused i64[S, Bg, 4] is the GLOBAL response block
        (status/limit/remaining/reset_time; fetch local rows with
        _fetch_local).

        Mesh mode: same lockstep contract as pipeline_dispatch — every
        process dispatches this at the same sequence position with the
        same K and identical nows/upd, every tick, staged lanes or not.

        `analytics_args=(tenants, decay)` composes the per-drain stats
        reduction into THE SAME dispatch (the analytics-geometry variant
        of the composed executable): tenants i32[K, S_local, B] host-staged
        ids, decay the 0/1 halving flag.  Returns an extra `stats`
        i64[S, V] (un-fetched) and updates the resident sketch in place.
        Enablement is config-level, so every mesh process picks the same
        variant — the executable choice never depends on per-tick data."""
        if self.multiprocess:
            packed = self._sharded_in_stacked(np.ascontiguousarray(packed))
            nows = self._repl_in(np.asarray(nows, np.int64))
            gbatch = WindowBatch(*[self._sharded_in(np.asarray(a))
                                   for a in gbatch])
            gacc = self._sharded_in(np.asarray(gacc))
            upd = tuple(self._repl_in(a) for a in upd)
        if analytics_args is not None:
            conf = self._an_conf
            tenants, decay = analytics_args
            if self.multiprocess:
                tenants = self._sharded_in_stacked(
                    np.ascontiguousarray(tenants))
                decay_in = self._repl_in(np.int64(decay))
            else:
                decay_in = jnp.int64(decay)
            fn = _compiled_pipeline_step_global(
                self.mesh, (conf.sketch_depth, conf.sketch_width,
                            conf.tenant_slots, conf.topk, conf.over_weight))
            with jax.profiler.StepTraceAnnotation(
                    "guber_drain", step_num=self.windows_processed):
                (self.state, words, limits, mism, gfused,
                 self.gstate, self.gcfg, self._an_sketch, stats) = fn(
                    self.state, self.gstate, self.gcfg, packed, gbatch,
                    gacc, upd, nows, self._an_sketch, tenants, decay_in)
            self.windows_processed += (int(packed.shape[0])
                                       if n_windows is None else n_windows)
            return words, limits, mism, gfused, stats
        fn = _compiled_pipeline_step_global(self.mesh)
        with jax.profiler.StepTraceAnnotation(
                "guber_drain", step_num=self.windows_processed):
            (self.state, words, limits, mism, gfused,
             self.gstate, self.gcfg) = fn(
                self.state, self.gstate, self.gcfg, packed, gbatch, gacc,
                upd, nows)
        self.windows_processed += (int(packed.shape[0]) if n_windows is None
                                   else n_windows)
        return words, limits, mism, gfused

    # ------------------------------------------------------ traffic analytics
    #
    # The per-drain stats reduction (ops/analytics.py) has two homes:
    #
    #   * the regular (non-lockstep) pipeline runs it as its OWN
    #     executable over the drain's inputs/outputs (analytics_dispatch
    #     below), so the drain builders stay byte-identical whether
    #     analytics is on or off — the disabled serving path is provably
    #     unchanged (tests/test_analytics.py);
    #   * the lockstep tick composes it INTO the GLOBAL-composed drain
    #     (pipeline_dispatch_global's analytics_args): one dispatch, one
    #     collective-sequence slot, and the reduction reads the drain's
    #     words and post-drain expiry plane in place.  The analytics=None
    #     builder is still byte-identical — composition is a separate
    #     lru_cache entry keyed on the config-level geometry.
    #
    # The reduction is collective-free either way: each shard emits its
    # own stats row and the host merges its local blocks, so the separate
    # executable is safe to dispatch outside the lockstep collective
    # contract, and the composed variant adds no collective to the drain.

    _an_conf = None
    _an_sketch = None

    def enable_analytics(self, conf) -> None:
        """Allocate the resident per-shard count-min sketch and record the
        reduction geometry (config.AnalyticsConfig).  Call once at wiring
        time (core/service.py), before serving starts."""
        self._an_conf = conf
        self._an_sketch = self._put_sharded(
            np.zeros((self.num_local_shards, conf.sketch_depth,
                      conf.sketch_width), np.int64), np.int64)

    def analytics_dispatch(self, packed, words, tenants, now: int,
                           decay: int):
        """Per-drain stats reduction: consume the drain's compact request
        stack (host [K, S_local, B, 2] — re-staged host→device, the cheap
        direction), its resident response words i64[K, S, B], and the
        host-staged tenant lanes i32[K, S_local, B]; update the resident
        sketch in place (donated carry) and return the UN-FETCHED stats
        array i64[S, V] (fetch local rows with _fetch_local, overlapped
        with the drain's own fetch — no extra device→host round trip).
        decay=1 halves the sketch before accumulating (host cadence)."""
        conf = self._an_conf
        if self.multiprocess:
            packed = self._sharded_in_stacked(np.ascontiguousarray(packed))
            tenants = self._sharded_in_stacked(np.ascontiguousarray(tenants))
            now_in = self._repl_in(np.int64(now))
            decay_in = self._repl_in(np.int64(decay))
        else:
            now_in = jnp.int64(now)
            decay_in = jnp.int64(decay)
        fn = _compiled_analytics_reduce(self.mesh, conf.sketch_depth,
                                        conf.sketch_width, conf.tenant_slots,
                                        conf.topk, conf.over_weight)
        # guber_analytics: devprof classification anchor — the standalone
        # reduction's kernels attribute to the analytics arm, not the drain
        with jax.profiler.TraceAnnotation("guber_analytics"):
            self._an_sketch, stats = fn(
                self._an_sketch, self.state.expire_lo, self.state.expire_hi,
                packed, words, tenants, now_in, decay_in)
        return stats

    def process(
        self,
        requests: Sequence[RateLimitReq],
        now: Optional[int] = None,
        accumulate: Optional[Sequence[bool]] = None,
        columns: Optional[tuple] = None,
    ) -> List[RateLimitResp]:
        """step() with automatic chunking when a window overflows the caps.

        `columns` is an optional prebuilt (key_bytes, key_ends, hits, limit,
        duration, algo) tuple covering ALL of `requests` (native path only,
        no GLOBAL requests) — callers that accumulate submissions in
        RequestColumns (core/window_buffers.py) hand over array slices
        instead of having this method re-walk the request objects."""
        if self.native is not None:
            return self._process_native(requests, now, accumulate,
                                        columns=columns)
        S = self.num_shards
        SL = self.num_local_shards
        if self.multiprocess:
            # validate routing BEFORE dispatching anything: a mis-routed key
            # discovered mid-stream would fail requests whose hits earlier
            # chunks already committed (double-count on client retry)
            for r in requests:
                if r.behavior != Behavior.GLOBAL:
                    key = r.hash_key()
                    if not (0 <= shard_of(key, S) - self.local_shard_offset < SL):
                        raise ValueError(
                            f"key {key!r} belongs to shard {shard_of(key, S)}, "
                            "not owned by this process")
        out: List[RateLimitResp] = []
        acc = list(accumulate) if accumulate is not None else [True] * len(requests)
        pos = 0
        while pos < len(requests):
            n = self.max_window_prefix(requests[pos:])
            out.extend(self.step(requests[pos:pos + n], now, acc[pos:pos + n]))
            pos += n
        return out

    def routing_error(self, r: RateLimitReq) -> Optional[str]:
        """Why this request cannot be served by THIS engine, or None.

        Used by the lockstep batcher to fail bad requests individually
        instead of letting a packing exception skip a mesh tick."""
        key = r.hash_key()
        if r.behavior == Behavior.GLOBAL:
            if not self._dynamic_global and not self.global_ready(key):
                return (f"GLOBAL key {key!r} is not registered; mesh mode "
                        "registers GLOBAL keys through the registrar")
            return None
        s = shard_of(key, self.num_shards)
        if not 0 <= s - self.local_shard_offset < self.num_local_shards:
            return (f"key {key!r} belongs to shard {s}, "
                    "not owned by this process")
        return None

    def max_window_prefix(self, requests: Sequence[RateLimitReq]) -> int:
        """How many leading requests fit in ONE step() window (>=1 when any
        are given).  Shared by process() chunking and the lockstep batcher's
        per-tick window assembly.

        Also enforces the replay-bound guard on this FULL-FORMAT path (the
        stacked compact paths enforce it natively — host_router.cc
        rep_track): a NON-uniform duplicate-key run longer than replay_cap
        lanes cuts the window there, so the kernel's per-window replay loop
        stays bounded even for traffic that fell off the compact path
        (e.g. after an out-of-range config permanently disabled it)."""
        S, SL = self.num_shards, self.num_local_shards
        reg_fill = [0] * SL
        g_count = 0
        gkeys: set = set()
        cap = self.replay_cap
        runs: dict = {}  # key -> [first (h,l,d,a), lanes, nonuniform]
        for i, r in enumerate(requests):
            key = r.hash_key()
            if r.behavior == Behavior.GLOBAL:
                new_gkey = 0 if key in gkeys else 1
                if (g_count + 1 > SL * self.global_batch_per_shard
                        or len(gkeys) + new_gkey > self.max_global_updates):
                    return max(i, 1)
                g_count += 1
                gkeys.add(key)
            else:
                s = shard_of(key, S) - self.local_shard_offset
                if not 0 <= s < SL:
                    raise ValueError(
                        f"key {key!r} belongs to shard {shard_of(key, S)}, "
                        "not owned by this process")
                if reg_fill[s] + 1 > self.batch_per_shard:
                    return max(i, 1)
                if cap:
                    tup = (r.hits, r.limit, r.duration, r.algorithm)
                    run = runs.get(key)
                    if run is None:
                        runs[key] = [tup, 1, r.hits == 0]
                    else:
                        run[1] += 1
                        if not run[2] and (tup != run[0] or r.hits == 0):
                            run[2] = True
                        if run[2] and run[1] > cap:
                            return max(i, 1)
                reg_fill[s] += 1
        return len(requests)

    # ---------------------------------------------------------------- metrics

    @property
    def cache_size(self) -> int:
        reg = (self.native.size if self.native is not None
               else sum(len(t) for t in self.tables))
        return reg + len(self.gtable)

    @property
    def cache_hits(self) -> int:
        reg = (self.native.hits if self.native is not None
               else sum(t.hits for t in self.tables))
        return reg + self.gtable.hits

    @property
    def cache_misses(self) -> int:
        reg = (self.native.misses if self.native is not None
               else sum(t.misses for t in self.tables))
        return reg + self.gtable.misses

    def cache_stats(self, now: Optional[int] = None) -> dict:
        """One coherent view of the key-map caches: hit/miss counters plus
        free/live/expired slot occupancy (by the host expiry estimates),
        covering the regular tables AND the GLOBAL table.  Replaces reading
        cache_size/cache_hits/cache_misses piecemeal — a scrape sees one
        consistent set."""
        now = int(now) if now is not None else millisecond_now()
        if self.native is not None:
            live, expired, free = self.native.occupancy(now)
            hits, misses = self.native.hits, self.native.misses
            size = self.native.size
        else:
            hits = sum(t.hits for t in self.tables)
            misses = sum(t.misses for t in self.tables)
            size = sum(len(t) for t in self.tables)
            live = expired = free = 0
            for t in self.tables:
                st = t.stats(now)
                free += st["free"]
                live += st["live"]
                expired += st["expired"]
        g = self.gtable.stats(now)
        return {
            "size": size + len(self.gtable),
            "capacity": (self.num_local_shards * self.capacity_per_shard
                         + self.global_capacity),
            "hits": hits + self.gtable.hits,
            "misses": misses + self.gtable.misses,
            "free": free + g["free"],
            "live": live + g["live"],
            "expired": expired + g["expired"],
        }

    # ------------------------------------------------------- state lifecycle
    #
    # Snapshot/restore and live key migration (state/snapshot.py,
    # state/migrate.py).  Every method here touches the device arenas and
    # the host tables together, so callers MUST quiesce serving first: run
    # them on the same single-thread executor that dispatches windows (the
    # lockstep batcher's), exactly like apply_global_registration.

    def _put_sharded(self, local_np, dtype):
        """Host [S_local, ...] block -> device array with the shard
        sharding (global [S, ...] when the mesh spans processes)."""
        arr = np.ascontiguousarray(local_np, dtype=dtype)
        if self.multiprocess:
            return self._sharded_in(arr)
        return jax.device_put(jnp.asarray(arr), self._shard_sharding)

    def _put_repl(self, arr, dtype):
        """Host [G] array -> replicated device array (every process must
        pass identical values, as with any replicated input)."""
        arr = np.ascontiguousarray(arr, dtype=dtype)
        if self.multiprocess:
            return self._repl_in(arr)
        return jax.device_put(jnp.asarray(arr), self._repl_sharding)

    def export_state(self, now: Optional[int] = None, layout: str = "auto"):
        """Device->host export of this process's arena blocks + key maps as
        an ArenaSnapshot.  `layout` picks the wire time-encoding ("int64" |
        "compact32" | "auto" = compact32 iff the engine is compact-sound);
        serialization falls back to int64 whenever compact32 cannot
        represent the data exactly, so the choice is never lossy."""
        from gubernator_tpu.state.snapshot import ArenaSnapshot, SnapshotError
        now = self._resolve_now(now)
        planes = kernel.arena_to_rows(ArenaPlanes(
            *[np.asarray(self._fetch_local(p)) for p in self.state]))._asdict()
        gplanes = {n: np.asarray(jax.device_get(getattr(self.gstate, n)))
                   for n in BucketState._fields}
        gcfg = {n: np.asarray(jax.device_get(getattr(self.gcfg, n)))
                for n in GlobalConfig._fields}

        tables, native_tables = [], []
        if self.native is not None:
            if self.native.exact:
                raise SnapshotError(
                    "exact-keys native router cannot export its key map "
                    "(key bytes are not part of the export format); disable "
                    "GUBER_EXACT_KEYS / EngineConfig.exact_keys to snapshot")
            backend = "native"
            for s in range(self.num_local_shards):
                native_tables.append(self.native.export_keys(s))
        else:
            backend = "python"
            for t in self.tables:
                ents = t.export_entries()
                tables.append((
                    [e[0] for e in ents],
                    np.asarray([e[1] for e in ents], np.int32),
                    np.asarray([e[2] for e in ents], np.int64)))
        gents = self.gtable.export_entries()
        gtable = ([e[0] for e in gents],
                  np.asarray([e[1] for e in gents], np.int32),
                  np.asarray([e[2] for e in gents], np.int64))

        warm = None
        if self._tiers is not None:
            # the warm tier rides the same snapshot: rows exported in
            # canonical int64 absolute form (dumps re-encodes per layout)
            warm = self._tiers.warm.export_rows()

        if layout == "auto":
            layout = "compact32" if self._compact_sound else "int64"
        return ArenaSnapshot(
            now=now, layout=layout, warm=warm,
            num_shards=self.num_shards,
            capacity_per_shard=self.capacity_per_shard,
            global_capacity=self.global_capacity,
            num_local_shards=self.num_local_shards,
            local_shard_offset=self.local_shard_offset,
            compact_sound=self._compact_sound,
            backend=backend,
            planes=planes, gplanes=gplanes, gcfg=gcfg,
            tables=tables, native_tables=native_tables, gtable=gtable,
            gpending=sorted(self._gpending),
        )

    def import_state(self, snap, rebase_to: Optional[int] = None) -> None:
        """Replace the arenas + key maps with a snapshot's contents.

        By default times stay ABSOLUTE: downtime between export and restore
        counts against every TTL, exactly as if the process had kept
        running (restart equivalence vs an uninterrupted oracle).
        `rebase_to` instead shifts every live timestamp by
        (rebase_to - snap.now), preserving each bucket's remaining lifetime
        across a clock-domain change."""
        from gubernator_tpu.state.snapshot import SnapshotError
        for attr in ("num_shards", "capacity_per_shard", "global_capacity",
                     "num_local_shards", "local_shard_offset"):
            if getattr(snap, attr) != getattr(self, attr):
                raise SnapshotError(
                    f"snapshot geometry mismatch: {attr}={getattr(snap, attr)}"
                    f" but engine has {getattr(self, attr)}")
        if snap.backend == "native" and self.native is None:
            raise SnapshotError(
                "snapshot holds a native fingerprint table but this engine "
                "routes in Python; key strings cannot be recovered from "
                "fingerprints")
        if self.native is not None and self.native.exact:
            raise SnapshotError(
                "exact-keys native router cannot import a snapshot key map "
                "(stored keys would stay empty and every lookup would "
                "collide); disable exact_keys to restore")
        shift = 0 if rebase_to is None else int(rebase_to) - snap.now

        def shifted(planes):
            if shift == 0:
                return planes
            out = dict(planes)
            live = planes["expire"] != 0
            for name in ("tstamp", "expire"):
                a = planes[name].copy()
                a[live] += shift
                out[name] = a
            return out

        rp, gp = shifted(snap.planes), shifted(snap.gplanes)
        planes = kernel.arena_from_rows(
            BucketState(**{f: rp[f] for f in BucketState._fields}))
        self.state = ArenaPlanes(
            *[self._put_sharded(p, np.uint32) for p in planes[:-1]],
            algo=self._put_sharded(planes.algo, np.int32))
        self.gstate = BucketState(
            limit=self._put_repl(gp["limit"], np.int64),
            duration=self._put_repl(gp["duration"], np.int64),
            remaining=self._put_repl(gp["remaining"], np.int64),
            tstamp=self._put_repl(gp["tstamp"], np.int64),
            expire=self._put_repl(gp["expire"], np.int64),
            algo=self._put_repl(gp["algo"], np.int32),
        )
        self.gcfg = GlobalConfig(
            limit=self._put_repl(snap.gcfg["limit"], np.int64),
            duration=self._put_repl(snap.gcfg["duration"], np.int64),
            algo=self._put_repl(snap.gcfg["algo"], np.int32),
        )

        if snap.backend == "native":
            for s in range(self.num_local_shards):
                fp, slots, exps = snap.native_tables[s]
                self.native.import_keys(
                    s, np.asarray(fp, np.uint64), np.asarray(slots, np.int32),
                    np.asarray(exps, np.int64) + shift)
        elif self.native is not None:
            # python-table snapshot into a native-routed engine: recompute
            # the fingerprints the C router would have assigned (same
            # FNV-1a 64, host_router.cc fnv1a64).  Expiry comes from the
            # DEVICE plane, not the table: the Python table's estimate may
            # lag the kernel (leaky hits extend expire on device only),
            # which is harmless under Python routing (the kernel owns lazy
            # expiry) but the native router trusts its host expire at
            # lookup and would spuriously re-init a still-live bucket.
            for s, (keys, slots, exps) in enumerate(snap.tables):
                fp = np.asarray([_fnv1a64(k.encode("utf-8")) for k in keys],
                                np.uint64)
                si = np.asarray(slots, np.int64)
                dev = rp["expire"][s, si] if len(si) else \
                    np.empty(0, np.int64)
                self.native.import_keys(
                    s, fp, np.asarray(slots, np.int32),
                    np.maximum(np.asarray(exps, np.int64) + shift, dev))
        else:
            for t, (keys, slots, exps) in zip(self.tables, snap.tables):
                t.restore_entries(zip(
                    keys, np.asarray(slots, np.int64).tolist(),
                    (np.asarray(exps, np.int64) + shift).tolist()))
        gkeys, gslots, gexps = snap.gtable if snap.gtable else ([], [], [])
        self.gtable.restore_entries(zip(
            gkeys, np.asarray(gslots, np.int64).tolist(),
            (np.asarray(gexps, np.int64) + shift).tolist()))
        self._gpending = set(snap.gpending)
        warm = getattr(snap, "warm", None)
        if self._tiers is not None:
            from gubernator_tpu.state.tiers import WarmStore
            tm = self._tiers
            now_r = self._resolve_now(rebase_to)
            # import replaces ALL key state: rebuild the warm store fresh
            # (new epoch == the restore clock) and re-insert the snapshot's
            # warm rows with the same shift as the arenas
            tm.warm = WarmStore(tm.conf.warm_rows, tm.conf.layout,
                                epoch=now_r)
            tm.pending_spills.clear()
            tm.pending_promos.clear()
            if warm is not None:
                tm.warm.restore_rows(warm[0], warm[1], now=now_r,
                                     shift=shift)
        elif warm is not None and len(warm[0]):
            log.warning(
                "snapshot carries %d warm-tier rows but tiers are disabled "
                "on this engine; dropping them to cold (keys re-init from "
                "request configs)", len(warm[0]))
        if not snap.compact_sound:
            # the snapshotted arena held out-of-range configs; the compact
            # wire could saturate serving them, same guard as the live path
            self._compact_sound = False
            self._compact_enabled = False

    # Live key migration (state/migrate.py) — cluster mode only.  The mesh
    # resizes by re-sharding the arena, not by moving keys, and the native
    # router keeps fingerprints rather than key strings, so the row-level
    # API below requires single-process engines routing in Python.

    def _check_migratable(self) -> None:
        if self.native is not None:
            raise RuntimeError(
                "native router does not retain key strings; live migration "
                "needs the Python tables (EngineConfig use_native=False)")
        if self.multiprocess:
            raise RuntimeError(
                "live key migration applies to cluster mode (one process "
                "per instance); a mesh resizes by re-sharding the arena")

    def local_keys(self) -> List[str]:
        """Every committed regular key resident on this engine."""
        self._check_migratable()
        out: List[str] = []
        for t in self.tables:
            out.extend(k for k in t.keys() if not t.is_pending(k))
        return out

    def global_keys(self) -> List[str]:
        """Every committed GLOBAL key registered on this engine."""
        return [k for k in self.gtable.keys()
                if not self.gtable.is_pending(k)]

    def export_rows(self, keys: Sequence[str]) -> List[dict]:
        """Gather the live device rows for `keys` (regular arena) as host
        dicts.  Keys not resident here, still pending their initializing
        dispatch, or whose device row was never written are skipped."""
        self._check_migratable()
        picks = []
        for key in keys:
            s = shard_of(key, self.num_shards)
            t = self.tables[s]
            slot = t.peek(key)
            if slot is None or t.is_pending(key):
                continue
            picks.append((key, s, slot))
        if not picks:
            return []
        n = len(picks)
        m = _pad_pow2(n)
        si = np.full(m, self.num_shards, np.int32)       # OOB pad -> fill 0
        li = np.full(m, self.capacity_per_shard, np.int32)
        si[:n] = [p[1] for p in picks]
        li[:n] = [p[2] for p in picks]
        got = _gather_rows_jit(self.state, jnp.asarray(si), jnp.asarray(li))
        vals = {f: np.asarray(getattr(got, f))[:n]
                for f in BucketState._fields}
        rows = []
        for j, (key, _s, _slot) in enumerate(picks):
            if vals["expire"][j] == 0:
                continue  # registered but never device-initialized
            rows.append({
                "key": key,
                "limit": int(vals["limit"][j]),
                "duration": int(vals["duration"][j]),
                "remaining": int(vals["remaining"][j]),
                "tstamp": int(vals["tstamp"][j]),
                "expire": int(vals["expire"][j]),
                "algo": int(vals["algo"][j]),
            })
        return rows

    def import_rows(self, rows: Sequence[dict],
                    now: Optional[int] = None) -> tuple:
        """Install migrated regular rows into the local arena.  Returns
        (imported, skipped_stale).

        Init-flag semantics: an incoming row NEVER clobbers a fresher local
        entry.  Fresher means a local pending-init entry (a request already
        arrived here and its slot initializes this window — created after
        the source stopped being authoritative) or a committed local row
        whose device expire >= the incoming row's."""
        self._check_migratable()
        now = self._resolve_now(now)
        skipped = 0
        cand = []
        for row in rows:
            key = row["key"]
            s = shard_of(key, self.num_shards)
            t = self.tables[s]
            if t.is_pending(key):
                skipped += 1
                continue
            cand.append((key, s, t.peek(key), row))
        # one gather for every already-resident key's device expire
        resident = [(i, c[1], c[2]) for i, c in enumerate(cand)
                    if c[2] is not None]
        dev_expire = {}
        if resident:
            n = len(resident)
            m = _pad_pow2(n)
            si = np.full(m, self.num_shards, np.int32)
            li = np.full(m, self.capacity_per_shard, np.int32)
            si[:n] = [r[1] for r in resident]
            li[:n] = [r[2] for r in resident]
            exp = np.asarray(_gather_rows_jit(
                self.state, jnp.asarray(si), jnp.asarray(li)).expire)[:n]
            dev_expire = {r[0]: int(exp[j]) for j, r in enumerate(resident)}
        winners = []
        for i, (key, s, slot, row) in enumerate(cand):
            if i in dev_expire and dev_expire[i] >= row["expire"]:
                skipped += 1
                continue
            winners.append((key, s, row))
        if not winners:
            return 0, skipped
        n = len(winners)
        m = _pad_pow2(n)
        si = np.full(m, self.num_shards, np.int32)      # OOB pad -> dropped
        li = np.full(m, self.capacity_per_shard, np.int32)
        vals = {f: np.zeros(m, np.int64) for f in BucketState._fields}
        for j, (key, s, row) in enumerate(winners):
            si[j] = s
            li[j] = self.tables[s].upsert(key, now, row["expire"])
            for f in BucketState._fields:
                vals[f][j] = row[f]
        self.state = _scatter_rows_jit(
            self.state, jnp.asarray(si), jnp.asarray(li),
            BucketState(**{f: jnp.asarray(vals[f]) for f in
                           BucketState._fields}))
        return n, skipped

    def export_global_rows(self, keys: Sequence[str]) -> List[dict]:
        """Gather GLOBAL rows (replicated arena state + registration
        config) for re-registration on a new owner.  A registered key whose
        state row was never written still exports (expire 0): its CONFIG
        must move for the new owner to serve it."""
        picks = []
        for key in keys:
            slot = self.gtable.peek(key)
            if slot is None or self.gtable.is_pending(key):
                continue
            picks.append((key, slot))
        if not picks:
            return []
        n = len(picks)
        m = _pad_pow2(n)
        gi = np.full(m, self.global_capacity, np.int32)
        gi[:n] = [p[1] for p in picks]
        gst = _gather_grows_jit(self.gstate, jnp.asarray(gi))
        gcf = _gather_gcfg_jit(self.gcfg, jnp.asarray(gi))
        rows = []
        for j, (key, _slot) in enumerate(picks):
            rows.append({
                "key": key,
                "cfg_limit": int(np.asarray(gcf.limit)[j]),
                "cfg_duration": int(np.asarray(gcf.duration)[j]),
                "cfg_algo": int(np.asarray(gcf.algo)[j]),
                **{f: int(np.asarray(getattr(gst, f))[j])
                   for f in BucketState._fields},
            })
        return rows

    def import_global_rows(self, rows: Sequence[dict],
                           now: Optional[int] = None) -> tuple:
        """Register + install migrated GLOBAL rows.  Same staleness rule as
        import_rows; a row with expire 0 registers config only (its state
        row stays dead until traffic initializes it)."""
        now = self._resolve_now(now)
        skipped = 0
        winners = []
        for row in rows:
            key = row["key"]
            if self.gtable.is_pending(key):
                skipped += 1
                continue
            slot = self.gtable.peek(key)
            if slot is not None:
                dev = int(np.asarray(
                    jax.device_get(self.gstate.expire[slot])))
                if dev >= row["expire"] and not (dev == 0
                                                 and row["expire"] == 0):
                    skipped += 1
                    continue
            winners.append(row)
        if not winners:
            return 0, skipped
        n = len(winners)
        m = _pad_pow2(n)
        gi = np.full(m, self.global_capacity, np.int32)
        svals = {f: np.zeros(m, np.int64) for f in BucketState._fields}
        cvals = {f: np.zeros(m, np.int64) for f in GlobalConfig._fields}
        for j, row in enumerate(winners):
            est = row["expire"] if row["expire"] else now + row["cfg_duration"]
            gi[j] = self.gtable.upsert(row["key"], now, est)
            for f in BucketState._fields:
                svals[f][j] = row[f]
            cvals["limit"][j] = row["cfg_limit"]
            cvals["duration"][j] = row["cfg_duration"]
            cvals["algo"][j] = row["cfg_algo"]
            self._gpending.discard(row["key"])
        gij = jnp.asarray(gi)
        self.gstate = _scatter_grows_jit(
            self.gstate, gij,
            BucketState(**{f: jnp.asarray(svals[f])
                           for f in BucketState._fields}))
        self.gcfg = _scatter_gcfg_jit(
            self.gcfg, gij,
            GlobalConfig(**{f: jnp.asarray(cvals[f])
                            for f in GlobalConfig._fields}))
        return n, skipped

    def remove_keys(self, keys: Sequence[str]) -> int:
        """Drop regular keys from the host tables after they migrated away.
        The device rows become dead tenants: slot reuse re-initializes them
        (is_init), and routing no longer sends these keys here."""
        self._check_migratable()
        removed = 0
        for key in keys:
            s = shard_of(key, self.num_shards)
            if key in self.tables[s]:
                self.tables[s].remove(key)
                removed += 1
        return removed

    # --------------------------------------------------------- tiered state
    #
    # Warm tier (state/tiers.py): the fixed arena becomes a managed cache
    # over an unbounded keyspace.  Demotion rides SlotTable._reclaim via
    # the spill hook; promotion happens in _stage_requests; both resolve in
    # ONE batched gather + scatter at the pre-dispatch fence below.  All of
    # it runs on the dispatch thread (same quiesce contract as migration).

    def enable_tiers(self, conf, analytics=None,
                     epoch: Optional[int] = None):
        """Install the warm tier.  Requires Python routing tables and a
        single-process engine — the same constraint as live key migration
        (the native router keeps fingerprints, not key strings, and a mesh
        resizes by re-sharding rather than spilling).  `epoch` anchors the
        warm store's compact32 pair-rebase domain (defaults to now)."""
        from gubernator_tpu.state.tiers import TierManager
        self._check_migratable()
        if conf.warm_rows <= 0:
            raise ValueError(
                "enable_tiers needs warm capacity (GUBER_TIER_WARM > 0); "
                "warm_rows=0 means tiers stay off")
        t = TierManager(conf, epoch=self._resolve_now(epoch),
                        analytics=analytics)
        self._tiers = t
        for s, table in enumerate(self.tables):
            table.spill_cb = (
                lambda key, slot, expire, stale, _s=s:
                t.on_spill(_s, key, slot, expire, stale))
            table.heat_fn = t.heat
            table.victim_sample = conf.victim_sample
        return t

    def tier_stats(self) -> Optional[dict]:
        """Tier counters + warm occupancy for /metrics and cli debug;
        None when tiers are off."""
        return None if self._tiers is None else self._tiers.stats()

    def _tier_fence(self, now: int) -> None:
        """Resolve every demotion/promotion pending since the last dispatch
        — BEFORE this window's dispatch, while the victims' device rows are
        still intact and so the promoted rows are resident when the kernel
        reads them.  One gather + one scatter per window regardless of how
        many keys moved; spill rows found dead or expired on device drop to
        cold (the kernel's lazy expiry already treats them as misses, so
        the infinite-arena oracle would re-init them too)."""
        t = self._tiers
        t.fences += 1
        if t.analytics is not None and t.fences % 256 == 0:
            t.refresh_heat()
        spills, promos = t.drain_pending()
        if not spills and not promos:
            return
        # one gather covers the spills AND the from-spill promotion sources
        gather = [(k, sh, sl) for k, sh, sl in spills]
        src_ix = {}
        for key, p in promos:
            if p[3] is not None:
                src_ix[key] = len(gather)
                gather.append((key, p[3][0], p[3][1]))
        vals = None
        if gather:
            n = len(gather)
            m = _pad_pow2(n)
            si = np.full(m, self.num_shards, np.int32)   # OOB pad -> fill 0
            li = np.full(m, self.capacity_per_shard, np.int32)
            si[:n] = [g[1] for g in gather]
            li[:n] = [g[2] for g in gather]
            got = _gather_rows_jit(self.state, jnp.asarray(si),
                                   jnp.asarray(li))
            vals = {f: np.asarray(getattr(got, f))[:n]
                    for f in BucketState._fields}
        puts = []
        for j, (key, _sh, _sl) in enumerate(spills):
            if vals["expire"][j] <= now:
                # dead (never written) or already expired on device: cold
                t.counters["demote_dropped_expired"] += 1
                continue
            row = {f: int(vals[f][j]) for f in BucketState._fields}
            row["key"] = key
            puts.append(row)
        if puts:
            t.warm.put_batch(puts, now)
            t.counters["demotions"] += len(puts)
        if promos:
            rows = []
            for key, p in promos:
                if p[3] is not None:
                    j = src_ix[key]
                    row = {f: int(vals[f][j]) for f in BucketState._fields}
                    row["key"] = key
                    row["rel"] = False
                else:
                    row = p[2]
                rows.append((p[0], p[1], row))
            t.decode_rows([r for _, _, r in rows])
            n = len(rows)
            m = _pad_pow2(n)
            si = np.full(m, self.num_shards, np.int32)   # OOB pad -> dropped
            li = np.full(m, self.capacity_per_shard, np.int32)
            svals = {f: np.zeros(m, np.int64) for f in BucketState._fields}
            for j, (sh, sl, row) in enumerate(rows):
                si[j] = sh
                li[j] = sl
                for f in BucketState._fields:
                    svals[f][j] = row[f]
            self.state = _scatter_rows_jit(
                self.state, jnp.asarray(si), jnp.asarray(li),
                BucketState(**{f: jnp.asarray(svals[f])
                               for f in BucketState._fields}))
            t.counters["promotions"] += n

    def tier_maintain(self, now: Optional[int] = None) -> int:
        """Proactive demotion between windows: shards running above the
        demote watermark spill their coldest committed entries to warm in
        one batch, so staging under a full arena pays fence-time spills
        instead of per-lookup forced evictions.  Also refreshes the heat
        map from analytics.  Returns entries demoted or dropped."""
        if self._tiers is None:
            return 0
        t = self._tiers
        now = self._resolve_now(now)
        t.refresh_heat()
        if t.pending_spills or t.pending_promos:
            # a staging pass aborted before its dispatch: resolve the
            # leftovers first (their device rows are still pre-dispatch)
            self._tier_fence(now)
        hi = int(t.conf.demote_watermark * self.capacity_per_shard)
        picks = []
        for s, table in enumerate(self.tables):
            excess = len(table) - hi
            if excess <= 0:
                continue
            take = min(excess, t.conf.demote_batch)
            scanned = 0
            for key in table.keys():              # LRU order, oldest first
                if take <= 0 or scanned >= 4 * t.conf.demote_batch:
                    break
                scanned += 1
                if table.is_pending(key) or t.heat(key) > 0.0:
                    continue                      # hot by analytics: keep
                picks.append((key, s, table.peek(key)))
                take -= 1
        if not picks:
            return 0
        n = len(picks)
        m = _pad_pow2(n)
        si = np.full(m, self.num_shards, np.int32)
        li = np.full(m, self.capacity_per_shard, np.int32)
        si[:n] = [p[1] for p in picks]
        li[:n] = [p[2] for p in picks]
        got = _gather_rows_jit(self.state, jnp.asarray(si), jnp.asarray(li))
        vals = {f: np.asarray(getattr(got, f))[:n]
                for f in BucketState._fields}
        puts = []
        for j, (key, s, _slot) in enumerate(picks):
            self.tables[s].remove(key)
            if vals["expire"][j] <= now:
                t.counters["demote_dropped_expired"] += 1
                continue
            row = {f: int(vals[f][j]) for f in BucketState._fields}
            row["key"] = key
            puts.append(row)
        if puts:
            t.warm.put_batch(puts, now)
            t.counters["demotions"] += len(puts)
        return n

    def tier_warmup(self, max_rows: int = 512) -> None:
        """Pre-compile the fence's gather/scatter pow2 ladder up to
        `max_rows` so serving never pays the jit stall mid-window (the
        same contract as warmup(); the helpers compile per padded shape).
        All-OOB indices make every dispatch a no-op on the arena."""
        if self._tiers is None:
            return
        m = 8
        while m <= _pad_pow2(max_rows):
            si = jnp.full(m, self.num_shards, jnp.int32)
            li = jnp.full(m, self.capacity_per_shard, jnp.int32)
            got = _gather_rows_jit(self.state, si, li)
            zeros = BucketState(**{f: jnp.zeros(m, jnp.int64)
                                   for f in BucketState._fields})
            self.state = _scatter_rows_jit(self.state, si, li, zeros)
            jax.block_until_ready(got)
            m *= 2


def _pad_pow2(n: int) -> int:
    """Pad gather/scatter index vectors to a power of two (>= 8) so the
    jitted helpers compile for a handful of shapes, not one per call."""
    return max(8, 1 << (n - 1).bit_length())


def _fnv1a64(data: bytes) -> int:
    """FNV-1a 64 over key bytes — bit-identical to host_router.cc fnv1a64,
    for restoring a Python-table snapshot into a native-routed engine.
    The seed below is the router's literal constant, NOT the textbook FNV
    offset basis (the .cc drops the basis's last digit); what matters here
    is agreeing with the fingerprints the C side assigns, so mirror the
    code, not the spec.  0 is remapped to 1 (0 marks an empty table cell)."""
    h = 1469598103934665603
    for b in data:
        h ^= b
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h if h else 1


@jax.jit
def _gather_rows_jit(state: ArenaPlanes, si, li) -> BucketState:
    # the resident planes' rows as int64 rows; OOB padded indices read as 0
    # (mode="fill"); callers slice them off
    return kernel.arena_to_rows(jax.tree.map(
        lambda a: a.at[si, li].get(mode="fill", fill_value=0), state))


@jax.jit
def _scatter_rows_jit(state: ArenaPlanes, si, li,
                      vals: BucketState) -> ArenaPlanes:
    vals = kernel.arena_from_rows(vals._replace(
        algo=vals.algo.astype(jnp.int32)))
    return jax.tree.map(
        lambda a, v: a.at[si, li].set(v, mode="drop"), state, vals)


@jax.jit
def _gather_grows_jit(gstate: BucketState, gi) -> BucketState:
    return jax.tree.map(
        lambda a: a.at[gi].get(mode="fill", fill_value=0), gstate)


@jax.jit
def _scatter_grows_jit(gstate: BucketState, gi, vals) -> BucketState:
    return jax.tree.map(
        lambda a, v: a.at[gi].set(v.astype(a.dtype), mode="drop"),
        gstate, vals)


@jax.jit
def _gather_gcfg_jit(gcfg: GlobalConfig, gi) -> GlobalConfig:
    return jax.tree.map(
        lambda a: a.at[gi].get(mode="fill", fill_value=0), gcfg)


@jax.jit
def _scatter_gcfg_jit(gcfg: GlobalConfig, gi, vals) -> GlobalConfig:
    return jax.tree.map(
        lambda a, v: a.at[gi].set(v.astype(a.dtype), mode="drop"),
        gcfg, vals)


# shard_map specs of the two resident states: the sharded arena's planes,
# and the replicated GLOBAL table's int64 rows
_ARENA_SHARDED = ArenaPlanes(*[P(SHARD_AXIS)] * len(ArenaPlanes._fields))
_GSTATE_REPL = BucketState(*[P()] * len(BucketState._fields))


def _apply_control(gstate: BucketState, gcfg: GlobalConfig, upd, ups):
    """Apply host control-plane writes to the GLOBAL arena (once per dispatch).

    Upserts land first: authoritative replica state pushed by a cross-host
    owner (the reference's UpdatePeerGlobals -> Cache.Add path,
    gubernator.go:199-207).  Then host-issued slot (re)configurations: the
    config write refreshes limit/duration/algorithm from the latest request
    each window (the reference owner applies the config carried on each
    aggregated request, global.go:115-153); the state reset (expire=0 reads
    as never-initialized) happens only for lanes the host just (re)allocated.
    """
    (pslot, plimit, pduration, premaining, ptstamp, pexpire, palgo) = ups
    gstate = BucketState(
        limit=gstate.limit.at[pslot].set(plimit, mode="drop"),
        duration=gstate.duration.at[pslot].set(pduration, mode="drop"),
        remaining=gstate.remaining.at[pslot].set(premaining, mode="drop"),
        tstamp=gstate.tstamp.at[pslot].set(ptstamp, mode="drop"),
        expire=gstate.expire.at[pslot].set(pexpire, mode="drop"),
        algo=gstate.algo.at[pslot].set(palgo, mode="drop"),
    )
    gcfg = GlobalConfig(
        limit=gcfg.limit.at[pslot].set(plimit, mode="drop"),
        duration=gcfg.duration.at[pslot].set(pduration, mode="drop"),
        algo=gcfg.algo.at[pslot].set(palgo, mode="drop"),
    )
    return _apply_config(gstate, gcfg, upd)


def _apply_config(gstate: BucketState, gcfg: GlobalConfig, upd):
    """The host-issued slot-(re)configuration half of _apply_control: the
    config write refreshes limit/duration/algorithm from the latest request
    each window; the state reset (expire=0 reads as never-initialized)
    happens only for lanes the host just (re)allocated.  The pipeline
    drain's GLOBAL window applies ONLY this half — drains never carry
    upserts (mesh mode forbids them outright, and the single-process
    batcher routes them through step())."""
    uslot, ulimit, uduration, ualgo, rslot = upd
    gcfg = GlobalConfig(
        limit=gcfg.limit.at[uslot].set(ulimit, mode="drop"),
        duration=gcfg.duration.at[uslot].set(uduration, mode="drop"),
        algo=gcfg.algo.at[uslot].set(ualgo, mode="drop"),
    )
    gstate = gstate._replace(
        expire=gstate.expire.at[rslot].set(jnp.int64(0), mode="drop")
    )
    return gstate, gcfg


def _global_window(gstate: BucketState, gcfg: GlobalConfig, gb: WindowBatch,
                   gacc_row, now):
    """One window of GLOBAL traffic: replica reads + the reconciliation psum.

    The whole GLOBAL dance — the reference's async hit send plus owner
    broadcast (global.go:72-232) — is this one collective.  Reads see the
    pre-apply replica; the apply runs on psum'd (replicated) inputs only.
    """
    delta = kernel.global_accumulate(
        jnp.zeros_like(gstate.remaining), gb._replace(hits=gacc_row)
    )
    summed = lax.psum(delta, SHARD_AXIS)
    # The replica reads (shard-varying lanes) and the post-psum apply
    # (replicated lanes) run as two ladders.  One concatenated ladder would
    # be bit-identical, but it taints the apply half as shard-varying and
    # shard_map's replication check can then no longer prove the GLOBAL
    # arena's P() out_specs.
    gout = kernel.global_read(gstate, gb, now)
    return kernel.global_apply(gstate, gcfg, summed, now), gout


@lru_cache(maxsize=None)
def _compiled_step(mesh: Mesh):
    def shard_fn(state, gstate, gcfg, batch, gbatch, gacc, upd, ups, now):
            # Block shapes inside shard_map: state [1, C]; batch/gbatch [1, B*];
            # gstate/gcfg [G] (replicated); upd/ups [K*] (replicated).
            st = jax.tree.map(lambda a: a[0], state)
            bt = WindowBatch(*jax.tree.map(lambda a: a[0], batch))
            new_st, out = kernel.window_step(st, bt, now)

            gstate, gcfg = _apply_control(gstate, gcfg, upd, ups)
            gb = WindowBatch(*jax.tree.map(lambda a: a[0], gbatch))
            new_g, gout = _global_window(gstate, gcfg, gb, gacc[0], now)

            expand = lambda a: a[None]
            return (
                jax.tree.map(expand, new_st),
                kernel.pack_outputs(out, gout)[None],
                new_g,
                gcfg,
            )

    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            _ARENA_SHARDED,
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
            WindowBatch(*[P(SHARD_AXIS)] * 6),
            WindowBatch(*[P(SHARD_AXIS)] * 6),
            P(SHARD_AXIS),
            (P(), P(), P(), P(), P()),
            (P(),) * 7,
            P(),
        ),
        out_specs=(
            _ARENA_SHARDED,
            P(SHARD_AXIS),
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
        ),
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2))


@lru_cache(maxsize=None)
def _compiled_step_compact(mesh: Mesh):
    """The serving fast path: compact request/response wire format.

    Same computation as _compiled_step, but the regular-key window crosses
    host<->device packed (kernel.decode_batch / encode_output_compact — 16B
    up + 8B down per lane instead of ~41B + 32B), cutting the per-window
    transfer cost ~3x.  GLOBAL lanes keep the full format: they are few
    (Bg ≈ 128) and their stored state may carry configs that predate the
    host's range checks, so they are exempt from compact saturation rules.
    """
    def shard_fn(state, gstate, gcfg, packed, gbatch, gacc, upd, ups, now):
        st = jax.tree.map(lambda a: a[0], state)
        bt = kernel.decode_batch(packed[0])
        new_st, out = kernel.window_step_compact32(st, bt, now)
        enc = kernel.encode_output_compact(out, now)

        gstate, gcfg = _apply_control(gstate, gcfg, upd, ups)
        gb = WindowBatch(*jax.tree.map(lambda a: a[0], gbatch))
        new_g, gout = _global_window(gstate, gcfg, gb, gacc[0], now)

        expand = lambda a: a[None]
        gfused = jnp.stack(
            [gout.status.astype(jnp.int64), gout.limit, gout.remaining,
             gout.reset_time], axis=-1)
        return (
            jax.tree.map(expand, new_st),
            enc[None],
            gfused[None],
            new_g,
            gcfg,
        )

    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            _ARENA_SHARDED,
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
            P(SHARD_AXIS),
            WindowBatch(*[P(SHARD_AXIS)] * 6),
            P(SHARD_AXIS),
            (P(), P(), P(), P(), P()),
            (P(),) * 7,
            P(),
        ),
        out_specs=(
            _ARENA_SHARDED,
            P(SHARD_AXIS),
            P(SHARD_AXIS),
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
        ),
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2))


@lru_cache(maxsize=None)
def _compiled_global_register(mesh: Mesh):
    """GLOBAL registration writes into the replicated arena — deliberately
    COLLECTIVE-FREE (pure scatters on fully-replicated arrays), so mesh
    processes may execute it at different wall times without wedging the
    lockstep: there is nothing to synchronize.  Correctness across hosts
    comes from every process applying identical registrar-ordered batches
    (see RateLimitEngine.register_global_keys)."""
    repl6 = BucketState(*[NamedSharding(mesh, P())] * 6)
    repl3 = GlobalConfig(*[NamedSharding(mesh, P())] * 3)

    def fn(gstate: BucketState, gcfg: GlobalConfig, upd):
        uslot, ulimit, uduration, ualgo, rslot = upd
        gcfg = GlobalConfig(
            limit=gcfg.limit.at[uslot].set(ulimit, mode="drop"),
            duration=gcfg.duration.at[uslot].set(uduration, mode="drop"),
            algo=gcfg.algo.at[uslot].set(ualgo, mode="drop"),
        )
        # expire=0 reads as never-initialized: a freshly (re)allocated slot
        # must not inherit its previous tenant's live counters
        gstate = gstate._replace(
            expire=gstate.expire.at[rslot].set(jnp.int64(0), mode="drop"))
        return gstate, gcfg

    return jax.jit(fn, donate_argnums=(0, 1),
                   out_shardings=(repl6, repl3))


@lru_cache(maxsize=None)
def _compiled_pipeline_step(mesh: Mesh):
    """K compact serving windows in ONE device dispatch — the drain
    executable of the serving pipeline (core/pipeline.py).

    Differences from _compiled_multi_step, all in service of making the
    response transfer as small and as late-bound as possible (the fetch
    round trip bounds small-window latency):

      * regular keys only — GLOBAL traffic needs the psum + control-plane
        writes and rides the legacy step path instead, so this executable
        carries zero GLOBAL inputs and outputs;
      * requests arrive in the compact 16B/lane format (kernel.decode_batch)
        and responses leave as ONE 8B word per lane (encode_output_word);
      * the response's `limit` field (stored limit, which on hit paths can
        differ from the request's) is NOT shipped per lane: the host echoes
        the request limit and fetches the device-side limit plane only when
        a window's mismatch flag fires (config changed on a live bucket —
        rare).

    The reference analog of the stacking is a peer draining its queue
    back-to-back without waiting for each response (peers.go:143-172).
    """
    def shard_fn(state, packed, nows):
        # Block shapes: state [1, C]; packed [K, 1, B, 2]; nows [K].
        st = jax.tree.map(lambda a: lax.squeeze(a, (0,)), state)
        st, words, limits, mism = _drain_scan(st, packed, nows)
        expand = lambda a: a[None]
        return (
            jax.tree.map(expand, st),
            words[:, None],
            limits[:, None],
            mism[:, None],
        )

    stackedP = stacked_spec()
    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(_ARENA_SHARDED, stackedP, P()),
        out_specs=(_ARENA_SHARDED, stackedP, stackedP, stackedP),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def _drain_scan(st: ArenaPlanes, packed, nows):
    """The drain's regular-key K windows (shared by the regular and the
    GLOBAL-composed drain executables): K compact windows applied
    sequentially to one shard's block, each decode → window_step_compact32
    → word-encode.  Returns (state, words[K,B], limits[K,B], mism[K])."""
    def body(st, xs):
        pk, now = xs
        bt = kernel.decode_batch(pk[0])
        st, out = kernel.window_step_compact32(st, bt, now)
        word = kernel.encode_output_word(out, now)
        mism = jnp.any((out.limit != bt.limit) & (bt.slot >= 0))
        return st, (word, out.limit, mism)

    st, (words, limits, mism) = lax.scan(body, st, (packed, nows))
    return st, words, limits, mism


@lru_cache(maxsize=None)
def _compiled_analytics_reduce(mesh: Mesh, depth: int, width: int,
                               tenant_slots: int, topk: int,
                               over_weight: int):
    """The traffic-analytics reduction (ops/analytics.py shard_stats) as a
    collective-free shard_map'd executable: per shard, fold one drain's
    (packed, words, tenants) into the resident count-min sketch (donated
    carry) and emit one flat stats row.  Deliberately NOT part of the
    drain builders: keyed only on geometry, it composes unchanged with
    the standalone drain and leaves its jaxpr byte-identical when
    analytics is off."""
    from gubernator_tpu.ops import analytics as ops_analytics

    def shard_fn(sketch, exp_lo, exp_hi, packed, words, tenants, now, decay):
        # Block shapes: sketch [1, D, W]; exp_lo/exp_hi [1, C] (the arena's
        # expiry planes, joined here: this reduction reads all C); packed
        # [K, 1, B, 2]; words [K, 1, B]; tenants [K, 1, B]; now/decay [].
        sk, stats = ops_analytics.shard_stats(
            sketch[0], packed[:, 0], words[:, 0], tenants[:, 0],
            kernel.join64(exp_lo[0], exp_hi[0]),
            now, decay, tenant_slots=tenant_slots, topk=topk,
            over_weight=over_weight)
        return sk[None], stats[None]

    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                  stacked_spec(), stacked_spec(), stacked_spec(), P(), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )
    return jax.jit(sharded, donate_argnums=(0,))


@lru_cache(maxsize=None)
def _compiled_pipeline_step_global(mesh: Mesh, analytics=None):
    """The mesh serving drain: _compiled_pipeline_step's K-scan PLUS one
    GLOBAL reconciliation window composed around it — the lockstep tick's
    single executable.

    Every chip runs window_step_compact32 per window over its own
    plane-arena shard, and the whole drain pays exactly ONE collective:
    the GLOBAL hit-delta psum of `_global_window`, applied once at the
    drain's timestamp (nows[0]; the lockstep tick stages all K windows at
    the tick time, so there is nothing later to order against), where the
    legacy mesh step pays a psum per window.

    GLOBAL lanes keep the FULL wire format (they are few — Bg per shard —
    and exempt from the compact saturation rules); the control plane is
    the upd 5-tuple only (config refresh + reallocation resets): drains
    never carry upserts.  Donation covers the sharded arena and the
    replicated GLOBAL arena/config, so planes are carried, not copied,
    across ticks.

    `analytics` (None or the geometry 5-tuple (sketch_depth, sketch_width,
    tenant_slots, topk, over_weight)) composes the per-drain stats
    reduction (ops/analytics.py shard_stats) INTO this executable: the
    reduction reads the drain's own packed stack, its response words and
    the post-drain expiry plane IN PLACE — no second dispatch, no second
    executable in the tick's collective sequence.  With analytics=None the
    traced body is byte-identical to the pre-analytics builder (the
    analytics-off serving path is provably unchanged); the geometry is
    config-level and identical on every process, so the executable choice
    is mesh-legal."""
    def shard_fn(state, gstate, gcfg, packed, gbatch, gacc, upd, nows, *an):
        # Block shapes: state [1, C]; packed [K, 1, B, 2]; gbatch/gacc
        # [1, Bg]; gstate/gcfg [G] (replicated); upd [Kg] (replicated);
        # nows [K]; analytics extras: sketch [1, D, W]; tenants [K, 1, B];
        # decay [].
        sq = lambda a: lax.squeeze(a, (0,))
        sq1 = lambda a: lax.squeeze(a, (1,))
        st = jax.tree.map(sq, state)
        st, words, limits, mism = _drain_scan(st, packed, nows)

        gstate, gcfg = _apply_config(gstate, gcfg, upd)
        gb = WindowBatch(*jax.tree.map(sq, gbatch))
        new_g, gout = _global_window(gstate, gcfg, gb, sq(gacc), nows[0])
        gfused = jnp.stack(
            [gout.status.astype(jnp.int64), gout.limit, gout.remaining,
             gout.reset_time], axis=-1)

        expand = lambda a: a[None]
        outs = (
            jax.tree.map(expand, st),
            words[:, None],
            limits[:, None],
            mism[:, None],
            gfused[None],
            new_g,
            gcfg,
        )
        if analytics is not None:
            _, _, tenant_slots, topk, over_weight = analytics
            sketch, tenants, decay = an
            # the occupancy counts read all C expiries: joined here only
            expire = kernel.join64(st.expire_lo, st.expire_hi)
            from gubernator_tpu.ops import analytics as ops_analytics
            sk, stats = ops_analytics.shard_stats(
                sq(sketch), sq1(packed), words, sq1(tenants), expire,
                nows[0], decay, tenant_slots=tenant_slots, topk=topk,
                over_weight=over_weight)
            outs = outs + (sk[None], stats[None])
        return outs

    stackedP = stacked_spec()
    in_specs = (
        _ARENA_SHARDED,
        _GSTATE_REPL,
        GlobalConfig(*[P()] * 3),
        stackedP,
        WindowBatch(*[shard_spec()] * 6),
        shard_spec(),
        (P(), P(), P(), P(), P()),
        P(),
    )
    out_specs = (
        _ARENA_SHARDED,
        stackedP,
        stackedP,
        stackedP,
        shard_spec(),
        _GSTATE_REPL,
        GlobalConfig(*[P()] * 3),
    )
    donate = (0, 1, 2)
    if analytics is not None:
        in_specs = in_specs + (P(SHARD_AXIS), stackedP, P())
        out_specs = out_specs + (P(SHARD_AXIS), P(SHARD_AXIS))
        donate = donate + (8,)  # the resident sketch is a carried plane
    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(sharded, donate_argnums=donate)


@lru_cache(maxsize=None)
def _compiled_multi_step(mesh: Mesh, with_global: bool = True):
    """K batching windows applied in ONE device dispatch via lax.scan.

    Each scanned iteration is a full serving window — its own timestamp, its
    own in-window sequencing, its own GLOBAL psum — identical in semantics to
    K sequential `_compiled_step` calls.  What it saves is K-1 host→device
    dispatch round trips, so scanning windows is the throughput path when
    the host has a backlog (the reference analog: a peer draining
    its queue ships batches back-to-back without waiting for each response,
    peers.go:143-172).

    Control-plane writes (GLOBAL upserts/config, host-rare) are applied once,
    before the first window.  Stacked inputs carry a leading K dimension;
    `nows` is i64[K], one timestamp per window.

    `with_global=False` compiles the GLOBAL-skipping variant: most stacked
    dispatches carry ZERO GLOBAL lanes and inert control (every slot points
    one past the arena), yet the composed executable still ran the whole
    GLOBAL sub-window — gathers, scatters and a psum per scanned iteration
    — just to produce an all-dropped output block.  Statically skipping it
    removes those ops from every window; the fused output keeps
    its [K, B+Bg, 4] shape (GLOBAL rows zero-filled) so every decode path
    is unchanged.  step_windows picks the variant from host-staged
    inertness, single-process only — a per-process data-dependent
    executable choice would break the mesh collective contract.
    """
    def shard_fn(state, gstate, gcfg, batches, gbatches, gaccs, upd, ups, nows):
        # Block shapes: state [1, C]; batches [K, 1, B]; gbatches [K, 1, Bg];
        # gaccs [K, 1, Bg]; gstate/gcfg [G] replicated; nows [K].
        st = jax.tree.map(lambda a: a[0], state)
        if with_global:
            gstate, gcfg = _apply_control(gstate, gcfg, upd, ups)

        def body(carry, xs):
            st, gst = carry
            b, gb, gacc, now = xs
            bt = WindowBatch(*jax.tree.map(lambda a: a[0], b))
            st, out = kernel.window_step(st, bt, now)
            if not with_global:
                o = jnp.stack([out.status.astype(jnp.int64), out.limit,
                               out.remaining, out.reset_time], axis=-1)
                Bg = gb.slot.shape[-1]
                fused = jnp.concatenate(
                    [o, jnp.zeros((Bg, 4), jnp.int64)], axis=0)
                return (st, gst), fused
            gbt = WindowBatch(*jax.tree.map(lambda a: a[0], gb))
            gst, gout = _global_window(gst, gcfg, gbt, gacc[0], now)
            return (st, gst), kernel.pack_outputs(out, gout)

        (st, gst), fused = lax.scan(
            body, (st, gstate), (batches, gbatches, gaccs, nows)
        )
        expand = lambda a: a[None]
        # fused: [K, B+Bg, 4] -> [K, 1, B+Bg, 4] so the shard axis is explicit
        return (
            jax.tree.map(expand, st),
            fused[:, None],
            gst,
            gcfg,
        )

    stackedP = P(None, SHARD_AXIS)
    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            _ARENA_SHARDED,
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
            WindowBatch(*[stackedP] * 6),
            WindowBatch(*[stackedP] * 6),
            stackedP,
            (P(), P(), P(), P(), P()),
            (P(),) * 7,
            P(),
        ),
        out_specs=(
            _ARENA_SHARDED,
            stackedP,
            _GSTATE_REPL,
            GlobalConfig(*[P()] * 3),
        ),
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2))
