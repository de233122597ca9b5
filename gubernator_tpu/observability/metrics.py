"""Prometheus metrics with the reference's metric names.

Metric surface parity (SURVEY.md §5):
  cache_size, cache_access_count{type}          reference cache/lru.go:56-59
  async_durations, broadcast_durations          reference global.go:44-51
  grpc_request_counts{status}/{method},
  grpc_request_duration_milliseconds            reference prometheus.go:52-59

Plus TPU-native additions under guber_tpu_*: device window count, window
occupancy, device step duration.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, Optional

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client import CONTENT_TYPE_LATEST

# Canonical stage names of the request lifecycle, in pipeline order.
# observability/tracing.py spans, the stage histograms, and the debug
# snapshot all use exactly these labels so dashboards, traces, and the
# `cli debug` table line up column-for-column.
STAGES = (
    "tick_lag",         # lockstep: a tick's deadline -> the tick runs
    "enqueue",          # submit -> appended to the pending window
    "admission_wait",   # oldest request of a drain: queued -> drain started
    "engine_queue",     # loop hands the drain over -> engine thread starts it
    "window_fill",      # host-side window build (pack keys, stage cols)
    "device_dispatch",  # engine thread: device step launch (the enqueue)
    "dispatch_hop",     # dispatch done -> _on_dispatched runs on the loop
    "fetch_queue",      # dispatch done -> a fetch worker picks the drain up
    "drain_commit",     # fetch thread: device_wait + decode
    "device_wait",      # fetch thread blocked on the device->host read
    "decode",           # fetch thread: every job's finish() (decode, encode)
    "complete_hop",     # fetch done -> _on_completed runs on the loop
    "commit",           # loop thread: futures resolved, arena released
    "peer_forward",     # non-owner hop: peer-lane RPC round trip
    "global_broadcast", # GLOBAL lane: owner's broadcast to all peers
)

# Stages of ONE request, summed per drain into
# guber_tpu_request_stage_{seconds,requests}_total (core/pipeline.py):
# queued -> its drain started -> its drain committed -> its coroutine ran
# again.  With the handler's own grpc_request_duration_milliseconds they
# split the server's time per RPC into measured parts.
REQUEST_STAGES = ("queue_wait", "in_drain", "reply_wake")

# Why _pump returned without dispatching
# (guber_tpu_pump_hold_seconds_total): `empty` = room for a drain and
# nothing queued, `gate` = the occupancy gate (a drain in flight and less
# than one batch queued), `coalesce` = the batch-wait timer, `depth` = work
# queued behind a full pipeline, `engine` = a batch queued and room under
# the depth, but the engine thread is still packing or enqueuing the drain
# before.
PUMP_HOLD_REASONS = ("empty", "gate", "coalesce", "depth", "engine")

# What a lockstep tick did (guber_tpu_lockstep_ticks_total): `drain` = it
# dispatched staged work, `idle` = nothing was queued, `held` = work was
# queued behind the pipeline's depth, its occupancy gate or a busy engine
# thread, `skipped` =
# whole periods passed over because the host was behind its deadlines
# (they are no ticks: nothing ran).  How a lockstep decision came
# (guber_tpu_lockstep_decisions_total): `raw` = in a whole RPC staged by
# the raw-RPC lane, `item` = per item through the pipeline's drain,
# `legacy` = per item through the tick's legacy step.
LOCKSTEP_TICK_KINDS = ("drain", "idle", "held", "skipped")
LOCKSTEP_LANES = ("raw", "item", "legacy")

# Lane width of a dispatched drain's executable (guber_tpu_drains_total):
# `full` = batch_per_shard, `narrow` = any smaller lane bucket.  Two fixed
# values, so a reader can name them whatever the engine's B is; the count
# per width is `pipeline.drain_widths` in /v1/admin/debug.
DRAIN_WIDTHS = ("narrow", "full")

# How many other drains were in flight (pumped and not yet committed) when
# the pump let a drain go (guber_tpu_drain_overlap_total): "0" = the
# pipeline was empty, so the drain runs alone unless a later one joins it,
# "2" = two or more.  The same counts are `pipeline.drain_overlap` in
# /v1/admin/debug.
DRAIN_AHEAD = ("0", "1", "2")


class _StageRing:
    """Fixed-size ring of recent stage durations (seconds) behind one
    lock — the rolling-window source for the p50/p95/p99 snapshot.  A
    Prometheus histogram alone can't answer "p99 over the last minute"
    without a scraping sidecar; the ring keeps the last `size` samples so
    the debug endpoint and `cli load` read live quantiles in-process."""

    __slots__ = ("_buf", "_size", "_idx", "_count", "_lock")

    def __init__(self, size: int = 1024):
        self._buf = [0.0] * size
        self._size = size
        self._idx = 0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._buf[self._idx] = seconds
            self._idx = (self._idx + 1) % self._size
            if self._count < self._size:
                self._count += 1

    def snapshot(self) -> Optional[dict]:
        with self._lock:
            n = self._count
            if n == 0:
                return None
            samples = sorted(self._buf[:n] if n < self._size
                             else list(self._buf))

        def pct(p: float) -> float:
            return samples[min(n - 1, int(math.ceil(p * n)) - 1)] * 1000.0

        return {
            "count": n,
            "p50_ms": pct(0.50),
            "p95_ms": pct(0.95),
            "p99_ms": pct(0.99),
            "mean_ms": sum(samples) / n * 1000.0,
        }


class Metrics:
    """Per-instance metric registry (instances in one process each get their
    own, like each reference node's prometheus.Registry, main.go:53)."""

    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self._scrape_hooks = []
        self.cache_size = Gauge(
            "cache_size",
            "Size of the cache which holds the rate limits.",
            registry=self.registry,
        )
        self.cache_access_count = Counter(
            "cache_access_count",
            "Cache access counts.",
            ["type"],
            registry=self.registry,
        )
        self.async_durations = Histogram(
            "async_durations",
            "The duration of GLOBAL async sends in seconds.",
            registry=self.registry,
        )
        self.broadcast_durations = Histogram(
            "broadcast_durations",
            "The duration of GLOBAL broadcasts to peers in seconds.",
            registry=self.registry,
        )
        self.grpc_request_counts = Counter(
            "grpc_request_counts",
            "The count of gRPC requests.",
            ["status", "method"],
            registry=self.registry,
        )
        self.grpc_request_duration = Histogram(
            "grpc_request_duration_milliseconds",
            "The timings of gRPC requests in milliseconds.",
            ["method"],
            registry=self.registry,
        )
        # TPU-native
        self.window_count = Counter(
            "guber_tpu_windows_total",
            "Device windows dispatched.",
            registry=self.registry,
        )
        self.window_occupancy = Histogram(
            "guber_tpu_window_occupancy",
            "Requests per device window.",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1000),
            registry=self.registry,
        )
        # duplicate-run aggregation: decisions served vs lanes staged —
        # rate(decisions)/rate(lanes) is the live fold factor
        self.agg_decisions = Counter(
            "guber_tpu_aggregation_decisions_total",
            "Decisions served by the pipelined drain.",
            registry=self.registry,
        )
        self.agg_lanes = Counter(
            "guber_tpu_aggregation_lanes_total",
            "Device lanes staged by the pipelined drain.",
            registry=self.registry,
        )
        self.window_duration = Histogram(
            "guber_tpu_window_duration_seconds",
            "Wall time of one device window step.",
            registry=self.registry,
        )
        # overlapped drain pipeline (core/pipeline.py): concurrent drains in
        # flight (the overlap ratio and the arena ring's reuse counts are
        # in /v1/admin/debug, pipeline.overlap)
        self.pipeline_inflight_windows = Gauge(
            "guber_tpu_pipeline_inflight_windows",
            "Drain windows currently in flight between dispatch and commit.",
            registry=self.registry,
        )
        # deferred-fetch dispatch chain (core/pipeline.py): the adaptive
        # stride (drains per stacked D2H fetch); chained_pending and
        # fetch_elided are in /v1/admin/debug, pipeline.overlap
        self.chain_fetch_stride = Gauge(
            "guber_tpu_chain_fetch_stride",
            "Current deferred-fetch chain stride (drains per stacked "
            "fetch; 1 = fetch every drain).",
            registry=self.registry,
        )
        # device-time flight recorder (observability/devprof.py): the
        # always-on dispatch->fetch-ready window clock per executable arm
        # (compact32_xla / composed_drain / composed_analytics; its EWMA is
        # in /v1/admin/debug, devprof.clock) and the continuous-mode
        # capture outcomes
        self.device_window_ms = Histogram(
            "guber_tpu_device_window_ms",
            "Dispatch-to-fetch-ready wall time of one drain window, by "
            "executable arm.",
            ["arm"],
            buckets=(0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 1000),
            registry=self.registry,
        )
        self.devprof_captures = Counter(
            "guber_tpu_devprof_captures_total",
            "Continuous-profiling capture cycles by outcome (folded = "
            "parsed into the kernel table; shed = skipped, a capture was "
            "already in flight; empty = trace parsed to nothing).",
            ["status"],  # folded | shed | empty
            registry=self.registry,
        )
        # state lifecycle (state/snapshot.py, state/migrate.py): the slot
        # occupancy gauges come from engine.cache_stats at scrape time
        self.cache_slots = Gauge(
            "guber_tpu_cache_slots",
            "Arena slot occupancy by state.",
            ["state"],  # free | live | expired
            registry=self.registry,
        )
        self.snapshot_duration = Histogram(
            "guber_tpu_snapshot_duration_seconds",
            "Wall time of one arena snapshot (export + serialize + write).",
            registry=self.registry,
        )
        self.snapshot_size = Gauge(
            "guber_tpu_snapshot_bytes",
            "Size of the last written snapshot in bytes.",
            registry=self.registry,
        )
        self.snapshot_total = Counter(
            "guber_tpu_snapshots_total",
            "Snapshot attempts.",
            ["status"],  # success | failed
            registry=self.registry,
        )
        self.restore_age = Gauge(
            "guber_tpu_restore_age_seconds",
            "Age of the snapshot restored at boot (0 when cold-started).",
            registry=self.registry,
        )
        self.migrated_keys = Counter(
            "guber_tpu_migrated_keys_total",
            "Bucket rows shipped or imported by live key migration.",
            ["direction"],  # out | in
            registry=self.registry,
        )
        self.migration_skipped_stale = Counter(
            "guber_tpu_migration_skipped_stale_total",
            "Incoming migrated rows dropped because a fresher local entry "
            "existed.",
            registry=self.registry,
        )
        # tiered key state (state/tiers.py): hot-arena <-> warm-store flow
        self.tier_events = Counter(
            "guber_tpu_tier_events_total",
            "Tiered key-state events by kind: promote/demote row moves, "
            "warm_hit/cold_miss on staging lookups behind a table miss, "
            "warm_evict overflow drops, demote_drop dead-or-expired spills, "
            "demote_stale same-drain victims dropped to cold.",
            ["event"],
            registry=self.registry,
        )
        self.tier_warm_rows = Gauge(
            "guber_tpu_tier_warm_rows",
            "Rows resident in the warm tier.",
            registry=self.registry,
        )
        self.tier_warm_bytes = Gauge(
            "guber_tpu_tier_warm_bytes",
            "Host bytes allocated to the warm tier's SoA store.",
            registry=self.registry,
        )
        # QoS subsystem (gubernator_tpu/qos/): admission queue, sheds by
        # reason, the AIMD window, and per-peer breaker state
        self.qos_queue_depth = Gauge(
            "guber_qos_queue_depth",
            "Pending decisions held in the bounded admission queue.",
            registry=self.registry,
        )
        self.qos_shed = Counter(
            "guber_qos_shed_total",
            "Requests shed by admission control, by reason.",
            ["reason"],  # queue_full | deadline | breaker_open
            registry=self.registry,
        )
        self.qos_effective_window = Gauge(
            "guber_qos_effective_window",
            "Congestion-adaptive window size (decisions per dispatch).",
            registry=self.registry,
        )
        self.qos_drain_latency_ewma = Gauge(
            "guber_qos_drain_latency_ewma_seconds",
            "EWMA of observed drain wall time feeding the AIMD.",
            registry=self.registry,
        )
        self.qos_drain_depth_ewma = Gauge(
            "guber_qos_drain_depth_ewma",
            "EWMA of occupied drain depth feeding the AIMD.",
            registry=self.registry,
        )
        self.breaker_state = Gauge(
            "guber_qos_breaker_state",
            "Per-peer circuit breaker state "
            "(0=closed, 1=half_open, 2=open).",
            ["peer"],
            registry=self.registry,
        )
        self.peer_retries = Counter(
            "guber_qos_peer_retries_total",
            "Peer-lane RPC retries after transient failures.",
            ["peer"],
            registry=self.registry,
        )
        self.fail_open_served = Counter(
            "guber_qos_fail_open_total",
            "Forwards answered locally (non-authoritative) while the "
            "owner's breaker was open.",
            registry=self.registry,
        )
        # self-healing ring (net/health.py + global_sync hinted handoff):
        # what we failed to send, what we buffered instead of dropping,
        # and what the failure detector thinks of each peer
        self.global_send_errors = Counter(
            "global_send_errors_total",
            "Failed per-peer GLOBAL aggregated-hit sends (after the peer "
            "lane's own retries).",
            ["peer"],
            registry=self.registry,
        )
        self.broadcast_errors = Counter(
            "broadcast_errors_total",
            "Failed per-peer GLOBAL owner-broadcast sends.",
            ["peer"],
            registry=self.registry,
        )
        self.hints = Counter(
            "guber_hints_total",
            "Hinted-handoff buffer events, by event "
            "(queued | replayed | expired).",
            ["event", "peer"],
            registry=self.registry,
        )
        self.peer_health_state = Gauge(
            "guber_peer_health_state",
            "Failure-detector verdict per peer (0=up, 1=suspect, 2=down).",
            ["peer"],
            registry=self.registry,
        )
        self.ring_rehomes = Counter(
            "guber_ring_rehomes_total",
            "Automatic ring membership changes driven by the failure "
            "detector, by direction (down | up).",
            ["direction"],
            registry=self.registry,
        )
        # stage-latency decomposition (observability/tracing.py records the
        # same boundaries as spans): per-stage wall time at window/drain
        # granularity, always on — a few µs per window, amortized over up
        # to 1000 decisions
        self.stage_duration = Histogram(
            "guber_tpu_stage_duration_ms",
            "Wall time of one request-lifecycle stage in milliseconds.",
            ["stage"],
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100,
                     250, 500, 1000, 2500),
            registry=self.registry,
        )
        # one request's stages, summed per drain (REQUEST_STAGES above):
        # seconds / requests is the mean per request, over the very
        # requests grpc_request_duration_milliseconds counts
        self.request_stage_seconds = Counter(
            "guber_tpu_request_stage_seconds_total",
            "Seconds requests spent in a stage of their own lifecycle, "
            "summed over the requests of every committed drain.",
            ["stage"],
            registry=self.registry,
        )
        self.request_stage_requests = Counter(
            "guber_tpu_request_stage_requests_total",
            "Requests counted into guber_tpu_request_stage_seconds_total.",
            ["stage"],
            registry=self.registry,
        )
        self.pump_hold_seconds = Counter(
            "guber_tpu_pump_hold_seconds_total",
            "Seconds the pump held no dispatch, by reason (empty = room "
            "for a drain and nothing queued | gate | coalesce | depth | "
            "engine = the engine thread still busy with the drain before).",
            ["reason"],
            registry=self.registry,
        )
        self.drains = Counter(
            "guber_tpu_drains_total",
            "Drains dispatched, by the lane width of their executable "
            "(narrow = a lane bucket below batch_per_shard | full).",
            ["width"],
            registry=self.registry,
        )
        self.drain_overlap = Counter(
            "guber_tpu_drain_overlap_total",
            "Drains dispatched, by how many others were in flight when "
            "the pump let them go (0 | 1 | 2 = two or more).",
            ["ahead"],
            registry=self.registry,
        )
        self.lockstep_ticks = Counter(
            "guber_tpu_lockstep_ticks_total",
            "Lockstep ticks by what they did (drain | idle | held), and "
            "the whole periods skipped when behind (skipped).",
            ["kind"],
            registry=self.registry,
        )
        self.lockstep_decisions = Counter(
            "guber_tpu_lockstep_decisions_total",
            "Decisions answered in lockstep, by how they came (raw = a "
            "whole RPC through the raw-RPC lane | item | legacy).",
            ["lane"],
            registry=self.registry,
        )
        self.global_decisions = Counter(
            "guber_tpu_global_decisions_total",
            "GLOBAL decisions answered from a lockstep drain's composed "
            "GLOBAL window.",
            registry=self.registry,
        )
        self.global_deferred = Counter(
            "guber_tpu_global_deferred_total",
            "GLOBAL items a tick's window had no lane for; each rode a "
            "later tick.",
            registry=self.registry,
        )
        self.global_register_batch = Histogram(
            "guber_tpu_global_register_batch_keys",
            "First-seen GLOBAL keys per mesh registration (the registrar "
            "side: one two-phase round for the whole batch).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
            registry=self.registry,
        )
        # a labelled child that was never touched is absent from /metrics,
        # and a reader cannot tell absent from zero: make every child now
        for stage in STAGES:
            self.stage_duration.labels(stage=stage)
        for stage in REQUEST_STAGES:
            self.request_stage_seconds.labels(stage=stage)
            self.request_stage_requests.labels(stage=stage)
        for reason in PUMP_HOLD_REASONS:
            self.pump_hold_seconds.labels(reason=reason)
        for width in DRAIN_WIDTHS:
            self.drains.labels(width=width)
        for ahead in DRAIN_AHEAD:
            self.drain_overlap.labels(ahead=ahead)
        for kind in LOCKSTEP_TICK_KINDS:
            self.lockstep_ticks.labels(kind=kind)
        for lane in LOCKSTEP_LANES:
            self.lockstep_decisions.labels(lane=lane)
        # traffic analytics (ops/analytics.py device reduction +
        # observability/analytics.py host merge): hot keys, per-tenant
        # accounting, device-computed arena occupancy/churn
        self.hot_key_hits = Counter(
            "guber_tpu_hot_key_hits_total",
            "Hits attributed to device-reported hot keys (top-K only; "
            "unresolved slots render as s<shard>:slot<n>).",
            ["key"],
            registry=self.registry,
        )
        self.tenant_decisions = Counter(
            "guber_tpu_tenant_decisions_total",
            "Decisions per fairness tenant, by outcome "
            "(under_limit | over_limit).",
            ["tenant", "outcome"],
            registry=self.registry,
        )
        self.arena_churn = Counter(
            "guber_tpu_arena_churn_total",
            "Bucket initializations seen by the drain reduction (slot "
            "allocations + window resets — the arena's write churn).",
            registry=self.registry,
        )
        self.arena_occupancy = Gauge(
            "guber_tpu_arena_occupancy_slots",
            "Device-computed arena slot occupancy from the last drain's "
            "expiry plane, by state (live | expired).",
            ["state"],
            registry=self.registry,
        )
        # algorithm plane (gubernator_tpu/algorithms/): per-algorithm
        # decision mix, and the host-side concurrency-lease book
        self.algo_decisions = Counter(
            "guber_tpu_decisions_total",
            "Rate-limit decisions served, by algorithm "
            "(token_bucket | leaky_bucket | gcra | sliding_window | "
            "concurrency).",
            ["algorithm"],
            registry=self.registry,
        )
        self.lease_held = Gauge(
            "guber_tpu_lease_held_slots",
            "Concurrency-lease slots currently held across all keys "
            "(host lease book; the device free-slot counters are the "
            "admission truth).",
            registry=self.registry,
        )
        self.lease_clients = Gauge(
            "guber_tpu_lease_clients",
            "Distinct clients holding at least one concurrency lease.",
            registry=self.registry,
        )
        self.lease_keys = Gauge(
            "guber_tpu_lease_keys",
            "Distinct keys with at least one live concurrency lease.",
            registry=self.registry,
        )
        self.lease_releases = Counter(
            "guber_tpu_lease_releases_total",
            "Lease slots released on behalf of clients, by reason "
            "(explicit | stream_close | peer_down | expired).",
            ["reason"],
            registry=self.registry,
        )
        # SLO burn-rate engine (observability/analytics.py SLOEngine)
        self.slo_burn_rate = Gauge(
            "guber_slo_burn_rate",
            "Error-budget burn rate per objective and window "
            "(1.0 = burning exactly the budget).",
            ["slo", "window"],
            registry=self.registry,
        )
        self.slo_firing = Gauge(
            "guber_slo_firing",
            "Multi-window burn-rate alert state per objective "
            "(1 = firing).",
            ["slo"],
            registry=self.registry,
        )
        # multi-process front door (frontdoor.py): per-worker counters
        # live in the shared-memory status block and aggregate here at
        # scrape time (watch_frontdoor's delta pattern), like the
        # reference's collect-at-scrape stats handler
        self.frontdoor_workers = Gauge(
            "guber_tpu_frontdoor_workers",
            "Configured frontdoor acceptor worker processes "
            "(0 = classic single-process serving).",
            registry=self.registry,
        )
        self.frontdoor_rpcs = Counter(
            "guber_tpu_frontdoor_rpcs_total",
            "RPCs completed through the frontdoor shm ring, per worker.",
            ["worker"],
            registry=self.registry,
        )
        self.frontdoor_sheds = Counter(
            "guber_tpu_frontdoor_sheds_total",
            "Requests shed in-band by frontdoor workers (draining / "
            "saturated / ring_full), per worker.",
            ["worker"],
            registry=self.registry,
        )
        self.frontdoor_restarts = Counter(
            "guber_tpu_frontdoor_restarts_total",
            "Frontdoor worker crash-restarts performed by the hub.",
            registry=self.registry,
        )
        self.shm_ring_depth = Gauge(
            "guber_tpu_shm_ring_depth",
            "Published-but-unconsumed submissions in each worker's shm "
            "ring at scrape time.",
            ["worker"],
            registry=self.registry,
        )
        self.shm_ring_stalls = Counter(
            "guber_tpu_shm_ring_stalls_total",
            "Producer-side ring-full events (every slab in flight; the "
            "worker shed in-band with reason ring_full), per worker.",
            ["worker"],
            registry=self.registry,
        )
        # worker-side response encoding (frontdoor.py): path=worker means
        # the worker built protobuf bytes from decision columns the engine
        # left in the completion-ring slab; path=engine means the slab
        # carried pre-serialized bytes (encode_mode=engine, or a response
        # shape columns cannot express, e.g. errors / owner metadata)
        self.frontdoor_encode = Counter(
            "guber_tpu_frontdoor_encode_total",
            "GetRateLimits responses delivered per worker, by encode "
            "path (worker = encoded from completion-ring decision "
            "columns; engine = pre-serialized on the engine).",
            ["worker", "path"],
            registry=self.registry,
        )
        self.frontdoor_batched_rpcs = Counter(
            "guber_tpu_frontdoor_batched_rpcs_total",
            "RPCs coalesced into multi-RPC columnar slab records by "
            "batched wire reads, per worker.",
            ["worker"],
            registry=self.registry,
        )
        self.frontdoor_batch_flushes = Counter(
            "guber_tpu_frontdoor_batch_flushes_total",
            "Multi-RPC batch records published to the shm ring "
            "(KIND_BATCH_COLS), per worker.",
            ["worker"],
            registry=self.registry,
        )
        # cluster scale-out surface (core/service.py): ring membership and
        # the cross-node forwarding tax the load harness
        # (scripts/load_cluster.py) reads to report peer overhead
        self.cluster_peers = Gauge(
            "guber_tpu_cluster_peers",
            "Peers in the installed consistent-hash ring, self included "
            "(0 until the first membership update).",
            registry=self.registry,
        )
        self.cluster_forwarded = Counter(
            "guber_tpu_cluster_forwarded_total",
            "Rate-limit items forwarded to their owning peer (both the "
            "per-item path and the native lane's spliced batches).",
            registry=self.registry,
        )
        self._stage_rings: Dict[str, _StageRing] = {}
        self._stage_rings_lock = threading.Lock()
        self._slo_sink = None

    def add_scrape_hook(self, fn) -> None:
        """Register a callable run before every expose() — the analog of the
        reference's Collector.Collect pulling live stats at scrape time
        (cache/lru.go:160-172, gubernator.go:313-322)."""
        self._scrape_hooks.append(fn)

    def watch_engine(self, engine) -> None:
        """Export the engine's cache stats at scrape time through ONE
        coherent accessor (engine.cache_stats): the cache_size gauge,
        hit/miss counters advanced by delta since the last scrape, and the
        free/live/expired slot occupancy gauges all come from the same
        read, so a scrape never mixes counters from different moments."""
        last = {"hit": 0, "miss": 0}

        def refresh():
            st = engine.cache_stats()
            self.cache_size.set(st["size"])
            for state in ("free", "live", "expired"):
                self.cache_slots.labels(state=state).set(st[state])
            if st["hits"] > last["hit"]:
                self.cache_access_count.labels(type="hit").inc(
                    st["hits"] - last["hit"])
                last["hit"] = st["hits"]
            if st["misses"] > last["miss"]:
                self.cache_access_count.labels(type="miss").inc(
                    st["misses"] - last["miss"])
                last["miss"] = st["misses"]

        self.add_scrape_hook(refresh)

    def watch_tiers(self, engine) -> None:
        """Export the warm tier's occupancy and event counters at scrape
        time from ONE engine.tier_stats read (same delta pattern as
        watch_engine: the TierManager keeps plain ints, the scrape
        advances the prometheus counters by the difference)."""
        events = {
            "promote": "promotions",
            "demote": "demotions",
            "warm_hit": "warm_hits",
            "cold_miss": "cold_misses",
            "warm_evict": "warm_evictions",
            "demote_drop": "demote_dropped_expired",
            "demote_stale": "demote_dropped_stale",
        }
        last = {k: 0 for k in events}

        def refresh():
            st = engine.tier_stats()
            if st is None:
                return
            self.tier_warm_rows.set(st["warm_rows"])
            self.tier_warm_bytes.set(st["warm_bytes"])
            for label, field in events.items():
                cur = st[field]
                if cur > last[label]:
                    self.tier_events.labels(event=label).inc(
                        cur - last[label])
                    last[label] = cur

        self.add_scrape_hook(refresh)

    def watch_leases(self, book) -> None:
        """Export the concurrency-lease book's occupancy at scrape time
        from ONE book.stats() read (keys/clients/held move together)."""

        def refresh():
            keys, clients, held = book.stats()
            self.lease_keys.set(keys)
            self.lease_clients.set(clients)
            self.lease_held.set(held)

        self.add_scrape_hook(refresh)

    def observe_algorithm(self, algorithm: str, n: int = 1) -> None:
        self.algo_decisions.labels(algorithm=algorithm).inc(n)

    def observe_lease_release(self, reason: str, n: int) -> None:
        if n > 0:
            self.lease_releases.labels(reason=reason).inc(n)

    def watch_qos(self, qos) -> None:
        """Export the QoS control state at scrape time: queue depth, the
        adaptive window, and the drain-latency EWMA all from the same
        QoSManager read."""

        def refresh():
            self.qos_queue_depth.set(qos.admission.pending)
            self.qos_effective_window.set(qos.congestion.effective_window())
            self.qos_drain_latency_ewma.set(qos.congestion.latency_ewma)
            self.qos_drain_depth_ewma.set(qos.congestion.depth_ewma)

        self.add_scrape_hook(refresh)

    def watch_analytics(self, analytics=None, slo=None) -> None:
        """Export the traffic-analytics occupancy gauges and the SLO
        burn rates at scrape time, and route the shed funnel
        (observe_shed) into the SLO engine's availability/shed-rate
        objectives — sheds are QoS events but SLO evidence."""
        if slo is not None:
            self._slo_sink = slo

        def refresh():
            if analytics is not None:
                occ = analytics.occupancy()
                for state in ("live", "expired"):
                    self.arena_occupancy.labels(state=state).set(occ[state])
            if slo is not None:
                for name, obj in slo.burn_rates().items():
                    for win, burn in obj["windows"].items():
                        self.slo_burn_rate.labels(
                            slo=name, window=win).set(burn)
                    self.slo_firing.labels(slo=name).set(
                        1 if obj["firing"] else 0)

        self.add_scrape_hook(refresh)

    def watch_frontdoor(self, hub) -> None:
        """Export the frontdoor hub's per-worker shared-memory counters at
        scrape time: the workers bump raw int64 cells in the status block
        (no prometheus client in the worker processes), and this hook
        advances the engine-side counters by the delta since the last
        scrape — the same pattern watch_engine uses for cache stats."""
        from gubernator_tpu.core import shm_ring as _sr
        last: Dict[tuple, int] = {}

        def _delta(w: str, field: int, counter, **lbls) -> None:
            cur = hub.status.get_w(int(w), field)
            prev = last.get((w, field), 0)
            if cur > prev:
                counter.labels(worker=w, **lbls).inc(cur - prev)
                last[(w, field)] = cur

        def refresh():
            self.frontdoor_workers.set(hub.workers)
            if hub.status is None:
                return
            for i in range(hub.workers):
                w = str(i)
                _delta(w, _sr.W_RPCS, self.frontdoor_rpcs)
                _delta(w, _sr.W_SHEDS, self.frontdoor_sheds)
                _delta(w, _sr.W_STALLS, self.shm_ring_stalls)
                _delta(w, _sr.W_ENCODES, self.frontdoor_encode,
                       path="worker")
                _delta(w, _sr.W_ENC_FALLBACK, self.frontdoor_encode,
                       path="engine")
                _delta(w, _sr.W_BATCH_RPCS, self.frontdoor_batched_rpcs)
                _delta(w, _sr.W_BATCH_FLUSHES, self.frontdoor_batch_flushes)
                if hub.chans:
                    self.shm_ring_depth.labels(worker=w).set(
                        hub.chans[i].sub_depth())
            cur = hub.restarts
            prev = last.get(("", "restarts"), 0)
            if cur > prev:
                self.frontdoor_restarts.inc(cur - prev)
                last[("", "restarts")] = cur

        self.add_scrape_hook(refresh)

    def observe_hot_key(self, key: str, hits: int) -> None:
        if hits > 0:
            self.hot_key_hits.labels(key=key).inc(hits)

    def observe_tenant(self, tenant: str, under: int, over: int) -> None:
        if under > 0:
            self.tenant_decisions.labels(
                tenant=tenant, outcome="under_limit").inc(under)
        if over > 0:
            self.tenant_decisions.labels(
                tenant=tenant, outcome="over_limit").inc(over)

    def observe_churn(self, inits: int) -> None:
        if inits > 0:
            self.arena_churn.inc(inits)

    def observe_shed(self, reason: str, n: int = 1) -> None:
        self.qos_shed.labels(reason=reason).inc(n)
        if self._slo_sink is not None:
            self._slo_sink.observe_shed(n)

    _BREAKER_STATES = {"closed": 0, "half_open": 1, "open": 2}

    def observe_breaker(self, peer: str, state: str) -> None:
        self.breaker_state.labels(peer=peer).set(
            self._BREAKER_STATES.get(state, 0))

    def observe_peer_retry(self, peer: str) -> None:
        self.peer_retries.labels(peer=peer).inc()

    def observe_global_error(self, peer: str, kind: str,
                             queued: int = 0) -> None:
        """One failed per-peer GLOBAL send (kind: hits|update), plus how
        many NEW hint entries it buffered."""
        if kind == "update":
            self.broadcast_errors.labels(peer=peer).inc()
        else:
            self.global_send_errors.labels(peer=peer).inc()
        if queued > 0:
            self.hints.labels(event="queued", peer=peer).inc(queued)

    def observe_hints(self, peer: str, replayed: int = 0,
                      expired: int = 0) -> None:
        if replayed:
            self.hints.labels(event="replayed", peer=peer).inc(replayed)
        if expired:
            self.hints.labels(event="expired", peer=peer).inc(expired)

    _HEALTH_STATES = {"up": 0, "suspect": 1, "down": 2}

    def observe_peer_health(self, peer: str, state: str) -> None:
        self.peer_health_state.labels(peer=peer).set(
            self._HEALTH_STATES.get(state, 0))

    def observe_rehome(self, direction: str) -> None:
        self.ring_rehomes.labels(direction=direction).inc()

    def observe_snapshot(self, seconds: float, size_bytes: int,
                         ok: bool) -> None:
        self.snapshot_total.labels(
            status="success" if ok else "failed").inc()
        if ok:
            self.snapshot_duration.observe(seconds)
            self.snapshot_size.set(size_bytes)

    def observe_migration(self, moved: int = 0, imported: int = 0,
                          skipped_stale: int = 0) -> None:
        if moved:
            self.migrated_keys.labels(direction="out").inc(moved)
        if imported:
            self.migrated_keys.labels(direction="in").inc(imported)
        if skipped_stale:
            self.migration_skipped_stale.inc(skipped_stale)

    def observe_stage(self, stage: str, seconds: float) -> None:
        """Record one stage duration into both the Prometheus histogram
        (milliseconds, for dashboards) and the in-process ring (for the
        rolling p50/p95/p99 snapshot)."""
        if seconds < 0.0:
            seconds = 0.0
        self.stage_duration.labels(stage=stage).observe(seconds * 1000.0)
        ring = self._stage_rings.get(stage)
        if ring is None:
            with self._stage_rings_lock:
                ring = self._stage_rings.setdefault(stage, _StageRing())
        ring.observe(seconds)

    def observe_request_stage(self, stage: str, seconds: float,
                              requests: int) -> None:
        """One drain's worth of one request stage: the summed seconds of
        `requests` requests (one increment per stage per drain)."""
        if requests <= 0:
            return
        self.request_stage_seconds.labels(stage=stage).inc(
            max(0.0, seconds))
        self.request_stage_requests.labels(stage=stage).inc(requests)

    def stage_snapshot(self) -> Dict[str, dict]:
        """Rolling per-stage quantiles, `engine.cache_stats`-style: one
        coherent read of every stage ring, keyed by stage name in
        pipeline order (stages with no samples yet are omitted)."""
        out: Dict[str, dict] = {}
        with self._stage_rings_lock:
            rings = dict(self._stage_rings)
        for stage in STAGES:
            ring = rings.pop(stage, None)
            if ring is not None:
                snap = ring.snapshot()
                if snap is not None:
                    out[stage] = snap
        for stage, ring in rings.items():  # non-canonical stages last
            snap = ring.snapshot()
            if snap is not None:
                out[stage] = snap
        return out

    def expose(self) -> bytes:
        for fn in self._scrape_hooks:
            fn()
        return generate_latest(self.registry)

    def observe_rpc(self, method: str, start: float, ok: bool) -> None:
        """Per-RPC accounting (replaces the reference's gRPC stats-handler
        channel pipeline, prometheus.go:65-134)."""
        self.grpc_request_counts.labels(
            status="success" if ok else "failed", method=method
        ).inc()
        self.grpc_request_duration.labels(method=method).observe(
            (time.monotonic() - start) * 1000.0
        )
