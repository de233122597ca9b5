"""Metric-name parity with the reference's prometheus surface.

The reference documents its scrape names in prometheus.go:22-63 and the
README's metrics table; operators migrating dashboards must find the
SAME series names on this implementation.  This suite pins them — a
rename here is a dashboard-breaking change, so it must fail a test, not
slip through a refactor.
"""

import time

import pytest

from gubernator_tpu.observability.metrics import (DRAIN_AHEAD, DRAIN_WIDTHS,
                                                  LOCKSTEP_LANES,
                                                  LOCKSTEP_TICK_KINDS,
                                                  PUMP_HOLD_REASONS,
                                                  REQUEST_STAGES, STAGES,
                                                  Metrics)

pytestmark = pytest.mark.obs

# the reference's names, verbatim (prometheus.go:22-63)
REFERENCE_NAMES = (
    "cache_size",
    "cache_access_count",
    "async_durations",
    "broadcast_durations",
    "grpc_request_counts",
    "grpc_request_duration_milliseconds",
)

# TPU-native additions this repo's own docs promise
NATIVE_NAMES = (
    "guber_tpu_windows_total",
    "guber_tpu_window_duration_seconds",
    "guber_tpu_stage_duration_ms",
    # traffic analytics + SLO engine (observability/analytics.py)
    "guber_tpu_hot_key_hits_total",
    "guber_tpu_tenant_decisions_total",
    "guber_tpu_arena_churn_total",
    "guber_tpu_arena_occupancy_slots",
    "guber_slo_burn_rate",
    "guber_slo_firing",
    # overlapped drain pipeline (core/pipeline.py, core/window_buffers.py)
    "guber_tpu_pipeline_inflight_windows",
    # one request's stages and the pump's holds (core/pipeline.py)
    "guber_tpu_request_stage_seconds_total",
    "guber_tpu_request_stage_requests_total",
    "guber_tpu_pump_hold_seconds_total",
    # lane-bucketed serving drain (core/pipeline.py _drain_lanes)
    "guber_tpu_drains_total",
    # drains let go beside a drain in flight (core/pipeline.py _held)
    "guber_tpu_drain_overlap_total",
    # deferred-fetch dispatch chain (core/pipeline.py)
    "guber_tpu_chain_fetch_stride",
    # multi-process front door (frontdoor.py, core/shm_ring.py)
    "guber_tpu_frontdoor_workers",
    "guber_tpu_frontdoor_rpcs",
    "guber_tpu_frontdoor_sheds",
    "guber_tpu_frontdoor_restarts",
    "guber_tpu_shm_ring_depth",
    "guber_tpu_shm_ring_stalls",
    # worker-side response encoding + batched wire reads (frontdoor.py)
    "guber_tpu_frontdoor_encode",
    "guber_tpu_frontdoor_batched_rpcs",
    "guber_tpu_frontdoor_batch_flushes",
    # multi-node scale-out surface (core/service.py, scripts/load_cluster.py)
    "guber_tpu_cluster_peers",
    "guber_tpu_cluster_forwarded",
    # tiered key state (state/tiers.py)
    "guber_tpu_tier_events_total",
    "guber_tpu_tier_warm_rows",
    "guber_tpu_tier_warm_bytes",
    # device-time flight recorder (observability/devprof.py)
    "guber_tpu_device_window_ms",
    "guber_tpu_devprof_captures",
    # algorithm plane + concurrency-lease book (algorithms/leases.py)
    "guber_tpu_decisions_total",
    "guber_tpu_lease_held_slots",
    "guber_tpu_lease_clients",
    "guber_tpu_lease_keys",
    "guber_tpu_lease_releases_total",
)


@pytest.mark.parametrize("name", REFERENCE_NAMES + NATIVE_NAMES)
def test_metric_family_exposed(name):
    text = Metrics().expose().decode("utf-8")
    assert f"# TYPE {name}" in text, f"metric family {name} missing"


def test_reference_series_shapes():
    """Label sets and units match the reference, not just the names."""
    m = Metrics()
    m.cache_size.set(3)
    m.cache_access_count.labels(type="hit").inc()
    m.cache_access_count.labels(type="miss").inc(2)
    m.async_durations.observe(0.01)
    m.broadcast_durations.observe(0.02)
    m.observe_rpc("/pb.gubernator.V1/GetRateLimits",
                  start=time.monotonic(), ok=True)
    m.observe_rpc("/pb.gubernator.V1/GetRateLimits",
                  start=time.monotonic(), ok=False)
    g = m.registry.get_sample_value
    assert g("cache_size") == 3.0
    assert g("cache_access_count_total", {"type": "hit"}) == 1.0
    assert g("cache_access_count_total", {"type": "miss"}) == 2.0
    assert g("async_durations_count") == 1.0
    assert g("broadcast_durations_count") == 1.0
    method = {"method": "/pb.gubernator.V1/GetRateLimits"}
    assert g("grpc_request_counts_total",
             {"status": "success", **method}) == 1.0
    assert g("grpc_request_counts_total",
             {"status": "failed", **method}) == 1.0
    assert g("grpc_request_duration_milliseconds_count", method) == 2.0


def test_every_metric_attribute_registered_exactly_once():
    """Registry drift guard: every prometheus collector hanging off a
    Metrics instance must live on THAT instance's registry (a collector
    accidentally created against the process-global REGISTRY would leak
    across instances and vanish from /metrics), and no two collectors may
    claim the same family name."""
    from prometheus_client.metrics import MetricWrapperBase

    m = Metrics()
    registered = m.registry._collector_to_names
    collectors = {attr: v for attr, v in vars(m).items()
                  if isinstance(v, MetricWrapperBase)}
    assert collectors, "Metrics lost its collectors?"
    for attr, coll in collectors.items():
        assert coll in registered, (
            f"Metrics.{attr} is not registered on the instance registry")
    all_names = [n for names in registered.values() for n in names]
    assert len(all_names) == len(set(all_names)), (
        "duplicate family names in the registry")


def test_no_orphaned_collectors():
    """Dead-metric audit: every collector attribute must be OBSERVED
    somewhere — referenced at least once outside its own `self.x = ...`
    definition (in metrics.py's observe_*/watch_* helpers or any other
    module).  A counter that is defined but never incremented is a
    dashboard lie; wire it or delete it."""
    import os
    import re

    from prometheus_client.metrics import MetricWrapperBase

    m = Metrics()
    attrs = [a for a, v in vars(m).items()
             if isinstance(v, MetricWrapperBase)]
    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "gubernator_tpu")
    blob = []
    for root, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f), encoding="utf-8") as fh:
                    blob.append(fh.read())
    blob = "\n".join(blob)
    orphans = []
    for attr in attrs:
        uses = len(re.findall(rf"\.{attr}\b", blob))
        # one hit is the `self.{attr} = Counter(...)` definition itself
        if uses < 2:
            orphans.append(attr)
    assert not orphans, f"collectors defined but never observed: {orphans}"


def test_stage_labels_are_canonical():
    """Every stage histogram child uses a label from STAGES — dashboards
    key on exactly these, in pipeline order."""
    m = Metrics()
    for stage in STAGES:
        m.observe_stage(stage, 0.001)
    for stage in STAGES:
        assert m.registry.get_sample_value(
            "guber_tpu_stage_duration_ms_count", {"stage": stage}) == 1.0
    assert STAGES == (
        "tick_lag", "enqueue", "admission_wait", "engine_queue", "window_fill",
        "device_dispatch", "dispatch_hop", "fetch_queue", "drain_commit",
        "device_wait", "decode", "complete_hop", "commit", "peer_forward",
        "global_broadcast")


# series that were removed because nothing read them; their numbers are in
# /v1/admin/debug (PERF.md section 3 names the field for each)
REMOVED_NAMES = (
    "guber_tpu_pipeline_overlap_ratio",
    "guber_tpu_fused_drains_total",
    "guber_tpu_drain_depth_windows",
    "guber_tpu_chain_fetch_elided_total",
    "guber_tpu_chain_inflight_windows",
    "guber_tpu_window_buffer_reuse_total",
    "guber_tpu_device_window_ewma_ms",
    "guber_tpu_frontdoor_trace_drops_total",
    "guber_tpu_kernels_per_window",
)


@pytest.mark.parametrize("name", REMOVED_NAMES)
def test_removed_series_stay_removed(name):
    text = Metrics().expose().decode("utf-8")
    assert f"# TYPE {name}" not in text


@pytest.mark.parametrize("series,label,values", [
    ("guber_tpu_stage_duration_ms_count", "stage", STAGES),
    ("guber_tpu_stage_duration_ms_sum", "stage", STAGES),
    ("guber_tpu_request_stage_seconds_total", "stage", REQUEST_STAGES),
    ("guber_tpu_request_stage_requests_total", "stage", REQUEST_STAGES),
    ("guber_tpu_pump_hold_seconds_total", "reason", PUMP_HOLD_REASONS),
    ("guber_tpu_drains_total", "width", DRAIN_WIDTHS),
    ("guber_tpu_drain_overlap_total", "ahead", DRAIN_AHEAD),
    ("guber_tpu_lockstep_ticks_total", "kind", LOCKSTEP_TICK_KINDS),
    ("guber_tpu_lockstep_decisions_total", "lane", LOCKSTEP_LANES),
])
def test_labelled_children_exist_at_zero(series, label, values):
    """A child that was never incremented is absent from /metrics, and a
    reader cannot tell absent from zero: a fresh Metrics exposes every
    stage and reason at 0."""
    m = Metrics()
    m.expose()
    for v in values:
        assert m.registry.get_sample_value(series, {label: v}) == 0.0, v
