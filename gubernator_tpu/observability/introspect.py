"""Runtime introspection: the debug snapshot and on-demand device capture.

`build_debug_snapshot` assembles the one-read operator view served by
`GET /v1/admin/debug` (api/http_gateway.py) and `cli debug` (cmd/cli.py):
arena occupancy, admission queue depth, per-peer breaker states, the AIMD
congestion window, per-stage latency quantiles, and recent-trace
summaries — every number from the same accessors the control loops read,
so what the operator sees is what the controllers saw.

`ProfileCapture` wraps the next N pipeline drains in
`jax.profiler.start_trace/stop_trace` (the GUBER_PROFILE plumbing from
bench.py, now armable at runtime via `POST /v1/admin/profile`).  The
armed check runs on the single engine thread around each dispatch, so
when disarmed the hot path pays one integer compare.
"""

from __future__ import annotations

import logging
import os
import threading
import time

log = logging.getLogger("gubernator.introspect")


class ProfileCapture:
    """Arm-and-forget device profiler: `arm(n, dir)` from the admin plane,
    `before_drain()`/`after_drain()` from the engine thread around each
    dispatch.  All state transitions happen under the lock, but the
    disarmed fast path reads the plain int `_remaining` first — stale
    reads only ever delay a capture by one drain, never corrupt one."""

    def __init__(self):
        self._lock = threading.Lock()
        self._remaining = 0
        self._dir = ""
        self._active = False
        self._stopping = False

    @property
    def armed(self) -> bool:
        return self._remaining > 0 or self._active

    def arm(self, drains: int, trace_dir: str = "") -> dict:
        """Schedule a capture of the next `drains` dispatches.  Default
        directory comes from GUBER_PROFILE (bench.py's knob) or a
        timestamped /tmp path."""
        trace_dir = (trace_dir or os.environ.get("GUBER_PROFILE", "")
                     or f"/tmp/guber-profile-{int(time.time())}")
        with self._lock:
            if self._active or self._remaining > 0:
                return {"armed": False, "error": "capture already in "
                        "progress", "dir": self._dir}
            self._remaining = max(1, int(drains))
            self._dir = trace_dir
        return {"armed": True, "drains": self._remaining, "dir": trace_dir}

    # ------------------------------------------------- engine-thread hooks

    def before_drain(self) -> None:
        """Engine thread, just before a dispatch: start the device trace
        on the first armed drain."""
        with self._lock:
            if self._remaining <= 0 or self._active:
                return
            self._active = True
        try:
            import jax
            jax.profiler.start_trace(self._dir)
            log.info("profile capture started -> %s (%d drains)",
                     self._dir, self._remaining)
        except Exception:
            log.exception("profile capture failed to start")
            with self._lock:
                self._active = False
                self._remaining = 0

    def after_drain(self) -> None:
        """Engine thread, after a dispatch completed: stop once the armed
        count runs out."""
        with self._lock:
            if not self._active:
                return
            self._remaining -= 1
            if self._remaining > 0:
                return
        self._stop("stopped")

    def cancel(self) -> None:
        """Disarm an in-flight capture (continuous profiling's recovery
        path when traffic never completes the armed drain count): stop the
        device trace if it started, drop any remaining armed drains."""
        with self._lock:
            self._remaining = 0
        self._stop("cancelled")

    def _stop(self, what: str) -> None:
        """stop_trace() returns once the profiler has written its files;
        `armed` stays true until then, so a reader that waits for it to
        drop never parses a half-written capture."""
        with self._lock:
            if not self._active or self._stopping:
                return
            self._stopping = True
        try:
            import jax
            jax.profiler.stop_trace()
            log.info("profile capture %s -> %s", what, self._dir)
        except Exception:
            log.exception("profile capture failed to stop")
        finally:
            with self._lock:
                self._active = False
                self._stopping = False

    def status(self) -> dict:
        with self._lock:
            return {"active": self._active, "remaining": self._remaining,
                    "dir": self._dir}


def _jsonable(d: dict) -> dict:
    """Coerce numpy scalars (engine counters) to plain Python types so the
    snapshot always survives json.dumps."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out[k] = _jsonable(v)
        elif isinstance(v, (bool, int, float, str)) or v is None:
            out[k] = v
        elif hasattr(v, "item"):
            out[k] = v.item()
        else:
            out[k] = str(v)
    return out


def build_debug_snapshot(instance) -> dict:
    """One coherent operator view of a core.service.Instance."""
    out: dict = {
        "address": instance.advertise_address,
        "mesh_mode": instance.mesh_mode,
        "standalone": instance.standalone,
        "engine": _jsonable(instance.engine.cache_stats()),
    }
    if instance.qos is not None:
        adm = instance.qos.admission
        cong = instance.qos.congestion
        out["admission"] = {
            "pending": adm.pending,
            "pending_peak": adm.pending_peak,
            "max_pending": adm.max_pending,
            "saturated": adm.saturated,
            "inflight_windows": adm.inflight_windows,
            "shed_counts": dict(adm.shed_counts),
        }
        out["congestion"] = {
            "effective_window": cong.effective_window(),
            "latency_ewma_ms": cong.latency_ewma * 1000.0,
            "depth_ewma": cong.depth_ewma,
            "congested": cong.congested,
            "increases": cong.increases,
            "decreases": cong.decreases,
            "stage_ewma_ms": {k: v * 1000.0
                              for k, v in cong.stage_ewma.items()},
        }
    out["peers"] = [
        {"host": p.host, "is_owner": p.is_owner,
         "breaker": p.breaker.state}
        for p in instance.peer_list()
    ]
    # what the GLOBAL plane failed to deliver + what the hint buffer holds
    gm = getattr(instance, "global_mgr", None)
    if gm is not None:
        out["global_sync"] = {
            "send_errors": dict(gm.send_errors),
            "broadcast_errors": dict(gm.broadcast_errors),
            "hints": gm.hints.snapshot(),
        }
    monitor = getattr(instance, "monitor", None)
    if monitor is not None:
        out["health"] = monitor.snapshot()
    frontdoor = getattr(instance, "frontdoor", None)
    if frontdoor is not None:
        out["frontdoor"] = _jsonable(frontdoor.debug_snapshot())
    from gubernator_tpu.net.faults import FAULTS
    if FAULTS.enabled:
        out["faults"] = FAULTS.describe()
    pipe = instance.batcher.pipeline
    if pipe is not None:
        out["pipeline"] = {
            "in_flight": pipe._in_flight,
            "rpc_served": pipe.rpc_served,
            "decisions_staged": pipe.decisions_staged,
            "lanes_staged": pipe.lanes_staged,
            "fused_serving": pipe.fused_serving,
            "staged_serving": pipe.staged_serving,
            "lockstep": pipe.lockstep,
            "depth": pipe.depth,
            "overlap": pipe.overlap_snapshot(),
        }
    analytics = getattr(instance, "analytics", None)
    if analytics is not None:
        snap = analytics.snapshot()
        out["analytics"] = {
            "totals": snap["totals"],
            "occupancy": snap["occupancy"],
            "tenants": snap["tenants"],
            "topk": snap["topk"][:10],  # the full table lives at /topk
        }
    tiers = getattr(instance.engine, "tier_stats", lambda: None)()
    if tiers is not None:
        out["tiers"] = tiers
    slo = getattr(instance, "slo", None)
    if slo is not None:
        out["slo"] = slo.snapshot()
    out["stages"] = instance.metrics.stage_snapshot()
    tracer = getattr(instance, "tracer", None)
    if tracer is not None:
        out["tracing"] = {
            "sample": tracer.sample,
            "recent_traces": tracer.recent_traces(),
        }
    profile = getattr(instance.batcher, "profile", None)
    if profile is not None:
        out["profile"] = profile.status()
    devprof = getattr(instance, "devprof", None)
    if devprof is not None:
        out["devprof"] = devprof.status()
    return out
