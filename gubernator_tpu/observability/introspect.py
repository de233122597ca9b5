"""Runtime introspection: the debug snapshot and on-demand device capture.

`build_debug_snapshot` assembles the one-read operator view served by
`GET /v1/admin/debug` (api/http_gateway.py) and `cli debug` (cmd/cli.py):
arena occupancy, admission queue depth, per-peer breaker states, the AIMD
congestion window, per-stage latency quantiles, and recent-trace
summaries — every number from the same accessors the control loops read,
so what the operator sees is what the controllers saw.

`ProfileCapture` records the next N pipeline drains under
`jax.profiler.start_trace/stop_trace` (armable at runtime via
`POST /v1/admin/profile`).  The profiler
is started and stopped on a thread of the capture's own: the stop writes
the trace for seconds, and the single engine thread must not sit through
it.  The engine thread only counts the armed drains down.
"""

from __future__ import annotations

import logging
import os
import threading
import time

log = logging.getLogger("gubernator.introspect")


class ProfileCapture:
    """Arm-and-forget device profiler.  `arm(n, dir)` (admin plane) starts
    the capture thread, which calls `start_trace`, waits until the engine
    thread's `after_drain()` has counted `n` drains (or `cancel()`), calls
    `stop_trace`, and only then clears `active`: a reader that waits for
    `armed` to drop never parses a half-written capture.

    The engine thread never calls into the profiler.  It reads the plain
    bool `tracing` before a drain and, when it was set, calls
    `after_drain()` after it, so every counted drain began after
    `start_trace` returned.  Drains dispatched while `stop_trace` runs are
    in the capture too: it may hold more drains than were armed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._remaining = 0
        self._dir = ""
        self._active = False
        self._done = threading.Event()
        self._thread = None
        # set by the capture thread between start_trace's return and the
        # end of the count; read without the lock on the engine thread (a
        # stale read only moves the count by one drain)
        self.tracing = False

    @property
    def armed(self) -> bool:
        return self._active

    def arm(self, drains: int, trace_dir: str = "") -> dict:
        """Start a capture of the next `drains` dispatches.  Default
        directory comes from GUBER_PROFILE or a
        timestamped /tmp path."""
        trace_dir = (trace_dir or os.environ.get("GUBER_PROFILE", "")
                     or f"/tmp/guber-profile-{int(time.time())}")
        with self._lock:
            if self._active:
                return {"armed": False, "error": "capture already in "
                        "progress", "dir": self._dir}
            self._active = True
            self._remaining = drains = max(1, int(drains))
            self._dir = trace_dir
            self._done.clear()
            self._thread = threading.Thread(
                target=self._run, name="guber-profile", daemon=True)
            self._thread.start()
        return {"armed": True, "drains": drains, "dir": trace_dir,
                "note": "drains dispatched while the profiler stops are "
                        "recorded too: the capture may hold more than "
                        "were armed"}

    def _run(self) -> None:
        """The capture thread: start, let the engine thread count, stop."""
        import jax
        try:
            jax.profiler.start_trace(self._dir)
        except Exception:
            log.exception("profile capture failed to start")
            with self._lock:
                self._active = False
                self._remaining = 0
            return
        log.info("profile capture started -> %s (%d drains)",
                 self._dir, self._remaining)
        self.tracing = True
        self._done.wait()
        self.tracing = False
        try:
            jax.profiler.stop_trace()
            log.info("profile capture stopped -> %s", self._dir)
        except Exception:
            log.exception("profile capture failed to stop")
        finally:
            with self._lock:
                self._active = False
                self._remaining = 0

    def after_drain(self) -> None:
        """Engine thread, after a dispatch that began with `tracing` set:
        one drain less to record; the last one wakes the capture thread."""
        with self._lock:
            self._remaining -= 1
            if self._remaining > 0:
                return
        self._done.set()

    def cancel(self, timeout: float = 30.0) -> None:
        """End a capture that traffic never completed (continuous
        profiling's recovery path): the capture thread stops the trace if
        it started.  Returns once it has, so a caller may arm again."""
        with self._lock:
            thread = self._thread
        self._done.set()
        if thread is not None:
            thread.join(timeout)

    def status(self) -> dict:
        with self._lock:
            return {"active": self._active,
                    "remaining": max(0, self._remaining), "dir": self._dir}


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


def device_memory(devices) -> dict:
    """`memory_stats()` of the fullest of `devices`: bytes_in_use,
    peak_bytes_in_use, bytes_limit, each only where the backend reports
    it (the CPU backend reports none: the result is then empty)."""
    best: dict = {}
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            continue
        if stats.get("bytes_in_use", 0) >= best.get("bytes_in_use", -1):
            best = stats
    return {k: int(best[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in best}


def build_debug_snapshot(instance) -> dict:
    """One coherent operator view of a core.service.Instance."""
    out: dict = {
        "address": instance.advertise_address,
        "mesh_mode": instance.mesh_mode,
        "standalone": instance.standalone,
        "engine": _jsonable(instance.engine.cache_stats()),
        "device": {"memory": device_memory(
            instance.engine.mesh.local_devices)},
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
            "lockstep": pipe.lockstep,
            "depth": pipe.depth,
            "overlap": pipe.overlap_snapshot(),
            "pump_hold_seconds": pipe.pump_hold_snapshot(),
            "drain_widths": dict(pipe.drain_widths),
            "drain_overlap": dict(pipe.drain_overlap),
        }
        if pipe.lockstep:
            clock = instance.batcher.clock
            out["pipeline"]["lockstep_state"] = {
                "ticks": dict(pipe.lockstep_ticks),
                "decisions_by_lane": dict(pipe.lane_decisions),
                "global_items": dict(pipe.global_items),
                "tick_index": clock.tick, "epoch_ms": clock.epoch_ms,
                "interval_ms": clock.interval_ms,
                "last_tick_lag_ms": clock.lag_s * 1000.0,
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
