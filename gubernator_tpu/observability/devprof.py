"""Device-time flight recorder: measured kernel attribution for the
serving window.

What the device spent, by kernel and by serving arm, read from profiler
captures of the running system:

  * `parse_run_dir` / `load_trace_events` — read the `.xplane.pb` files
    a `jax.profiler` capture leaves under its run dir (through
    `jax.profiler.ProfileData`) into chrome-trace-style complete events;
  * `self_times` — per-(pid, tid) interval nesting turns the raw events
    into per-kernel SELF time (a fusion nested inside an executable
    wrapper is not double-counted) and attributes each kernel to a
    serving arm by the `guber_*` trace annotations the engine stamps
    around dispatch/fetch/analytics (core/engine.py);
  * `KernelTable` — a rolling fold of those rows, normalized to
    ms/window per arm;
  * `WindowClock` — the always-on dispatch→fetch-ready clock the
    pipeline feeds per drain (EWMA + `guber_tpu_device_window_ms{arm}`
    histogram; disabled path = one attribute check) with a bounded ring
    of slow-window records carrying trace-ID exemplars, so a p99 window
    links to its stitched trace in `/v1/admin/debug`;
  * `DevprofController` — the `GUBER_DEVPROF=periodic` continuous mode:
    a shedding background thread that re-arms an N-drain capture,
    parses, folds into the rolling table, and discards the trace dir;
  * `build_probe_arms` / `measure_probe_arms` — the serving arms as
    runnable specs over a tiny probe engine, each run under an arm-scoped
    capture (`GET /v1/admin/kernels?measure=1`, the admin plane's offline
    probe).

Malformed or empty traces degrade to a logged no-op — a broken capture
must never fail a request or a bench run.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from gubernator_tpu.config import env_float, env_int

log = logging.getLogger("gubernator.devprof")

# serving-arm vocabulary: the executables the engine dispatches plus the
# runtime-only buckets the trace annotations distinguish
ARM_DRAIN = "composed_drain"
ARM_ANALYTICS = "composed_analytics"
ARM_STEP = "legacy_step"
ARM_FETCH = "fetch"
ARM_OTHER = "xla_shoulder"

# trace-annotation name -> arm, most specific first (core/engine.py stamps
# these around every dispatch/fetch/analytics call)
ANNOTATION_ARMS: Tuple[Tuple[str, str], ...] = (
    ("guber_analytics", ARM_ANALYTICS),
    ("guber_fetch", ARM_FETCH),
    ("guber_drain", ARM_DRAIN),
    ("guber_window", ARM_STEP),
)

# host-side scaffolding that must not masquerade as device kernels in the
# measured table (python source events, pjit wrappers, runtime plumbing)
_NOISE_PREFIXES = (
    "$", "PjitFunction", "ParseArguments", "ThreadpoolListener",
    "TfrtCpu", "ThunkExecutor", "XlaModule", "ProgramRegion",
    "RunBackend", "HloModule", "profiler",
)


def _is_noise(name: str) -> bool:
    return name.startswith(_NOISE_PREFIXES)


def _annotation_arm(name: str) -> Optional[str]:
    for prefix, arm in ANNOTATION_ARMS:
        if name.startswith(prefix):
            return arm
    return None


# ------------------------------------------------------------------ parsing


def find_trace_files(run_dir: str) -> List[str]:
    """Every `*.xplane.pb` under a jax.profiler run dir (the profiler
    nests them under plugins/profile/<timestamp>/<host>.xplane.pb)."""
    out: List[str] = []
    for root, _dirs, files in os.walk(run_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                out.append(os.path.join(root, f))
    return sorted(out)


def load_trace_events(path: str) -> List[dict]:
    """One xplane file as chrome-trace-style complete events (ts/dur in
    microseconds, pid/tid = plane/line index, positive duration only);
    malformed input degrades to a logged empty list."""
    from jax.profiler import ProfileData
    try:
        planes = list(ProfileData.from_file(path).planes)
    except Exception as e:  # noqa: BLE001 — RuntimeError / JaxRuntimeError
        log.warning("devprof: unreadable trace %s: %s", path, e)
        return []
    events: List[dict] = []
    for pid, plane in enumerate(planes):
        for tid, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.duration_ns > 0:
                    events.append({
                        "ph": "X", "pid": pid, "tid": tid, "name": ev.name,
                        "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                        "plane": plane.name, "line": line.name})
    return events


def parse_run_dir(run_dir: str) -> List[dict]:
    """All complete events from every trace file under `run_dir` (empty
    and logged when the capture produced nothing parseable)."""
    events: List[dict] = []
    files = find_trace_files(run_dir)
    if not files:
        log.warning("devprof: no xplane.pb under %s", run_dir)
        return events
    for path in files:
        events.extend(load_trace_events(path))
    return events


def self_times(events: List[dict],
               arm_hint: Optional[str] = None) -> List[Tuple[str, float, str]]:
    """(kernel name, self-time ms, arm) rows from raw trace events.

    Self time = duration minus same-track nested children, so a fusion
    inside an executable wrapper counts once.  Arm attribution: the
    `arm_hint` when the whole capture is arm-scoped (measure_probe_arms),
    else the narrowest `guber_*` annotation interval covering the
    event midpoint — annotations and kernels land on DIFFERENT threads
    (the annotation on the engine thread, the kernel on the runtime's
    executor), and drains serialize on one engine thread, so time-window
    containment is the sound join.  Kernels outside any annotation are
    the XLA shoulders.
    """
    # annotation intervals across every track (ts/dur are microseconds)
    spans: List[Tuple[float, float, str]] = []
    for e in events:
        arm = _annotation_arm(e["name"])
        if arm is not None:
            spans.append((e["ts"], e["ts"] + e["dur"], arm))
    spans.sort(key=lambda s: (s[0], -(s[1] - s[0])))

    def arm_of(mid: float) -> str:
        best = None
        best_len = None
        for s0, s1, arm in spans:
            if s0 > mid:
                break
            if s1 >= mid and (best_len is None or s1 - s0 < best_len):
                best, best_len = arm, s1 - s0
        return best if best is not None else ARM_OTHER

    tracks: Dict[tuple, List[dict]] = {}
    for e in events:
        name = e["name"]
        if _is_noise(name) or _annotation_arm(name) is not None:
            continue
        tracks.setdefault((e.get("pid"), e.get("tid")), []).append(e)

    rows: List[Tuple[str, float, str]] = []

    def flush(done: list) -> None:
        ev = done[2]
        self_us = max(0.0, ev["dur"] - done[1])
        arm = arm_hint or arm_of(ev["ts"] + ev["dur"] / 2.0)
        rows.append((ev["name"], self_us / 1000.0, arm))

    for track in tracks.values():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[list] = []  # [end_us, child_sum_us, event]
        for e in track:
            ts, dur = e["ts"], e["dur"]
            while stack and stack[-1][0] <= ts:
                flush(stack.pop())
            if stack:
                stack[-1][1] += dur
            stack.append([ts + dur, 0.0, e])
        while stack:
            flush(stack.pop())
    return rows


# -------------------------------------------------------------- kernel table


class KernelTable:
    """Rolling per-kernel attribution: (arm, name) -> {count, total_ms},
    normalized to ms/window by the windows each fold covered.  Keyed by
    arm AND kernel name — XLA emits bare HLO instruction names (fusion.3)
    that repeat across executables, and a name-only key would fold a
    later arm's kernels under whichever arm saw the name first.
    Thread-safe (the continuous controller folds from its own thread
    while the admin plane snapshots)."""

    def __init__(self) -> None:
        self._rows: Dict[Tuple[str, str], dict] = {}
        self._windows = 0.0
        self._folds = 0
        self._lock = threading.Lock()

    def fold(self, events: List[dict], windows: float = 1.0,
             arm_hint: Optional[str] = None) -> int:
        """Fold one parsed capture covering `windows` request windows into
        the table; returns the number of kernel rows folded (0 = the
        capture was empty/malformed — a logged no-op)."""
        rows = self_times(events, arm_hint=arm_hint)
        if not rows:
            log.warning("devprof: capture folded 0 kernel rows "
                        "(empty or unclassifiable trace)")
            return 0
        with self._lock:
            self._windows += max(1.0, float(windows))
            self._folds += 1
            for name, ms, arm in rows:
                row = self._rows.get((arm, name))
                if row is None:
                    row = self._rows[(arm, name)] = {
                        "count": 0, "total_ms": 0.0}
                row["count"] += 1
                row["total_ms"] += ms
        return len(rows)

    def ms_per_window(self) -> Dict[str, float]:
        """Measured ms/window decomposition per arm."""
        with self._lock:
            if not self._windows:
                return {}
            out: Dict[str, float] = {}
            for (arm, _name), row in self._rows.items():
                out[arm] = out.get(arm, 0.0) + row["total_ms"]
            return {arm: ms / self._windows for arm, ms in out.items()}

    def snapshot(self, top: int = 50) -> dict:
        with self._lock:
            windows = self._windows
            rows = sorted(self._rows.items(),
                          key=lambda kv: -kv[1]["total_ms"])[:top]
            table = [{"kernel": name, "arm": arm, "count": r["count"],
                      "total_ms": round(r["total_ms"], 4),
                      "ms_per_window":
                          round(r["total_ms"] / windows, 5) if windows
                          else 0.0}
                     for (arm, name), r in rows]
            folds = self._folds
        return {"windows": windows, "folds": folds, "rows": table,
                "ms_per_window": {a: round(v, 5)
                                  for a, v in self.ms_per_window().items()}}


# -------------------------------------------------------------- window clock


class WindowClock:
    """Always-on per-executable window clock: the pipeline feeds one
    dispatch→fetch-ready observation per drain, keyed by the executable
    arm (compact32_xla / composed_drain / composed_analytics).  Keeps a
    per-arm EWMA, feeds the `guber_tpu_device_window_ms{arm}` histogram,
    and records slow windows into a bounded ring WITH the trace-ID
    exemplars of the requests that rode them — the p99 link back to a
    stitched trace."""

    ALPHA = 0.2

    def __init__(self, metrics=None, ring: Optional[int] = None,
                 slow_ms: Optional[float] = None) -> None:
        self.metrics = metrics
        self.slow_ms = (env_float("GUBER_DEVPROF_SLOW_MS", 50.0)
                        if slow_ms is None else float(slow_ms))
        n = env_int("GUBER_DEVPROF_RING", 64) if ring is None else int(ring)
        self._slow: List[dict] = []
        self._slow_cap = max(1, n)
        self._ewma: Dict[str, float] = {}
        self._count: Dict[str, int] = {}
        self._lock = threading.Lock()

    def observe(self, arm: str, seconds: float,
                trace_ids: Optional[Callable[[], List[str]]] = None,
                windows: int = 1) -> bool:
        """One drain's dispatch→fetch-ready duration.  `trace_ids` is a
        thunk evaluated ONLY when the window is slow (the fast path never
        walks the job list).  Returns True when the window was recorded as
        a slow exemplar."""
        ms = max(0.0, seconds) * 1000.0
        m = self.metrics
        if m is not None:
            m.device_window_ms.labels(arm=arm).observe(ms)
        with self._lock:
            prev = self._ewma.get(arm)
            ew = ms if prev is None else prev + self.ALPHA * (ms - prev)
            self._ewma[arm] = ew
            self._count[arm] = self._count.get(arm, 0) + 1
        # slow = past the absolute floor AND well past this arm's norm
        if ms < self.slow_ms or ms < 3.0 * ew:
            return False
        rec = {"arm": arm, "ms": round(ms, 3), "windows": windows,
               "at": time.time(),
               "trace_ids": (trace_ids() if trace_ids is not None else [])}
        with self._lock:
            self._slow.append(rec)
            if len(self._slow) > self._slow_cap:
                del self._slow[0]
        return True

    def snapshot(self) -> dict:
        with self._lock:
            arms = {arm: {"ewma_ms": round(ew, 4),
                          "count": self._count.get(arm, 0)}
                    for arm, ew in self._ewma.items()}
            slow = list(self._slow[-16:])
        return {"arms": arms, "slow_windows": slow}


# ------------------------------------------------------- continuous profiling


class DevprofController:
    """`GUBER_DEVPROF=periodic`: every `interval` seconds, arm an N-drain
    `jax.profiler` capture through the instance's ProfileCapture, wait for
    it to complete, parse + fold the trace into the rolling KernelTable,
    and delete the trace dir.  Sheds (skips the cycle, counted) whenever a
    capture is already in flight — an operator-armed capture always wins —
    and cancels a capture the traffic never completed."""

    def __init__(self, profile, table: KernelTable,
                 interval: Optional[float] = None,
                 drains: Optional[int] = None,
                 metrics=None,
                 windows_fn: Optional[Callable[[], int]] = None) -> None:
        self.profile = profile
        self.table = table
        self.metrics = metrics
        self.interval = (env_float("GUBER_DEVPROF_INTERVAL_S", 30.0,
                                   minimum=0.05)
                         if interval is None else max(0.05, float(interval)))
        self.drains = (env_int("GUBER_DEVPROF_DRAINS", 8)
                       if drains is None else max(1, int(drains)))
        self.windows_fn = windows_fn
        self.cycles = 0
        self.sheds = 0
        self.kernel_rows = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._tmp: Optional[str] = None

    # split out so tests drive one deterministic cycle without the thread
    def run_once(self, capture_timeout: Optional[float] = None) -> bool:
        if self.profile is None or self.profile.armed:
            self.sheds += 1
            self._count("shed")
            return False
        tmp = self._tmp = tempfile.mkdtemp(prefix="guber-devprof-")
        try:
            w0 = self.windows_fn() if self.windows_fn is not None else 0
            out = self.profile.arm(self.drains, tmp)
            if not out.get("armed"):
                self.sheds += 1
                self._count("shed")
                return False
            budget = (self.interval if capture_timeout is None
                      else capture_timeout)
            deadline = time.monotonic() + budget
            while (self.profile.armed and time.monotonic() < deadline
                   and not self._stop.is_set()):
                time.sleep(0.02)
            if self.profile.armed:
                # traffic too idle to complete N drains inside the budget:
                # stop the capture and fold whatever it caught
                self.profile.cancel()
            # `armed` drops only once stop_trace has written the capture
            # (on the capture's own thread; cancel waits for it)
            settle = time.monotonic() + 10.0
            while (self.profile.armed and time.monotonic() < settle
                   and not self._stop.is_set()):
                time.sleep(0.02)
            w1 = self.windows_fn() if self.windows_fn is not None else 0
            windows = max(1, w1 - w0) if self.windows_fn else self.drains
            events = parse_run_dir(tmp)
            folded = self.table.fold(events, windows=windows)
            self.kernel_rows += folded
            self.cycles += 1
            self._count("folded" if folded else "empty")
            return folded > 0
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
            self._tmp = None

    def _count(self, status: str) -> None:
        if self.metrics is not None:
            self.metrics.devprof_captures.labels(status=status).inc()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.run_once()
            except Exception:  # noqa: BLE001 — profiling never kills serving
                log.exception("devprof: periodic capture cycle failed")

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            name="guber-devprof", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        # if the join timed out mid-cycle (stop_trace can block past it),
        # the thread's finally never ran — reap its capture dir here so a
        # shutdown never strands trace output on disk
        tmp = self._tmp
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
            self._tmp = None

    def status(self) -> dict:
        return {"interval_s": self.interval, "drains": self.drains,
                "cycles": self.cycles, "sheds": self.sheds,
                "kernel_rows": self.kernel_rows,
                "running": self._thread is not None}


class Devprof:
    """Instance-level facade: the rolling kernel table, the pipeline's
    window clock (wired by core/service.py), and the optional continuous
    controller."""

    def __init__(self, mode: str = "", metrics=None, profile=None,
                 windows_fn: Optional[Callable[[], int]] = None,
                 interval: Optional[float] = None,
                 drains: Optional[int] = None) -> None:
        self.mode = mode or "off"
        self.table = KernelTable()
        self.clock: Optional[WindowClock] = None
        self.controller: Optional[DevprofController] = None
        if mode == "periodic" and profile is not None:
            self.controller = DevprofController(
                profile, self.table, interval=interval, drains=drains,
                metrics=metrics, windows_fn=windows_fn)

    def start(self) -> None:
        if self.controller is not None:
            self.controller.start()

    def close(self) -> None:
        if self.controller is not None:
            self.controller.stop()

    def status(self) -> dict:
        snap = self.table.snapshot(top=0)
        out = {"mode": self.mode,
               "table": {"windows": snap["windows"],
                         "folds": snap["folds"],
                         "ms_per_window": snap["ms_per_window"]}}
        if self.clock is not None:
            out["clock"] = self.clock.snapshot()
        if self.controller is not None:
            out["controller"] = self.controller.status()
        return out

    def kernels_snapshot(self, top: int = 50) -> dict:
        """The `/v1/admin/kernels` payload: measured ms/window per arm,
        plus the rolling kernel table and the window clock."""
        table = self.table.snapshot(top=top)
        arms = {arm: {"measured_ms_per_window": ms}
                for arm, ms in sorted(table["ms_per_window"].items())}
        out = {"arms": arms, "table": table["rows"],
               "windows": table["windows"]}
        if self.clock is not None:
            out["clock"] = self.clock.snapshot()
        if self.controller is not None:
            out["controller"] = self.controller.status()
        return out


# --------------------------------------------------- probe arms, measured pass


def build_probe_arms(k: int = 8):
    """The serving-arm programs as runnable specs over a tiny
    single-device probe engine: [{name, fn, args, windows}], for
    measure_probe_arms to compile and run under a `jax.profiler`
    capture."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gubernator_tpu.config import AnalyticsConfig
    from gubernator_tpu.core import engine as em
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.ops import kernel
    from gubernator_tpu.parallel.mesh import make_mesh

    t0 = 1_700_000_000_000
    mesh = make_mesh(jax.devices()[:1])
    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=256,
                          batch_per_shard=64, global_capacity=32,
                          global_batch_per_shard=8, max_global_updates=8)
    s, b = eng.num_shards, eng.batch_per_shard

    # the oracle steps int64 rows; the serving arms step the arena's
    # resident uint32 planes, as the engine's executables do
    st1 = kernel.BucketState.zeros(eng.capacity_per_shard)
    arena1 = kernel.ArenaPlanes.zeros(eng.capacity_per_shard)
    packed1 = jnp.zeros((b, 2), jnp.int64)

    def xla64(state, packed, now):
        return kernel.window_step(state, kernel.decode_batch(packed), now)

    def c32(state, packed, now):
        st, out = kernel.window_step_compact32(
            state, kernel.decode_batch(packed), now)
        return st, kernel.encode_output_word(out, now)

    packed = np.zeros((k, s, b, 2), np.int64)
    nows = np.full(k, t0, np.int64)
    gb, ga, upd = eng.empty_drain_control()
    fdrain = em._compiled_pipeline_step_global(eng.mesh)
    conf = AnalyticsConfig()
    eng.enable_analytics(conf)
    fan = em._compiled_pipeline_step_global(
        eng.mesh, (conf.sketch_depth, conf.sketch_width, conf.tenant_slots,
                   conf.topk, conf.over_weight))
    ten = np.zeros((k, s, b), np.int32)

    # mixed-algorithm composed window: every wire algorithm (token, leaky,
    # GCRA, sliding-window, concurrency) live in ONE packed window's lanes,
    # so the measured pass drives all five transition ladders through the
    # same program as composed_drain.
    lane = np.arange(b, dtype=np.int64)
    mix1 = kernel.encode_batch_host(
        lane % eng.capacity_per_shard, np.ones(b, np.int64),
        np.full(b, 100, np.int64), np.full(b, 60_000, np.int64),
        lane % 5, np.zeros(b, np.int64))
    packed_mix = np.broadcast_to(mix1, (k, s, b, 2)).copy()

    one = (st1, packed1, jnp.int64(t0))
    one_arena = (arena1, packed1, jnp.int64(t0))
    drain_args = (eng.state, eng.gstate, eng.gcfg, packed, gb, ga, upd, nows)
    mix_args = (eng.state, eng.gstate, eng.gcfg, packed_mix, gb, ga, upd,
                nows)
    an_args = drain_args + (eng._an_sketch, ten, jnp.int64(0))
    return [
        {"name": "int64_xla", "fn": xla64, "args": one, "windows": 1},
        {"name": "compact32_xla", "fn": c32, "args": one_arena, "windows": 1},
        {"name": "composed_drain", "fn": fdrain, "args": drain_args,
         "windows": k},
        {"name": "composed_mixed_algos", "fn": fdrain, "args": mix_args,
         "windows": k},
        {"name": "composed_analytics", "fn": fan, "args": an_args,
         "windows": k},
    ]


def measure_probe_arms(arms=None, iters: int = 2,
                       table: Optional[KernelTable] = None) -> dict:
    """Compile each probe arm, warm it, run `iters` iterations under an
    arm-scoped `jax.profiler` capture, and parse the trace into measured
    ms/window, keyed by the arm's name.  Returns
    {"arms": {name: {...}}, "kernel_table": snapshot} and folds into
    `table` when given (the Instance's rolling table)."""
    import jax

    if arms is None:
        arms = build_probe_arms()
    if table is None:
        table = KernelTable()
    measured: Dict[str, dict] = {}
    # arms sharing one body (composed_drain / composed_mixed_algos differ
    # only in data) share one jitted wrapper so the body compiles once
    jits: Dict[int, object] = {}
    for spec in arms:
        name, windows = spec["name"], spec["windows"]
        fn = spec["fn"]
        jf = jits.get(id(fn))
        if jf is None:
            jf = jits[id(fn)] = jax.jit(fn)
        out = jf(*spec["args"])
        jax.block_until_ready(out)
        tmp = tempfile.mkdtemp(prefix=f"guber-measure-{name}-")
        try:
            jax.profiler.start_trace(tmp)
            try:
                for _ in range(max(1, iters)):
                    out = jf(*spec["args"])
                    jax.block_until_ready(out)
            finally:
                jax.profiler.stop_trace()
            events = parse_run_dir(tmp)
            rows = self_times(events, arm_hint=name)
            total_ms = sum(ms for _n, ms, _a in rows)
            table.fold(events, windows=windows * max(1, iters),
                       arm_hint=name)
            measured[name] = {
                "measured_ms_per_window":
                    round(total_ms / (windows * max(1, iters)), 5),
                "kernel_events": len(rows),
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return {"arms": measured, "kernel_table": table.snapshot()}
