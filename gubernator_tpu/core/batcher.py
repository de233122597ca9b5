"""Window batcher: accumulates decisions into device windows.

The TPU-side analog of the reference's per-peer batching loop
(peers.go:143-172): requests queue until `batch_limit` (1000) items or
`batch_wait` (500µs) elapses, then the whole window ships — there as one
GetPeerRateLimits RPC, here as one device step.  Responses resolve back to
awaiting callers by lane index (the reference demuxes by slice index,
peers.go:204-207).

The engine is not thread-safe, so all device work funnels through a
single-thread executor; NO_BATCHING requests jump the window but share that
serialization (the reference gets the same property from the cache mutex,
gubernator.go:237).
"""

from __future__ import annotations

import asyncio
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

from jax.profiler import TraceAnnotation

from gubernator_tpu.api.types import Behavior, RateLimitReq, RateLimitResp
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.core.interval import ArmedInterval
from gubernator_tpu.core.pipeline import DispatchPipeline
from gubernator_tpu.core.window_buffers import RequestColumns
from gubernator_tpu.net.faults import FAULTS, SEAM_ENGINE_DISPATCH
from gubernator_tpu.qos import interleave_by_tenant, shed_response
from gubernator_tpu.qos.fairness import tenant_of

log = logging.getLogger("gubernator.batcher")


class WindowBatcher:
    def __init__(
        self,
        engine: RateLimitEngine,
        behaviors: Optional[BehaviorConfig] = None,
        metrics=None,
        lockstep_clock=None,
        qos=None,
        tracer=None,
        analytics=None,
        slo=None,
    ):
        self.engine = engine
        self.behaviors = behaviors or BehaviorConfig()
        self.metrics = metrics
        # observability/tracing.py Tracer or None; the pipeline shares it
        # for per-request stage spans (sampled requests only)
        self.tracer = tracer
        # on-demand device capture (observability/introspect.py), armed by
        # POST /v1/admin/profile; the engine thread reads one bool around
        # each dispatch and never calls into the profiler itself
        from gubernator_tpu.observability import ProfileCapture
        self.profile = ProfileCapture()
        # QoSManager (gubernator_tpu/qos/) or None: admission control on
        # submit, congestion-adaptive window sizing, tenant-fair slotting.
        # None keeps every legacy code path byte-identical.
        self.qos = qos
        self._pending: List[tuple] = []  # (req, accumulate, future)
        # Columnar mirror of _pending (classic batched lane, non-lockstep
        # only): submit-time accumulation so _flush can hand engine.process
        # zero-copy column slices instead of re-walking the request objects
        # on the engine thread.  Valid only while the mirror exactly matches
        # _pending row-for-row (no GLOBAL entries); any deviation — GLOBAL
        # submit, tenant-fair permutation, cwnd split leftover — drops the
        # columns for that window and resynchronizes.
        self._cols: Optional[RequestColumns] = (
            None if lockstep_clock is not None or engine.native is None
            else RequestColumns())
        self._cols_valid = True
        self._interval: Optional[ArmedInterval] = None
        self._waiter: Optional[asyncio.Task] = None
        # one thread == one device stream; serializes all engine access
        self._executor = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="guber-device")
        self._closed = False
        # Injectable clock for the classic (non-pipeline) window path —
        # None means wall time.  Tests pin it alongside pipeline.now_fn so
        # a job that falls back off the pipeline stays on the same clock.
        self.now_fn = None
        # Mesh mode: windows dispatch on a fixed cluster-wide clock — every
        # tick, even empty, because all processes must issue the same
        # dispatch sequence (parallel/distributed.py).  submit_now loses its
        # jump-the-window property; everything rides the next tick.
        self.clock = lockstep_clock
        self._tick_task: Optional[asyncio.Task] = None
        # set when this host can no longer keep its collective sequence
        # aligned (repeated dispatch failure): fail-stop, don't diverge
        self._failed = False
        # Graceful lockstep drain: every process agrees on a final tick index
        # and stops after dispatching exactly that many windows, so no host
        # is left waiting on a collective that will never be issued.
        self.stop_at_tick: Optional[int] = None
        # The pipelined serving lane (core/pipeline.py): compact-eligible
        # non-GLOBAL traffic coalesces into stacked compact dispatches;
        # everything else (out-of-range configs, no native router) stays
        # on the legacy lanes below.  In lockstep (mesh) mode the SAME
        # lane runs in lockstep form: staging is continuous, the drain
        # dispatches as slot 1 of every cluster tick (fixed shape, the
        # GLOBAL-composed fused executable — GLOBAL accumulate singles
        # ride ITS composed psum window via eligible_global), and the
        # legacy stacked step is slot 2 — so mesh serving gets the
        # compact wire + duplicate-run fold + fused megakernel without
        # executable divergence across processes.
        if engine.multiprocess and lockstep_clock is None:
            # fail loudly at construction: without a tick loop nothing
            # would ever drain a multiprocess engine's windows, and
            # eligible submits would hang forever
            raise ValueError("a multiprocess (mesh) engine needs a "
                             "lockstep_clock-driven WindowBatcher")
        self.pipeline: Optional[DispatchPipeline] = DispatchPipeline(
            engine, self._executor, metrics,
            lockstep=lockstep_clock is not None, qos=qos, tracer=tracer,
            profile=self.profile, analytics=analytics, slo=slo)
        if not self.pipeline.enabled:
            self.pipeline = None
        elif self.pipeline.lockstep:
            # fallbacks must ride the tick queue, not dispatch directly
            self.pipeline.legacy = self._legacy_lockstep
        else:
            self.pipeline.legacy = self._legacy_process
            # submit-side coalescing window = the configured BatchWait
            # (the reference's knob, config.go:60-62) — not a hardcoded
            # twin of its default
            self.pipeline.coalesce_wait = self.behaviors.batch_wait

    async def _legacy_process(self, reqs: Sequence[RateLimitReq]
                              ) -> List[RateLimitResp]:
        """Full-path processing for pipeline fallbacks (chunking, full wire
        format, every semantic).  Honors the injectable clock (now_fn) so
        tests keep fallbacks on the same timeline as pipeline drains."""
        loop = asyncio.get_running_loop()
        now = self.now_fn() if self.now_fn is not None else None
        return await loop.run_in_executor(
            self._executor, lambda: self.engine.process(reqs, now))

    async def _legacy_lockstep(self, reqs: Sequence[RateLimitReq]
                               ) -> List[RateLimitResp]:
        """Lockstep-mode pipeline fallback: a direct engine.process would
        dispatch OUTSIDE the tick sequence and desync the mesh — fallbacks
        instead join the tick queue and ride the next cluster tick, with
        per-item error semantics like submit_now."""
        loop = asyncio.get_running_loop()
        futs = [loop.create_future() for _ in reqs]
        self._pending.extend((r, True, f) for r, f in zip(reqs, futs))
        results = await asyncio.gather(*futs, return_exceptions=True)
        return [r if isinstance(r, RateLimitResp)
                else RateLimitResp(error=str(r)) for r in results]

    async def submit_rpc(self, data: bytes, peer_mode: bool = False):
        """Serve a whole serialized GetRateLimitsReq (or, with peer_mode,
        an authoritative GetPeerRateLimitsReq) through the pipeline; None
        => caller must use the full path (always the case in lockstep
        mode where other hosts own some of the shards: the pipeline keeps
        the raw-RPC lane gated off there — rpc_enabled — because the C
        parser routes by local shard)."""
        if self.pipeline is None:
            return None
        return await self.pipeline.submit_rpc(data, peer_mode=peer_mode)

    async def submit_cols(self, cols: tuple, n: int,
                          want_cols: bool = False, ctx=None):
        """Frontdoor shm lane: serve worker-parsed request COLUMNS through
        the pipeline (core/pipeline.py ColsJob); with want_cols the result
        is decision columns for a worker-encoded completion instead of
        engine-encoded bytes.  `ctx` carries the worker-propagated
        traceparent (shm trace region) so drain spans root under the
        caller's trace.  None => the hub runs the engine-side Python
        fallback."""
        if self.pipeline is None:
            return None
        return await self.pipeline.submit_cols(cols, n, want_cols=want_cols,
                                               ctx=ctx)

    def start_lockstep(self) -> None:
        """Begin the lockstep tick loop (mesh mode; call inside the loop)."""
        assert self.clock is not None
        if self._tick_task is None:
            self._tick_task = asyncio.create_task(self._tick_loop())

    async def _tick_loop(self) -> None:
        """The lockstep clock's ticks, on the wall clock: sleep to the next
        deadline, never run ahead of it, skip whole periods when behind
        (parallel/distributed.py LockstepClock).  A tick's sequence, the
        same on every process: [compact drain, legacy stacked step].

        Where other hosts wait on this one's collectives (a multiprocess
        engine) both are dispatched every tick, work or none, and the tick
        ends when they have been.  A process that holds every shard
        dispatches the drain only when something is staged and the
        pipeline has room (lockstep_pump), the legacy step only for items
        that had to take it, and does not wait for either: an idle tick
        costs no device work, and the next drain is packed while the last
        one runs."""
        clock = self.clock
        loop = asyncio.get_running_loop()
        pipe = self.pipeline
        if pipe is not None and not pipe.lockstep:
            pipe = None
        must = self.engine.multiprocess
        stack = max(self.behaviors.lockstep_stack, 1)
        m = self.metrics
        kinds = pipe.lockstep_ticks if pipe is not None else {}
        skipped = 0

        def count(kind: str, n: int = 1) -> None:
            if n:
                kinds[kind] = kinds.get(kind, 0) + n
                if m is not None:
                    m.lockstep_ticks.labels(kind=kind).inc(n)

        # the epoch is the first tick's: start-up, warm-up and a fill lie
        # behind it.  Several hosts agree it (and each tick's index)
        # through a collective, which belongs on the engine thread.
        if clock.agrees:
            await loop.run_in_executor(self._executor, clock.start)
        else:
            clock.start()
        while not self._closed:
            if (self.stop_at_tick is not None
                    and clock.tick >= self.stop_at_tick):
                return
            # sleep(0) when behind: the loop still gets to the submits
            await asyncio.sleep(max(clock.until_next(), 0.0))
            if self._closed:
                return
            try:
                if clock.agrees:
                    now = await loop.run_in_executor(self._executor,
                                                     clock.next_now)
                else:
                    now = clock.next_now()
                if m is not None:
                    m.observe_stage("tick_lag", clock.lag_s)
                count("skipped", clock.skipped - skipped)
                skipped = clock.skipped
                with TraceAnnotation("guber_tick"):
                    # per-window try: a failure taking window k must not
                    # discard windows already taken (their futures would
                    # hang forever)
                    windows = []
                    if must or self._pending:
                        for _ in range(stack):
                            try:
                                windows.append(self._take_window())
                            except Exception:  # the tick loop must never die
                                windows.append([])
                    # Both land on the single-thread engine executor in
                    # submission order, so queueing the drain first fixes
                    # the collective order process-wide.
                    drain_fut = (pipe.lockstep_pump(now, stack, must)
                                 if pipe is not None else None)
                    legacy = must or any(windows)
                if drain_fut is not None:
                    count("drain")
                elif pipe is not None and pipe._hold_reason not in (
                        None, "empty"):
                    # queued work the pump holds back, whatever the reason
                    count("held")
                elif not legacy:
                    count("idle")
                if legacy:
                    await self._run_lockstep_window(windows, now)
                if drain_fut is not None and must:
                    # surfaces only irrecoverable drain-dispatch failure
                    # (the zero-stack realign also failed): fail-stop
                    await drain_fut
            except Exception:
                # dispatch irrecoverably failed (see the fail-stop in
                # _run_lockstep_window): stop ticking and fail everything
                # still queued instead of silently desyncing the mesh.
                # Close the pipeline FIRST — it fails its queued
                # singles/jobs with an error (no tick will ever drain
                # them); fallback jobs already re-routed by
                # _legacy_lockstep sit in _pending and fail below
                self._failed = True
                if self.pipeline is not None:
                    self.pipeline.close()
                for _, _, fut in self._pending:
                    if not fut.done():
                        fut.set_exception(
                            RuntimeError("lockstep dispatch failed; "
                                         "this host left the mesh"))
                self._pending.clear()
                raise

    def _take_window(self) -> List[tuple]:
        """Pull one window's worth of valid pending requests.

        Invalid entries (mis-routed key, unregistered GLOBAL key — e.g. from
        a peer with a stale picker) are failed INDIVIDUALLY here: a packing
        exception later would skip this host's dispatch for the tick and
        wedge the mesh lockstep.  Only as many entries as a window has
        lanes are looked at: a backlog behind them waits untouched."""
        if not self._pending:
            return []
        eng = self.engine
        reach = eng.num_local_shards * (eng.batch_per_shard
                                        + eng.global_batch_per_shard)
        head, tail = self._pending[:reach], self._pending[reach:]
        ok = []
        for item in head:
            err = eng.routing_error(item[0])
            if err is None:
                ok.append(item)
            elif not item[2].done():
                item[2].set_exception(ValueError(err))
        if self.qos is not None and self.qos.fair_slotting:
            # tenant-fair slotting: the prefix cut below must not hand every
            # lane to one hot tenant's burst (stable within tenant, so
            # per-key order is preserved — same key => same tenant)
            ok = interleave_by_tenant(ok, lambda t: tenant_of(t[0]))
        fit = eng.max_window_prefix([w[0] for w in ok])
        if self.qos is not None:
            fit = min(fit, self._window_limit())
        window, self._pending = ok[:fit], ok[fit:] + tail
        return window

    async def _run_lockstep_window(self, windows: List[List[tuple]],
                                   now: int) -> None:
        """Dispatch one tick's legacy stacked step: `windows` is the tick's
        window list — length 1 (classic) or lockstep_stack (stacked, one
        device call via engine.step_stacked).  Either way this issues
        EXACTLY one dispatch of the tick's agreed executable shape."""
        stacked = self.behaviors.lockstep_stack > 1
        loop = asyncio.get_running_loop()
        start = time.monotonic()
        n_reqs = sum(len(w) for w in windows)
        if self.pipeline is not None and self.pipeline.lockstep and n_reqs:
            self.pipeline.lane_decisions["legacy"] += n_reqs
            if self.metrics is not None:
                self.metrics.lockstep_decisions.labels(
                    lane="legacy").inc(n_reqs)
        # Structural invariant: this tick issues EXACTLY one device dispatch,
        # no matter what step() does.  windows_processed increments once per
        # dispatch (K times for a stacked tick), so compare it instead of
        # guessing whether step() raised before or after its device work.
        # Captured INSIDE run() (on the engine thread): the tick's drain
        # dispatch is queued ahead of us on the same executor and also
        # advances the counter, so a loop-thread read here would be stale.
        before = None

        def run():
            if FAULTS.enabled:
                FAULTS.on_sync(SEAM_ENGINE_DISPATCH, "lockstep")
            nonlocal before
            before = self.engine.windows_processed
            if stacked:
                resps = self.engine.step_stacked(
                    [[t[0] for t in w] for w in windows], now,
                    [[t[1] for t in w] for w in windows],
                    k_stack=self.behaviors.lockstep_stack)
            else:
                w = windows[0]
                resps = [self.engine.step([t[0] for t in w], now,
                                          [t[1] for t in w])]
            self._tier_maintain(now)
            return resps

        def run_empty():
            if stacked:
                return self.engine.step_stacked(
                    [[]], now, k_stack=self.behaviors.lockstep_stack)
            return self.engine.step([], now)

        def run_profiled():
            prof = self.profile
            profiling = prof is not None and prof.tracing
            try:
                return run()
            finally:
                if profiling:
                    prof.after_drain()

        try:
            resps = await loop.run_in_executor(self._executor, run_profiled)
        except Exception as e:
            for w in windows:
                for _, _, fut in w:
                    if not fut.done():
                        fut.set_exception(e)
            if self.engine.windows_processed == before:
                # step raised before any device work: issue the tick's
                # collective so the other processes' dispatches pair up
                # (an empty dispatch has the same executable shape).
                # Retry transient failures — skipping the dispatch entirely
                # would desync this host's collective sequence permanently,
                # which is worse than blocking the tick (the other hosts just
                # wait in the collective, which is ordinary backpressure).
                for attempt in range(3):
                    try:
                        await loop.run_in_executor(self._executor, run_empty)
                        break
                    except Exception:
                        if attempt == 2:
                            # fail-stop beats silent divergence: a host that
                            # cannot dispatch can never rejoin the lockstep
                            self._failed = True
                            raise
                        await asyncio.sleep(0.05)
            return
        if self.qos is not None and n_reqs:
            self.qos.congestion.observe_drain(time.monotonic() - start,
                                             depth=len(windows))
        if self.metrics is not None and n_reqs:
            self.metrics.window_count.inc()
            self.metrics.window_occupancy.observe(n_reqs)
            self.metrics.window_duration.observe(time.monotonic() - start)
            # the legacy stacked step is dispatch-through-done in one call;
            # stage decomposition attributes it all to device_dispatch
            self.metrics.observe_stage("device_dispatch",
                                       time.monotonic() - start)
        for w, rs in zip(windows, resps):
            for (_, _, fut), resp in zip(w, rs):
                if not fut.done():
                    fut.set_result(resp)

    def _tier_maintain(self, now) -> None:
        """Proactive warm-tier demotion between windows (state/tiers.py).
        Runs on the engine executor right after a drain, where the device
        rows are current; a no-op attribute check when tiers are off.
        Never fails the window — maintenance is an optimization, forced
        eviction inside staging still covers correctness."""
        if self.engine._tiers is None:
            return
        try:
            self.engine.tier_maintain(now)
        except Exception:
            log.exception("warm-tier maintenance failed; continuing")

    # ------------------------------------------------------------- batched

    def _window_limit(self) -> int:
        """Flush threshold: the static batch_limit capped by the AIMD
        congestion window (qos/congestion.py) when QoS is active."""
        limit = self.behaviors.batch_limit
        if self.qos is not None:
            limit = min(limit, self.qos.congestion.effective_window())
        return max(1, limit)

    async def submit(self, req: RateLimitReq, accumulate: bool = True,
                     deadline: Optional[float] = None) -> RateLimitResp:
        """Queue into the current window; resolves when the window executes.

        With QoS active the request first passes admission control:
        a full bounded queue or an unserviceable deadline (monotonic
        absolute, see QoSManager.deadline_from_timeout) yields an in-band
        shed response instead of queueing.  The admission slot is held
        until the decision resolves, so `pending` counts real in-flight
        decisions, not just the unflushed window."""
        if self._failed:
            raise RuntimeError("lockstep dispatch failed; "
                               "this host left the mesh")
        if self.qos is None:
            return await self._submit_admitted(req, accumulate)
        reason = self.qos.admission.try_admit(1, deadline=deadline)
        if reason is not None:
            return shed_response(req, reason)
        try:
            return await self._submit_admitted(req, accumulate)
        finally:
            self.qos.admission.release(1)

    async def _submit_admitted(self, req: RateLimitReq,
                               accumulate: bool) -> RateLimitResp:
        if (self.pipeline is not None and accumulate
                and (self.pipeline.eligible(req)
                     or self.pipeline.eligible_global(req))):
            return await self.pipeline.submit_one(req)
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending.append((req, accumulate, fut))
        if self._cols is not None:
            if req.behavior == Behavior.GLOBAL:
                # GLOBAL rides the listed lane inside process(); the
                # columnar fast path covers regular keys only
                self._cols_valid = False
            else:
                self._cols.append(req)
        if self.clock is not None:
            return await fut  # the tick loop drains on the cluster cadence
        if len(self._pending) >= self._window_limit():
            self._flush()
        elif len(self._pending) == 1:
            if self._interval is None:
                self._interval = ArmedInterval(self.behaviors.batch_wait)
            self._interval.arm()
            if self._waiter is None or self._waiter.done():
                self._waiter = asyncio.create_task(self._wait_interval())
        return await fut

    async def _wait_interval(self) -> None:
        await self._interval.wait()
        if self._pending:
            self._flush()

    def _flush(self) -> None:
        window = self._pending
        self._pending = []
        use_cols = self._cols is not None and self._cols_valid
        if self.qos is not None:
            if self.qos.fair_slotting:
                window = interleave_by_tenant(window, lambda t: tenant_of(t[0]))
                use_cols = False  # permuted: rows no longer match _cols
            # the congestion window caps decisions-per-dispatch: the excess
            # stays queued for the next cycle (and re-arms the timer so it
            # cannot strand if no further submit arrives)
            limit = self._window_limit()
            if len(window) > limit:
                window, self._pending = window[:limit], window[limit:]
                use_cols = False  # leftovers desync the columnar mirror
                if self._interval is None:
                    self._interval = ArmedInterval(self.behaviors.batch_wait)
                self._interval.arm()
                if self._waiter is None or self._waiter.done():
                    self._waiter = asyncio.create_task(self._wait_interval())
        cols = None
        if self._cols is not None:
            if use_cols and self._cols.n == len(window):
                # detach: the window task reads these arrays while new
                # submits accumulate into a fresh mirror
                cols, self._cols = self._cols, RequestColumns()
            else:
                self._cols.reset()
            self._cols_valid = True
        asyncio.create_task(self._run_window(window, cols))

    async def _run_window(self, window: List[tuple],
                          cols: Optional[RequestColumns] = None) -> None:
        reqs = [w[0] for w in window]
        accumulate = [w[1] for w in window]
        columns = cols.take(None, 0, cols.n) if cols is not None else None
        loop = asyncio.get_running_loop()
        start = time.monotonic()
        def run():
            if FAULTS.enabled:
                FAULTS.on_sync(SEAM_ENGINE_DISPATCH, "window")
            prof = self.profile
            profiling = prof is not None and prof.tracing
            try:
                now = self.now_fn() if self.now_fn is not None else None
                resps = self.engine.process(reqs, now, accumulate,
                                            columns=columns)
                self._tier_maintain(now)
                return resps
            finally:
                if profiling:
                    prof.after_drain()

        try:
            resps = await loop.run_in_executor(self._executor, run)
        except Exception as e:  # resolve every waiter with the failure
            for _, _, fut in window:
                if not fut.done():
                    fut.set_exception(e)
            return
        wall = time.monotonic() - start
        if self.qos is not None:
            self.qos.congestion.observe_drain(wall)
        if self.metrics is not None:
            self.metrics.window_count.inc()
            self.metrics.window_occupancy.observe(len(reqs))
            self.metrics.window_duration.observe(wall)
            # legacy full-path window: one engine.process call covers
            # dispatch through fetch; attributed to device_dispatch
            self.metrics.observe_stage("device_dispatch", wall)
        for (_, _, fut), resp in zip(window, resps):
            if not fut.done():
                fut.set_result(resp)

    # ----------------------------------------------------------- immediate

    async def submit_now(
        self,
        reqs: Sequence[RateLimitReq],
        accumulate: Optional[Sequence[bool]] = None,
    ) -> List[RateLimitResp]:
        """Run a ready-made window immediately (NO_BATCHING fast path, and
        batches arriving from peers that were already aggregated remotely).

        In lockstep (mesh) mode there is no immediate path — the requests
        join the queue and ride the next cluster tick."""
        loop = asyncio.get_running_loop()
        acc = list(accumulate) if accumulate is not None else [True] * len(reqs)
        if (self.pipeline is not None and reqs and all(acc)
                and all(self.pipeline.eligible(r) for r in reqs)):
            return await self.pipeline.submit_many(reqs)
        if self.clock is not None:
            futs = [loop.create_future() for _ in reqs]
            self._pending.extend(
                (r, a, f) for r, a, f in zip(reqs, acc, futs))
            # Per-item error semantics (the reference returns item-level
            # errors inside the batch response, gubernator.go:218-226): one
            # invalid request — e.g. mis-routed by a peer's stale picker and
            # failed individually by _take_window — must not discard the
            # responses of valid requests whose hits this tick committed.
            results = await asyncio.gather(*futs, return_exceptions=True)
            return [r if isinstance(r, RateLimitResp)
                    else RateLimitResp(error=str(r)) for r in results]
        return await loop.run_in_executor(
            self._executor, lambda: self.engine.process(reqs, None, acc)
        )

    async def apply_upserts(self, upserts: Sequence) -> None:
        """Write owner-broadcast replica state (chunked to the engine cap)."""
        loop = asyncio.get_running_loop()
        cap = self.engine.max_global_updates
        for i in range(0, len(upserts), cap):
            chunk = list(upserts[i:i + cap])
            await loop.run_in_executor(
                self._executor, lambda c=chunk: self.engine.step([], upserts=c)
            )

    def close(self) -> None:
        self._closed = True
        if self.pipeline is not None:
            self.pipeline.close()
        if self._interval is not None:
            self._interval.stop()
        if self._tick_task is not None:
            self._tick_task.cancel()
        self._executor.shutdown(wait=False)
