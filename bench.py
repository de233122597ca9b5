"""Benchmark: rate-limit decisions/sec/chip, measured at several depths.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

One process.  `--platform` (default tpu) names the device the run is FOR:
if jax.devices()[0] is anything else the bench fails before measuring, and
a tier that raises ends the run with a non-zero exit — there is no
fallback device, no stale record and no child process.  JAX_PLATFORMS
picks the backend; `JAX_PLATFORMS=cpu python bench.py --platform cpu` is the
CPU smoke (small shapes), whose numbers are CPU numbers.

Tiers (each on a FRESH engine so no tier can poison another — the round-3
bench disabled the compact wire format for every later tier by sharing one
engine):

  device_decisions_per_sec   saturation: K pre-packed windows per dispatch
                             (RateLimitEngine.step_windows), inputs resident,
                             outputs un-fetched.  Mixed TOKEN+LEAKY over a
                             1M-slot arena, Zipf(1.1) — the shape of
                             BASELINE.md eval configs (2)/(3).
  host_decisions_per_sec     the PIPELINED host path (core/pipeline.py):
                             pre-serialized 1000-item GetRateLimitsReq bytes
                             through C parse -> stacked compact dispatch ->
                             C proto encode, fetches overlapped — everything
                             the serving host does except the gRPC socket.
  host_sync_decisions_per_sec  legacy synchronous engine.process() calls
                             (one fetch round trip per window — the floor
                             the pipeline exists to beat).
  e2e_decisions_per_sec      gRPC-in -> response-out on a real loopback
                             server (the analog of the reference's full
                             GetRateLimits path, gubernator.go:75-166).
  healthcheck_rtt_ms_p50     HealthCheck round trip (the reference's
                             BenchmarkServer_Ping floor, benchmark_test.go:81).
  thundering_herd_rps/p99    100 concurrent single-item RPC loops (the
                             reference's BenchmarkServer_ThunderingHeard,
                             benchmark_test.go:109).

vs_baseline compares the headline (e2e) against the reference's published
single-node throughput: >2,000 client requests/sec in production
(README.md:94-99 — its only headline throughput number; see BASELINE.md).

Optional: GUBER_PROFILE=<dir> wraps the host tier in a jax.profiler trace.
"""

import argparse
import json
import os
import sys
import time

BASELINE_REQS_PER_SEC = 2000.0


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def bench_device(kernel, jax, jnp, mesh, capacity, lanes, iters):
    """Saturation: K pre-packed windows per dispatch, resident inputs.

    Every measurement here CHAINS dispatches through the donated state
    and ends with a real device_get, so the wall time provably contains
    the device work (a loop that only enqueues measures the enqueue
    rate)."""
    import numpy as np
    from gubernator_tpu.core.engine import RateLimitEngine

    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    K = 8
    N_STACKS = 4
    rng = np.random.default_rng(7)

    def pack_window():
        zipf = rng.zipf(1.1, size=lanes)
        s = ((zipf - 1) % capacity).astype(np.int32)
        return kernel.WindowBatch(
            slot=s[None, :],
            hits=np.ones((1, lanes), np.int64),
            limit=np.full((1, lanes), 1_000_000, np.int64),
            duration=np.full((1, lanes), 60_000, np.int64),
            algo=(s % 2).astype(np.int32)[None, :],
            is_init=np.zeros((1, lanes), bool),
        )

    def stack(ws):
        return kernel.WindowBatch(*[
            np.stack([getattr(w, f) for w in ws]) for f in ws[0]._fields])

    stacks = [jax.device_put(stack([pack_window() for _ in range(K)]))
              for _ in range(N_STACKS)]
    gbatch, gacc, upd, ups = eng.empty_control()
    gstack = jax.device_put(kernel.WindowBatch(*[
        np.stack([getattr(gbatch, f)] * K) for f in gbatch._fields]))
    gaccs = jax.device_put(np.stack([gacc] * K))
    upd = jax.device_put(upd)
    ups = jax.device_put(ups)

    now = 1_700_000_000_000

    def dispatch(i, t):
        nows = jnp.arange(K, dtype=jnp.int64) + t
        return eng.step_windows(stacks[i % N_STACKS], gstack, gaccs,
                                upd, ups, nows, compact_safe=True,
                                n_decisions=K * lanes)

    out = None
    for i in range(3):  # warmup: compile + arena fill
        out = dispatch(i, now + i * K)
    np.asarray(out)  # REAL sync (fetch), not block_until_ready

    t0 = time.perf_counter()
    for i in range(iters):
        out = dispatch(i, now + (3 + i) * K)
    np.asarray(out)  # chained by donated state: fetch waits for ALL
    total = time.perf_counter() - t0
    per_sec = iters * K * lanes / total
    log(f"# device tier (fetch-synced): {iters} x {K} windows x {lanes} "
        f"lanes -> {per_sec:,.0f} decisions/s; capacity={capacity}")

    # single-window latency: CH chained single dispatches, one final fetch;
    # the separately-measured fetch RTT (median of trivial-op fetches of the
    # same output shape) is subtracted before amortizing.  LIMITATION: each
    # sample is a chain MEAN — per-window tails inside a chain are averaged
    # ~CH-fold (per-window fetches would measure the fetch RTT instead),
    # so the reported "p99" is the WORST CHAIN MEAN, a damped tail signal.
    sb = jax.device_put(kernel.WindowBatch(*[a[:1] for a in pack_window()]))
    sg = jax.device_put(gbatch)
    sa = jax.device_put(gacc)
    sout = None
    for i in range(3):
        eng.state, sout, eng.gstate, eng.gcfg = eng._step_fn(
            eng.state, eng.gstate, eng.gcfg, sb, sg, sa, upd, ups,
            jnp.int64(now + 10_000 + i))
    np.asarray(sout)
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jnp.asarray(sout) + 0)  # trivial op + fetch ≈ pure RTT
        rtts.append(time.perf_counter() - t0)
    rtt = float(np.median(rtts))
    slat = []
    CH = 10
    for rep in range(5):
        w0 = time.perf_counter()
        for i in range(CH):
            eng.state, sout, eng.gstate, eng.gcfg = eng._step_fn(
                eng.state, eng.gstate, eng.gcfg, sb, sg, sa, upd, ups,
                jnp.int64(now + 20_000 + rep * CH + i))
        np.asarray(sout)
        slat.append(max(time.perf_counter() - w0 - rtt, 0.0) / CH)
    slat_ms = np.array(slat) * 1000.0
    p50, worst = float(np.percentile(slat_ms, 50)), float(np.max(slat_ms))
    log(f"# single window ({lanes} lanes, chained, rtt {rtt * 1e3:.1f}ms "
        f"subtracted): chain-mean p50={p50:.3f}ms worst={worst:.3f}ms")
    return per_sec, p50, worst


def _zipf_payloads(pb, n_payloads, items, keyspace, name):
    import numpy as np

    rng = np.random.default_rng(11)
    payloads = []
    for p in range(n_payloads):
        keys = (rng.zipf(1.1, size=items) - 1) % keyspace
        msg = pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name=name, unique_key=f"k{keys[i]}", hits=1,
                            limit=1_000_000, duration=60_000,
                            algorithm=int(keys[i]) % 2)
            for i in range(items)])
        payloads.append(msg.SerializeToString())
    return payloads


def bench_host_pipeline(mesh, capacity, lanes, seconds=5.0, concurrency=128):
    """The pipelined host path: RPC bytes -> C parse -> stacked compact
    dispatch -> C encode, fetches overlapped.  No gRPC socket."""
    import asyncio

    from gubernator_tpu.api import pb
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine

    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    batcher = WindowBatcher(eng, BehaviorConfig())
    if batcher.pipeline is None or not batcher.pipeline.enabled:
        # no native router on this box: report 0 for this tier and let the
        # sync/e2e tiers still produce their numbers
        log("# host tier (pipelined): native router unavailable; skipped")
        batcher.close()
        return 0.0, 1.0
    N = 1000
    payloads = _zipf_payloads(pb, 16, N, 100_000, "host")

    import jax
    eng.warmup()  # compiles every serving executable incl. all K buckets

    prof_dir = os.environ.get("GUBER_PROFILE")
    if prof_dir:
        jax.profiler.start_trace(prof_dir)

    async def run():
        done = {"n": 0}
        stop_at = time.perf_counter() + seconds

        async def worker(wid):
            i = 0
            while time.perf_counter() < stop_at:
                out = await batcher.submit_rpc(payloads[(wid + i) % 16])
                assert out is not None
                done["n"] += N
                i += 1

        # one warm round (slot tables, ramp)
        await asyncio.gather(*(batcher.submit_rpc(p) for p in payloads[:4]))
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(w) for w in range(concurrency)))
        return done["n"] / (time.perf_counter() - t0)

    per_sec = asyncio.run(run())
    if prof_dir:
        jax.profiler.stop_trace()
    pipe = batcher.pipeline
    fold = (pipe.decisions_staged / pipe.lanes_staged
            if pipe.lanes_staged else 1.0)
    batcher.close()
    log(f"# host tier (pipelined): {per_sec:,.0f} decisions/sec "
        f"({concurrency} x {N}-item RPC streams, "
        f"aggregation fold {fold:.2f}x)")
    return per_sec, fold


def bench_host_sync(mesh, capacity, lanes, seconds=3.0):
    """Legacy synchronous process() loop: one fetch round trip per window."""
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.core.engine import RateLimitEngine

    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    N = 1000
    reqs = [RateLimitReq(name="hs", unique_key=f"k{i}", hits=1, limit=100,
                         duration=60_000) for i in range(N)]
    now = 1_700_000_100_000
    eng.process(reqs, now=now)  # warm slot table + compile
    t0 = time.perf_counter()
    iters = 0
    while time.perf_counter() - t0 < seconds:
        eng.process(reqs, now=now + 1 + iters)
        iters += 1
    per_sec = iters * N / (time.perf_counter() - t0)
    log(f"# host tier (sync): {per_sec:,.0f} decisions/sec "
        f"({iters} x {N}-request process calls, "
        f"native={'yes' if eng.native is not None else 'no'})")
    return per_sec


def bench_algorithms(mesh, capacity, lanes, seconds=1.0):
    """Algorithm-plane tier: one process() loop per wire algorithm —
    token, leaky, GCRA, sliding-window, concurrency — plus a MIXED batch
    with all five algorithms live in one window.  Runs through the
    engine's adopted serving arm (on chip that is the fused Pallas path
    when the A/B adopted it), so the numbers answer "what does each
    transition ladder cost" next to the host-sync tier's token-only
    figure."""
    from gubernator_tpu.api.types import Algorithm, RateLimitReq
    from gubernator_tpu.core.engine import RateLimitEngine

    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    N = 500
    now = 1_700_000_100_000

    def reqs_for(tag, algo_of):
        # concurrency lanes acquire one lease per round and never release
        # during the bench, so give them a limit the run can't exhaust
        return [RateLimitReq(
                    name=f"alg_{tag}", unique_key=f"k{i}", hits=1,
                    limit=(1_000_000 if algo_of(i) == Algorithm.CONCURRENCY
                           else 100),
                    duration=60_000, algorithm=algo_of(i))
                for i in range(N)]

    batches = [(a.name.lower(), reqs_for(a.name.lower(), lambda _i, a=a: a))
               for a in Algorithm]
    batches.append(("mixed", reqs_for("mixed",
                                      lambda i: Algorithm(i % 5))))
    eng.process(batches[0][1], now=now)  # compile the serving executables
    out = {}
    for tag, reqs in batches:
        eng.process(reqs, now=now)  # warm THIS batch's slot-table rows
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < seconds:
            eng.process(reqs, now=now + 1 + iters)
            iters += 1
        out[tag] = round(iters * N / (time.perf_counter() - t0), 1)
    log("# algorithms tier: " + ", ".join(
        f"{t}={v:,.0f}/s" for t, v in out.items()))
    return {"algorithms_decisions_per_sec": out}


def bench_chain(mesh, capacity, lanes, strides=(1, 2, 4, 8), seconds=2.0):
    """Deferred-fetch chain sweep: the serving drain loop (host re-stage ->
    pipeline_dispatch -> fetch) with the blocking device_get issued every
    Nth dispatch via ONE stacked fetch_stacked_many (the core/pipeline.py
    chain mechanism, isolated from RPC plumbing).  Stride 1 is today's
    fetch-every-drain serving cadence; the sweep measures what each elided
    fetch round trip buys on THIS link (a link with a flat per-fetch cost
    amortizes it N-fold at stride N; on CPU the fetch is cheap and the
    gain is mostly dispatch/stage overlap)."""
    import numpy as np

    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.ops import kernel

    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    rng = np.random.default_rng(11)
    S = eng.num_local_shards
    now = 1_700_000_200_000

    # rotating slot pools; the compact encode runs per dispatch so every
    # stride pays the SAME honest host re-staging cost
    pools = [((rng.zipf(1.1, (S, lanes)) - 1) % capacity).astype(np.int64)
             for _ in range(8)]
    ones = np.ones((S, lanes), np.int64)
    limit = np.full((S, lanes), 1_000_000, np.int64)
    duration = np.full((S, lanes), 60_000, np.int64)
    algo = np.zeros((S, lanes), np.int64)
    noinit = np.zeros((S, lanes), np.int64)

    def stage(i):
        packed = kernel.encode_batch_host(
            pools[i % 8], ones, limit, duration, algo, noinit)
        return np.ascontiguousarray(packed[None])  # [1, S, B, 2]

    for i in range(3):  # warm: compile the K=1 drain + fill the arena
        w, _, m = eng.pipeline_dispatch(stage(i), np.full(1, now, np.int64),
                                        n_windows=1)
    eng.fetch_stacked_many([w, m])

    sweep = {}
    for stride in strides:
        pending = []
        done = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            i = done
            w, _, m = eng.pipeline_dispatch(
                stage(i), np.full(1, now + 10 + i, np.int64), n_windows=1)
            pending.extend((w, m))
            done += 1
            if len(pending) >= 2 * stride:
                eng.fetch_stacked_many(pending)
                pending = []
        if pending:
            eng.fetch_stacked_many(pending)
        total = time.perf_counter() - t0
        per_sec = done * lanes / total
        sweep[stride] = per_sec
        log(f"# chain tier: stride={stride} -> {per_sec:,.0f} decisions/s "
            f"({done} x {lanes}-lane drains, one stacked fetch per "
            f"{stride})")
    base = sweep.get(1, 0.0)
    for stride in strides[1:]:
        if base:
            log(f"# chain tier: stride={stride} speedup vs stride-1 = "
                f"{sweep[stride] / base:.2f}x")
    return sweep


def bench_bigkeys(mesh, on_cpu, seconds=5.0):
    """BASELINE eval config 5: a ~100M-key arena (2^27 slots, ~6.4GB HBM on
    the real chip) under Zipf(1.1) skew with allocation/eviction churn on a
    FULL router table.  Reports sustained decisions/s through the pipelined
    host path plus the device window latency at that arena size (the
    'p99 < 2ms @ 100M keys' half of the north star)."""
    import gc

    import jax
    import numpy as np

    from gubernator_tpu.core.engine import RateLimitEngine

    capacity = (1 << 20) if on_cpu else (1 << 27)
    lanes = 4096 if on_cpu else 32768
    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=64,
                          global_batch_per_shard=8, max_global_updates=8)
    native = eng.native
    if native is None:
        log("# bigkey tier: native router unavailable; skipped")
        return {}

    # ---- prefill the router to a FULL table (8-byte binary keys) ----
    t0 = time.perf_counter()
    chunk = 1 << 16
    ends = (np.arange(chunk, dtype=np.int64) + 1) * 8
    ones = np.ones(chunk, np.int64)
    lim = np.full(chunk, 1_000_000, np.int64)
    dur = np.full(chunk, 600_000, np.int64)
    alg = np.zeros(chunk, np.int32)
    o_slot = np.empty(chunk, np.int32)
    o_hits = np.empty(chunk, np.int64)
    o_lim = np.empty(chunk, np.int64)
    o_dur = np.empty(chunk, np.int64)
    o_alg = np.empty(chunk, np.int32)
    o_init = np.empty(chunk, np.uint8)
    o_shard = np.empty(chunk, np.int32)
    o_lane = np.empty(chunk, np.int32)
    now = 1_700_000_000_000
    for base in range(0, capacity, chunk):
        keys = (base + np.arange(chunk, dtype=np.uint64)).view(np.uint8)
        fill = np.zeros(1, np.int32)
        o_slot.fill(-1)
        native.pack(keys, ends, ones, lim, dur, alg, now, chunk,
                    o_slot, o_hits, o_lim, o_dur, o_alg, o_init,
                    o_shard, o_lane, fill)
        native.commit()
    log(f"# bigkey tier: router prefilled to {native.size:,} keys "
        f"in {time.perf_counter() - t0:.1f}s")

    # ---- serving loop: Zipf hot head + tail churn on the full table ----
    rng = np.random.default_rng(13)
    packed = np.zeros((1, 1, lanes, 2), np.int64)
    row = np.empty(lanes, np.int32)
    lane_arr = np.empty(lanes, np.int32)
    pos_arr = np.empty(lanes, np.int32)
    l_ends = (np.arange(lanes, dtype=np.int64) + 1) * 8
    l_ones = np.ones(lanes, np.int64)
    l_lim = np.full(lanes, 1_000_000, np.int64)
    l_dur = np.full(lanes, 600_000, np.int64)
    l_alg = np.zeros(lanes, np.int32)
    keyspace = capacity + capacity // 8  # tail past capacity -> evictions

    def one_window(i, fetch=True):
        ids = ((rng.zipf(1.1, lanes) - 1) % keyspace).astype(np.uint64)
        keys = ids.view(np.uint8)
        kcur = np.zeros(1, np.int32)
        fills = np.zeros((1, 1), np.int32)
        native.drain_begin()
        # pack_stack caps at 1024 items per call; chunked calls share the
        # drain (one pack sequence, accumulating commits)
        step = 1024
        for b in range(0, lanes, step):
            rc = native.pack_stack(
                keys[b * 8:(b + step) * 8], l_ends[:step],
                l_ones[:step], l_lim[:step], l_dur[:step], l_alg[:step],
                now + i, lanes, 1, packed, kcur, fills,
                row[b:b + step], lane_arr[b:b + step], pos_arr[b:b + step])
            assert rc == step, rc
        words, _, _ = eng.pipeline_dispatch(
            packed, np.full(1, now + i, np.int64), n_windows=1)
        if fetch:
            np.asarray(words)
        native.commit()
        return words

    for i in range(3):  # compile + warm
        one_window(i)
    lat = []
    t0 = time.perf_counter()
    iters = 0
    while time.perf_counter() - t0 < seconds:
        w0 = time.perf_counter()
        one_window(100 + iters)
        lat.append(time.perf_counter() - w0)
        iters += 1
    per_sec = iters * lanes / (time.perf_counter() - t0)
    lat_ms = np.array(lat) * 1e3
    host_p99 = float(np.percentile(lat_ms, 99))

    # device window time at this arena size: chained dispatches (donated
    # state serializes them on-device), ONE final fetch with the measured
    # fetch RTT subtracted — block_until_ready is an enqueue no-op on this
    # runtime, so per-dispatch blocking would under-report (round-4
    # finding).  Samples are chain means: per-window tails are damped
    # ~CH-fold; the "p99" key carries the WORST chain mean.
    last = one_window(9_999, fetch=True)
    rtts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.numpy.asarray(last) + 0)
        rtts.append(time.perf_counter() - t0)
    rtt = float(np.median(rtts))
    dlat = []
    CH = 5
    for rep in range(6):
        w0 = time.perf_counter()
        words = None
        for i in range(CH):
            words = one_window(10_000 + rep * CH + i, fetch=False)
        np.asarray(words)
        dlat.append(max(time.perf_counter() - w0 - rtt, 0.0) / CH)
    dlat_ms = np.array(dlat) * 1e3
    out = {
        "bigkey_keys": int(native.size),
        "bigkey_decisions_per_sec": round(per_sec, 1),
        "bigkey_host_p99_ms": round(host_p99, 3),
        "bigkey_window_p50_ms": round(float(np.percentile(dlat_ms, 50)), 3),
        # worst CHAIN MEAN, not a true per-window p99 (see comment above)
        "bigkey_window_p99_ms": round(float(np.max(dlat_ms)), 3),
        "window_timing_method": "chained_mean_rtt_subtracted",
    }
    log(f"# bigkey tier: {native.size:,} keys, {per_sec:,.0f} decisions/s, "
        f"host p99 {host_p99:.1f}ms, device window "
        f"p50 {out['bigkey_window_p50_ms']}ms "
        f"p99 {out['bigkey_window_p99_ms']}ms")
    del eng
    gc.collect()
    return out


def bench_e2e(mesh, capacity, lanes, seconds=5.0, concurrency=32):
    """gRPC-in -> response-out on a real loopback server, plus the two
    reference benchmark analogs (Ping RTT, ThunderingHeard).

    Client and server share one process and event loop — this box has a
    single CPU core, so a separate client process would just contend for
    it (measured: 6x worse).  On the TPU the core mostly idles inside
    fetch round trips, so the client's proto work interleaves cleanly.

    Runs FIRST among the tiers (the headline must reach the durable
    checkpoint before a wall-budget kill); its warmup pays any cold
    compiles, which the later tiers then reuse (jit caches by
    mesh + shapes, plus the persistent compilation cache)."""
    import asyncio

    import grpc
    import numpy as np

    from gubernator_tpu.api import pb
    from gubernator_tpu.api.grpc_api import V1Stub
    from gubernator_tpu.config import BehaviorConfig, Config, EngineConfig
    from gubernator_tpu.core.service import Instance
    from gubernator_tpu.server import GrpcServer

    N = 1000          # items per RPC (the reference's max batch)

    async def run():
        inst = Instance(
            Config(
                behaviors=BehaviorConfig(),
                engine=EngineConfig(
                    capacity_per_shard=capacity, batch_per_shard=lanes,
                    global_capacity=1024, global_batch_per_shard=128,
                    max_global_updates=128),
            ),
            mesh=mesh,
        )
        inst.engine.warmup()
        srv = GrpcServer(inst, "127.0.0.1:0")
        await srv.start()
        chan = grpc.aio.insecure_channel(srv.address)
        stub = V1Stub(chan)

        payloads = _zipf_payloads(pb, 8, N, 100_000, "e2e")
        raw = chan.unary_unary(
            "/pb.gubernator.V1/GetRateLimits",
            request_serializer=lambda b: b,
            response_deserializer=pb.GetRateLimitsResp.FromString)

        for p in payloads:  # warm: compile + slot tables
            await raw(p)

        done = {"n": 0}
        stop_at = time.perf_counter() + seconds

        async def worker(wid):
            i = 0
            while time.perf_counter() < stop_at:
                resp = await raw(payloads[(wid + i) % 8])
                assert len(resp.responses) == N
                done["n"] += N
                i += 1

        t0 = time.perf_counter()
        await asyncio.gather(*(worker(w) for w in range(concurrency)))
        e2e_ps = done["n"] / (time.perf_counter() - t0)
        log(f"# e2e tier: {e2e_ps:,.0f} decisions/sec "
            f"({N}-item RPCs x {concurrency} in flight)")

        # --- HealthCheck RTT floor (benchmark_test.go:81) ---
        ping = pb.HealthCheckReq()
        rtts = []
        for _ in range(100):
            t = time.perf_counter()
            await stub.HealthCheck(ping)
            rtts.append(time.perf_counter() - t)
        ping_p50 = float(np.percentile(np.array(rtts) * 1e3, 50))
        log(f"# healthcheck rtt p50: {ping_p50:.3f}ms")

        # --- ThunderingHeard: 100 concurrent single-item RPC loops
        #     (benchmark_test.go:109).  Single-core box: this measures
        #     python gRPC handling of 100 tiny concurrent streams as much
        #     as the engine (the no-gRPC herd does ~13k rps). ---
        single = [pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name="th", unique_key=f"t{i}", hits=1,
                            limit=100_000, duration=60_000)]
        ).SerializeToString() for i in range(100)]
        lat = []
        herd = {"n": 0}
        stop_herd = time.perf_counter() + 2.0

        async def herd_worker(wid):
            while time.perf_counter() < stop_herd:
                t = time.perf_counter()
                await raw(single[wid])
                lat.append(time.perf_counter() - t)
                herd["n"] += 1

        t0 = time.perf_counter()
        await asyncio.gather(*(herd_worker(w) for w in range(100)))
        herd_rps = herd["n"] / (time.perf_counter() - t0)
        herd_p99 = float(np.percentile(np.array(lat) * 1e3, 99))
        log(f"# thundering herd: {herd_rps:,.0f} rps, p99 {herd_p99:.2f}ms")

        await chan.close()
        await srv.stop(grace=0.2)
        inst.close()
        return e2e_ps, ping_p50, herd_rps, herd_p99

    return asyncio.run(run())


def bench_cluster(on_cpu, seconds=3.0):
    """Multi-node scale-out tier: a 3-node loopback consistent-hash ring
    under open-loop Zipf load (scripts/load_cluster.py shares the
    harness).  Reports cluster-aggregate decisions/s, the cross-node
    forwarding fraction, and the worst node's p99 — the numbers that
    change when the peer lane or the ring classification regresses,
    which the single-node tiers cannot see."""
    import asyncio

    from scripts.load_cluster import run_cluster

    nodes = 3
    rate = 20.0 if on_cpu else 100.0
    batch = 32 if on_cpu else 256
    r = asyncio.run(run_cluster(nodes, seconds, rate, batch,
                                2_000_000, 1024, 1.2, 0))
    total = sum(n["decisions"] for n in r["per_node"])
    wall = max(n["wall"] for n in r["per_node"]) or 1e-9
    fwd = sum(f["forwarded"] for f in r["forward"])
    p99 = max(n["p99_ms"] for n in r["per_node"])
    agg = total / wall
    fwd_pct = 100.0 * fwd / max(1, total)
    log(f"# cluster tier: {nodes} nodes, {agg:,.0f} decisions/s "
        f"aggregate, {fwd_pct:.0f}% forwarded, worst node p99 "
        f"{p99:.1f}ms")
    return {
        "cluster_nodes": nodes,
        "cluster_decisions_per_sec": round(agg, 1),
        "cluster_forwarded_pct": round(fwd_pct, 1),
        "cluster_p99_ms": round(p99, 2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--platform", default="tpu",
                    help="the device this run is for; any other "
                         "jax.devices()[0].platform fails the run")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from gubernator_tpu.config import env_int, place_compile_cache
    place_compile_cache()
    from gubernator_tpu.ops import kernel
    from gubernator_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != args.platform:
        raise SystemExit(
            f"bench.py --platform {args.platform}: jax.devices()[0] is "
            f"{dev.platform} ({dev.device_kind}); not measuring")
    log(f"# backend: {dev.platform} ({dev.device_kind}) x{len(devs)}")
    result = {
        "metric": "rate_limit_decisions_per_sec_per_chip",
        "unit": "decisions/s",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)},
        "backend": dev.platform,
    }

    # CPU smoke runs get small shapes; a chip gets the production shapes
    on_cpu = dev.platform == "cpu"
    capacity = (1 << 16) if on_cpu else (1 << 20)
    lanes = 4096 if on_cpu else 32768
    iters = 20 if on_cpu else 100
    mesh = make_mesh(devs[:1])

    e2e_ps, ping_p50, herd_rps, herd_p99 = bench_e2e(
        mesh, capacity, lanes, seconds=3.0 if on_cpu else 5.0,
        concurrency=env_int("GUBER_BENCH_E2E_CONC", 8 if on_cpu else 32))
    result["e2e_decisions_per_sec"] = round(e2e_ps, 1)
    result["healthcheck_rtt_ms_p50"] = round(ping_p50, 3)
    result["thundering_herd_rps"] = round(herd_rps, 1)
    result["thundering_herd_p99_ms"] = round(herd_p99, 2)
    result["value"] = round(e2e_ps, 1)
    result["vs_baseline"] = round(e2e_ps / BASELINE_REQS_PER_SEC, 2)

    dev_ps, p50_ms, p99_ms = bench_device(kernel, jax, jnp, mesh,
                                          capacity, lanes, iters)
    result["device_decisions_per_sec"] = round(dev_ps, 1)
    result["window_p50_ms"] = round(p50_ms, 3)
    result["window_p99_ms"] = round(p99_ms, 3)

    host_ps, fold = bench_host_pipeline(
        mesh, capacity, lanes, seconds=3.0 if on_cpu else 5.0,
        concurrency=32 if on_cpu else 256)
    result["host_decisions_per_sec"] = round(host_ps, 1)
    result["aggregation_fold"] = round(fold, 2)

    sync_ps = bench_host_sync(mesh, capacity, lanes,
                              seconds=2.0 if on_cpu else 3.0)
    result["host_sync_decisions_per_sec"] = round(sync_ps, 1)

    result.update(bench_algorithms(mesh, capacity, lanes,
                                   seconds=1.0 if on_cpu else 2.0))

    sweep = bench_chain(mesh, capacity, lanes,
                        seconds=1.5 if on_cpu else 3.0)
    result["chain_stride_sweep"] = {str(s): round(v, 1)
                                    for s, v in sweep.items()}
    if sweep.get(1):
        result["chain_speedup_at_stride4"] = round(
            sweep.get(4, 0.0) / sweep[1], 2)

    result.update(bench_bigkeys(mesh, on_cpu,
                                seconds=3.0 if on_cpu else 5.0))

    result.update(bench_cluster(on_cpu, seconds=2.0 if on_cpu else 5.0))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
