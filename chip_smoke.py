"""chip_smoke.py — the quickest proof that gubernator-tpu still starts on the chip.

Drives the serving path once on the attached TPU, through the entry points a
user calls, at a size a deployment holds, and checks every answer against the
plain-python serial oracle (gubernator_tpu/algorithms/oracles.py) computed
from the same --seed.  One process touches JAX; the front-door workers it
spawns pin themselves to the CPU or die.

    python chip_smoke.py             # one chip: engine, server, front door
    python chip_smoke.py --chips 4   # ONLY the cross-chip path: sharded
                                     # arena, GLOBAL psum drain, eviction

Default phases (one chip):
  1. engine   10,485,760-slot arena (BASELINE.json config 3), 16,384-lane
              windows: >= 1M distinct keys loaded, Zipf(1.1) traffic, token +
              leaky mixed, GCRA / sliding window / concurrency families,
              duplicate-key runs, GLOBAL stale-then-consistent — through
              engine.process() and through the pipeline drain
              (core/pipeline.py -> engine.pipeline_dispatch).
  2. server   Daemon on loopback at the same sizes: gRPC GetRateLimits of 1,
              2 and 1000 items, one over the HTTP gateway, HealthCheck,
              /metrics, a concurrency lease; clean stop() with the shutdown
              phases in order.
  3. front door  the same daemon with 2 acceptor worker processes.

Any phase that fails fails the run.  Where jax.devices() holds no TPU the
script exits non-zero and never prints "ok": true; `--tiny` cuts the sizes
for a CPU rehearsal of the control flow, which still ends non-zero.

The last line of stdout on success is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import asyncio
import gc
import json
import socket
import sys
import time

import numpy as np

T0 = 1_790_000_000_000  # fixed epoch-ms base: every `now` derives from it
NAME = "smoke"
# Daemon.stop()'s order for a one-node ring with no snapshot directory: no
# survivor to hand keys to, nothing to snapshot
SHUTDOWN_PHASES = ["monitor_stop", "drain", "global_flush",
                   "handoff_skipped", "teardown"]


def say(msg):
    print(msg, flush=True)


def require(cond, why="a check of the smoke failed"):
    """A failed check fails the run (an `assert` would vanish under -O)."""
    if not cond:
        raise RuntimeError(why)


class Sizes:
    def __init__(self, tiny: bool, chips: int):
        if chips == 1:
            self.C = 8192 if tiny else 10_485_760
            self.B = 256 if tiny else 16_384
            self.keys = 3000 if tiny else 1_000_000
            self.zipf = 4000 if tiny else 262_144
            self.pipe_jobs = 6 if tiny else 128
        else:
            # per shard; the churn keyspace below exceeds 4 x C so cold
            # slots recycle while the LRU keeps the hot set exact
            self.C = 512 if tiny else 262_144
            self.B = 128 if tiny else 4096
            self.hot = 64 if tiny else 4096
            self.churn = 4 * self.C + (512 if tiny else 262_144)
        self.family = 40 if tiny else 300   # keys per extra algorithm


# ------------------------------------------------------------------ oracle


class Oracle:
    """Serial reference: one dict of rows, requests applied one by one in
    plain python integers (algorithms/oracles.py shares no code with the
    kernels)."""

    def __init__(self):
        self.rows = {}
        self.grows = {}

    def hit(self, r, now):
        from gubernator_tpu.algorithms.oracles import apply
        key = r.hash_key()
        row, resp = apply(self.rows.get(key), r.hits, r.limit, r.duration,
                          int(r.algorithm), now)
        self.rows[key] = row
        return resp

    def global_window(self, reqs, now):
        """GLOBAL semantics: every read in a window answers from the replica
        as it stood BEFORE the window (a miss answers as-if-initialized with
        the request's own hits); the window's summed hits land afterwards."""
        import copy

        from gubernator_tpu.algorithms.oracles import apply
        out, summed, conf = [], {}, {}
        for r in reqs:
            key = r.hash_key()
            row = self.grows.get(key)
            live = (row is not None and row.expire >= now
                    and row.algo == int(r.algorithm))
            _, resp = apply(copy.copy(row) if live else None,
                            0 if live else r.hits, r.limit, r.duration,
                            int(r.algorithm), now)
            out.append(resp)
            summed[key] = summed.get(key, 0) + r.hits
            conf[key] = r
        for key, h in summed.items():
            if h:
                r = conf[key]
                self.grows[key], _ = apply(self.grows.get(key), h, r.limit,
                                           r.duration, int(r.algorithm), now)
        return out


def check(got, want, what, reset=True):
    """Every decision equals the oracle's (status, limit, remaining,
    reset_time); returns the number checked."""
    require(len(got) == len(want), (what, len(got), len(want)))
    for j, (g, w) in enumerate(zip(got, want)):
        if g.error:
            raise RuntimeError(f"{what}[{j}]: error {g.error!r}")
        have = (int(g.status), g.limit, g.remaining,
                g.reset_time if reset else w[3])
        if have != tuple(w):
            raise RuntimeError(
                f"{what}[{j}]: engine {have} != oracle {tuple(w)}")
    return len(got)


# ----------------------------------------------------------------- traffic


class Traffic:
    """Everything random comes from --seed: the key permutation, the Zipf
    draws, the hit sizes."""

    def __init__(self, seed: int, n_keys: int):
        self.rng = np.random.default_rng(seed)
        self.n = n_keys
        self.perm = self.rng.permutation(n_keys)
        w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** 1.1
        self.cdf = np.cumsum(w / w.sum())

    def req(self, i, hits=1):
        from gubernator_tpu import RateLimitReq
        i = int(i)
        return RateLimitReq(name=NAME, unique_key=f"k{i}", hits=int(hits),
                            limit=4 + i % 13, duration=60_000 + 1000 * (i % 7),
                            algorithm=i % 2)  # token / leaky mixed

    def zipf_ids(self, n):
        ranks = np.searchsorted(self.cdf, self.rng.random(n))
        return self.perm[np.minimum(ranks, self.n - 1)]

    def family(self, algo, tag, n, hits=1):
        """A few hundred keys of one of the added algorithms."""
        from gubernator_tpu import RateLimitReq
        conf = {2: (10, 10_000), 3: (20, 4_000), 4: (5, 30_000)}[algo]
        return [RateLimitReq(name=NAME, unique_key=f"{tag}{i}", hits=hits,
                             limit=conf[0], duration=conf[1], algorithm=algo)
                for i in range(n)]

    def globals_(self, n, hits):
        from gubernator_tpu import Behavior, RateLimitReq
        return [RateLimitReq(name=NAME, unique_key=f"gl{i}", hits=hits,
                             limit=1000, duration=600_000,
                             behavior=Behavior.GLOBAL) for i in range(n)]


def process_checked(eng, oracle, reqs, now, what):
    """One engine.process() call; regular decisions against the serial
    oracle in request order, GLOBAL ones against the window model."""
    from gubernator_tpu import Behavior
    got = eng.process(reqs, now=now)
    reg_i = [i for i, r in enumerate(reqs) if r.behavior != Behavior.GLOBAL]
    glo_i = [i for i, r in enumerate(reqs) if r.behavior == Behavior.GLOBAL]
    n = check([got[i] for i in reg_i],
              [oracle.hit(reqs[i], now) for i in reg_i], what)
    if glo_i:
        n += check([got[i] for i in glo_i],
                   oracle.global_window([reqs[i] for i in glo_i], now),
                   what + "/GLOBAL")
    return n


# ------------------------------------------------------------ phase 1: engine


def phase_engine(jax, sz, seed):
    from gubernator_tpu import native
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.mesh import make_mesh

    dev = jax.devices()[0]
    require(native.available(), "native router (host_router.cc) did not build")
    t = time.perf_counter()
    eng = RateLimitEngine(mesh=make_mesh([dev]), capacity_per_shard=sz.C,
                          batch_per_shard=sz.B, use_native="on")
    eng.warmup(now=T0)
    compile_s = time.perf_counter() - t
    arena = sum(a.nbytes for a in eng.state)
    stats = dev.memory_stats() or {}
    say(f"[engine] arena {sz.C:,} slots x 44 B = {arena:,} B resident; "
        f"window {sz.B:,} lanes; router native={eng.native is not None}; "
        f"device bytes_in_use={stats.get('bytes_in_use', 'n/a')} "
        f"peak={stats.get('peak_bytes_in_use', 'n/a')}")
    say(f"[engine] construct + warmup (every serving executable compiled): "
        f"{compile_s:.1f} s")

    tr = Traffic(seed, sz.keys)
    oracle = Oracle()
    run0 = time.perf_counter()
    checked = 0
    now = T0 + 1000

    # load: every key once (>= 1M distinct), 8 windows per call
    step = 8 * sz.B
    for lo in range(0, sz.keys, step):
        reqs = [tr.req(i) for i in range(lo, min(lo + step, sz.keys))]
        checked += process_checked(eng, oracle, reqs, now, f"load@{lo}")
        now += 250
    live = eng.native.size
    say(f"[engine] loaded {sz.keys:,} distinct keys ({live:,} live in the "
        f"router), {checked:,} decisions == oracle")
    require(live >= sz.keys, (live, sz.keys))

    # Zipf(1.1): hot keys form long duplicate runs inside one window
    fam = sz.family
    for rnd in range(4):
        ids = tr.zipf_ids(sz.zipf // 4)
        reqs = [tr.req(i) for i in ids]
        hot = int(ids[0])
        # mixed-config duplicate run on a hot key: read, partial, over-ask
        reqs += [tr.req(hot, h) for h in (2, 3, 0, 999, 1)]
        reqs += tr.family(2, "g", fam) + tr.family(3, "s", fam)
        # concurrency: two rounds acquire, then release, then acquire again
        reqs += tr.family(4, "c", fam, hits=(-1 if rnd == 2 else 1))
        if rnd < 3:  # GLOBAL over consecutive calls: stale, then consistent
            reqs += tr.globals_(8, hits=rnd + 1)
        checked += process_checked(eng, oracle, reqs, now, f"zipf#{rnd}")
        now += 500
    g = oracle.grows[f"{NAME}_gl0"]
    require(g.remaining == 1000 - (1 + 2 + 3), g)
    say(f"[engine] process(): Zipf(1.1) + GCRA/sliding/concurrency x{fam} "
        f"+ 8 GLOBAL keys over 3 calls (stale, then consistent): "
        f"{checked:,} decisions == oracle")

    # the pipeline drain: core/pipeline.py -> engine.pipeline_dispatch
    b = WindowBatcher(eng, BehaviorConfig())
    require(b.pipeline is not None and b.pipeline.enabled)
    b.pipeline.now_fn = b.now_fn = lambda: now
    jobs = [[tr.req(i) for i in tr.zipf_ids(1000)]
            for _ in range(sz.pipe_jobs)]
    w0 = eng.windows_processed

    async def drive():
        return await asyncio.gather(
            *(b.pipeline.submit_many(j) for j in jobs))
    try:
        outs = asyncio.run(drive())
    finally:
        b.close()
    n_pipe = 0
    for j, (job, got) in enumerate(zip(jobs, outs)):
        n_pipe += check(got, [oracle.hit(r, now) for r in job], f"drain#{j}")
    checked += n_pipe
    run_s = time.perf_counter() - run0
    say(f"[engine] pipeline drain: {len(jobs)} x 1000-item jobs, "
        f"{eng.windows_processed - w0} windows, {n_pipe:,} decisions == "
        f"oracle")
    say(f"[engine] PASS: {checked:,} decisions checked, every one equal to "
        f"the serial oracle; run {run_s:.1f} s (host oracle included), "
        f"compile {compile_s:.1f} s")
    require(checked >= (100_000 if sz.keys >= 1_000_000 else 1000))
    del b, eng
    gc.collect()


# ----------------------------------------------- phases 2 and 3: the server


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def metric(text, name, label=""):
    """Sum of a Prometheus family's samples whose label set holds `label`."""
    total = None
    for line in text.splitlines():
        if (line.startswith(name) and line[len(name)] in " {"
                and label in line):
            total = (total or 0.0) + float(line.rsplit(" ", 1)[1])
    return total


async def serve_and_check(sz, seed, workers):
    import aiohttp

    from gubernator_tpu import RateLimitReq
    from gubernator_tpu.client import AsyncClient
    from gubernator_tpu.config import DaemonConfig, EngineConfig
    from gubernator_tpu.daemon import Daemon

    tag = f"frontdoor x{workers}" if workers else "server"
    conf = DaemonConfig()
    conf.grpc_listen_address = f"127.0.0.1:{free_port()}"
    conf.http_listen_address = f"127.0.0.1:{free_port()}"
    conf.advertise_address = conf.grpc_listen_address
    conf.engine = EngineConfig(capacity_per_shard=sz.C, batch_per_shard=sz.B,
                               use_native="on")
    conf.frontdoor_workers = workers
    d = Daemon(conf)
    t = time.perf_counter()
    await d.start()
    say(f"[{tag}] Daemon up in {time.perf_counter() - t:.1f} s: gRPC "
        f"{d.frontdoor.address if workers else d.grpc.address}, HTTP "
        f"{conf.http_listen_address}, arena "
        f"{d.instance.engine.capacity_per_shard:,} slots")
    require(d.instance.engine.native is not None)
    now = T0 + 10_000_000 + workers
    bat = d.instance.batcher
    bat.now_fn = lambda: now
    if bat.pipeline is not None:
        bat.pipeline.now_fn = lambda: now

    tr = Traffic(seed + 1 + workers, 50_000)
    oracle = Oracle()
    grpc_addr = d.frontdoor.address if workers else d.grpc.address
    cl = AsyncClient(grpc_addr)
    checked = 0
    try:
        hc = await cl.health_check(timeout=30)
        require(hc.status == "healthy", hc)
        sizes = (200, 1) if workers else (1, 2, 1000, 2, 1)
        for n in sizes:
            reqs = [tr.req(i, hits=1 + int(i) % 3) for i in tr.zipf_ids(n)]
            got = await cl.get_rate_limits(reqs, timeout=120)
            checked += check(got, [oracle.hit(r, now) for r in reqs],
                             f"{tag} gRPC x{n}")
        if not workers:
            # a concurrency lease through core/service.py: acquire, release
            lease = RateLimitReq(name=NAME, unique_key="lease0", hits=1,
                                 limit=2, duration=30_000, algorithm=4)
            for h in (1, 1, 1, -1, 1):
                lease.hits = h
                got = await cl.get_rate_limits([lease], timeout=120)
                checked += check(got, [oracle.hit(lease, now)],
                                 f"{tag} lease hits={h}", reset=False)
        async with aiohttp.ClientSession() as http:
            base = f"http://{conf.http_listen_address}"
            reqs = [tr.req(i) for i in tr.zipf_ids(3)]
            body = {"requests": [
                {"name": r.name, "uniqueKey": r.unique_key,
                 "hits": str(r.hits), "limit": str(r.limit),
                 "duration": str(r.duration),
                 "algorithm": int(r.algorithm)} for r in reqs]}
            async with http.post(f"{base}/v1/GetRateLimits",
                                 json=body) as resp:
                require(resp.status == 200, await resp.text())
                out = (await resp.json())["responses"]
            for j, (o, r) in enumerate(zip(out, reqs)):
                w = oracle.hit(r, now)
                have = (1 if o.get("status") in ("OVER_LIMIT", 1) else 0,
                        int(o.get("limit") or 0),
                        int(o.get("remaining") or 0),
                        int(o.get("resetTime") or 0))
                require(have == tuple(w), (f"{tag} HTTP[{j}]", have, w))
            checked += len(reqs)
            async with http.get(f"{base}/v1/HealthCheck") as resp:
                require(resp.status == 200, await resp.text())
            async with http.get(f"{base}/metrics") as resp:
                require(resp.status == 200)
                text = await resp.text()
        served = metric(text, "guber_tpu_windows_total")
        if workers:
            enc = metric(text, "guber_tpu_frontdoor_encode_total",
                         'path="worker"')
            nw = metric(text, "guber_tpu_frontdoor_workers")
            require(nw == workers, ("frontdoor workers gauge", nw))
            require(enc and enc > 0, ("worker-encoded responses", enc))
            say(f"[{tag}] /metrics: frontdoor_workers={nw:.0f} "
                f'frontdoor_encode_total{{path="worker"}}={enc:.0f}')
            for w in d.frontdoor.procs:
                require(w.is_alive(), "a front-door worker died")
        say(f"[{tag}] HealthCheck healthy; /metrics read "
            f"({len(text):,} B, windows_total={served}); "
            f"{checked:,} decisions over gRPC + HTTP == oracle")
    finally:
        await cl.close()
        await d.stop()
    require(d.shutdown_phases == SHUTDOWN_PHASES, d.shutdown_phases)
    say(f"[{tag}] PASS: clean stop(), phases {' > '.join(d.shutdown_phases)}")
    del d
    gc.collect()


# ------------------------------------------------------- --chips 4: the mesh


def phase_mesh(jax, sz, seed, n_chips):
    """BASELINE.json config 4 on a real mesh: the keyspace sharded over
    four chips, GLOBAL keys hit from every shard with ONE psum per drain
    (the lockstep tick's _compiled_pipeline_step_global), plus non-GLOBAL
    churn that evicts — all against the serial reference."""
    from gubernator_tpu import Behavior, RateLimitReq
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core import engine as engine_mod
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.parallel.distributed import LockstepClock
    from gubernator_tpu.parallel.mesh import make_mesh

    devs = jax.devices()[:n_chips]
    mesh = make_mesh(devs)
    K = 2
    G_LIMIT = 1_000_000  # never reached: every GLOBAL read stays UNDER
    t = time.perf_counter()
    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=sz.C,
                          batch_per_shard=sz.B, use_native="on")
    eng.warmup(now=T0, k_stack=K)
    compile_s = time.perf_counter() - t
    # eleven resident planes (ten uint32 halves and algo), each sharded alike
    blocks = [s for plane in eng.state for s in plane.addressable_shards]
    homes = {s.device for s in blocks}
    shapes = {s.data.shape for s in blocks}
    require(len(homes) == n_chips and homes == set(devs), homes)
    require(shapes == {(1, sz.C)}, shapes)
    say(f"[mesh] arena {n_chips} x {sz.C:,} slots: "
        f"{len(blocks)} blocks of {shapes} on "
        f"{sorted(str(d) for d in homes)}")

    # the compiled lockstep drain holds the reconciliation all-reduce
    fn = engine_mod._compiled_pipeline_step_global(mesh)
    gb, ga, upd = eng.empty_drain_control()
    text = fn.lower(
        eng.state, eng.gstate, eng.gcfg,
        np.zeros((K, n_chips, sz.B, 2), np.int64), gb, ga, upd,
        np.full(K, T0, np.int64)).compile().as_text()
    n_ar = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    require(n_ar == 1, f"{n_ar} all-reduces in the lockstep drain, want 1")
    say(f"[mesh] compiled lockstep drain (K={K}): {n_ar} all-reduce; "
        f"construct + warmup {compile_s:.1f} s")

    oracle = Oracle()
    run0 = time.perf_counter()
    checked = 0
    rng = np.random.default_rng(seed)

    def hot(i, hits=1):
        return RateLimitReq(name=NAME, unique_key=f"h{i}", hits=hits,
                            limit=30, duration=600_000, algorithm=i % 2)

    # (a) engine.step on the mesh: churn that evicts, hot set stays exact,
    #     GLOBAL keys registered and hit stale-then-consistent
    eng.register_global_keys(
        [(f"{NAME}_gm{j}", G_LIMIT, 600_000, 0) for j in range(8)], now=T0)
    now = T0 + 1000
    cold = 0
    per = n_chips * sz.B - sz.hot - 64
    while cold < sz.churn:
        reqs = [hot(i) for i in range(sz.hot)]
        reqs += [hot(int(rng.integers(sz.hot)), h) for h in (2, 3, 0, 999, 1)]
        for _ in range(min(per, sz.churn - cold)):
            reqs.append(RateLimitReq(
                name=NAME, unique_key=f"c{cold}", hits=cold % 5, limit=3,
                duration=1500, algorithm=1 if cold % 3 else 0))
            cold += 1
        greqs = [RateLimitReq(name=NAME, unique_key=f"gm{j}", hits=1,
                              limit=G_LIMIT, duration=600_000,
                              behavior=Behavior.GLOBAL) for j in range(8)]
        checked += process_checked(eng, oracle, reqs + greqs, now,
                                   f"mesh step@{cold}")
        now += 500
    arena = n_chips * sz.C
    require(cold + sz.hot > arena and eng.native.size <= arena, (
        cold, arena, eng.native.size))
    say(f"[mesh] engine.process on {n_chips} shards: {cold + sz.hot:,} keys "
        f"through a {arena:,}-slot arena (recycle exercised, "
        f"{eng.native.size:,} live), {checked:,} decisions == oracle")

    # (b) the lockstep serving drain: regular + GLOBAL singles ride ONE
    #     composed executable per tick, one psum per drain
    tick = 0.02
    clock = LockstepClock(now, tick)
    b = WindowBatcher(eng, BehaviorConfig(batch_wait=tick, lockstep_stack=K),
                      lockstep_clock=clock)
    require(b.pipeline is not None and b.pipeline.lockstep)
    regs = [hot(int(i)) for i in rng.integers(sz.hot, size=sz.hot)]
    d0 = eng.windows_processed

    async def drive():
        b.start_lockstep()
        outs = await asyncio.gather(*(b.submit(r) for r in regs))
        gouts = []
        for _ in range(3):  # sequential: each lands in its own drain
            gouts.append(await asyncio.gather(*(b.submit(RateLimitReq(
                name=NAME, unique_key=f"gm{j}", hits=2, limit=G_LIMIT,
                duration=600_000, behavior=Behavior.GLOBAL))
                for j in range(8))))
        return outs, gouts
    try:
        outs, gouts = asyncio.run(drive())
    finally:
        b.close()
    # ticks stamp their own (deterministic) times; durations are long, so
    # status/limit/remaining do not depend on which tick served a request
    n_lock = check(outs, [oracle.hit(r, now) for r in regs],
                   "lockstep drain", reset=False)
    base = oracle.grows[f"{NAME}_gm0"].remaining
    for rnd, gs in enumerate(gouts):
        for g in gs:
            require(not g.error and g.remaining == base - 2 * rnd, (
                "lockstep GLOBAL", rnd, g, base))
        n_lock += len(gs)
    checked += n_lock
    say(f"[mesh] lockstep drain: {n_lock:,} decisions in "
        f"{eng.windows_processed - d0} windows, regular == oracle, GLOBAL "
        f"remaining {base} > {base - 2} > {base - 4} (stale, then consistent "
        f"via the psum)")
    say(f"[mesh] PASS: {checked:,} decisions checked; run "
        f"{time.perf_counter() - run0:.1f} s, compile {compile_s:.1f} s")


# -------------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the cross-chip path")
    ap.add_argument("--tiny", action="store_true",
                    help="cut sizes for a CPU rehearsal of the control flow "
                         "(a run that saw no TPU still ends non-zero)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    from gubernator_tpu.config import place_compile_cache
    cache = place_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    on_tpu = dev.platform == "tpu"
    say(f"[start] jax {jax.__version__}; {len(devs)} x {dev.platform} "
        f"({dev.device_kind}); compile cache {cache}; seed {args.seed}")
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: jax.devices() holds no TPU "
              f"({dev.platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 2

    sz = Sizes(args.tiny, args.chips)
    if args.chips == 4:
        phase_mesh(jax, sz, args.seed, 4)
    else:
        phase_engine(jax, sz, args.seed)
        asyncio.run(serve_and_check(sz, args.seed, workers=0))
        asyncio.run(serve_and_check(sz, args.seed, workers=2))
    say(f"[done] all phases passed in {time.perf_counter() - t_start:.1f} s")
    if not on_tpu:
        print("chip_smoke: rehearsal passed, but no TPU was seen — this is "
              "not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
