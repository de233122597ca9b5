"""Worker sweep for the multi-process front door (gubernator_tpu/frontdoor.py).

For each worker count the probe boots a fresh engine Instance, serves it
through the corresponding front door (workers=0 = the classic in-process
GrpcServer, the baseline every multi-worker row is read against), drives
closed-loop gRPC load from several concurrent connections — SO_REUSEPORT
spreads them across the acceptor workers — and prints:

  * e2e decisions/s over real loopback gRPC (parse + decide + encode);
  * shm ring stall %: worker-side alloc failures (every slab in flight)
    per RPC attempt — sustained stalls mean GUBER_SHM_RING_SLOTS is the
    bottleneck, not the engine;
  * the engine pipeline's per-stage busy split (host_encode /
    device_dispatch / fetch_decode), same accounting as
    scripts/probe_overlap.py — with N >= 2 workers the worker processes
    own the request parse, so the BASELINE.md frontdoor cost model
    t_e2e ~= max(worker_parse, engine_drain) shows up here as the engine
    split no longer being gated on host parse time.

Standalone:

    JAX_PLATFORMS=cpu python scripts/probe_frontdoor.py
    GUBER_PROBE_FD_WORKERS=1,2,4 GUBER_PROBE_SECONDS=5 \
        JAX_PLATFORMS=cpu python scripts/probe_frontdoor.py

On a single-core box every process shares one CPU, so the multi-worker
rows understate the win; the sweep is still a live differential check of
the whole worker/ring/engine path under saturation.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import jax  # noqa: E402


def build_instance(capacity: int, lanes: int):
    from gubernator_tpu.config import (BehaviorConfig, Config, EngineConfig,
                                       QoSConfig)
    from gubernator_tpu.core.service import Instance
    inst = Instance(Config(
        behaviors=BehaviorConfig(),
        engine=EngineConfig(capacity_per_shard=capacity,
                            batch_per_shard=lanes),
        qos=QoSConfig(max_pending=4096)))
    inst.engine.warmup()
    return inst


def make_batch(pb, items: int, tag: str):
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name=f"fdprobe-{tag}", unique_key=f"k:{i:06d}",
                        hits=1, limit=1 << 30, duration=60_000)
        for i in range(items)
    ])


def probe_workers(workers: int, seconds: float, capacity: int, lanes: int,
                  concurrency: int, items: int) -> dict:
    """One closed-loop saturated run against a fresh instance served
    through `workers` acceptor processes (0 = classic in-process)."""
    import asyncio
    import time

    import grpc

    from gubernator_tpu.api import pb
    from gubernator_tpu.api.grpc_api import V1Stub
    from gubernator_tpu.core import shm_ring

    inst = build_instance(capacity, lanes)
    hub = server = None

    async def run():
        nonlocal hub, server
        if workers > 0:
            from gubernator_tpu.config import DaemonConfig
            from gubernator_tpu.frontdoor import FrontdoorHub
            hub = FrontdoorHub(inst, workers=workers, ring_slots=64,
                               slab_bytes=DaemonConfig.shm_slab_bytes,
                               listen_address="127.0.0.1:0")
            await hub.start()
            address = hub.address
        else:
            from gubernator_tpu.server import GrpcServer
            server = GrpcServer(inst, "127.0.0.1:0")
            await server.start()
            address = server.address

        msg = make_batch(pb, items, f"w{workers}")
        done = {"n": 0}

        async def client(cid):
            # one channel per client task: one TCP connection each, so
            # the kernel's reuseport hash spreads them across workers
            async with grpc.aio.insecure_channel(address) as ch:
                stub = V1Stub(ch)
                await stub.GetRateLimits(msg, timeout=60)  # warm
                stop_at = time.perf_counter() + seconds
                while time.perf_counter() < stop_at:
                    resp = await stub.GetRateLimits(msg, timeout=60)
                    done["n"] += len(resp.responses)

        t0 = time.perf_counter()
        await asyncio.gather(*(client(c) for c in range(concurrency)))
        wall = time.perf_counter() - t0

        out = {"workers": workers, "decisions_per_sec": done["n"] / wall}
        if hub is not None:
            st = hub.stats()
            attempts = max(1, st["rpcs"] + st["sheds"] + st["stalls"])
            out["stall_pct"] = 100.0 * st["stalls"] / attempts
            out["sheds"] = st["sheds"]
            out["restarts"] = st["restarts"]
        else:
            out["stall_pct"] = 0.0
            out["sheds"] = 0
            out["restarts"] = 0
        pipe = inst.batcher.pipeline
        if pipe is not None and pipe.enabled:
            out["stage_busy"] = dict(
                pipe.overlap_snapshot()["stage_busy_seconds"])
        if hub is not None:
            await hub.stop()
        elif server is not None:
            await server.stop()
        return out

    try:
        return asyncio.run(run())
    finally:
        inst.close()


def main() -> int:
    devs = jax.devices()
    print(f"# backend: {devs[0].platform}", flush=True)
    on_cpu = devs[0].platform == "cpu"
    capacity = (1 << 16) if on_cpu else (1 << 20)
    lanes = 4096 if on_cpu else 32768
    seconds = float(os.environ.get("GUBER_PROBE_SECONDS",
                                   "3.0" if on_cpu else "5.0"))
    sweep = [int(w) for w in
             os.environ.get("GUBER_PROBE_FD_WORKERS", "0,1,2,4").split(",")]
    items = int(os.environ.get("GUBER_PROBE_FD_ITEMS", "500"))
    base = None
    for workers in sweep:
        conc = max(4, 2 * workers)
        r = probe_workers(workers, seconds, capacity, lanes, conc, items)
        label = (f"workers={workers}" if workers
                 else "workers=0 (in-process baseline)")
        line = (f"{label}: {r['decisions_per_sec']:,.0f} decisions/s  "
                f"ring stall {r['stall_pct']:.2f}%")
        if workers == 0:
            base = r["decisions_per_sec"]
        elif base:
            line += f"  ({r['decisions_per_sec'] / base:.2f}x of baseline)"
        if r["restarts"]:
            line += f"  [{r['restarts']} worker restarts]"
        print(line, flush=True)
        busy = r.get("stage_busy")
        if busy:
            total = sum(busy.values()) or 1e-9
            split = "  ".join(f"{k} {v:6.3f}s ({v / total * 100.0:4.1f}%)"
                              for k, v in busy.items())
            print(f"  engine stages: {split}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
