"""Open-loop probe of the overlapped drain pipeline.

Drives the pipelined host path (RPC bytes -> C parse -> stacked compact
dispatch -> C encode) at saturation for a few seconds per configured
depth and prints the stage-utilization split, the realized overlap ratio
and the arena-reuse accounting — the live form of BASELINE.md's overlap
cost model (`t_pipelined ~= max(stage)`, not the sum):

  * stage busy seconds: host_encode / device_dispatch / fetch_decode,
    accumulated per completed drain (core/pipeline.py stage_busy)
  * overlap ratio: sum(stage busy) / wall time with >= 1 drain in
    flight.  1.0 = serial; the depth-3 ceiling is 3.0.
  * implied ceiling: sum(stage) / max(stage) — what perfect overlap of
    the measured split could buy over serial.

Depth 1 vs configured depth shows what the overlap itself contributes
on this box, separate from the columnar host-path wins (which depth 1
keeps).  The default sweep is depths 1 and 3, ~3 s each:

    JAX_PLATFORMS=cpu python scripts/probe_overlap.py
    GUBER_PROBE_DEPTHS=1,2,3 GUBER_PROBE_SECONDS=5 ... # custom sweep
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import jax  # noqa: E402


def _zipf_payloads(pb, n_payloads, items, keyspace, name):
    import numpy as np

    rng = np.random.default_rng(11)
    payloads = []
    for _ in range(n_payloads):
        keys = (rng.zipf(1.1, size=items) - 1) % keyspace
        msg = pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name=name, unique_key=f"k{keys[i]}", hits=1,
                            limit=1_000_000, duration=60_000,
                            algorithm=int(keys[i]) % 2)
            for i in range(items)])
        payloads.append(msg.SerializeToString())
    return payloads


def probe_depth(depth: int, seconds: float, capacity: int, lanes: int,
                concurrency: int) -> dict:
    """One saturated open-loop run at a fixed pipeline depth."""
    import asyncio
    import time

    from gubernator_tpu.api import pb
    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.core.batcher import WindowBatcher
    from gubernator_tpu.core.engine import RateLimitEngine

    os.environ["GUBER_PIPELINE_DEPTH"] = str(depth)
    from gubernator_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(jax.devices()[:1])
    eng = RateLimitEngine(mesh=mesh, capacity_per_shard=capacity,
                          batch_per_shard=lanes, global_capacity=1024,
                          global_batch_per_shard=128, max_global_updates=128)
    batcher = WindowBatcher(eng, BehaviorConfig())
    pipe = batcher.pipeline
    if pipe is None or not pipe.enabled:
        batcher.close()
        return {}
    N = 1000
    payloads = _zipf_payloads(pb, 16, N, 100_000, "overlap")
    eng.warmup()

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

        await asyncio.gather(*(batcher.submit_rpc(p) for p in payloads[:4]))
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(w) for w in range(concurrency)))
        return done["n"] / (time.perf_counter() - t0)

    per_sec = asyncio.run(run())
    snap = pipe.overlap_snapshot()
    snap["decisions_per_sec"] = per_sec
    snap["depth"] = pipe.depth
    batcher.close()
    return snap


def main() -> int:
    devs = jax.devices()
    print(f"# backend: {devs[0].platform}", flush=True)
    on_cpu = devs[0].platform == "cpu"
    capacity = (1 << 16) if on_cpu else (1 << 20)
    lanes = 4096 if on_cpu else 32768
    conc = 32 if on_cpu else 256
    seconds = float(os.environ.get("GUBER_PROBE_SECONDS",
                                   "3.0" if on_cpu else "5.0"))
    depths = [int(d) for d in
              os.environ.get("GUBER_PROBE_DEPTHS", "1,3").split(",")]

    for depth in depths:
        snap = probe_depth(depth, seconds, capacity, lanes, conc)
        if not snap:
            print("# native router unavailable on this box; probe skipped",
                  flush=True)
            return 0
        busy = snap["stage_busy_seconds"]
        total = sum(busy.values()) or 1e-9
        peak = max(busy.values()) or 1e-9
        split = "  ".join(f"{k} {v:6.3f}s ({v / total * 100.0:4.1f}%)"
                          for k, v in busy.items())
        print(f"depth={snap['depth']}: {snap['decisions_per_sec']:,.0f} "
              f"decisions/s", flush=True)
        print(f"  stages: {split}", flush=True)
        print(f"  overlap ratio {snap['overlap_ratio']:.2f} "
              f"(active wall {snap['active_wall_seconds']:.2f}s); "
              f"implied overlap ceiling {total / peak:.2f}x", flush=True)
        print(f"  arena reuse {snap['arena_reuse_events']} / "
              f"alloc {snap['arena_alloc_events']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
