"""Multi-node open-loop scale-out harness (gubernator_tpu/cluster.py).

Boots an N-node consistent-hash ring on loopback (>= 3 nodes for a real
run; N=1 is the degenerate single-box smoke),
optionally fronts every node with the multi-process front door, and
drives OPEN-LOOP load: each node receives RPCs at a fixed offered rate
regardless of how fast responses come back — the load does not slow down
when the server does, so saturation shows up as latency and lateness,
not as a politely reduced request rate (the coordinated-omission trap
closed-loop probes fall into).

The key population models a real fleet edge: each item's unique_key is
drawn from GUBER_CLUSTER_CLIENTS distinct client ids (millions by
default — far more keys than any node's device arena, so the tiered
key-state path is exercised, not a hot cache), and the rate-limit NAME
is a tenant drawn Zipf(a) over GUBER_CLUSTER_TENANTS tenants — a few
tenants dominate, the tail is long, exactly the shape multi-tenant
front doors see.

Reported per run:

  * cluster-aggregate decisions/s (achieved vs offered rate: an
    achieved/offered gap means the cluster could not keep up);
  * per-node p50/p99 RPC latency over real loopback gRPC;
  * peer-forwarding overhead: the fraction of items decided on a node
    other than the one that received them (guber_tpu_cluster_forwarded)
    and the mean peer_forward stage cost — with a uniform hash ring,
    expect ~ (N-1)/N of items to forward;
  * per-node frontdoor stats (worker encodes, batch coalescing) when
    GUBER_CLUSTER_FRONTDOOR > 0.

Environment knobs (defaults in parentheses):

    GUBER_CLUSTER_NODES      ring size (3)
    GUBER_CLUSTER_SECONDS    measured window per run (5)
    GUBER_CLUSTER_RATE       offered RPCs/s per node (50)
    GUBER_CLUSTER_BATCH      items per RPC (64)
    GUBER_CLUSTER_CLIENTS    distinct client keys (2_000_000)
    GUBER_CLUSTER_TENANTS    Zipf tenant population (1024)
    GUBER_CLUSTER_ZIPF       Zipf exponent a (1.2)
    GUBER_CLUSTER_FRONTDOOR  acceptor workers per node (0 = in-process)

Example:

    JAX_PLATFORMS=cpu GUBER_CLUSTER_NODES=3 \
        GUBER_CLUSTER_RATE=100 python scripts/load_cluster.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, str(default)))


class KeyModel:
    """Pre-sampled open-loop traffic: Zipf tenants over a huge uniform
    client population.  Sampling ahead of the run keeps the load
    generator off the hot path (no RNG between sends)."""

    def __init__(self, clients: int, tenants: int, zipf_a: float,
                 n_batches: int, batch: int, seed: int = 11):
        rng = np.random.default_rng(seed)
        # np.random.zipf is unbounded; fold the tail back into range so
        # the tenant distribution stays Zipf-shaped but finite
        t = rng.zipf(zipf_a, size=n_batches * batch) % tenants
        c = rng.integers(0, clients, size=n_batches * batch)
        self.tenants = t.reshape(n_batches, batch)
        self.clients = c.reshape(n_batches, batch)
        self.n_batches = n_batches

    def batch(self, pb, i: int):
        j = i % self.n_batches
        ts, cs = self.tenants[j], self.clients[j]
        return pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name=f"tenant-{int(t):04d}",
                            unique_key=f"client:{int(c):07d}",
                            hits=1, limit=1 << 30, duration=60_000)
            for t, c in zip(ts, cs)
        ])


async def drive_node(address: str, model: KeyModel, pb, stub_cls,
                     rate: float, seconds: float, batch: int,
                     max_inflight: int = 512) -> dict:
    """Open-loop generator for ONE node: schedule sends on a fixed
    cadence, never waiting for responses.  Sends that would exceed
    max_inflight are counted as overruns (the open-loop signal that the
    node fell behind) rather than silently skipped."""
    import asyncio
    import time

    import grpc

    lat: list = []
    done = {"decisions": 0, "errors": 0, "overruns": 0, "sent": 0}
    inflight: set = set()

    async def one(stub, msg):
        t0 = time.perf_counter()
        try:
            resp = await stub.GetRateLimits(msg, timeout=30)
            lat.append(time.perf_counter() - t0)
            done["decisions"] += len(resp.responses)
        except Exception:
            done["errors"] += 1

    async with grpc.aio.insecure_channel(address) as ch:
        stub = stub_cls(ch)
        # warm the connection + the engine's compiled step
        await stub.GetRateLimits(model.batch(pb, 0), timeout=60)
        interval = 1.0 / rate
        t_start = time.perf_counter()
        i = 0
        while True:
            now = time.perf_counter()
            if now - t_start >= seconds:
                break
            due = t_start + i * interval
            if now < due:
                await asyncio.sleep(due - now)
            if len(inflight) >= max_inflight:
                done["overruns"] += 1
            else:
                task = asyncio.ensure_future(one(stub, model.batch(pb, i)))
                inflight.add(task)
                task.add_done_callback(inflight.discard)
                done["sent"] += 1
            i += 1
        if inflight:
            await asyncio.gather(*list(inflight), return_exceptions=True)
    wall = time.perf_counter() - t_start
    arr = np.asarray(lat) if lat else np.asarray([0.0])
    return {
        "wall": wall,
        "decisions": done["decisions"],
        "sent": done["sent"],
        "offered": int(rate * seconds) * batch,
        "errors": done["errors"],
        "overruns": done["overruns"],
        "p50_ms": float(np.percentile(arr, 50)) * 1e3,
        "p99_ms": float(np.percentile(arr, 99)) * 1e3,
    }


def _node_forward_stats(inst) -> dict:
    g = inst.metrics.registry.get_sample_value
    fwd = g("guber_tpu_cluster_forwarded_total") or 0.0
    st_sum = g("guber_tpu_stage_duration_ms_sum",
               {"stage": "peer_forward"}) or 0.0
    st_cnt = g("guber_tpu_stage_duration_ms_count",
               {"stage": "peer_forward"}) or 0.0
    return {"forwarded": int(fwd), "stage_ms_sum": st_sum,
            "stage_count": int(st_cnt)}


async def run_cluster(nodes: int, seconds: float, rate: float, batch: int,
                      clients: int, tenants: int, zipf_a: float,
                      fd_workers: int) -> dict:
    import asyncio

    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu.api import pb
    from gubernator_tpu.api.grpc_api import V1Stub
    from gubernator_tpu.config import DaemonConfig, EngineConfig

    on_cpu = jax.devices()[0].platform == "cpu"
    engine = EngineConfig(
        capacity_per_shard=(1 << 14) if on_cpu else (1 << 18),
        batch_per_shard=2048 if on_cpu else 16384,
        global_capacity=256, global_batch_per_shard=64,
        max_global_updates=64)
    c = await cluster_mod.start(nodes, engine=engine)
    hubs = []
    try:
        addresses = list(c.addresses)
        if fd_workers > 0:
            from gubernator_tpu.frontdoor import FrontdoorHub
            for i in range(nodes):
                hub = FrontdoorHub(c.instance_at(i), workers=fd_workers,
                                   ring_slots=64,
                                   slab_bytes=DaemonConfig.shm_slab_bytes,
                                   listen_address="127.0.0.1:0")
                await hub.start()
                hubs.append(hub)
            addresses = [h.address for h in hubs]

        n_batches = max(64, int(rate * seconds) + 8)
        model = KeyModel(clients, tenants, zipf_a,
                         min(n_batches, 4096), batch)
        per_node = await asyncio.gather(*[
            drive_node(addr, model, pb, V1Stub, rate, seconds, batch)
            for addr in addresses
        ])
        fstats = [_node_forward_stats(c.instance_at(i))
                  for i in range(nodes)]
        fd_stats = [h.stats() for h in hubs]
    finally:
        for h in hubs:
            await h.stop()
        await c.stop()
    return {"per_node": per_node, "forward": fstats, "frontdoor": fd_stats}


def main() -> int:
    import asyncio

    devs = jax.devices()
    nodes = _env_int("GUBER_CLUSTER_NODES", 3)
    seconds = _env_float("GUBER_CLUSTER_SECONDS", 5.0)
    rate = _env_float("GUBER_CLUSTER_RATE", 50.0)
    batch = _env_int("GUBER_CLUSTER_BATCH", 64)
    clients = _env_int("GUBER_CLUSTER_CLIENTS", 2_000_000)
    tenants = _env_int("GUBER_CLUSTER_TENANTS", 1024)
    zipf_a = _env_float("GUBER_CLUSTER_ZIPF", 1.2)
    fd_workers = _env_int("GUBER_CLUSTER_FRONTDOOR", 0)

    print(f"# backend: {devs[0].platform}  nodes={nodes}  "
          f"rate={rate:.0f} rpc/s/node  batch={batch}  "
          f"clients={clients:,}  tenants={tenants} (zipf a={zipf_a})  "
          f"frontdoor={fd_workers}", flush=True)

    r = asyncio.run(run_cluster(nodes, seconds, rate, batch, clients,
                                tenants, zipf_a, fd_workers))

    total_dec = sum(n["decisions"] for n in r["per_node"])
    total_off = sum(n["offered"] for n in r["per_node"])
    wall = max(n["wall"] for n in r["per_node"])
    agg = total_dec / wall if wall > 0 else 0.0
    print(f"cluster aggregate: {agg:,.0f} decisions/s achieved "
          f"({total_dec:,} decisions / {wall:.1f}s; offered "
          f"{total_off / seconds:,.0f}/s)", flush=True)
    for i, n in enumerate(r["per_node"]):
        f = r["forward"][i]
        fwd_pct = 100.0 * f["forwarded"] / max(1, n["decisions"])
        fwd_ms = (f["stage_ms_sum"] / f["stage_count"]
                  if f["stage_count"] else 0.0)
        line = (f"node {i}: p50 {n['p50_ms']:7.1f}ms  "
                f"p99 {n['p99_ms']:7.1f}ms  "
                f"decisions {n['decisions']:,}  "
                f"forwarded {f['forwarded']:,} ({fwd_pct:.0f}%)  "
                f"peer hop {fwd_ms:.1f}ms avg")
        if n["errors"] or n["overruns"]:
            line += (f"  [{n['errors']} errors, "
                     f"{n['overruns']} open-loop overruns]")
        print(line, flush=True)
    for i, st in enumerate(r["frontdoor"]):
        print(f"node {i} frontdoor: rpcs {st['rpcs']:,}  "
              f"worker encodes {st['encodes']:,}  "
              f"engine-encode fallbacks {st['enc_fallbacks']:,}  "
              f"batched rpcs {st['batch_rpcs']:,} in "
              f"{st['batch_flushes']:,} flushes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
