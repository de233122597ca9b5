"""The five BASELINE.md eval configs as a runnable benchmark report.

  1. TOKEN_BUCKET, 10k keys, BATCHING, single node   (service host path)
  2. LEAKY_BUCKET, 1M keys, Zipf(1.1), batch=1000    (device path)
  3. Mixed TOKEN+LEAKY, 10M keys, 500µs window       (device path)
  4. GLOBAL 4-peer -> 4-chip psum                    (sharded device path)
  5. 100M keys, Zipf + churn                         (device path, scaled to
                                                      available HBM/devices)

Prints one JSON object per config.  Configs 2/3/5 measure the jitted device
step with pre-packed windows (the decision engine); config 1 exercises the
full Python/native host packing path; config 4 runs the psum reconciliation
across however many devices exist (8 virtual CPU devices in tests, the
attached chips otherwise).

Usage: python bench_configs.py [--iters N] [--scale-keys N]
"""

import argparse
import json
import sys
import time

import numpy as np


def measure_device(eng, kernel, jnp, jax, capacity, lanes, slots_fn, algo_fn,
                   iters, n_windows=8):
    step = eng._step_fn
    batches = []
    for w in range(n_windows):
        s = slots_fn(w)
        batches.append(jax.device_put(kernel.WindowBatch(
            slot=jnp.asarray(s[None, :]),
            hits=jnp.ones((1, lanes), jnp.int64),
            limit=jnp.full((1, lanes), 1_000_000, jnp.int64),
            duration=jnp.full((1, lanes), 60_000, jnp.int64),
            algo=jnp.asarray(algo_fn(s)[None, :]),
            is_init=jnp.zeros((1, lanes), bool),
        )))
    G, Kg = eng.global_capacity, eng.max_global_updates
    empty_g = jax.device_put(kernel.WindowBatch(*[
        a[None, :] for a in kernel.WindowBatch.pad(eng.global_batch_per_shard)]))
    gacc = jax.device_put(jnp.zeros((1, eng.global_batch_per_shard), jnp.int64))
    upd = jax.device_put((jnp.full((Kg,), G, jnp.int32), jnp.zeros((Kg,), jnp.int64),
                          jnp.zeros((Kg,), jnp.int64), jnp.zeros((Kg,), jnp.int32),
                          jnp.full((Kg,), G, jnp.int32)))
    ups = jax.device_put((jnp.full((Kg,), G, jnp.int32),) + tuple(
        jnp.zeros((Kg,), jnp.int64) for _ in range(5)) + (jnp.zeros((Kg,), jnp.int32),))
    state, gstate, gcfg = eng.state, eng.gstate, eng.gcfg
    now = 1_700_000_000_000
    out = None
    for i in range(3):
        state, out, gstate, gcfg = step(state, gstate, gcfg,
                                        batches[i % n_windows], empty_g,
                                        gacc, upd, ups, jnp.int64(now + i))
    jax.block_until_ready(out)
    lat = []
    t0 = time.perf_counter()
    for i in range(iters):
        w0 = time.perf_counter()
        state, out, gstate, gcfg = step(state, gstate, gcfg,
                                        batches[i % n_windows], empty_g,
                                        gacc, upd, ups, jnp.int64(now + 3 + i))
        jax.block_until_ready(out)
        lat.append(time.perf_counter() - w0)
    total = time.perf_counter() - t0
    eng.state, eng.gstate, eng.gcfg = state, gstate, gcfg
    lat_ms = np.array(lat) * 1000
    return {
        "decisions_per_sec": round(iters * lanes / total, 1),
        "window_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "window_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--scale-keys", type=int, default=None,
                    help="cap the large-config key counts (default: sized to backend)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import gubernator_tpu  # noqa: F401
    from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq, Second
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.ops import kernel
    from gubernator_tpu.parallel.mesh import make_mesh

    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    rng = np.random.default_rng(3)
    report = {"backend": f"{dev.platform} ({dev.device_kind})",
              "devices": len(jax.devices())}
    print(f"# backend: {report['backend']} x{report['devices']}", file=sys.stderr)

    def zipf_slots(capacity, lanes):
        return lambda w: ((rng.zipf(1.1, size=lanes) - 1) % capacity).astype(np.int32)

    def uniform_slots(capacity, lanes):
        return lambda w: rng.integers(0, capacity, size=lanes).astype(np.int32)

    # ---- config 1: service host path, 10k token-bucket keys ----
    eng1 = RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                           capacity_per_shard=16384, batch_per_shard=1024)
    keys = [f"cfg1_k{i}" for i in range(10_000)]
    reqs = [RateLimitReq(name="bench", unique_key=k, hits=1, limit=1_000_000,
                         duration=60 * Second) for k in keys[:1000]]
    eng1.process(reqs)  # warm
    t0 = time.perf_counter()
    n_iter = max(3, args.iters // 20)
    for i in range(n_iter):
        eng1.process(reqs)
    dt = time.perf_counter() - t0
    report["config1_token_10k_single_node"] = {
        "decisions_per_sec": round(n_iter * len(reqs) / dt, 1),
        "path": "full host packing (native router)" if eng1.native else "python host path",
    }

    # ---- config 2: leaky, 1M keys, Zipf(1.1), batch=1000 ----
    cap2 = min(args.scale_keys or 1 << 20, 1 << 20)
    eng2 = RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                           capacity_per_shard=cap2, batch_per_shard=1024)
    report["config2_leaky_1m_zipf"] = dict(
        keys=cap2, **measure_device(
            eng2, kernel, jnp, jax, cap2, 1024, zipf_slots(cap2, 1024),
            lambda s: np.full(s.shape, 1, np.int32), args.iters))

    # ---- config 3: mixed, 10M keys, 500µs-window-sized batches ----
    cap3 = args.scale_keys or ((1 << 21) if on_cpu else (1 << 23))
    eng3 = RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                           capacity_per_shard=cap3, batch_per_shard=4096)
    report["config3_mixed_10m"] = dict(
        keys=cap3, **measure_device(
            eng3, kernel, jnp, jax, cap3, 4096, uniform_slots(cap3, 4096),
            lambda s: (s % 2).astype(np.int32), args.iters))

    # ---- config 4: GLOBAL psum across the mesh ----
    n_dev = min(len(jax.devices()), 4) if len(jax.devices()) >= 4 else len(jax.devices())
    eng4 = RateLimitEngine(mesh=make_mesh(jax.devices()[:n_dev]),
                           capacity_per_shard=4096, batch_per_shard=256,
                           global_capacity=1024, global_batch_per_shard=256,
                           max_global_updates=256)
    gkeys = [f"cfg4_g{i}" for i in range(200)]
    greqs = [RateLimitReq(name="bench4", unique_key=k, hits=1, limit=1_000_000,
                          duration=60 * Second, behavior=Behavior.GLOBAL)
             for k in gkeys]
    eng4.process(greqs)
    t0 = time.perf_counter()
    for i in range(n_iter):
        eng4.process(greqs)
    dt = time.perf_counter() - t0
    report["config4_global_psum"] = {
        "devices_in_mesh": n_dev,
        "decisions_per_sec": round(n_iter * len(greqs) / dt, 1),
    }

    # ---- config 5: max keys, Zipf + churn (expiring entries re-init) ----
    cap5 = args.scale_keys or ((1 << 21) if on_cpu else (1 << 24))
    eng5 = RateLimitEngine(mesh=make_mesh(jax.devices()[:1]),
                           capacity_per_shard=cap5, batch_per_shard=4096)
    churn = rng.random(4096) < 0.05  # 5 percent of lanes are fresh keys

    def churn_slots(w):
        s = ((rng.zipf(1.1, size=4096) - 1) % cap5).astype(np.int32)
        return s

    # churn is modeled with short durations on a slice of lanes: give 5% of
    # traffic duration=1ms so entries constantly expire and re-init in-kernel
    report["config5_max_keys_zipf_churn"] = dict(
        keys=cap5, **measure_device(
            eng5, kernel, jnp, jax, cap5, 4096, churn_slots,
            lambda s: (s % 2).astype(np.int32), args.iters))

    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
