"""Clean single-config measurement of the production step.

Usage: python scripts/profile_lanes.py LANES [scan_k]
Measures blocking-per-window throughput and per-window latency; if scan_k>1,
also measures a lax.scan-of-k-windows-per-dispatch variant.
"""

import sys
import time

import numpy as np


def main():
    LANES = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    SCAN_K = int(sys.argv[2]) if len(sys.argv) > 2 else 0

    import jax
    import jax.numpy as jnp

    import gubernator_tpu  # noqa: F401
    from gubernator_tpu.core.engine import RateLimitEngine
    from gubernator_tpu.ops import kernel
    from gubernator_tpu.parallel.mesh import make_mesh

    CAPACITY = 1 << 20
    N_WINDOWS = 8
    rng = np.random.default_rng(7)

    mesh = make_mesh(jax.devices()[:1])
    eng = RateLimitEngine(
        mesh=mesh, capacity_per_shard=CAPACITY, batch_per_shard=LANES,
        global_capacity=1024, global_batch_per_shard=128,
        max_global_updates=128,
    )
    step = eng._step_fn
    zipf = rng.zipf(1.1, size=(N_WINDOWS, LANES))
    slots = ((zipf - 1) % CAPACITY).astype(np.int32)
    batches = []
    for i in range(N_WINDOWS):
        s = slots[i]
        batches.append(jax.device_put(kernel.WindowBatch(
            slot=jnp.asarray(s[None, :]),
            hits=jnp.ones((1, LANES), jnp.int64),
            limit=jnp.full((1, LANES), 1_000_000, jnp.int64),
            duration=jnp.full((1, LANES), 60_000, jnp.int64),
            algo=jnp.asarray((s % 2).astype(np.int32)[None, :]),
            is_init=jnp.zeros((1, LANES), bool),
        )))
    gbatch, gacc, upd, ups = eng.empty_control()
    empty_g = jax.device_put(gbatch)
    gacc = jax.device_put(gacc)
    upd = jax.device_put(upd)
    ups = jax.device_put(ups)

    state, gstate, gcfg = eng.state, eng.gstate, eng.gcfg
    now = 1_700_000_000_000

    def run(i, state, gstate, gcfg, t):
        return step(state, gstate, gcfg, batches[i % N_WINDOWS], empty_g,
                    gacc, upd, ups, jnp.int64(t))

    for i in range(5):
        state, out, gstate, gcfg = run(i, state, gstate, gcfg, now + i)
    jax.block_until_ready(out)

    ITERS = 100
    lat = []
    t0 = time.perf_counter()
    for i in range(ITERS):
        w0 = time.perf_counter()
        state, out, gstate, gcfg = run(i, state, gstate, gcfg, now + 5 + i)
        jax.block_until_ready(out)
        lat.append(time.perf_counter() - w0)
    tb = time.perf_counter() - t0
    lat_ms = np.array(lat) * 1e3
    print(f"B={LANES}: blocking {ITERS*LANES/tb/1e6:.1f} M/s  "
          f"p50={np.percentile(lat_ms,50):.3f}ms p99={np.percentile(lat_ms,99):.3f}ms")

    if SCAN_K > 1:
        from jax import lax

        # one dispatch applies SCAN_K stacked windows sequentially via scan
        stack = kernel.WindowBatch(*[
            jnp.stack([getattr(batches[i % N_WINDOWS], f)
                       for i in range(SCAN_K)])
            for f in kernel.WindowBatch._fields
        ])
        stack = jax.device_put(stack)

        def multi(state, gstate, gcfg, stk, t0):
            def body(carry, xs):
                st, gst, gc, t = carry
                b, = xs
                st, gst, gc, out, _ = step_inner(st, gst, gc, b, t)
                return (st, gst, gc, t + 1), out

            # inline the per-window computation: reuse the shard_fn by calling
            # the already-jitted step is not composable; rebuild with scan over
            # kernel.window_step on shard 0 only (single-chip scan probe)
            def step_inner(st, gst, gc, b, t):
                s0 = jax.tree.map(lambda a: a[0], st)  # the arena's planes
                b0 = kernel.WindowBatch(*jax.tree.map(lambda a: a[0], b))
                ns, out = kernel.window_step(s0, b0, t)
                expand = lambda a: a[None]
                return (jax.tree.map(expand, ns), gst, gc,
                        kernel.WindowOutput(*jax.tree.map(expand, out)), None)

            (st, gst, gc, _), outs = lax.scan(body, (state, gstate, gcfg, t0), (stk,))
            return st, gst, gc, outs

        multi_j = jax.jit(multi, donate_argnums=(0,))
        t = jnp.int64(now + 500)
        st2 = state
        for _ in range(2):
            st2, gstate, gcfg, outs = multi_j(st2, gstate, gcfg, stack, t)
        jax.block_until_ready(outs)
        M_ITERS = 40
        t0c = time.perf_counter()
        for i in range(M_ITERS):
            st2, gstate, gcfg, outs = multi_j(st2, gstate, gcfg, stack,
                                              jnp.int64(now + 600 + i))
            jax.block_until_ready(outs)
        tm = time.perf_counter() - t0c
        dec = M_ITERS * SCAN_K * LANES
        print(f"scan K={SCAN_K}: {dec/tm/1e6:.1f} M/s  "
              f"({tm/M_ITERS*1e3:.3f} ms per dispatch of {SCAN_K*LANES} decisions)")


if __name__ == "__main__":
    main()
