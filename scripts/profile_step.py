"""Microbenchmark: where does the window step's time go on the real chip?

Times (a) the full production step at several lane widths, (b) the argsort+
gather prologue alone, (c) the transition math alone on pre-sorted input,
(d) an int32-state variant of the transition math, (e) bare dispatch floor
(empty jitted fn), to locate the bottleneck.
"""

import sys
import time

import numpy as np


def timeit(fn, *args, iters=50, warmup=3):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp

    import gubernator_tpu  # noqa: F401
    from gubernator_tpu.ops import kernel

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})")

    CAPACITY = 1 << 20
    rng = np.random.default_rng(7)

    # --- (e) dispatch floor
    @jax.jit
    def nop(x):
        return x + 1

    x = jnp.zeros((8,), jnp.int32)
    print(f"dispatch floor (tiny jit): {timeit(nop, x)*1e3:.3f} ms")

    # the resident layout the engine serves from: (lo, hi) uint32 planes
    state = kernel.ArenaPlanes.zeros(CAPACITY)
    state = jax.block_until_ready(state)

    for LANES in (4096, 8192, 16384, 32768, 65536):
        zipf = rng.zipf(1.1, size=LANES)
        slots = ((zipf - 1) % CAPACITY).astype(np.int32)
        batch = kernel.WindowBatch(
            slot=jnp.asarray(slots),
            hits=jnp.ones((LANES,), jnp.int64),
            limit=jnp.full((LANES,), 1_000_000, jnp.int64),
            duration=jnp.full((LANES,), 60_000, jnp.int64),
            algo=jnp.asarray((slots % 2).astype(np.int32)),
            is_init=jnp.zeros((LANES,), bool),
        )
        batch = jax.device_put(batch)
        now = jnp.int64(1_700_000_000_000)

        step = jax.jit(kernel.window_step, donate_argnums=0)
        # keep state fresh each call: donate makes this awkward; time with
        # non-donated state instead (extra copy ~ states touched rows only)
        step_nd = jax.jit(kernel.window_step)
        t = timeit(step_nd, state, batch, now)
        print(f"window_step   B={LANES:6d}: {t*1e3:7.3f} ms  {LANES/t/1e6:7.1f} M/s")

        # --- (b) sort prologue alone
        @jax.jit
        def sort_only(b):
            valid = b.slot >= 0
            sort_key = jnp.where(valid, b.slot, jnp.int32(2**31 - 1))
            order = jnp.argsort(sort_key)
            return (sort_key[order], b.hits[order], b.limit[order],
                    b.duration[order], b.algo[order], b.is_init[order])

        t = timeit(sort_only, batch)
        print(f"  sort+gather           : {t*1e3:7.3f} ms")

        # --- (c) transition math alone (no sort, no scatter)
        @jax.jit
        def trans_only(st, b, now):
            reg = kernel.gather_registers(
                st, jnp.clip(b.slot, 0, CAPACITY - 1))
            fresh = b.is_init | (reg.expire < now)
            return kernel.transition(reg, b.hits, b.limit, b.duration, b.algo, now, fresh)

        t = timeit(trans_only, state, batch, now)
        print(f"  gather+transition     : {t*1e3:7.3f} ms")

        # --- scatter commit alone
        @jax.jit
        def scatter_only(st, b, vals):
            wslot = jnp.where(b.slot >= 0, b.slot, jnp.int32(CAPACITY))
            return st.remaining_lo.at[wslot].set(vals, mode="drop")

        vals = jnp.ones((LANES,), jnp.uint32)
        t = timeit(scatter_only, state, batch, vals)
        print(f"  scatter (1 plane)     : {t*1e3:7.3f} ms")

    # --- (d) int32 variant of full sorted pipeline (sort + seg + math int32)
    LANES = 8192
    zipf = rng.zipf(1.1, size=LANES)
    slots = ((zipf - 1) % CAPACITY).astype(np.int32)
    b32 = dict(
        slot=jnp.asarray(slots),
        hits=jnp.ones((LANES,), jnp.int32),
        limit=jnp.full((LANES,), 1_000_000, jnp.int32),
        duration=jnp.full((LANES,), 60_000, jnp.int32),
        algo=jnp.asarray((slots % 2).astype(np.int32)),
    )
    b32 = jax.device_put(b32)

    @jax.jit
    def sort32(b):
        order = jnp.argsort(b["slot"])
        return tuple(v[order] for v in b.values())

    t = timeit(sort32, b32)
    print(f"int32 sort+gather B=8192 : {t*1e3:7.3f} ms")

    # packed single-key sort: slot<<13 | lane in one int32? slot max 2^20 →
    # need int64 packed key, or sort (slot, lane) as int64
    @jax.jit
    def sort_packed(b):
        packed = b["slot"].astype(jnp.int64) * LANES + jnp.arange(LANES, dtype=jnp.int64)
        s = jnp.sort(packed)
        return s // LANES, (s % LANES).astype(jnp.int32)

    t = timeit(sort_packed, b32)
    print(f"packed-key single sort   : {t*1e3:7.3f} ms")


if __name__ == "__main__":
    main()
