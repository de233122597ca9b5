"""Probe: tiered key-state sweep (state/tiers.py).

Zipf traffic over a logical namespace far larger than the hot arena,
swept over arena fractions (hot slots / namespace).  For each fraction
the probe reports what the tier machinery costs and buys:

  * warm hit rate — of the keys that were NOT hot at request time, how
    many re-promoted from warm with their counters intact (the rest are
    true cold inits, which a single-tier engine would serve WRONG after
    an eviction, not just slower)
  * promotions/s and demotions/s through the pre-dispatch fence
  * per-window wall p50/p99 — the fence rides the serving path, so its
    cost must show up here and nowhere else
  * a tiers-OFF baseline at the same arena size: same stream, no fence,
    the single-tier eviction cliff this subsystem removes

Standalone (CPU smoke):

    JAX_PLATFORMS=cpu python scripts/probe_tiers.py

Knobs: GUBER_PROBE_TIER_NS (namespace, default 32768),
GUBER_PROBE_TIER_FRACS (comma fractions, default 1/64,1/16,1/4),
GUBER_PROBE_TIER_WINDOWS (default 300), GUBER_PROBE_B (reqs/window,
default 256), GUBER_PROBE_TIER_S (Zipf skew, default 1.15).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import numpy as np  # noqa: E402

NS = int(os.environ.get("GUBER_PROBE_TIER_NS", "32768"))
FRACS = [float(eval(f)) for f in os.environ.get(  # noqa: S307 — "1/64" etc.
    "GUBER_PROBE_TIER_FRACS", "1/64,1/16,1/4").split(",")]
WINDOWS = int(os.environ.get("GUBER_PROBE_TIER_WINDOWS", "300"))
B = int(os.environ.get("GUBER_PROBE_B", "256"))
SKEW = float(os.environ.get("GUBER_PROBE_TIER_S", "1.15"))
NOW = 1_700_000_000_000


def eprint(msg):
    print(msg, file=sys.stderr, flush=True)


def _stream(rng, n_windows):
    """Zipf head + long tail, mixed durations, token bucket."""
    from gubernator_tpu.api.types import Algorithm, RateLimitReq
    durations = (2_000, 10_000, 60_000)
    now = NOW
    for _ in range(n_windows):
        now += int(rng.integers(1, 40))
        ks = (rng.zipf(SKEW, B) - 1) % NS
        yield now, [RateLimitReq(
            name="p", unique_key=f"t:{k}", hits=1, limit=100,
            duration=durations[k % 3], algorithm=Algorithm.TOKEN_BUCKET)
            for k in ks]


def _run(capacity, tiered):
    from gubernator_tpu.config import TierConfig
    from gubernator_tpu.core.engine import RateLimitEngine

    eng = RateLimitEngine(capacity_per_shard=capacity, batch_per_shard=B,
                          global_capacity=8, use_native=False)
    if tiered:
        eng.enable_tiers(TierConfig(warm_rows=NS * 2), epoch=NOW)
        eng.tier_warmup(max_rows=2 * B)  # compile the fence ladder up front
    rng = np.random.default_rng(7)
    stream = list(_stream(rng, WINDOWS))
    # untimed warm-up: the first window of the PROCESS pays the lane-bucket
    # jit compile; without this the engine that happens to run first eats
    # it and the comparison is compile time, not serving time
    for now, reqs in stream[:5]:
        eng.step(reqs, now=now)
    walls = []
    decisions = 0
    t0 = time.perf_counter()
    for i, (now, reqs) in enumerate(stream[5:]):
        w0 = time.perf_counter()
        eng.step(reqs, now=now)
        walls.append(time.perf_counter() - w0)
        decisions += len(reqs)
        if tiered and i % 50 == 49:
            eng.tier_maintain(now)
    elapsed = time.perf_counter() - t0
    walls = np.asarray(walls) * 1e3
    out = {
        "dps": decisions / elapsed,
        "p50": float(np.percentile(walls, 50)),
        "p99": float(np.percentile(walls, 99)),
    }
    if tiered:
        st = eng.tier_stats()
        misses = st["warm_hits"] + st["cold_misses"]
        out.update(
            hit_rate=st["warm_hits"] / max(misses, 1),
            promotes_s=st["promotions"] / elapsed,
            demotes_s=st["demotions"] / elapsed,
            warm_rows=st["warm_rows"],
        )
    return out


def main():
    import jax
    devs = jax.devices()
    eprint(f"# backend: {devs[0].platform} ({devs[0].device_kind})")
    eprint(f"# namespace={NS} zipf_s={SKEW} windows={WINDOWS} reqs/win={B}")
    eprint(f"{'arena':>8} {'frac':>6} | {'tiers dps':>10} {'p50ms':>7} "
           f"{'p99ms':>7} {'hit%':>6} {'promo/s':>8} {'demo/s':>8} "
           f"{'warm':>7} | {'off dps':>10} {'off p99':>8}")
    for frac in FRACS:
        cap = max(64, int(NS * frac))
        on = _run(cap, tiered=True)
        off = _run(cap, tiered=False)
        eprint(f"{cap:>8} {frac:>6.3f} | {on['dps']:>10.0f} "
               f"{on['p50']:>7.2f} {on['p99']:>7.2f} "
               f"{100 * on['hit_rate']:>5.1f}% {on['promotes_s']:>8.0f} "
               f"{on['demotes_s']:>8.0f} {on['warm_rows']:>7} | "
               f"{off['dps']:>10.0f} {off['p99']:>8.2f}")
    eprint("# tiers-off serves the same stream through the same arena but "
           "evicted keys silently re-init; hit% is the share of arena "
           "misses the warm tier answered with intact counters.")


if __name__ == "__main__":
    main()
