"""Measure the tracing instrumentation's cost on the serving drain.

Two sweeps over the same in-process Instance (CPU or chip, whatever JAX
finds): rounds of single-key requests, each under a root span of its own
as the gRPC servicer starts one (server.py), through the full pipeline
drain with

  (a) tracing OFF  (sample=0.0, the default) — the hot path should pay
      one attribute check per request; and
  (b) tracing ON   (sample=1.0) — every request records its full span
      set (enqueue, admission_wait, queue_wait, window_fill,
      device_dispatch, drain_commit, in_drain, reply_wake); the sweep
      fails if one of the per-request three is missing.

Both pay the always-on part: a drain's eleven boundary stamps, its stage
observations, the per-request stage counters and five inactive
TraceAnnotations (core/pipeline.py, server.py).

Prints decisions/s for both and the relative overhead.  The acceptance
bar is <5% for the OFF case relative to the median of its own warm
rounds (i.e. the disabled-path cost is noise), and the ON case is
reported for the record — sampling at 1.0 is a debugging posture, not a
production one.

A third sweep runs with the continuous device profiler armed
(GUBER_DEVPROF=periodic, observability/devprof.py): the controller
re-arms short jax.profiler captures on a background thread while the
sweep drains, so the median round shows what always-on attribution
costs the serving path.  This one IS asserted: overhead past
GUBER_DEVPROF_OVERHEAD_PCT (default 2.0, median-of-rounds so a lone
capture round cannot trip it) exits nonzero.
"""
import asyncio
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from gubernator_tpu.api.types import Algorithm, RateLimitReq, Second
from gubernator_tpu.config import Config, EngineConfig
from gubernator_tpu.core.service import Instance

N_KEYS = int(os.environ.get("GUBER_PROBE_KEYS", "512"))
ROUNDS = int(os.environ.get("GUBER_PROBE_ROUNDS", "30"))
WARMUP = 5


def make_reqs():
    return [
        RateLimitReq(name="probe", unique_key=f"k{i}", hits=1,
                     limit=1 << 20, duration=Second,
                     algorithm=Algorithm.TOKEN_BUCKET)
        for i in range(N_KEYS)
    ]


REQUEST_SPANS = {"queue_wait", "in_drain", "reply_wake"}


async def sweep(sample: float, devprof: bool = False) -> float:
    conf = Config(engine=EngineConfig(capacity_per_shard=4096,
                                      batch_per_shard=1024))
    conf.trace_sample = sample
    if devprof:
        # continuous mode with an interval short enough that captures
        # actually land inside the sweep (the controller sheds overlaps)
        conf.devprof_mode = "periodic"
        conf.devprof_interval_s = 0.5
        conf.devprof_drains = 2
    inst = Instance(conf)
    inst.engine.warmup()
    reqs = make_reqs()
    rates = []

    async def one(req):
        # the servicer's root span: a no-op unless the request is sampled
        with inst.tracer.start_trace("rpc"):
            return await inst.get_rate_limits([req])
    try:
        for r in range(ROUNDS):
            t0 = time.monotonic()
            await asyncio.gather(*[one(q) for q in reqs])
            dt = time.monotonic() - t0
            if r >= WARMUP:
                rates.append(N_KEYS / dt)
        if sample > 0.0:
            missing = REQUEST_SPANS - {s.name for s in inst.tracer.spans()}
            if missing:
                raise SystemExit(f"FAIL: no {sorted(missing)} span recorded "
                                 f"at sample={sample}")
    finally:
        inst.close()
    return statistics.median(rates)


async def main() -> int:
    off = await sweep(0.0)
    on = await sweep(1.0)
    dev = await sweep(0.0, devprof=True)
    overhead = (off - on) / off * 100.0
    dev_overhead = (off - dev) / off * 100.0
    budget = float(os.environ.get("GUBER_DEVPROF_OVERHEAD_PCT", "2.0"))
    print(f"tracing off: {off:,.0f} decisions/s")
    print(f"tracing on (sample=1.0): {on:,.0f} decisions/s")
    print(f"sampled-vs-off overhead: {overhead:+.1f}%")
    print(f"devprof periodic: {dev:,.0f} decisions/s")
    print(f"devprof-vs-off overhead: {dev_overhead:+.1f}% "
          f"(budget {budget:.1f}%)")
    if dev_overhead > budget:
        print(f"FAIL: continuous devprof costs {dev_overhead:.1f}% "
              f"> {budget:.1f}% budget", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
