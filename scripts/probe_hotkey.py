"""Hot-key analytics probe: does the device top-K find real heavy hitters?

Drives a Zipf(s)-skewed keyset open-loop through a full Instance (native
router -> drain -> device stats reduction -> host rolling merge), then
scores the reported top-K against the TRUE heavy hitters of the sampled
trace: precision@K = |reported-K intersect true-K| / K.  The acceptance
bar mirrored in tests/test_analytics.py is precision@10 >= 0.9 at s=1.1.

  JAX_PLATFORMS=cpu python scripts/probe_hotkey.py
  GUBER_PROBE_KEYS=5000 GUBER_PROBE_DECISIONS=100000 ... # bigger trace
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# analytics on BEFORE the config module reads the environment
os.environ.setdefault("GUBER_ANALYTICS", "1")

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import numpy as np  # noqa: E402

from gubernator_tpu.api.types import Algorithm, RateLimitReq  # noqa: E402
from gubernator_tpu.config import Config, EngineConfig  # noqa: E402
from gubernator_tpu.core.service import Instance  # noqa: E402

N_KEYS = int(os.environ.get("GUBER_PROBE_KEYS", "2000"))
DECISIONS = int(os.environ.get("GUBER_PROBE_DECISIONS", "40000"))
BATCH = int(os.environ.get("GUBER_PROBE_BATCH", "512"))
ZIPF_S = float(os.environ.get("GUBER_PROBE_ZIPF_S", "1.1"))
SEED = int(os.environ.get("GUBER_PROBE_SEED", "7"))


def zipf_trace(rng) -> np.ndarray:
    """DECISIONS key ranks drawn Zipf(ZIPF_S) over a finite N_KEYS set."""
    p = 1.0 / np.arange(1, N_KEYS + 1) ** ZIPF_S
    return rng.choice(N_KEYS, size=DECISIONS, p=p / p.sum())


async def drive(inst: Instance, ranks: np.ndarray) -> None:
    for off in range(0, len(ranks), BATCH):
        reqs = [RateLimitReq(name="hot", unique_key=f"key{r:05d}",
                             hits=1, limit=1 << 20, duration=60_000,
                             algorithm=Algorithm.TOKEN_BUCKET)
                for r in ranks[off:off + BATCH]]
        await inst.get_rate_limits(reqs)


def main() -> int:
    conf = Config(engine=EngineConfig(
        capacity_per_shard=1 << 14, batch_per_shard=1024,
        global_capacity=128, global_batch_per_shard=32,
        max_global_updates=32))
    assert conf.analytics.enabled, "set GUBER_ANALYTICS=1"
    inst = Instance(conf)
    inst.engine.warmup()
    rng = np.random.default_rng(SEED)
    ranks = zipf_trace(rng)
    asyncio.run(drive(inst, ranks))

    counts = np.bincount(ranks, minlength=N_KEYS)
    order = np.argsort(-counts, kind="stable")
    reported = [row["key"] for row in inst.analytics.topk_snapshot(
        inst.analytics.conf.topk)]
    print(f"trace: {DECISIONS} decisions over {N_KEYS} keys, "
          f"zipf s={ZIPF_S}; hottest true key x{counts[order[0]]}")
    worst = 1.0
    for k in (5, 10, 20):
        if k > len(order):
            continue
        true = {f"hot_key{r:05d}" for r in order[:k]}
        got = set(reported[:k])
        prec = len(true & got) / k
        if k == 10:
            worst = prec
        print(f"precision@{k}: {prec:.2f}  "
              f"(reported {sorted(got)[:3]}...)")
    inst.close()
    if worst < 0.9:
        print(f"FAIL: precision@10 {worst:.2f} < 0.9", file=sys.stderr)
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
