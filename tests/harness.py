"""Single-shard kernel harness: drives window_step directly with explicit time.

Lets algorithm-semantics tests control `now` deterministically (the reference
tests sleep real wall-clock between hits, functional_test.go:97-206; we advance
a virtual clock instead).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import gubernator_tpu  # noqa: F401  (enables x64)
from gubernator_tpu.api.types import RateLimitReq, RateLimitResp
from gubernator_tpu.ops import kernel
from gubernator_tpu.ops.kernel import BucketState, WindowBatch
from gubernator_tpu.state.arena import SlotTable


class KernelHarness:
    def __init__(self, capacity: int = 64, batch: int = 32):
        self.capacity = capacity
        self.batch = batch
        self.state = BucketState.zeros(capacity)
        self.table = SlotTable(capacity)
        self.now = 1_700_000_000_000  # fixed epoch start, ms
        self._step = jax.jit(kernel.window_step)

    def advance(self, ms: int):
        self.now += ms

    def window(self, reqs: Sequence[RateLimitReq], now: Optional[int] = None) -> List[RateLimitResp]:
        """Run one window containing all of `reqs` (in order)."""
        if now is None:
            now = self.now
        n = len(reqs)
        assert n <= self.batch
        slot = np.full((self.batch,), kernel.PAD_SLOT, dtype=np.int32)
        hits = np.zeros((self.batch,), dtype=np.int64)
        limit = np.zeros((self.batch,), dtype=np.int64)
        duration = np.zeros((self.batch,), dtype=np.int64)
        algo = np.zeros((self.batch,), dtype=np.int32)
        is_init = np.zeros((self.batch,), dtype=bool)
        for i, r in enumerate(reqs):
            s, init = self.table.lookup(r.hash_key(), now, r.duration)
            slot[i] = s
            hits[i] = r.hits
            limit[i] = r.limit
            duration[i] = r.duration
            algo[i] = r.algorithm
            is_init[i] = init
        batch = WindowBatch(slot=slot, hits=hits, limit=limit,
                            duration=duration, algo=algo, is_init=is_init)
        self.state, out = self._step(self.state, batch, jnp.int64(now))
        return [
            RateLimitResp(
                status=int(out.status[i]),
                limit=int(out.limit[i]),
                remaining=int(out.remaining[i]),
                reset_time=int(out.reset_time[i]),
            )
            for i in range(n)
        ]

    def one(self, req: RateLimitReq, now: Optional[int] = None) -> RateLimitResp:
        return self.window([req], now)[0]


def wire_window(state, packed, now):
    """One compact-encoded window through the body the chip runs, as the
    scan body of engine._drain_scan composes it: decode_batch →
    window_step_compact32 → encode_output_word, plus the limit lanes and
    the limit-mismatch flag.  Returns (state, words, limits, mism)."""
    bt = kernel.decode_batch(packed)
    state, out = kernel.window_step_compact32(state, bt, now)
    mism = jnp.any((out.limit != bt.limit) & (bt.slot >= 0))
    return state, kernel.encode_output_word(out, now), out.limit, mism


def count_eqns(jaxpr) -> int:
    """Equations of a (Closed)Jaxpr, those of its sub-jaxprs (scan, cond,
    shard_map, pjit bodies) included."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    return sum(1 + sum(count_eqns(sub)
                       for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)
