# Dev targets (the reference Makefile:1-15 has only release/docker; we add
# the working set).

.PHONY: test test-core test-mesh-drain test-snapshot test-qos test-obs test-chaos test-analytics test-overlap test-chain test-frontdoor test-tiers test-devprof test-algorithms proto chip-smoke docker lint cluster

test:
	python -m pytest tests/ -x -q

# per-commit run: everything except the @pytest.mark.slow soak/fuzz/e2e
test-core:
	python -m pytest tests/ -x -q -m "not slow"

# the sharded serving differential suite (forced 8-device CPU mesh): the
# GLOBAL-composed drain vs the int64 oracle, analytics composed in or not.
# Part of tier-1 (`test-core` picks it up too); this target runs just the slice.
test-mesh-drain:
	python -m pytest tests/ -x -q -m "mesh_drain and not slow"

# the state-lifecycle slice: snapshot/restore restart equivalence + live
# key migration on ring change.  Part of tier-1 (`test-core` picks it up
# too); this target runs just the slice.
test-snapshot:
	python -m pytest tests/ -x -q -m "snapshot and not slow"

# the QoS slice: admission/shedding, AIMD window adaptation, tenant-fair
# slotting, peer circuit breaking — all CPU-only with injectable clocks.
# Part of tier-1 (`test-core` picks it up too); this target runs just it.
test-qos:
	python -m pytest tests/ -x -q -m "qos and not slow"

# the observability slice: stitched cross-node traces, stage-latency
# decomposition, metric-name parity, debug/profile admin plane.  Part of
# tier-1 (`test-core` picks it up too); this target runs just the slice.
test-obs:
	python -m pytest tests/ -x -q -m "obs and not slow"

# the self-healing slice: heartbeat failure detection + ring re-home,
# hinted handoff of GLOBAL payloads, graceful drain, deterministic fault
# injection.  Part of tier-1 (`test-core` picks it up too); this target
# runs just the slice.
test-chaos:
	python -m pytest tests/ -x -q -m "chaos and not slow"

# the traffic-analytics slice: device stats reduction vs the numpy oracle,
# Zipf hot-key top-K precision, SLO burn-rate alerting, analytics-off
# zero overhead.  Part of tier-1 (`test-core` picks it up too).
test-analytics:
	python -m pytest tests/ -x -q -m "analytics and not slow"

# the overlapped-pipeline slice: depth-2/3 drains bit-identical to the
# serial oracle (token+leaky, GLOBAL reconciliation, compact wire),
# commit-queue ordering under injected dispatch faults and out-of-order
# fetch completion, window-arena reuse accounting.  Part of tier-1
# (`test-core` picks it up too); this target runs just the slice.
test-overlap:
	python -m pytest tests/ -x -q -m "overlap and not slow"

# the deferred-fetch chain slice: stride-N stacked fetch bit-identical to
# the depth-1 serial oracle (incl. GLOBAL interleave), whole-stride fault
# atomicity, commit ordering under out-of-order chain fetch, adaptive
# stride growth/shrink/deadline-bound.  Part of tier-1 (`test-core` picks
# it up too); this target runs just the slice.
test-chain:
	python -m pytest tests/ -x -q -m "chain and not slow"

# the multi-process front-door slice: worker-sharded serving differential
# vs the single-process oracle (columnar + raw lanes, GLOBAL, forwarding),
# in-band sheds (draining / ring_full), worker crash-restart with no
# partial commit.  Part of tier-1 (`test-core` picks it up too).
test-frontdoor:
	python -m pytest tests/ -x -q -m "frontdoor and not slow"

# the tiered key-state slice: warm-tier engine bit-identical to the
# unbounded-arena oracle under Zipf traffic (incl. demote→re-promote in
# one drain), O(1) SlotTable.stats vs a fresh scan, warm snapshot
# persistence, version-mismatch cold-start degradation.  Part of tier-1
# (`test-core` picks it up too); this target runs just the slice.
test-tiers:
	python -m pytest tests/ -x -q -m "tiers and not slow"

# the device-time flight-recorder slice: jax.profiler trace parsing +
# kernel attribution (every probe arm gets nonzero measured ms/window
# from a REAL parsed trace), window-clock EWMA + slow-window exemplars,
# shm traceparent region roundtrip, the /v1/admin/kernels plane, and
# malformed-trace degradation.  Part of tier-1 (`test-core` picks it up
# too); this target runs just the slice.
test-devprof:
	python -m pytest tests/ -x -q -m "devprof and not slow"

# the algorithm-plane slice: GCRA / sliding-window / concurrency ladders
# bit-exact vs the plain-python serial oracles on both window bodies
# (int64, compact32) and through the packed wire, the all-algorithm fold
# fuzz seeds, lease-book lifecycle, out-of-range→token fallback, and
# snapshot forward-compat row dropping.  Part of tier-1 (`test-core`
# picks it up too); this target runs just the slice.
test-algorithms:
	python -m pytest tests/ -x -q -m "algorithms and not slow"

proto:
	cd gubernator_tpu/api/proto && protoc --python_out=. gubernator.proto peers.proto

# The quickest proof the system still starts on the chip (run it through
# the chip tool; `python chip_smoke.py --chips 4` is the cross-chip path).
# Here, without a chip, it must fail: `JAX_PLATFORMS=cpu python
# chip_smoke.py --tiny` rehearses the control flow and still ends non-zero.
chip-smoke:
	python chip_smoke.py

docker:
	docker build -t gubernator-tpu:latest .

cluster:
	python -m gubernator_tpu.cmd.cluster_main
