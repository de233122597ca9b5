"""Per-arm kernel-census probe: the ISSUE-14 kernel-ladder scoreboard.

Counts executed-kernel census (jaxpr equations, scan/while bodies once,
pallas_call = 1 — `pallas_kernel.kernel_census`) for every serving arm,
normalizes to kernels **per request window** at the serving stack depth
K=8, and projects on-chip decision throughput from the repo's dispatch
cost model (BASELINE.md): a serving window is dispatch-bound, so

    projected decisions/s ~= lanes_per_window / (kpw * overhead_ms / 1000)

where overhead_ms is the per-kernel window cost.  When a profiler
capture is available (GUBER_PROBE_MEASURE=1) the probe re-derives it
empirically per arm — measured_ms_per_window / kernels_per_window —
so the projection tracks the arm's real dispatch cost instead of the
BASELINE.md constant; without a capture it falls back to the
DISPATCH_MS=0.15 model constant and says so.

The census is a property of the traced program, not the box it runs on —
the same numbers come out on a laptop and on the pod — which is what
makes it a gateable regression signal (scripts/bench_compare.py).

The arm programs themselves live in observability/devprof.py
(`build_census_arms`), shared with the measured device-time probe: the
census count and the measured ms/window for an arm always come from the
SAME traced program.

Arms:
  int64_xla            one window, int64 oracle lowering
  compact32_xla        one window, compact-word XLA lowering
  fused_window         one window, fused Pallas megakernel
  composed_drain       K=8 composed drain WITH GLOBAL sub-window
  composed_mixed_algos K=8 composed drain, all 5 wire algorithms live in
                       one window (same traced program as composed_drain
                       — the algorithm plane is select depth, not kernels)
  composed_analytics   K=8 composed drain + GLOBAL + analytics reduction

Env: JAX_PLATFORMS (cpu for smoke), GUBER_PROBE_JSON=<path> to
also write the table as json, GUBER_PROBE_MEASURE=1 to ALSO compile and
run each arm under a real `jax.profiler` capture and report measured
ms/window next to the census count (box-dependent — never gated
absolutely, only against the same host's stash).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts._probe_env import setup as _setup  # noqa: E402
_setup()

import jax  # noqa: E402

from gubernator_tpu.observability.devprof import (  # noqa: E402
    build_census_arms,
    measure_census_arms,
)
from gubernator_tpu.ops import pallas_kernel as pk  # noqa: E402

K = 8                    # serving stack depth the repo benches at
DISPATCH_MS = 0.15       # per-kernel dispatch cost, BASELINE.md model
PROJ_LANES = 32768       # production serving shape (bench.py TPU tier);
                         # census is lane-count independent, so the probe
                         # traces small and projects at chip scale
T0 = 1_700_000_000_000


def census(fn, *args):
    return pk.kernel_census(jax.make_jaxpr(fn)(*args))


def main():
    arms = build_census_arms(k=K)

    rows = []
    for spec in arms:
        total = census(spec["fn"], *spec["args"])
        kpw = total / spec["windows"]
        rows.append({"arm": spec["name"], "census_total": int(total),
                     "windows": spec["windows"],
                     "kernels_per_window": round(kpw, 1)})

    measured = None
    if os.environ.get("GUBER_PROBE_MEASURE") == "1":
        measured = measure_census_arms(arms=arms)
        for r in rows:
            m = measured["arms"].get(r["arm"])
            if m is not None:
                r["measured_ms_per_window"] = m["measured_ms_per_window"]

    # Projection: prefer the arm's empirical per-kernel cost when a
    # capture gave us measured ms/window; model constant otherwise.
    fell_back = False
    for r in rows:
        kpw = r["kernels_per_window"]
        meas = r.get("measured_ms_per_window")
        if meas and meas > 0:
            overhead = meas / kpw
        else:
            overhead = DISPATCH_MS
            fell_back = True
        r["overhead_ms_per_kernel"] = round(overhead, 4)
        r["projected_chip_decisions_per_sec"] = \
            int(PROJ_LANES / (kpw * overhead / 1000.0))
    if fell_back:
        print(f"# no profiler capture for some arms — projection uses "
              f"the BASELINE.md DISPATCH_MS={DISPATCH_MS} constant there "
              f"(set GUBER_PROBE_MEASURE=1 for empirical overhead)")

    hdr = (f"{'arm':<20} {'census':>7} {'win':>4} {'kern/win':>9} "
           f"{'ms/kern':>8} {'proj decisions/s':>17}"
           + (f" {'meas ms/win':>12}" if measured else ""))
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        line = (f"{r['arm']:<20} {r['census_total']:>7} {r['windows']:>4} "
                f"{r['kernels_per_window']:>9} "
                f"{r['overhead_ms_per_kernel']:>8} "
                f"{r['projected_chip_decisions_per_sec']:>17,}")
        if measured:
            line += f" {r.get('measured_ms_per_window', 0.0):>12.4f}"
        print(line)

    out = {"k_stack": K, "lanes_per_window": PROJ_LANES,
           "dispatch_ms_per_kernel": DISPATCH_MS, "arms": rows}
    if measured is not None:
        out["measured_ms_per_window"] = {
            name: m["measured_ms_per_window"]
            for name, m in measured["arms"].items()}
        out["measured_kernel_table"] = measured["kernel_table"]
    path = os.environ.get("GUBER_PROBE_JSON")
    if path:
        with open(path, "w") as fh:
            fh.write(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
