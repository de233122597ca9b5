"""Tools of the benchmark's builder, not of a run: several windows in one set-up.

    python benchmark/probe.py sweep    --workload <cell> --rates 200,400,...
    python benchmark/probe.py diagnose --workload <cell> --windows 6

`sweep` finds an open-loop cell's knee: one daemon, one arena fill, then one
short window per offered rate.  `diagnose` measures the same cell several
times back to back in one process tree and writes, per window, the latency
percentiles, a 2 ms histogram, the adaptive controllers' state at the window's
start and end, and the generators' lateness.  Both write JSON lines under
--out (default chiprun_out/) and never print a result line: they are how the
numbers in PERF.md and benchmark/findings were found, not part of a check.
"""

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark.run import tpu_only  # noqa: E402


def controller_state(debug):
    """(AIMD window, pipeline depth, fetch-stride target) as the daemon says."""
    pipe = debug.get("pipeline", {})
    return (debug.get("congestion", {}).get("effective_window"),
            pipe.get("depth"),
            pipe.get("overlap", {}).get("fetch_stride_target"))


def window_row(cell, m, extra):
    client = harness.client_stats(cell, m["results"], m["window"])
    w0, w1 = m["window"]
    row = dict(extra, **client)
    row["state_start"] = controller_state(m["before"]["debug"])
    row["state_end"] = controller_state(m["after"]["debug"])
    row["pending_end"] = m["after"]["debug"].get("admission", {}).get("pending")
    row["in_flight_end"] = m["after"]["debug"].get("pipeline", {}).get("in_flight")
    if cell["mix"]["loop"] == "open":
        due = np.concatenate([r["rpc_due"] for r in m["results"]])
        recv = np.concatenate([r["rpc_recv"] for r in m["results"]])
        ok = np.concatenate([r["rpc_ok"] for r in m["results"]]) > 0
        inw = (due >= w0) & (due < w1) & ok
        lat = (recv[inw] - due[inw]) * 1e3
        hist, _ = np.histogram(lat, bins=np.arange(0, 202, 2))
        row["hist_2ms"] = hist.tolist()
        row["over_200ms"] = int((lat >= 200).sum())
        # does the backlog grow?  latency of the window's last fifth over its first
        t = due[inw] - w0
        span = w1 - w0
        a, b = lat[t < span / 5], lat[t >= span * 4 / 5]
        row["p50_first_fifth"] = harness.pct(a, 50)
        row["p50_last_fifth"] = harness.pct(b, 50)
    prom0, prom1 = m["before"]["prom"], m["after"]["prom"]
    key = ("guber_tpu_windows_total", ())
    drains = prom1.get(key, 0) - prom0.get(key, 0)
    row["drains"] = drains
    key = ("guber_tpu_aggregation_decisions_total", ())
    row["server_decisions"] = prom1.get(key, 0) - prom0.get(key, 0)
    return row


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("what", choices=("sweep", "diagnose"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", default="")
    p.add_argument("--windows", type=int, default=6)
    p.add_argument("--rate", type=float, default=None,
                   help="diagnose at another offered rate than the cell's")
    p.add_argument("--out", default="chiprun_out")
    a = p.parse_args(argv)

    bench = harness.Bench()
    cell = bench.cell(a.workload)
    if a.rate is not None:
        cell["mix"]["rate_rps"] = a.rate
    cores = harness.split_cores(int(cell["mix"]["generator_procs"]))
    os.sched_setaffinity(0, set(cores["harness"]))
    os.makedirs(a.out, exist_ok=True)
    name = f"{a.what}_{a.workload}" + (f"_at{a.rate:g}" if a.rate else "")
    out_path = os.path.join(a.out, name + ".jsonl")
    workdir = tempfile.mkdtemp(prefix="bench_probe_")
    server = harness.Server(cell["config"], workdir, cores["server"])
    ok = tpu_only(bench)
    try:
        server.start()
        server.wait_ready(lambda i: ok(i, cell), 1150.0)
        _, fill_s = harness.fill(cell, server, a.seed, workdir, cores["generators"])
        harness.say(f"filled in {fill_s:.1f}s; cores {cores}")
        if a.what == "sweep":
            steps = [("rate", float(r)) for r in a.rates.split(",")]
        else:
            steps = [("window", i) for i in range(a.windows)]
        with open(out_path, "a") as f:
            for i, (kind, val) in enumerate(steps):
                c = copy.deepcopy(cell)
                if kind == "rate":
                    c["mix"]["rate_rps"] = val
                m = harness.measure(c, server, a.seed + i, a.seconds,
                                    workdir, cores["generators"], tag=f"p{i}")
                row = window_row(c, m, {kind: val, "workload": a.workload,
                                        "seconds": a.seconds, "fill_s": fill_s})
                f.write(json.dumps(row) + "\n")
                f.flush()
                brief = {k: row.get(k) for k in (
                    kind, "rpc_p50_ms", "rpc_p95_ms", "rpc_p99_ms", "failed",
                    "attempted", "gen_late_p99_ms", "decisions_per_s",
                    "p50_first_fifth", "p50_last_fifth", "state_start",
                    "state_end", "pending_end", "drains")}
                print(json.dumps(brief), flush=True)
    finally:
        server.stop()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
