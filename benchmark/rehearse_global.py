"""The rehearsal of `global-4chip.global-1000`, the cell a later PR adds.

    python benchmark/rehearse_global.py --out chiprun_out/rehearsal.jsonl

Builds, in a scratch root, what that PR will add as files (configuration
`global-4chip`, its reference, the mix `global-1000`, the entries in
BENCHMARK.json; nothing of it is committed under benchmark/configs,
reference, traffic or cells), and runs the cell through `harness.run_cell`
on the machine it is started on: one cold run, untraced runs on seeds of
their own, a traced run, and a run at a tick period the program can keep.
One JSON line per run in `--out`, a failed run with the daemon's last log
lines.  `--tiny` is the same plan at a size the CPU holds (a rehearsal of
this script, on four virtual devices).  Not part of a check.
"""

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check as checker  # noqa: E402
from benchmark import harness, traffic  # noqa: E402

CELL, CONFIG, MIX = "global-4chip.global-1000", "global-4chip", "global-1000"

MESH_ENV = {"GUBER_MESH_COORDINATOR": "127.0.0.1:{port}",
            "GUBER_MESH_NUM_PROCESSES": "1", "GUBER_MESH_PROCESS_ID": "0",
            "GUBER_MESH_PEERS": "{grpc}"}


def configuration(tiny, env):
    cap, lanes = ("4096", "256") if tiny else ("2621440", "16384")
    return {
        "name": CONFIG,
        "source": "BASELINE.json configs[3] 'Behavior=GLOBAL, 4-peer cluster "
                  "-> 4-chip mesh, psum hit aggregation'",
        "chips": 4,
        "daemon_env": dict({"GUBER_TPU_CAPACITY_PER_SHARD": cap,
                            "GUBER_TPU_BATCH_PER_SHARD": lanes},
                           **MESH_ENV, **env),
        "keyspace": {
            "population": 3000 if tiny else 10000000, "zipf_s": 1.1,
            "algorithms": "parity", "limits": [10, 100, 1000, 10000],
            "duration_ms": 60000, "name": "requests_per_account",
            "key_prefix": "account:",
            "global": {"keys": 64 if tiny else 1024, "zipf_s": 0.0,
                       "algorithms": "token",
                       "limits": [1000, 10000, 100000], "duration_ms": 60000,
                       "name": "requests_per_tenant", "key_prefix": "tenant:"}},
        "fill_keys": 1000 if tiny else 1000000,
        "guarantees": [
            "BATCHING keys: every decision equals the serial per-key "
            "application of the acknowledged requests",
            "GLOBAL keys: stale, then consistent: every answer of a window "
            "reads the row as it stood before it, the window's summed hits "
            "land once after it (reference global_window)",
            "no request is dropped silently"],
        "reduced": ["fill_keys"], "assumed": []}


def mix(tiny, change=None):
    m = {"base_seed": 20260930, "generator_procs": 4, "warm_s": 8,
         "rpc_timeout_s": 20, "grace_s": 10, "fill_connections": 32,
         "loop": "closed", "items_per_rpc": 1000, "connections": 32,
         "pool_rpcs_per_proc": 4096, "trace_drains": 40,
         "global_item_share": 0.10,
         "check": {"sample_mod": 256, "hot_ranks": [8, 9, 13, 16, 17],
                   "global_sample_mod": 4, "min_checked_decisions": 20000,
                   "min_global_checked_decisions": 20000,
                   "max_undecided_share": 0.02, "max_failed_share": 0.01}}
    if tiny:
        m.update(generator_procs=2, connections=8, warm_s=1.5,
                 pool_rpcs_per_proc=256, trace_drains=5, grace_s=5,
                 fill_connections=8, items_per_rpc=50)
        m["check"].update(sample_mod=4, min_checked_decisions=100,
                          global_sample_mod=1,
                          min_global_checked_decisions=100)
    m.update(change or {})
    return m


def scratch_root(tiny):
    root = tempfile.mkdtemp(prefix="rehearsal_root_")
    data = os.path.join(root, "benchmark")
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(data, d))
    for d in ("layer_metrics", "reference"):
        shutil.copytree(os.path.join(harness.HERE, d), os.path.join(data, d))
    shutil.copy(os.path.join(harness.HERE, "peaks.json"), data)
    with open(os.path.join(data, "reference", CONFIG + ".py"), "w") as f:
        f.write("from benchmark.reference.serial import apply, global_window"
                "  # noqa: F401\n")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": CONFIG, "source": "rehearsal",
                        "file": f"benchmark/configs/{CONFIG}.json",
                        "reduced": ["fill_keys"], "why": "rehearsal"}]
    spec["workloads"] = [{"name": CELL, "config": CONFIG, "traffic": MIX,
                          "chips": 4, "why": "rehearsal"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:     # a closed loop: what moves with its rate
            rate = "decisions_per_s" in (m["name"], m.get("moves"))
            m["workloads"] = [CELL] if rate else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def one_run(root, tiny, label, seed, seconds, trace, env, device_ok, child_env,
            mix_change=None):
    with open(os.path.join(root, "benchmark", "configs", CONFIG + ".json"),
              "w") as f:
        json.dump(configuration(tiny, env), f)
    with open(os.path.join(root, "benchmark", "traffic", MIX + ".json"),
              "w") as f:
        json.dump(mix(tiny, mix_change), f)
    bench = harness.Bench(root)
    out = {"run": label, "seed": seed, "seconds": seconds, "trace": int(trace),
           "daemon_env_beside_the_mesh": env,
           "mix_beside_global-1000": mix_change or {}}
    t = time.time()
    started = t - harness.T_PROCESS_START    # ready_s counts from the process
    try:
        line, m, client, ctx = harness.run_cell(
            bench, CELL, seed, seconds, trace, device_ok,
            server_env=child_env, require_device_trace=False)
    except Exception as e:               # a run that fails is a line too
        out.update(ok=False, error=f"{type(e).__name__}: {e}"[-3000:],
                   wall_s=time.time() - t)
        return out
    run = line["run"]
    out.update(ok=True, correct=line["correct"],
               ready_s=run["ready_s"] - started,
               fill_s=run["fill_s"], warm_s=run["warm_s"],
               decisions_per_s=client.get("decisions_per_s"),
               closed_rpc_p50_ms=client.get("closed_rpc_p50_ms"),
               attempted=line["attempted"], failed=line["failed"],
               errors=run["errors"], mesh=run["mesh"],
               mesh_mode=m["after"]["debug"].get("mesh_mode"),
               engine=m["after"]["debug"].get("engine"),
               compared=line["compared"], families=run["families"],
               told_lag=run["told_lag"], device=line["device"],
               metrics=line["metrics"], wall_s=time.time() - t)
    if ctx.get("trace_error"):
        out["trace_error"] = ctx["trace_error"][-3000:]
    if "breakdown" in line:
        busy = line["device"]["busy_s"]
        ops = line["breakdown"]["device_ops"]
        out["breakdown"] = line["breakdown"]
        out["busy_share"] = busy / line["device"]["window_s"]
        out["all_reduce"] = [[n, s, s / busy] for n, s in ops
                             if "all-reduce" in n]
    # what the comparison says once the clock's lag is allowed for: not part
    # of `correct`, which holds the program's timestamps to the clients' clock
    lag = [v for k, v in run["told_lag"].items()
           if k.endswith("_ms") and v is not None]
    if lag and max(lag) > checker.TOL_MS:
        tol, checker.TOL_MS = checker.TOL_MS, int(math.ceil(max(lag))) + 2000
        try:
            ref = bench.reference_functions(CONFIG)
            ks = traffic.KeySpace(configuration(tiny, env)["keyspace"])
            got = checker.check(ctx["ops"], ctx["tainted"], ks, ref["apply"],
                                global_window=ref["global_window"])
            out["with_the_lag_allowed_for"] = dict(
                got, tol_ms=checker.TOL_MS)
        finally:
            checker.TOL_MS = tol
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--untraced", type=int, default=3)
    p.add_argument("--seed", type=int, default=3_000_030_001)
    p.add_argument("--tick-s", default="0.05",
                   help="GUBER_BATCH_WAIT of the `tick` run")
    p.add_argument("--runs", default="cold,untraced,traced,tick",
                   help="which of cold, untraced, traced, tick, light")
    a = p.parse_args(argv)
    child_env = None
    if a.tiny:
        child_env = {"JAX_PLATFORMS": "cpu",
                     "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

    def device_ok(info, cell):
        if not a.tiny and info["platform"] != "tpu":
            raise harness.BenchError(f"no accelerator: {info['platform']!r}")
        if info["count"] < 4:
            raise harness.BenchError(f"4 chips wanted, {info['count']} seen")
    root = scratch_root(a.tiny)
    wide = {"GUBER_GLOBAL_TIMEOUT": "30"}
    light = {"connections": 4, "fill_connections": 8}
    plans = {
        "cold": [("cold, the daemon's defaults", a.seed, False, {}, None)],
        "untraced": [(f"untraced {i + 1}, GLOBAL registration given 30 s",
                      a.seed + 1 + i, False, wide, None)
                     for i in range(a.untraced)],
        "traced": [("traced, GLOBAL registration given 30 s", a.seed + 50,
                    True, wide, None)],
        "tick": [(f"tick period {a.tick_s} s, GLOBAL registration given 30 s",
                  a.seed + 60, False, dict(wide, GUBER_BATCH_WAIT=a.tick_s),
                  None)],
        "light": [("traced, 4 connections (fill 8), GLOBAL registration "
                   "given 30 s", a.seed + 70, True, wide, light)]}
    plan = [run for name in a.runs.split(",") for run in plans[name]]
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    try:
        for label, seed, trace, env, mix_change in plan:
            got = one_run(root, a.tiny, label, seed, a.seconds, trace, env,
                          device_ok, child_env, mix_change)
            with open(a.out, "a") as f:
                f.write(json.dumps(got) + "\n")
            harness.say(f"rehearsal: {label}: ok={got['ok']} "
                        f"correct={got.get('correct')} "
                        f"rate={got.get('decisions_per_s')} "
                        f"lag={got.get('told_lag')} {got.get('error', '')[-400:]}")
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
