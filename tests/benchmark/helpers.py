"""Shared pieces of the benchmark's own tests: a tiny copy of the data files
in a temporary root, and a simulated windowed server for the comparison."""

import contextlib
import json
import os
import random
import shutil
import signal

import numpy as np

from benchmark import traffic
from benchmark.reference import serial
# a mesh of chips in one process, stated wholly by the configuration's file:
# the run's own address and a fresh port come from the placeholders
from benchmark.rehearse_global import MESH_ENV  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the child daemon of a test runs on one CPU device, whatever the test
# process itself was given
CPU_CHILD = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}


def tiny_root(tmp, duration_ms=60000):
    """BENCHMARK.json and the data files it names, cut to a size a test run
    can hold: same names, same code, small arenas and few keys."""
    tmp = str(tmp)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(tmp, "benchmark", d))
    for d in ("layer_metrics", "reference"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(tmp, "benchmark", d))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"),
                os.path.join(tmp, "benchmark", "peaks.json"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg["daemon_env"] = {"GUBER_TPU_CAPACITY_PER_SHARD": "8192",
                             "GUBER_TPU_BATCH_PER_SHARD": "256"}
        cfg["keyspace"]["population"] = 3000
        cfg["keyspace"]["duration_ms"] = duration_ms
        cfg["fill_keys"] = 2000
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in spec["workloads"]:
        path = os.path.join("benchmark", "traffic", w["traffic"] + ".json")
        with open(os.path.join(REPO, path)) as f:
            mix = json.load(f)
        mix.update(generator_procs=2, connections=8, warm_s=1.5,
                   pool_rpcs_per_proc=256, trace_drains=5,
                   grace_s=5, fill_connections=8)
        mix["check"]["min_checked_decisions"] = 100
        if mix["items_per_rpc"] > 50:
            mix["items_per_rpc"] = 50
            mix["check"]["sample_mod"] = 4
        with open(os.path.join(tmp, path), "w") as f:
            json.dump(mix, f)
        if mix["loop"] == "open":
            with open(os.path.join(tmp, "benchmark", "cells",
                                   w["name"] + ".json"), "w") as f:
                json.dump({"rate_rps": 80}, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp




def add_global_deployment(root, daemon_env=None, algorithms="token",
                          share=0.2, loop="closed"):
    """What a later PR adds for a GLOBAL deployment, as files and entries
    only: configuration `global-tiny` (a `global` block in its keyspace),
    its reference, the mix `global-50`, the cell `global-tiny.global-50`."""
    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    write("benchmark/configs/global-tiny.json", {
        "name": "global-tiny", "chips": 1,
        "daemon_env": dict({"GUBER_TPU_CAPACITY_PER_SHARD": "4096",
                            "GUBER_TPU_BATCH_PER_SHARD": "256"},
                           **(daemon_env or {})),
        "keyspace": {"population": 3000, "zipf_s": 1.1, "algorithms": "parity",
                     "limits": [10, 100, 1000, 10000], "duration_ms": 60000,
                     "name": "requests_per_account", "key_prefix": "account:",
                     "global": {"keys": 64, "zipf_s": 0.0,
                                "algorithms": algorithms,
                                "limits": [100, 1000, 10000],
                                "duration_ms": 60000,
                                "name": "requests_per_tenant",
                                "key_prefix": "tenant:"}},
        "fill_keys": 1000,
        "guarantees": ["BATCHING keys: serial", "GLOBAL keys: stale, then "
                       "consistent (reference global_window)"],
        "reduced": [], "assumed": []})
    with open(os.path.join(root, "benchmark/reference/global-tiny.py"), "w") as f:
        f.write("from benchmark.reference.serial import apply, global_window"
                "  # noqa: F401\n")
    mix = {"loop": loop, "items_per_rpc": 50, "connections": 8,
           "generator_procs": 2, "pool_rpcs_per_proc": 256, "base_seed": 11,
           "warm_s": 1.5, "rpc_timeout_s": 20, "grace_s": 5,
           "fill_connections": 8, "trace_drains": 5,
           "global_item_share": share,
           "check": {"sample_mod": 4, "hot_ranks": [1, 2],
                     "min_checked_decisions": 100,
                     "min_global_checked_decisions": 100,
                     "max_undecided_share": 0.02, "max_failed_share": 0.01}}
    write("benchmark/traffic/global-50.json", mix)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "global-tiny", "source": "a test",
                            "file": "benchmark/configs/global-tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "global-tiny.global-50",
                              "config": "global-tiny", "traffic": "global-50",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "mixed-10m-1chip.bulk-1000" in m.get("workloads", ()):
            m["workloads"].append("global-tiny.global-50")
    write("BENCHMARK.json", spec)
    return "global-tiny.global-50"


@contextlib.contextmanager
def time_limit(seconds):
    """A test's own time limit: SIGALRM raises in the test's thread."""
    def over(signum, frame):
        raise TimeoutError(f"the test's own limit of {seconds}s is spent")
    old = signal.signal(signal.SIGALRM, over)
    signal.alarm(int(seconds))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def keyspace(algorithms="parity", duration_ms=60000, population=100000,
             family=None):
    spec = {"population": population, "zipf_s": 1.1, "algorithms": algorithms,
            "limits": [10, 100, 1000, 10000], "duration_ms": duration_ms,
            "name": "n", "key_prefix": "k:"}
    if family:
        spec["global"] = {"keys": 64, "zipf_s": 1.1, "algorithms": family,
                          "limits": [10, 100, 1000, 10000],
                          "duration_ms": duration_ms, "name": "g",
                          "key_prefix": "t:"}
    return traffic.KeySpace(spec)


def simulate(ks, seed, fault=None, nops=20000, span_ms=70000, window_ms=21,
             lag_ms=21, share=0.3):
    """A history as the clients of a windowed server would record it: the
    reference applied window by window, every request of a window at the
    window's one timestamp.  Where the keyspace has a `global` family,
    `share` of the requests ask for its keys and are served by the rule
    (serial.global_window).  `fault`: None, or
      stale    every request of a window reads the row as it stood before it
      frozen   every fourth window leaves the state as it was
      altered  every fourth window alters one answer
    and for the GLOBAL family alone
      lossy    the summed hits of every fourth window never land
      late     a window's hits land two windows on
      serial   its requests are served serially, each showing its own hit."""
    rng = random.Random(seed)
    nrng = np.random.default_rng(seed)
    ranks = traffic.Zipf(ks.population, ks.zipf_s).draw(nrng, nops)
    if ks.glob is not None:
        g = nrng.random(nops) < share
        ranks[g] = ks.population + ks.glob.draw(nrng, int(g.sum()))
    arrive = np.sort(nrng.uniform(0, span_ms, nops)) + 1_700_000_000_000
    store = serial.SerialStore()
    cols = {k: [] for k in ("rank", "sent", "recv", "status", "remaining",
                            "reset", "hint")}
    i, last_now, windows = 0, 0, 0
    landing = []
    while i < nops:
        j = i
        while j < nops and arrive[j] < arrive[i] + window_ms:
            j += 1
        now = max(last_now, int(arrive[i] + window_ms + rng.random() * 3))
        last_now = now
        windows += 1
        bad = fault in ("frozen", "altered") and windows % 4 == 0
        before, saved, sums = {}, {}, {}
        for k in range(i, j):
            rk = int(ranks[k])
            args = (1, ks.limit(rk), ks.duration(rk), ks.algo(rk), now)
            if bad and fault == "frozen" and rk not in saved:
                old = store.rows.get(rk)
                saved[rk] = old.copy() if old else None
            if ks.is_global(rk) and fault != "serial":
                old = store.rows.get(rk)
                _, (resp,) = serial.global_window(
                    old.copy() if old else None, [args[:4]], now)
                sums.setdefault(rk, []).append(args[:4])
            elif fault == "stale":
                if rk not in before:
                    old = store.rows.get(rk)
                    before[rk] = old.copy() if old else None
                row = before[rk].copy() if before[rk] else None
                _, resp = serial.apply(row, *args)
                store.hit(rk, *args)
            else:
                resp = store.hit(rk, *args)
            if bad and fault == "altered":
                resp = (resp[0], resp[1], resp[2] + 1, resp[3])
                bad = False
            cols["rank"].append(rk)
            cols["sent"].append(arrive[k] - rng.random() * 2)
            cols["recv"].append(now + lag_ms + rng.random() * 3)
            cols["status"].append(resp[0])
            cols["remaining"].append(resp[2])
            cols["reset"].append(resp[3])
            cols["hint"].append(0)
        if fault == "late":
            landing.append(sums)
            sums = landing.pop(0) if len(landing) > 2 else {}
        if not (fault == "lossy" and windows % 4 == 0):
            for rk, asks in sums.items():
                store.rows[rk], _ = serial.global_window(
                    store.rows.get(rk), asks, now)
        for rk, old in saved.items():
            if old is None:
                store.rows.pop(rk, None)
            else:
                store.rows[rk] = old
        i = j
    return {k: np.asarray(v, dtype=np.float64 if k in ("sent", "recv")
                          else np.int64) for k, v in cols.items()}
