"""The cell `global-4chip.global-open-1000` from its committed files, cut to
a size the CPU holds: the real daemon in one-process mesh mode on four
virtual devices, stated wholly by the configuration's `daemon_env` (the
arena, the lanes, the mesh placeholders; every other setting the daemon's
default), under the cell's open-loop GLOBAL mix.  The program has to come
out `correct` in both families with told timestamps inside a turnaround;
the reference with each guarantee broken has to come out not correct."""

import json
import os

import pytest

from benchmark import control, harness
from tests.benchmark.helpers import CPU_CHILD, REPO, time_limit, tiny_root

CELL = "global-4chip.global-open-1000"
FOUR_DEVICES = dict(CPU_CHILD,
                    XLA_FLAGS="--xla_force_host_platform_device_count=4")


def rewrite(root, rel, change):
    path = os.path.join(root, rel)
    with open(path) as f:
        obj = json.load(f)
    change(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The committed files of the cell, each number cut, each shape kept."""
    root = tiny_root(tmp_path_factory.mktemp("root"))
    with open(os.path.join(REPO, "benchmark/configs/global-4chip.json")) as f:
        committed = json.load(f)

    def config(cfg):
        # tiny_root put a one-chip daemon_env in: the mesh is the
        # committed one's, the sizes a shard of the tiny arena
        cfg["daemon_env"] = dict(committed["daemon_env"],
                                 GUBER_TPU_CAPACITY_PER_SHARD="4096",
                                 GUBER_TPU_BATCH_PER_SHARD="256")
        cfg["keyspace"]["global"]["keys"] = 64
        cfg["fill_keys"] = 1000
    rewrite(root, "benchmark/configs/global-4chip.json", config)

    def mix(m):
        m["check"].update(sample_mod=4, hot_ranks=[1, 2],
                          global_sample_mod=1, min_checked_decisions=100,
                          min_global_checked_decisions=100)
    rewrite(root, "benchmark/traffic/global-open-1000.json", mix)
    rewrite(root, f"benchmark/cells/{CELL}.json",
            lambda c: c.update(rate_rps=60))
    b = harness.Bench(root)
    env = b.cell(CELL)["config"]["daemon_env"]
    assert sorted(env) == ["GUBER_MESH_COORDINATOR", "GUBER_MESH_NUM_PROCESSES",
                           "GUBER_MESH_PEERS", "GUBER_MESH_PROCESS_ID",
                           "GUBER_TPU_BATCH_PER_SHARD",
                           "GUBER_TPU_CAPACITY_PER_SHARD"]
    return b


def test_the_capture_fits_the_traffic_the_trace_phase_offers():
    """`harness.trace_after` offers an open-loop cell's traffic for `warm_s`
    + 5 s and posts the capture at `warm_s`, so the drains the capture
    counts have to come out of those 5 s.  A lockstep drain of this cell
    takes 0.06-0.125 s under the profiler (31 ms without; PERF.md section
    6, PR 32): 40 drains filled the 5 s to the brim and the driver's traced
    run ended with no file written."""
    with open(os.path.join(REPO, "benchmark/traffic/global-open-1000.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "open"
    assert mix["trace_drains"] * 0.125 <= 5.0 / 2


def test_the_daemon_serves_the_cell_as_a_mesh_of_four_and_is_correct(bench):
    seen = {}

    def four_cpu_devices(info, cell):
        seen.update(info)
        assert info["platform"] == "cpu" and info["count"] == 4
    with time_limit(280):
        line, m, client, ctx = harness.run_cell(
            bench, CELL, 3_000_000_321, 4.0, False, four_cpu_devices,
            server_env=FOUR_DEVICES)
    run = line["run"]
    assert "mesh mode: 1 processes, 4 global shards" in run["mesh"]
    debug = m["after"]["debug"]
    assert debug["mesh_mode"] is True and debug["pipeline"]["lockstep"]
    # the daemon's defaults held: no GLOBAL item was answered with an
    # error, nothing failed, nothing was shed
    assert run["errors"] == {}, run["errors"]
    assert line["failed"] == 0 and line["attempted"] > 100
    # correct, in both families, with the floors met
    assert line["correct"], line["compared"]
    fam = run["families"]
    assert fam["mismatched_keys"] == 0 == fam["global_mismatched_keys"]
    assert fam["tainted_keys"] == 0
    assert fam["global_checked_keys"] == 64
    cmp_ = line["compared"]
    assert cmp_["global_checked_decisions"]["value"] \
        > cmp_["global_checked_decisions"]["limit"]
    assert cmp_["checked_decisions"]["value"] \
        > cmp_["checked_decisions"]["limit"]
    # the lockstep clock tells the time: an answer is received within a
    # turnaround of the timestamp it tells (the parent: minutes)
    lag = run["told_lag"]
    assert lag["told"] > 0
    for k in ("first_fifth_ms", "last_fifth_ms"):
        assert lag[k] is not None and -2 <= lag[k] < 1000, lag
    # whole RPCs rode the lockstep lane, GLOBAL items with them
    state = debug["pipeline"]["lockstep_state"]
    lanes = state["decisions_by_lane"]
    assert lanes["raw"] > 0.9 * sum(lanes.values()), lanes
    assert state["global_items"]["staged"] > 1000
    assert state["ticks"]["idle"] > state["ticks"]["drain"] > 0
    # an open-loop cell reports its median, and the new counters read
    assert line["metrics"]["rpc_p50_ms"]["value"] > 0
    ctx.update(peaks={"hbm_bytes_per_s": 1.0},
               trace={"module_s": 1.0, "modules": 1, "devices": 4})
    for name in bench.metrics_for(CELL, "per_layer"):
        if name.split(".")[0] in ("tick_lag_ms", "idle_tick_pct",
                                  "raw_lane_pct", "global_deferred_pct",
                                  "global_decisions_per_drain",
                                  "mesh_window_roofline"):
            v = harness.evaluate(bench.layer_file(name)["read"], ctx)
            assert v is not None and v >= 0, name
    print("the cell on the CPU:", json.dumps({
        "compared": line["compared"], "told_lag": lag, "state": state,
        "rpc_p50_ms": client["rpc_p50_ms"], "ready_s": run["ready_s"],
        "fill_s": run["fill_s"]}))


@pytest.mark.parametrize("mode", ["lossy", "late", "serial", "stale"])
def test_the_reference_with_a_guarantee_broken_is_not_correct(bench, mode):
    with time_limit(200):
        line, *_ = harness.run_cell(
            bench, CELL, 3_000_000_330, 3.0, False, control.accept_control,
            server_argv=control.control_argv(mode))
    assert not line["correct"], line["compared"]
    assert line["compared"]["mismatched_keys"]["value"] > 0


def test_the_reference_served_soundly_is_correct(bench):
    with time_limit(200):
        line, *_ = harness.run_cell(
            bench, CELL, 3_000_000_331, 3.0, False, control.accept_control,
            server_argv=control.control_argv("sound"))
    assert line["correct"], line["compared"]
    assert line["run"]["families"]["global_checked_keys"] == 64
