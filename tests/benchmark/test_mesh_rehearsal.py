"""The served mesh path, rehearsed on the CPU: the real daemon as the
harness's child on four virtual devices, in one-process mesh mode stated
wholly by a configuration's `daemon_env` with the placeholders, under a tiny
GLOBAL mix.  It asserts what the benchmark owns: the daemon became ready as
a mesh of four shards, GLOBAL items were sent and answered without error,
the comparison sorted them into the GLOBAL family and reached a verdict on
each.  Whether the program comes out `correct` there is a finding, printed
and not asserted (PERF.md, Open questions, row 1)."""

import json

from benchmark import harness
from tests.benchmark.helpers import (CPU_CHILD, MESH_ENV, add_global_deployment,
                                     time_limit, tiny_root)

FOUR_DEVICES = dict(CPU_CHILD,
                    XLA_FLAGS="--xla_force_host_platform_device_count=4")


def test_the_daemon_serves_a_global_mix_as_a_mesh_of_four(tmp_path):
    root = tiny_root(tmp_path)
    # 64 first-seen GLOBAL keys queue behind the registrar's lock: with the
    # daemon's default GUBER_GLOBAL_TIMEOUT of 0.5 s some of them are refused.
    # A 20 ms tick: at the default 0.5 ms the tick loop never sleeps, and this
    # test would take a core from the tests that run beside it
    cell = add_global_deployment(
        root, daemon_env=dict(MESH_ENV, GUBER_GLOBAL_TIMEOUT="30",
                              GUBER_BATCH_WAIT="0.02"))
    seen = {}

    def four_cpu_devices(info, cell):
        seen.update(info)
        assert info["platform"] == "cpu" and info["count"] == 4
    with time_limit(280):
        line, m, client, ctx = harness.run_cell(
            harness.Bench(root), cell, 3_000_000_071, 3.0, False,
            four_cpu_devices, server_env=FOUR_DEVICES)
    run = line["run"]
    # the daemon's own report: a mesh, four shards, served in lockstep
    assert seen["count"] == 4
    assert "mesh mode: 1 processes, 4 global shards" in run["mesh"]
    debug = m["after"]["debug"]
    assert debug["mesh_mode"] is True and debug["standalone"] is False
    assert debug["engine"]["capacity"] >= 4 * 4096
    # GLOBAL items were sent, and answered without `error`
    assert line["failed"] == 0 and client["decisions"] > 0
    assert run["errors"] == {}, run["errors"]
    fam = run["families"]
    assert fam["tainted_keys"] == 0
    assert fam["global_followed_decisions"] > 1000
    # every key of the GLOBAL family got a verdict (a witness, none, or
    # undecided), and so did the serial family's
    keys = {int(r) for r in ctx["ops"]["rank"]}
    assert sum(r > 3000 for r in keys) == 64
    assert (fam["global_checked_keys"] + fam["global_mismatched_keys"]
            + fam["global_undecided_keys"]) == 64
    assert (fam["checked_keys"] + fam["mismatched_keys"]
            + fam["undecided_keys"]) == len(keys)
    assert "global_checked_decisions" in line["compared"]
    # the finding, for the report: which family mismatched, and the lag of
    # the lockstep clock behind the clients'
    print("mesh rehearsal on the CPU:", json.dumps({
        "correct": line["correct"], "compared": line["compared"],
        "families": fam, "told_lag": run["told_lag"],
        "decisions_per_s": client["decisions_per_s"],
        "ready_s": run["ready_s"], "fill_s": run["fill_s"]}))
    assert run["told_lag"]["told"] > 0
