"""A configuration, a traffic mix, a cell and a counter-backed layer metric
are each added by adding files and entries, with no existing file edited."""

import json
import os

from benchmark import control, harness
from tests.benchmark.helpers import tiny_root


def test_a_later_pr_adds_one_of_each_without_editing_a_file(tmp_path):
    root = tiny_root(tmp_path)
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()

    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("benchmark/configs/token-tiny.json", {
        "name": "token-tiny", "chips": 1, "daemon_env": {},
        "keyspace": {"population": 500, "zipf_s": 1.1, "algorithms": "token",
                     "limits": [10, 100], "duration_ms": 60000,
                     "name": "new", "key_prefix": "t:"},
        "fill_keys": 100, "guarantees": ["exact"], "reduced": [], "assumed": []})
    with open(os.path.join(root, "benchmark/reference/token-tiny.py"), "w") as f:
        f.write("from benchmark.reference.serial import apply\n")
    write("benchmark/traffic/quad-5item.json", {
        "loop": "open", "items_per_rpc": 5, "connections": 4,
        "generator_procs": 2, "pool_rpcs_per_proc": 128, "base_seed": 9,
        "warm_s": 1, "rpc_timeout_s": 10, "grace_s": 5, "fill_connections": 4,
        "trace_drains": 3,
        "check": {"sample_mod": 1, "hot_ranks": [], "min_checked_decisions": 50,
                  "max_undecided_share": 0.02, "max_failed_share": 0.001}})
    write("benchmark/cells/token-tiny.quad-5item.json", {"rate_rps": 50})
    write("benchmark/layer_metrics/control_windows.json", {
        "layer": "window fill (core/batcher.py, core/pipeline.py)",
        "unit": "windows", "source": "prom",
        "read": {"prom": "guber_tpu_windows_total"}})
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "token-tiny", "source": "a test",
                            "file": "benchmark/configs/token-tiny.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "token-tiny.quad-5item",
                              "config": "token-tiny", "traffic": "quad-5item",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("rpc_p50_ms", "rpc_p95_ms"):
            m["workloads"].append("token-tiny.quad-5item")
    spec["per_layer"].append({
        "name": "control_windows.lat", "unit": "windows", "better": "higher",
        "source": "program_counter", "moves": "rpc_p50_ms",
        "layer": "window fill (core/batcher.py, core/pipeline.py)",
        "workloads": ["token-tiny.quad-5item"]})
    write("BENCHMARK.json", spec)

    bench = harness.Bench(root)
    line, *_ = harness.run_cell(
        bench, "token-tiny.quad-5item", 31, 2.0, True, control.accept_control,
        server_argv=control.control_argv("sound"), require_device_trace=False)
    assert line["correct"], line["compared"]
    assert line["attempted"] == 100
    assert line["metrics"]["control_windows.lat"]["value"] > 0
    assert "admission_wait_ms.lat" not in line["metrics"]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p
