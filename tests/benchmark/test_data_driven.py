"""A configuration, a traffic mix, a cell and a counter-backed layer metric
are each added by adding files and entries, with no existing file edited."""

import json
import os

from benchmark import control, harness
from tests.benchmark.helpers import MESH_ENV, add_global_deployment, tiny_root


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

    # and a GLOBAL deployment on a mesh of chips: a `global` block in the
    # keyspace, the mesh environment stated with placeholders, a reference
    # that exports the rule, a mix with a share of GLOBAL items, a cell
    cell = add_global_deployment(root, daemon_env=MESH_ENV)
    bench = harness.Bench(root)
    line, *_ = harness.run_cell(
        bench, cell, 3_000_000_032, 3.0, False, control.accept_control,
        server_argv=control.control_argv("sound"))
    assert line["correct"], line["compared"]
    floor = line["compared"]["global_checked_decisions"]
    assert floor["value"] >= floor["limit"] == 100 and floor["holds"] == "min"
    assert line["run"]["families"]["global_checked_keys"] == 64
    assert list(line)[-1] == "compared"
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_what_a_global_deployment_lacks_is_said_before_any_run(tmp_path):
    root = tiny_root(tmp_path)
    cell = add_global_deployment(root)

    def rewrite(rel, change):
        path = os.path.join(root, rel)
        obj = json.load(open(path))
        change(obj)
        with open(path, "w") as f:
            json.dump(obj, f)
    # a reference that does not state the rule
    with open(os.path.join(root, "benchmark/reference/global-tiny.py"), "w") as f:
        f.write("from benchmark.reference.serial import apply\n")
    try:
        harness.Bench(root).cell(cell)
        raise AssertionError("a reference without the rule was accepted")
    except harness.BenchError as e:
        assert "global_window" in str(e)
    # a share of GLOBAL items, and no family to draw them from
    with open(os.path.join(root, "benchmark/reference/global-tiny.py"), "w") as f:
        f.write("from benchmark.reference.serial import apply, global_window\n")
    rewrite("benchmark/configs/global-tiny.json",
            lambda cfg: cfg["keyspace"].pop("global"))
    try:
        harness.Bench(root).cell(cell)
        raise AssertionError("a share without the block was accepted")
    except harness.BenchError as e:
        assert "global_item_share" in str(e)
    # a mix without the key means a share of 0: the cell loads
    rewrite("benchmark/traffic/global-50.json",
            lambda mix: mix.pop("global_item_share"))
    assert harness.Bench(root).cell(cell)["mix"]["loop"] == "closed"


def test_daemon_env_placeholders_are_filled_per_run(tmp_path):
    cfg = {"daemon_env": dict(MESH_ENV, GUBER_TPU_BATCH_PER_SHARD="256")}
    a = harness.Server(cfg, str(tmp_path), [])
    b = harness.Server(cfg, str(tmp_path), [])
    assert a.fill_in("{grpc}") == a.grpc != b.grpc
    assert a.fill_in("x,{http}") == "x," + a.http
    port = a.fill_in("127.0.0.1:{port}").rsplit(":", 1)[1]
    assert port.isdigit() and port not in (a.grpc.rsplit(":", 1)[1],
                                           a.http.rsplit(":", 1)[1])
    assert a.fill_in("256") == "256" and a.fill_in(16384) == "16384"
    try:
        a.fill_in("{nope}")
        raise AssertionError("an unknown placeholder was passed on")
    except harness.BenchError as e:
        assert "{nope}" in str(e)
    a.log.close()
    b.log.close()
