"""The harness end to end on the CPU at a tiny size: the real daemon as a
child, load over the gRPC socket, the comparison with the reference.  The
test itself satisfies the device check (the command has no option for it)."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness
from tests.benchmark.helpers import CPU_CHILD, REPO, tiny_root


def any_device(info, cell):
    assert info["platform"] == "cpu"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return harness.Bench(tiny_root(tmp_path_factory.mktemp("root")))


def test_open_loop_cell_runs_and_is_correct(bench):
    line, m, client, ctx = harness.run_cell(
        bench, "mixed-10m-1chip.edge-2item", 3_000_000_011, 4.0, False,
        any_device, server_env=CPU_CHILD)
    assert line["correct"], line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"rpc_p50_ms", "rpc_p95_ms", "setup_s"} & set(
        bench.metrics_for("mixed-10m-1chip.edge-2item", "end_to_end")) | {"setup_s"}
    assert line["attempted"] == 320 and line["failed"] == 0
    assert line["metrics"]["rpc_p50_ms"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["compared"]["mismatched_keys"] == {"value": 0, "limit": 0,
                                                   "holds": "max"}
    assert line["compared"]["checked_decisions"]["value"] >= 100
    # latency runs from the due time: never shorter than send to reply
    assert client["rpc_p50_ms"] >= 0 and client["gen_late_p99_ms"] >= 0
    json.dumps(line)


def test_closed_loop_cell_with_trace_reports_layer_metrics(bench, monkeypatch):
    armed = []
    post = harness.http_post

    def spy(url, body, timeout=30.0):
        armed.append((time.time(), url))
        return post(url, body, timeout)
    monkeypatch.setattr(harness, "http_post", spy)
    line, m, client, ctx = harness.run_cell(
        bench, "mixed-10m-1chip.bulk-1000", 12, 4.0, True, any_device,
        server_env=CPU_CHILD, require_device_trace=False)
    assert line["correct"], line["compared"]
    # the profiler is armed only once the window has closed, the counters
    # have been read and the window's last reply is in: the instrument stays
    # out of what it measures
    (t_armed, url), = armed
    assert url.endswith("/v1/admin/profile")
    assert t_armed > m["window"][1] and t_armed > m["after"]["t"]
    assert t_armed > max(float(r["rpc_recv"].max()) for r in m["results"])
    got = line["metrics"]
    for name in ("admission_wait_ms.tput", "decisions_per_drain.tput",
                 "server_cpu_us_per_dec.tput", "closed_rpc_p50_ms.tput",
                 "host_pack_ms.tput", "window_turnaround_ms.tput"):
        assert got[name]["value"] > 0, name
    # a reader with nothing to read leaves its metric out: no device plane in
    # a CPU trace, so no roofline, and never a 0 in its place
    assert "window_roofline.tput" not in got
    assert "decisions_per_s" not in got
    assert client["decisions_per_s"] > 0


def test_altered_answer_in_the_real_daemon_is_not_correct(bench):
    line, *_ = harness.run_cell(
        bench, "mixed-10m-1chip.edge-2item", 13, 3.0, False, any_device,
        server_env=CPU_CHILD,
        server_argv=[sys.executable,
                     os.path.join(REPO, "tests", "benchmark", "faulty_serve.py")])
    assert not line["correct"]
    assert line["compared"]["mismatched_keys"]["value"] > 0


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, **CPU_CHILD)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mixed-10m-1chip.bulk-1000", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr
