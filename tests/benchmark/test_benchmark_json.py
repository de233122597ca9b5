"""BENCHMARK.json against the contract's limits, and every name it gives
against the files the harness looks for."""

import json
import os
import re

import pytest

from benchmark import harness
from tests.benchmark.helpers import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
BENCH = harness.Bench()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 24
    assert (2 + 14 * cells) * (SPEC["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert SPEC["paths"] == ["benchmark", "tests/benchmark"]
    assert all(not w.startswith("/") and ".." not in w for w in SPEC["command"])


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if m in SPEC["end_to_end"]:
        assert set(m) <= allowed | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= allowed | {"layer", "moves"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)
        spec = BENCH.layer_file(m["name"])
        assert spec["unit"] == m["unit"] and spec["layer"] == m["layer"]
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["source"]) <= 200
    assert c["file"].startswith("benchmark/")
    with open(os.path.join(REPO, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == c["reduced"] and cfg["guarantees"] and cfg["assumed"]
    assert cfg["chips"] == 1 and cfg["keyspace"]["zipf_s"] == 1.1
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    assert callable(BENCH.reference(c["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_entry_and_its_files_are_found_by_name(cell):
    w = BENCH.workloads[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    got = BENCH.cell(cell)
    mix = got["mix"]
    assert mix["loop"] in ("open", "closed")
    if mix["loop"] == "open":
        assert isinstance(mix["rate_rps"], (int, float)) and mix["rate_rps"] > 0
        assert "decisions_per_s" not in BENCH.metrics_for(cell, "end_to_end")
        assert "rpc_p50_ms" in BENCH.metrics_for(cell, "end_to_end")
    else:
        assert BENCH.metrics_for(cell, "end_to_end") == ["decisions_per_s", "setup_s"]
    assert "setup_s" in BENCH.metrics_for(cell, "end_to_end")
    assert len(BENCH.metrics_for(cell, "per_layer")) >= 1


def test_no_file_under_benchmark_lists_cells():
    for base, _, files in os.walk(os.path.join(REPO, "benchmark")):
        if "findings" in base or "__pycache__" in base:
            continue
        for f in files:
            if f.endswith((".json", ".py")) and "cells" not in base:
                with open(os.path.join(base, f)) as fh:
                    text = fh.read()
                for cell in CELLS:
                    assert f'"{cell}"' not in text, (f, cell)


def test_every_benchmark_file_name_is_made_of_name_characters():
    for path in SPEC["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_peaks_name_their_source_and_unknown_devices_fail():
    assert "819 GB/s" in BENCH.peaks("TPU v5 lite")["source"]
    with pytest.raises(harness.BenchError):
        BENCH.peaks("TPU v9 imaginary")
