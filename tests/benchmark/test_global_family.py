"""A GLOBAL deployment through the whole harness, against the reference in
the daemon's place: the rule served soundly is `correct` with the floor on
verified GLOBAL decisions met; each way of breaking the stale-then-consistent
guarantee comes out `correct` false, by the GLOBAL family's keys."""

import pytest

from benchmark import control, harness
from tests.benchmark.helpers import MESH_ENV, add_global_deployment, tiny_root


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("root"))
    add_global_deployment(root, daemon_env=MESH_ENV)
    return harness.Bench(root)


CELL = "global-tiny.global-50"


def run(bench, mode, seed):
    line, *_ = harness.run_cell(bench, CELL, seed, 3.0, False,
                                control.accept_control,
                                server_argv=control.control_argv(mode))
    return line


@pytest.mark.parametrize("seed", [3_000_000_062])
def test_the_rule_served_soundly_comes_out_correct(bench, seed):
    line = run(bench, "sound", seed)
    assert line["correct"], line["compared"]
    fam = line["run"]["families"]
    assert fam["global_checked_keys"] == 64 and fam["tainted_keys"] == 0
    assert fam["global_checked_decisions"] == fam["global_followed_decisions"]
    assert line["compared"]["global_checked_decisions"]["value"] > 5000
    assert line["run"]["errors"] == {}


@pytest.mark.parametrize("mode", ["lossy", "late", "serial"])
@pytest.mark.parametrize("seed", [3_000_000_064])
def test_a_broken_global_guarantee_comes_out_not_correct(bench, mode, seed):
    line = run(bench, mode, seed)
    assert not line["correct"]
    fam = line["run"]["families"]
    assert line["compared"]["mismatched_keys"]["value"] \
        == fam["global_mismatched_keys"] >= 32


def test_the_stale_control_fails_the_serial_family_beside_it(bench):
    # GLOBAL items are served by the rule in every mode but `serial`; the
    # BATCHING keys of the same cell are what `stale` breaks
    line = run(bench, "stale", 65)
    assert not line["correct"]
    fam = line["run"]["families"]
    assert fam["mismatched_keys"] > 0 == fam["global_mismatched_keys"]
    assert fam["global_checked_keys"] == 64
