"""The control and the planted faults, through the whole harness: the
reference in the program's place, one guarantee broken, `correct` false."""

import pytest

from benchmark import control, harness
from tests.benchmark.helpers import tiny_root


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return harness.Bench(tiny_root(tmp_path_factory.mktemp("root")))


def run(bench, cell, mode, seed):
    line, *_ = harness.run_cell(bench, cell, seed, 3.0, False,
                                control.accept_control,
                                server_argv=control.control_argv(mode))
    return line


CELLS = ["mixed-10m-1chip.bulk-1000", "mixed-10m-1chip.edge-2item",
         "leaky-1m-1chip.edge-2item"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_served_as_it_is_comes_out_correct(bench, cell):
    line = run(bench, cell, "sound", 21)
    assert line["correct"], line["compared"]


@pytest.mark.parametrize("seed", [22, 3_000_000_023])
@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(bench, cell, seed):
    line = run(bench, cell, "stale", seed)
    assert not line["correct"]
    assert line["compared"]["mismatched_keys"]["value"] > 0


@pytest.mark.parametrize("mode", ["frozen", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_each_planted_fault_comes_out_not_correct(bench, cell, mode):
    line = run(bench, cell, mode, 23)
    assert not line["correct"]
    assert line["compared"]["mismatched_keys"]["value"] > 0
