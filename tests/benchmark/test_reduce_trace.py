"""The trace reducer: against a trace recorded on the chip (three drains of
the 10,485,760-slot cell on a TPU v5 lite, cut down to the lines and host
annotations the reducer reads: tests/benchmark/recorded_3_drains.xplane.pb),
and against traces laid out by hand."""

import os

import pytest

from benchmark import reduce_trace as rt
from tests.benchmark import xplane_writer
from tests.benchmark.helpers import REPO

RECORDED = os.path.join(REPO, "tests", "benchmark", "recorded_3_drains.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return rt.reduce_planes(rt.read_planes(RECORDED))


def test_recorded_trace_is_small():
    assert os.path.getsize(RECORDED) < 200 * 1024


def test_recorded_busy_time_is_the_modules_line_alone(recorded):
    assert recorded["modules"] == 3 and recorded["devices"] == 1
    assert recorded["busy_s"] == pytest.approx(0.048902097)
    assert recorded["module_s"] == pytest.approx(0.048902097)
    assert recorded["window_s"] == pytest.approx(0.062229447)
    assert 0 < recorded["busy_s"] <= recorded["window_s"]


def test_recorded_breakdown_names_the_arena_sized_passes(recorded):
    names = [n for n, _ in recorded["device_ops"]]
    assert len(names) == 10 and all(len(n) <= 120 for n in names)
    assert names[0].startswith("fusion.") and "u32[10485760]" in names[0]
    secs = [s for _, s in recorded["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # self times: the enclosing while is not counted on top of its body
    assert sum(secs) <= recorded["module_s"]


def test_recorded_idle_gaps_are_named_by_host_annotations(recorded):
    gaps = dict(recorded["idle_gaps"])
    assert gaps["guber_fetch"] > 0 and "unattributed" in gaps
    assert sum(gaps.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)


def lay_out(tmp_path, planes):
    path = os.path.join(str(tmp_path), "t.xplane.pb")
    xplane_writer.write(path, planes)
    return rt.reduce_dir(str(tmp_path))


def test_lines_are_not_added_together(tmp_path):
    got = lay_out(tmp_path, [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_drain", 1000, 4000), ("jit_drain", 7000, 2000)]),
            ("XLA Ops", [("%while.1 = () while()", 1000, 4000),
                         ("%fusion.2 = u32[8]{0} fusion(u32[8]{0} %p)", 1500, 1000),
                         ("%fusion.3 = u32[8]{0} fusion(u32[8]{0} %p)", 7000, 2000)]),
            ("Async XLA Ops", [("%copy-start.1", 1000, 8000)])]),
        ("/host:CPU", [("python", [("guber_fetch", 5000, 1500),
                                   ("guber_drain", 6400, 300),
                                   ("something_else", 0, 20000)])])])
    assert got["busy_s"] == pytest.approx(6000e-9)
    assert got["window_s"] == pytest.approx(8000e-9)
    assert got["modules"] == 2
    ops = dict(got["device_ops"])
    assert ops["while.1 ()"] == pytest.approx(3000e-9)      # 4000 less its child
    assert ops["fusion.2 u32[8]"] == pytest.approx(1000e-9)
    assert "copy-start.1" not in ops
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"guber_fetch": 1500e-9, "guber_drain": 200e-9, "unattributed": 300e-9})


def test_busy_is_averaged_over_devices_and_window_is_the_fullest(tmp_path):
    got = lay_out(tmp_path, [
        ("/device:TPU:0", [("XLA Modules", [("m", 0, 1000)])]),
        ("/device:TPU:1", [("XLA Modules", [("m", 0, 3000)])])])
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(2000e-9)
    assert got["window_s"] == pytest.approx(3000e-9)


def test_four_chips_of_a_mesh(tmp_path):
    """One lockstep drain program on four chips: every plane runs the same
    three modules and one all-reduce, chip 2 is busy longest (it waits in the
    collective for the others).  Busy time and op self-times are means over
    the chips; the window and the named idle gaps are the fullest chip's."""
    def plane(i, module_ns, reduce_ns):
        mods = [("jit_drain", 1000 + 10000 * k, module_ns) for k in range(3)]
        ops = []
        for k in range(3):
            t = 1000 + 10000 * k
            ops += [("%fusion.7 = u32[2621440]{0} fusion(u32[8]{0} %p)", t,
                     module_ns - reduce_ns),
                    ("%all-reduce.1 = s64[1024]{0} all-reduce(s64[1024]{0} %d)",
                     t + module_ns - reduce_ns, reduce_ns)]
        return (f"/device:TPU:{i}", [("XLA Modules", mods), ("XLA Ops", ops)])
    got = lay_out(tmp_path, [
        plane(0, 4000, 100), plane(1, 5000, 1100), plane(2, 8000, 4100),
        plane(3, 7000, 3100),
        ("/host:CPU", [("python", [("guber_fetch", 9500, 1000),
                                   ("guber_pack", 19200, 600),
                                   ("guber_commit", 5500, 2000)])])])
    assert got["devices"] == 4 and got["modules"] == 3
    assert got["busy_s"] == pytest.approx((4000 + 5000 + 8000 + 7000) * 3 / 4 * 1e-9)
    # chip 2: first module at 1000, last ends at 21000 + 8000
    assert got["window_s"] == pytest.approx(28000e-9)
    assert got["module_s"] == pytest.approx(24000e-9)
    ops = dict(got["device_ops"])
    assert ops["all-reduce.1 s64[1024]"] == pytest.approx(
        (100 + 1100 + 4100 + 3100) * 3 / 4 * 1e-9)
    assert ops["fusion.7 u32[2621440]"] == pytest.approx(3900 * 3e-9)
    # chip 2 idles 9000-11000 and 19000-21000; chip 0 idles from 5000 on, and
    # `guber_commit` (5500-7500) names none of chip 2's gaps
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"guber_fetch": 1000e-9, "guber_pack": 600e-9, "unattributed": 2400e-9})
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["module_s"])


def test_a_trace_without_device_events_reduces_to_nothing(tmp_path):
    assert lay_out(tmp_path, [("/host:CPU", [("python", [("guber_drain", 0, 10)])])]) is None
    assert rt.reduce_dir(os.path.join(str(tmp_path), "nowhere")) is None


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.618 = (u32[10485760]{0:T(1024)}, u32[10485760]{0:T(1024)}) fusion(u32[1]{0} %a), kind=kCustom",
     "fusion.618 (u32[10485760],u32[10485760])"),
    ("%custom-call.16 = s64[1,10485760]{1,0:T(1,128)} custom-call(u32[1,10485760]{1,0} %b)",
     "custom-call.16 s64[1,10485760]"),
    ("jit_drain", "jit_drain")])
def test_short_names(hlo, want):
    assert rt.short_name(hlo) == want


def test_interval_arithmetic():
    assert rt.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert rt.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert rt.overlap([[0, 2], [3, 5]], [[1, 4]]) == 2
