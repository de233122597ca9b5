"""Run a cell against the control: the reference with a guarantee broken.

    python benchmark/control.py --workload <cell> --mode stale --seeds 1,2,3

The cell's own traffic, connections, fill and comparison, with
benchmark/control_server.py in the daemon's place.  `stale` is the control of
"How correct is decided": its runs have to come out `correct: false`.  `frozen`
and `altered` are the planted faults; `lossy`, `late` and `serial` break the
GLOBAL family's guarantee (control_server.py); `sound` has to come out true.
One JSON line per seed, the compared numbers beside their limits.  Not part of
a check.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def control_argv(mode, tick_ms=5.0):
    return [sys.executable,
            os.path.join(harness.HERE, "control_server.py"),
            "--mode", mode, "--tick-ms", str(tick_ms)]


def accept_control(info, cell):
    if info["platform"] != "control":
        raise harness.BenchError("this is not the control server")


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--mode", default="stale")
    p.add_argument("--seeds", default="1,2,3")
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--tick-ms", type=float, default=5.0)
    a = p.parse_args(argv)
    bench = harness.Bench()
    for seed in a.seeds.split(","):
        line, *_ = harness.run_cell(
            bench, a.workload, int(seed), a.seconds, False, accept_control,
            server_argv=control_argv(a.mode, a.tick_ms))
        print(json.dumps({"workload": a.workload, "mode": a.mode,
                          "seed": int(seed), "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "decisions": line["run"]["decisions"],
                          "compared": line["compared"]}), flush=True)


if __name__ == "__main__":
    main()
