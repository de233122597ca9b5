"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on: the
real daemon as a child that holds the chip, load from client processes over
the gRPC socket, one JSON object as the last line of standard output.  It
fails, printing no result, unless the daemon reports a TPU whose kind is in
benchmark/peaks.json and at least as many chips as the cell asks for.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def tpu_only(bench):
    def device_ok(info, cell):
        if info["platform"] != "tpu":
            raise harness.BenchError(
                f"JAX found no accelerator: platform {info['platform']!r}")
        bench.peaks(info["kind"])
        if info["count"] < cell["chips"]:
            raise harness.BenchError(
                f"the cell needs {cell['chips']} chips, JAX sees {info['count']}")
    return device_ok


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    bench = harness.Bench()
    try:
        line, *_ = harness.run_cell(bench, a.workload, a.seed, a.seconds,
                                    bool(a.trace), tpu_only(bench))
    except harness.BenchError as e:
        harness.say(f"benchmark: {e}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
