"""Plain reference of configuration `leaky-1m-1chip`.

The configuration serves LEAKY_BUCKET keys; what each request must
answer is the serial per-key application in benchmark/reference/serial.py,
restricted here to the algorithms this configuration's keyspace draws.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.reference import serial  # noqa: E402

ALGORITHMS = (serial.LEAKY_BUCKET,)


def apply(row, hits, limit, duration, algo, now):
    if algo not in ALGORITHMS:
        raise ValueError(f"`leaky-1m-1chip` has no key of algorithm {algo}")
    return serial.apply(row, hits, limit, duration, algo, now)
