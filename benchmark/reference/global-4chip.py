"""The plain reference of configuration `global-4chip`: the serial token
and leaky semantics for the BATCHING family, and the window rule (stale,
then consistent) for the GLOBAL family.  Imports nothing of the program."""

from benchmark.reference.serial import (LEAKY_BUCKET, TOKEN_BUCKET,  # noqa: F401
                                        apply, global_window)

ALGORITHMS = (TOKEN_BUCKET, LEAKY_BUCKET)
