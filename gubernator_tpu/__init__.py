"""gubernator-tpu: a TPU-native distributed rate-limiting framework.

A ground-up redesign of Gubernator (reference: /root/reference, mailgun/gubernator
v0.5.0) for TPU hardware.  Where the reference keeps each rate-limit counter in a
per-node LRU map mutated under a mutex (reference cache/lru.go:30,
algorithms.go:24-186), this framework keeps the whole keyspace as dense
structure-of-arrays state resident in TPU HBM, evaluates every batching window
with one fused XLA computation (ops/kernel.py), partitions keys over a
`jax.sharding.Mesh` axis instead of a consistent-hash ring of Go processes
(reference hash.go:28-96), and replaces the GLOBAL behavior's async gRPC hit
broadcast (reference global.go:72-232) with a `lax.psum` over the mesh axis.

Rate-limit quantities (hits/limit/remaining) and millisecond-epoch timestamps
are int64 on the wire (reference proto/gubernator.proto:97-143), so the device
state is int64 as well; we therefore enable JAX x64 support at import time,
before any tracing can happen.
"""

import jax

jax.config.update("jax_enable_x64", True)

from gubernator_tpu.api.types import (  # noqa: E402
    Algorithm,
    Behavior,
    Status,
    RateLimitReq,
    RateLimitResp,
    HealthCheckResp,
    Second,
    Minute,
    Hour,
    Millisecond,
)

__version__ = "0.1.0"

__all__ = [
    "Algorithm",
    "Behavior",
    "Status",
    "RateLimitReq",
    "RateLimitResp",
    "HealthCheckResp",
    "Second",
    "Minute",
    "Hour",
    "Millisecond",
    "__version__",
]
