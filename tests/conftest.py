"""Test env: force an 8-virtual-device CPU backend BEFORE jax initializes.

Mirrors the reference's multi-node-in-one-process testing strategy
(cluster/cluster.go:70-118): multi-shard = multi-device simulation on the CPU
backend, per SURVEY.md §4.

The tests are CPU tests whatever JAX_PLATFORMS says: the platform is pinned
through jax.config before any backend can initialize.  XLA_FLAGS must be set
before first backend init, which this top-level conftest guarantees for all
test modules.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
