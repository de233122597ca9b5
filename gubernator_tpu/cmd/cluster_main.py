"""Standalone dev cluster: six nodes on fixed ports, prints "Ready".

Equivalent of the reference's cmd/gubernator-cluster (main.go:29-55), used
by client development and the Python client tests (which wait for the
"Ready" line, python/tests/test_client.py:24-38 in the reference).

Run: python -m gubernator_tpu.cmd.cluster_main
"""

from __future__ import annotations

import asyncio

from gubernator_tpu import cluster as cluster_mod

ADDRESSES = [f"127.0.0.1:{port}" for port in range(9090, 9096)]


async def _amain() -> None:
    c = await cluster_mod.start_with(ADDRESSES)
    print("Ready", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await c.stop()


def main() -> None:
    try:
        asyncio.run(_amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
