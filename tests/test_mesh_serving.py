"""End-to-end mesh-mode serving: two full gRPC nodes, one SPMD arena.

Each child process runs the real serving stack — Instance with the
MeshShardPicker, lockstep window clock, gRPC server — joined into one
8-shard mesh.  A gRPC client drives node A:

  * keys owned by node B's shards forward over gRPC and land in B's
    lockstep windows (response annotated with the owner's address);
  * a pre-registered GLOBAL key hit on node A becomes visible in node B's
    replica purely through the in-mesh psum (no GlobalManager gRPC runs);
  * shutdown drains on an agreed final tick so no host hangs on a
    collective the other never issues.
"""

import json
import os
import socket
import subprocess
import sys

T0 = 1_700_000_000_000


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _child(pid, coord_port, grpc0, grpc1, ctrl_port, stack=1):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["GUBER_MESH_COORDINATOR"] = f"127.0.0.1:{coord_port}"
    os.environ["GUBER_MESH_NUM_PROCESSES"] = "2"
    os.environ["GUBER_MESH_PROCESS_ID"] = str(pid)
    import jax

    jax.config.update("jax_platforms", "cpu")

    import asyncio

    from gubernator_tpu.parallel.distributed import (
        global_mesh,
        initialize_from_env,
        owning_process,
    )

    assert initialize_from_env()

    from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
    from gubernator_tpu.client import AsyncClient
    from gubernator_tpu.config import BehaviorConfig, Config, EngineConfig
    from gubernator_tpu.core.engine import shard_of
    from gubernator_tpu.core.service import Instance
    from gubernator_tpu.discovery.static import StaticPool
    from gubernator_tpu.server import GrpcServer

    addrs = [f"127.0.0.1:{grpc0}", f"127.0.0.1:{grpc1}"]
    me = addrs[pid]
    mesh = global_mesh()

    async def main():
        inst = Instance(
            Config(
                behaviors=BehaviorConfig(batch_wait=0.05,
                                         lockstep_stack=stack),
                engine=EngineConfig(
                    capacity_per_shard=64, batch_per_shard=16,
                    global_capacity=16, global_batch_per_shard=8,
                    max_global_updates=8),
                advertise_address=me,
            ),
            mesh=mesh,
            mesh_peers=addrs,
        )
        epoch = inst.batcher.clock.now_ms()
        inst.engine.warmup(now=epoch, k_stack=stack)
        inst.engine.register_global_keys(
            [("msrv_gbl_g", 100, 60_000, Algorithm.TOKEN_BUCKET)], now=epoch)

        grpc_srv = GrpcServer(inst, me)
        await grpc_srv.start()
        pool = StaticPool(addrs, me, inst.set_peers)
        await pool.start()
        inst.batcher.start_lockstep()

        # control channel: child 1 listens, child 0 connects
        if pid == 1:
            server = await asyncio.start_server(
                lambda r, w: handle_ctrl(r, w), "127.0.0.1", ctrl_port)
            done = asyncio.get_running_loop().create_future()

            async def handle_ctrl(reader, writer):
                writer.write(b"READY\n")
                await writer.drain()
                while True:
                    line = (await reader.readline()).decode().strip()
                    if line.startswith("CHECK"):
                        _, name, key, limit, expect = line.split()
                        probe = RateLimitReq(
                            name=name, unique_key=key, hits=0,
                            limit=int(limit), duration=60_000,
                            behavior=Behavior.GLOBAL)
                        client = AsyncClient(me)
                        r = (await client.get_rate_limits([probe]))[0]
                        ok = r.remaining == int(expect) and not r.error
                        writer.write(
                            f"{'OK' if ok else f'BAD {r}'}\n".encode())
                        await writer.drain()
                    elif line.startswith("STOP"):
                        _, t = line.split()
                        # the compact lockstep drain (not the legacy full
                        # stack) must have carried the forwarded regular
                        # traffic that landed on this node
                        pipe = inst.batcher.pipeline
                        assert pipe is not None and pipe.lockstep
                        assert pipe.lanes_staged > 0, \
                            "mesh drain never staged a lane"
                        inst.batcher.stop_at_tick = int(t)
                        writer.write(b"STOPPING\n")
                        await writer.drain()
                        done.set_result(int(t))
                        return

            stop_tick = await done
            while inst.batcher.clock.tick < stop_tick:
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.3)  # let in-flight responses drain
            server.close()
            print("child 1: OK", flush=True)
            return

        # ---- child 0: the driver
        for _ in range(200):
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", ctrl_port)
                break
            except OSError:
                await asyncio.sleep(0.1)
        assert (await reader.readline()).strip() == b"READY"

        client = AsyncClient(me)
        # one key owned locally, one owned by B
        local_key = remote_key = None
        for i in range(300):
            k = f"k{i}"
            owner = owning_process(shard_of("msrv_" + k, 8), mesh)
            if owner == 0 and local_key is None:
                local_key = k
            if owner == 1 and remote_key is None:
                remote_key = k
            if local_key and remote_key:
                break

        for key, forwarded in ((local_key, False), (remote_key, True)):
            seq = []
            for _ in range(3):
                r = (await client.get_rate_limits([RateLimitReq(
                    name="msrv", unique_key=key, hits=1, limit=2,
                    duration=60_000)]))[0]
                seq.append((r.remaining, r.status))
                assert not r.error, r.error
                if forwarded:
                    assert r.metadata.get("owner") == addrs[1], r.metadata
            assert seq == [(1, 0), (0, 0), (0, 1)], (key, seq)

        # GLOBAL: hit on A, observe on B via the psum
        g = RateLimitReq(name="msrv_gbl", unique_key="g", hits=2, limit=100,
                         duration=60_000, behavior=Behavior.GLOBAL)
        r = (await client.get_rate_limits([g]))[0]
        assert not r.error, r.error
        await asyncio.sleep(0.5)  # a few ticks: psum applies the hits
        writer.write(b"CHECK msrv_gbl g 100 98\n")
        await writer.drain()
        resp = (await reader.readline()).decode().strip()
        assert resp == "OK", f"B's replica disagrees: {resp}"

        # DYNAMIC GLOBAL: a key never pre-registered anywhere — first use
        # routes through the registrar's two-phase flow and then serves,
        # and the hits become visible on B purely via the psum
        dg = RateLimitReq(name="msrv_dyn", unique_key="d", hits=3, limit=50,
                          duration=60_000, behavior=Behavior.GLOBAL)
        r = (await client.get_rate_limits([dg]))[0]
        assert not r.error, r.error
        assert r.remaining == 47, r
        await asyncio.sleep(0.5)
        writer.write(b"CHECK msrv_dyn d 50 47\n")
        await writer.drain()
        resp = (await reader.readline()).decode().strip()
        assert resp == "OK", f"B's dynamic-global replica disagrees: {resp}"

        # the compact lockstep drain must have carried the local regular
        # traffic (the legacy stack only carries GLOBAL + fallbacks now)
        pipe = inst.batcher.pipeline
        assert pipe is not None and pipe.lockstep
        assert pipe.lanes_staged > 0, "mesh drain never staged a lane"
        assert pipe.decisions_staged >= pipe.lanes_staged > 0

        stop_tick = inst.batcher.clock.tick + 40
        writer.write(f"STOP {stop_tick}\n".encode())
        await writer.drain()
        assert (await reader.readline()).strip() == b"STOPPING"
        inst.batcher.stop_at_tick = stop_tick
        while inst.batcher.clock.tick < stop_tick:
            await asyncio.sleep(0.02)
        await asyncio.sleep(0.3)
        print("child 0: OK", flush=True)

    asyncio.run(main())


import pytest  # noqa: E402


@pytest.mark.parametrize("stack", [1, 2])
@pytest.mark.slow
def test_mesh_serving_two_nodes(stack):
    """stack=2 drives the stacked lockstep tick (engine.step_stacked): two
    windows per collective dispatch on the cluster clock."""
    coord, grpc0, grpc1, ctrl = _free_ports(4)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, __file__, "CHILD",
             json.dumps([i, coord, grpc0, grpc1, ctrl, stack])],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env)
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT>"
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"child {i} failed:\n{out[-5000:]}"
        assert f"child {i}: OK" in out


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "CHILD":
        _child(*json.loads(sys.argv[2]))
