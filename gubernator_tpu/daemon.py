"""Server daemon: the composition root.

Equivalent of the reference's cmd/gubernator/main.go:40-140: env config,
device engine (in place of the LRU cache), gRPC server, discovery pool
(k8s | etcd | static), HTTP gateway with /metrics, SIGINT/SIGTERM graceful
shutdown.  Run as `python -m gubernator_tpu.daemon` (flags: --config
<env-file>, --debug — the reference's only two flags,
cmd/gubernator/config.go:63-66).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
from typing import Optional

from gubernator_tpu.config import (
    BehaviorConfig,
    Config,
    DaemonConfig,
    config_from_env,
)
from gubernator_tpu.api.http_gateway import HttpGateway
from gubernator_tpu.core.service import Instance
from gubernator_tpu.server import GrpcServer

log = logging.getLogger("gubernator.daemon")


class Daemon:
    def __init__(self, conf: DaemonConfig):
        self.conf = conf
        self.instance: Optional[Instance] = None
        self.grpc: Optional[GrpcServer] = None
        self.frontdoor = None  # FrontdoorHub when GUBER_FRONTDOOR_WORKERS > 0
        self.http: Optional[HttpGateway] = None
        self.pool = None
        self.monitor = None  # net/health.py HeartbeatMonitor (static pools)
        self._snapshot_task: Optional[asyncio.Task] = None
        self._lease_sweep_task: Optional[asyncio.Task] = None
        # phase names appended as stop() executes them, in order — the
        # shutdown-ordering contract the signal-path tests assert
        self.shutdown_phases: list = []

    def _snapshot_file(self) -> str:
        from gubernator_tpu.state.snapshot import snapshot_path
        eng = self.instance.engine
        return snapshot_path(self.conf.snapshot_dir,
                             local_shard_offset=eng.local_shard_offset,
                             multiprocess=eng.multiprocess)

    async def _snapshot_once(self) -> None:
        try:
            await self.instance.save_snapshot(self._snapshot_file())
        except Exception:
            self.instance.metrics.observe_snapshot(0.0, 0, ok=False)
            log.exception("periodic snapshot failed")

    async def _snapshot_loop(self) -> None:
        interval = self.conf.snapshot_interval_ms / 1000.0
        while True:
            await asyncio.sleep(interval)
            await self._snapshot_once()

    async def _lease_sweep_loop(self, interval_ms: int) -> None:
        """Periodically drop expired grants from the concurrency-lease
        book (GUBER_LEASE_SWEEP_MS).  The device buckets already expired,
        so this only keeps the lease gauges and per-client holds honest."""
        from gubernator_tpu.api.types import millisecond_now
        while True:
            await asyncio.sleep(interval_ms / 1000.0)
            try:
                dropped = self.instance.leases.sweep(millisecond_now())
                if dropped:
                    self.instance.metrics.observe_lease_release(
                        "expired", sum(c for _, _, c in dropped))
            except Exception:
                log.exception("lease sweep failed")

    async def start(self) -> None:
        c = self.conf
        from gubernator_tpu.config import place_compile_cache
        place_compile_cache()

        # Mesh mode: join the jax.distributed runtime BEFORE any device use;
        # the arena then shards over every process's chips and all hosts
        # dispatch windows on the lockstep clock (parallel/distributed.py).
        import os
        from gubernator_tpu.parallel.distributed import initialize_from_env
        mesh = None
        mesh_peers = None
        if initialize_from_env():
            from gubernator_tpu.parallel.distributed import global_mesh
            mesh = global_mesh()
            peers_env = os.environ.get("GUBER_MESH_PEERS", "")
            mesh_peers = [a.strip() for a in peers_env.split(",") if a.strip()]
            if not mesh_peers:
                raise ValueError(
                    "mesh mode requires GUBER_MESH_PEERS (gRPC addresses in "
                    "process-rank order)")
            import jax
            if len(mesh_peers) != jax.process_count():
                raise ValueError(
                    f"GUBER_MESH_PEERS lists {len(mesh_peers)} addresses but "
                    f"the mesh has {jax.process_count()} processes — the "
                    "list must name every process, in rank order")
            log.info("mesh mode: %d processes, %d global shards",
                     len(mesh_peers), mesh.devices.size)

        # deterministic fault injection (net/faults.py): GUBER_FAULTS is
        # read ONCE here — a production boot without it pays one attribute
        # check per seam crossing
        from gubernator_tpu.net.faults import FAULTS
        FAULTS.load_from_env()

        self.instance = Instance(Config(
            behaviors=c.behaviors,
            engine=c.engine,
            advertise_address=c.advertise_address,
            qos=c.qos,
            health=c.health,
        ), mesh=mesh, mesh_peers=mesh_peers)
        # compile the device step before accepting traffic; mesh mode needs a
        # cluster-agreed timestamp (all processes warm up in lockstep)
        if mesh_peers is not None:
            eng = self.instance.engine
            # one agreed reading of the clock for the warm-up and the
            # preload (the epoch itself is taken at the first tick)
            boot_ms = self.instance.batcher.clock.now_ms()
            eng.warmup(now=boot_ms, k_stack=c.behaviors.lockstep_stack)
            gk_file = os.environ.get("GUBER_GLOBAL_KEYS_FILE", "")
            if gk_file:
                import json
                with open(gk_file) as f:
                    specs = [(d["key"], d["limit"], d["duration"],
                              d.get("algorithm", 0))
                             for d in (json.loads(ln) for ln in f
                                       if ln.strip())]
                eng.register_global_keys(specs, now=boot_ms)
                log.info("registered %d GLOBAL keys", len(specs))
        else:
            self.instance.engine.warmup()

        # State lifecycle: restore the arena BEFORE serving (a corrupt or
        # missing snapshot degrades to a cold start, never a failed boot),
        # then re-snapshot periodically and once on clean shutdown.  In
        # mesh mode every process restores its own shard blocks from the
        # shared directory at the same pre-lockstep point.
        if c.snapshot_dir:
            import os as _os
            _os.makedirs(c.snapshot_dir, exist_ok=True)
            from gubernator_tpu.state.snapshot import restore_engine
            loop = asyncio.get_running_loop()
            snap = await loop.run_in_executor(
                self.instance.batcher._executor,
                lambda: restore_engine(self.instance.engine,
                                       self._snapshot_file(),
                                       metrics=self.instance.metrics))
            if snap is not None and getattr(snap, "leases", None):
                # re-register restored concurrency leases (the device
                # free-slot counters came back with the arena planes)
                self.instance.leases.import_rows(snap.leases)
            self._snapshot_task = asyncio.create_task(self._snapshot_loop())
            log.info("snapshots -> %s every %dms", c.snapshot_dir,
                     c.snapshot_interval_ms)

        sweep_ms = getattr(getattr(c, "leases", None),
                           "sweep_interval_ms", 0)
        if sweep_ms > 0:
            self._lease_sweep_task = asyncio.create_task(
                self._lease_sweep_loop(sweep_ms))

        if c.frontdoor_workers > 0 and mesh_peers is None:
            # multi-process front door (frontdoor.py): N acceptor worker
            # processes share the gRPC port via SO_REUSEPORT and hand
            # records to this engine over shm rings; this process binds
            # no public gRPC port of its own.  Mesh mode keeps the
            # classic in-process server: lockstep ticks own the loop.
            from gubernator_tpu.frontdoor import FrontdoorHub
            self.frontdoor = FrontdoorHub(
                self.instance, workers=c.frontdoor_workers,
                ring_slots=c.shm_ring_slots, slab_bytes=c.shm_slab_bytes,
                listen_address=c.grpc_listen_address,
                encode=c.frontdoor_encode,
                batch_reads=c.frontdoor_batch_reads)
            await self.frontdoor.start()
            # surfaced in /v1/admin/debug + metrics like any subsystem
            self.instance.frontdoor = self.frontdoor
            self.instance.metrics.watch_frontdoor(self.frontdoor)
            log.info("frontdoor: %d workers on %s (engine pid %d)",
                     c.frontdoor_workers, self.frontdoor.address,
                     os.getpid())
        else:
            if c.frontdoor_workers > 0:
                log.warning("GUBER_FRONTDOOR_WORKERS ignored in mesh mode")
            self.grpc = GrpcServer(self.instance, c.grpc_listen_address)
            await self.grpc.start()
            log.info("gRPC listening on %s", self.grpc.address)

        static_peers = os.environ.get("GUBER_STATIC_PEERS", "")
        if mesh_peers is not None:
            # mesh membership is fixed by process rank; discovery backends
            # don't apply (elasticity = re-forming the mesh)
            from gubernator_tpu.discovery.static import StaticPool
            self.pool = StaticPool(
                addresses=mesh_peers,
                advertise_address=c.advertise_address,
                on_update=self.instance.set_peers,
            )
            await self.pool.start()
            self.instance.batcher.start_lockstep()
        elif c.k8s_enabled:
            from gubernator_tpu.discovery.kubernetes import K8sPool
            self.pool = K8sPool(
                namespace=c.k8s_namespace,
                pod_ip=c.k8s_pod_ip,
                pod_port=c.k8s_pod_port,
                selector=c.k8s_endpoints_selector,
                on_update=self.instance.set_peers,
            )
            await self.pool.start()
        elif c.etcd_enabled:
            from gubernator_tpu.discovery.etcd import EtcdPool
            self.pool = EtcdPool(
                endpoints=c.etcd_addresses,
                advertise_address=c.advertise_address,
                on_update=self.instance.set_peers,
                prefix=c.etcd_prefix,
                username=c.etcd_username,
                password=c.etcd_password,
                ssl_context=c.etcd_ssl_context(),
            )
            await self.pool.start()
        elif static_peers:
            from gubernator_tpu.discovery.static import StaticPool
            addresses = [a.strip() for a in static_peers.split(",")
                         if a.strip()]
            self.pool = StaticPool(
                addresses=addresses,
                advertise_address=c.advertise_address,
                on_update=self.instance.set_peers,
            )
            await self.pool.start()
            # Static pools have no discovery backend to remove dead peers —
            # the heartbeat failure detector is their self-healing layer
            # (k8s/etcd pools already watch membership; mesh membership is
            # fixed by process rank).
            if c.health.heartbeat_enabled:
                from gubernator_tpu.net.health import HeartbeatMonitor
                self.monitor = HeartbeatMonitor(
                    self.instance, addresses, conf=c.health)
                self.instance.monitor = self.monitor
                self.monitor.start()
                log.info("heartbeat detector on %d peers (interval %.1fs, "
                         "down after %d misses)", len(addresses) - 1,
                         c.health.heartbeat_interval, c.health.suspect_after)

        self.http = HttpGateway(self.instance, c.http_listen_address)
        await self.http.start()
        log.info("HTTP gateway listening on %s", c.http_listen_address)

    async def stop(self) -> None:
        """Graceful departure, in phases (each bounded, none skippable by
        a failure in the previous one):

          1. stop the failure detector (it must not react to our own
             departure);
          2. drain — close admission intake (new work sheds in-band with
             reason `draining`) and wait out already-admitted decisions;
          3. flush the GlobalManager (queued aggregated hits/updates ship
             now instead of being dropped by stop());
          4. handoff — when a surviving ring remains, ship every key this
             node owns to the survivors (skipped entirely when this node
             is the whole ring: a handoff with no destination must not
             hang the shutdown);
          5. final snapshot (AFTER handoff: the snapshot then records the
             post-departure state, so a restart doesn't resurrect keys
             the survivors now own);
          6. teardown: discovery, http, grpc, instance
             (main.go:127-139 order).
        """
        await self._stop_monitor()
        await self._drain_requests()
        await self._flush_globals()
        await self._handoff_keys()
        await self._final_snapshot()
        await self._teardown()

    def _phase(self, name: str) -> None:
        self.shutdown_phases.append(name)

    async def _stop_monitor(self) -> None:
        self._phase("monitor_stop")
        if self.monitor is not None:
            try:
                await self.monitor.stop()
            except Exception:
                log.exception("stopping heartbeat monitor failed")

    async def _drain_requests(self) -> None:
        self._phase("drain")
        if self.frontdoor is not None:
            # workers shed new work in-band (reason `draining`) without a
            # ring round-trip from here on
            self.frontdoor.set_draining()
        if self.instance is None:
            return
        try:
            await self.instance.drain(self.conf.health.drain_timeout)
        except Exception:
            log.exception("drain failed; continuing shutdown")

    async def _flush_globals(self) -> None:
        self._phase("global_flush")
        if self.instance is None:
            return
        try:
            await asyncio.wait_for(self.instance.global_mgr.flush(),
                                   self.conf.health.drain_timeout)
        except Exception:
            log.exception("global flush failed; continuing shutdown")

    async def _handoff_keys(self) -> None:
        inst = self.instance
        if inst is None:
            return
        all_hosts = [p.host for p in inst.peer_list()]
        survivors = [h for h in all_hosts if h != inst.advertise_address]
        if not survivors:
            # no surviving ring (standalone, or last node standing): the
            # final snapshot is the only continuity there is
            self._phase("handoff_skipped")
            return
        self._phase("handoff")
        try:
            totals = await asyncio.wait_for(
                inst.migrate_keys(all_hosts, survivors),
                self.conf.health.drain_timeout)
            log.info("departure handoff: %s", totals)
        except Exception:
            log.exception("departure handoff failed; survivors restart "
                          "these keys cold")

    async def _final_snapshot(self) -> None:
        if self._snapshot_task is None:
            return
        self._phase("snapshot")
        self._snapshot_task.cancel()
        try:
            await self._snapshot_task
        except asyncio.CancelledError:
            pass
        # final snapshot while the engine is serving-quiesced: a clean
        # shutdown loses zero decisions
        await self._snapshot_once()

    async def _teardown(self) -> None:
        self._phase("teardown")
        if self._lease_sweep_task is not None:
            self._lease_sweep_task.cancel()
            try:
                await self._lease_sweep_task
            except asyncio.CancelledError:
                pass
        if self.pool is not None:
            await self.pool.close()
        if self.http is not None:
            await self.http.stop()
        if self.frontdoor is not None:
            await self.frontdoor.stop()
        if self.grpc is not None:
            await self.grpc.stop()
        if self.instance is not None:
            await self.instance.aclose()


async def _amain(conf: DaemonConfig) -> None:
    daemon = Daemon(conf)
    await daemon.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    log.info("caught signal; shutting down")
    await daemon.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser("gubernator-tpu")
    p.add_argument("--config", dest="config_file", default=None,
                   help="environment config file (KEY=value lines)")
    p.add_argument("--debug", action="store_true")
    args = p.parse_args(argv)

    conf = config_from_env(args.config_file)
    import os
    if args.debug or conf.debug:
        logging.basicConfig(level=logging.DEBUG)
        log.debug("debug enabled")
    else:
        logging.basicConfig(level=logging.INFO)

    asyncio.run(_amain(conf))


if __name__ == "__main__":
    main()
