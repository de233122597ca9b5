"""Service core: request fan-out, ownership routing, behavior dispatch.

The equivalent of the reference's Instance (gubernator.go:41-322), built
around the device window engine instead of a mutex'd cache:

  * public GetRateLimits: per-item validation (exact reference error
    strings, gubernator.go:102-110), owner-vs-forward routing over the
    consistent-hash ring (:114-152), the 1000-item RPC cap (:78-81);
  * local decisions flow through the WindowBatcher → one device step per
    window (replacing the per-key mutex'd algorithm calls, :236-251);
  * peer plane GetPeerRateLimits/UpdatePeerGlobals (:199-227);
  * GLOBAL behavior: owner applies + broadcasts; non-owner answers from its
    replica and queues hits (:173-195) — within the mesh the psum does this
    with zero RPCs.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Dict, List, Optional, Sequence

from gubernator_tpu.algorithms.leases import LeaseBook
from gubernator_tpu.algorithms.oracles import ALGORITHM_NAMES
from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    HealthCheckResp,
    RateLimitReq,
    RateLimitResp,
    Status,
    millisecond_now,
)
from gubernator_tpu.config import MAX_BATCH_SIZE, Config, PeerInfo
from gubernator_tpu.core.batcher import WindowBatcher
from gubernator_tpu.core.engine import RateLimitEngine
from gubernator_tpu.core.global_sync import GlobalManager
from gubernator_tpu.net.peers import BreakerOpenError, PeerClient
from gubernator_tpu.observability import Metrics, Tracer
from gubernator_tpu.parallel.router import ConsistentHashRing, MeshShardPicker
from gubernator_tpu.qos import QoSManager, shed_response
from gubernator_tpu.qos.admission import SHED_BREAKER_OPEN

HEALTHY = "healthy"
UNHEALTHY = "unhealthy"

log = logging.getLogger("gubernator.instance")


class BatchTooLargeError(Exception):
    """Maps to gRPC OutOfRange at the transport layer (gubernator.go:78-81)."""


class Instance:
    def __init__(
        self,
        config: Optional[Config] = None,
        mesh=None,
        engine: Optional[RateLimitEngine] = None,
        metrics: Optional[Metrics] = None,
        mesh_peers: Optional[List[str]] = None,
        tracer: Optional[Tracer] = None,
    ):
        """mesh_peers: gRPC addresses of every mesh process in PROCESS-RANK
        order — enables mesh serving mode (parallel/distributed.py): shard-
        exact routing, lockstep window clock, GLOBAL via in-mesh psum (the
        gRPC GlobalManager dance is not used)."""
        self.conf = config or Config()
        self.conf.behaviors.validate()
        self.metrics = metrics or Metrics()
        # per-instance span recorder, like the Metrics registry — each
        # node's ring buffer is its own, so a stitched trace is assembled
        # by trace id across nodes (tests: tests/test_tracing.py)
        self.tracer = tracer if tracer is not None else Tracer(
            sample=self.conf.trace_sample,
            export=self.conf.trace_export or None,
            node=self.conf.advertise_address or "local")
        e = self.conf.engine
        self.engine = engine or RateLimitEngine(
            mesh=mesh,
            capacity_per_shard=e.capacity_per_shard,
            batch_per_shard=e.batch_per_shard,
            global_capacity=e.global_capacity,
            global_batch_per_shard=e.global_batch_per_shard,
            max_global_updates=e.max_global_updates,
            use_native=e.use_native,
            exact_keys=e.exact_keys,
            replay_cap=e.replay_cap,
            skip_global=e.skip_global,
        )
        self.metrics.watch_engine(self.engine)
        # QoS control plane (gubernator_tpu/qos/): admission, congestion
        # window, fairness, breaker policy.  Disabled => every path below
        # behaves exactly like the seed.
        self.qos: Optional[QoSManager] = None
        if self.conf.qos.enabled:
            self.qos = QoSManager(self.conf.qos, metrics=self.metrics)
            self.metrics.watch_qos(self.qos)
        # Concurrency-lease book (algorithms/leases.py): host-side shadow
        # of who holds which CONCURRENCY slots, so stream-close and peer
        # death can release them and migration can re-register them.  The
        # template map remembers how to rebuild a release request per key
        # (the book itself stores only hash keys).
        self.leases = LeaseBook()
        self._lease_tmpl: Dict[str, RateLimitReq] = {}
        self.metrics.watch_leases(self.leases)
        # Traffic analytics + SLO burn-rate engine (observability/
        # analytics.py).  Off by default: the pipeline then holds None and
        # the serving path is byte-identical to the seed (one attribute
        # check per drain).  The enabled flag comes from config, so every
        # mesh process makes the same choice — the analytics executable is
        # part of each drain's issue sequence when on.
        self.analytics = None
        self.slo = None
        if self.conf.analytics.enabled:
            from gubernator_tpu.observability.analytics import TrafficAnalytics
            self.conf.analytics.validate()
            self.analytics = TrafficAnalytics(self.conf.analytics,
                                              metrics=self.metrics)
            self.engine.enable_analytics(self.conf.analytics)
        if self.conf.slo.enabled:
            from gubernator_tpu.observability.analytics import SLOEngine
            self.conf.slo.validate()
            self.slo = SLOEngine(self.conf.slo)
        if self.analytics is not None or self.slo is not None:
            self.metrics.watch_analytics(self.analytics, self.slo)
        # Tiered key state (state/tiers.py).  Off by default (warm_rows=0):
        # the engine hot path is byte-identical to the single-tier seed.
        # When on, the warm tier hangs off the engine's Python tables and
        # feeds on the analytics heat map when that is also enabled.
        tconf = getattr(self.conf, "tiers", None)
        if tconf is not None and tconf.enabled:
            tconf.validate()
            self.engine.enable_tiers(tconf, analytics=self.analytics)
            self.engine.tier_warmup()
            self.metrics.watch_tiers(self.engine)
        self.mesh_mode = mesh_peers is not None
        clock = None
        if self.mesh_mode:
            from gubernator_tpu.parallel.distributed import (
                LockstepClock,
                agree_max,
            )

            # the epoch is taken at the first tick; several hosts agree it
            # and every tick's index through one tiny collective
            mesh_ = self.engine.mesh
            clock = LockstepClock(
                None, self.conf.behaviors.batch_wait,
                agree=((lambda v: agree_max(mesh_, v))
                       if self.engine.multiprocess else None))
        self.batcher = WindowBatcher(self.engine, self.conf.behaviors,
                                     self.metrics, lockstep_clock=clock,
                                     qos=self.qos, tracer=self.tracer,
                                     analytics=self.analytics, slo=self.slo)
        # Device-time flight recorder (observability/devprof.py): the
        # kernel table + optional continuous-capture controller, sharing
        # the batcher's armable ProfileCapture.  The pipeline's per-drain
        # window clock (devclock) is folded into the same facade so
        # /v1/admin/kernels and `cli kernels` read one snapshot.
        from gubernator_tpu.observability.devprof import Devprof
        eng = self.engine
        self.devprof = Devprof(
            mode=getattr(self.conf, "devprof_mode", ""),
            metrics=self.metrics,
            profile=self.batcher.profile,
            windows_fn=lambda: int(eng.windows_processed),
            interval=getattr(self.conf, "devprof_interval_s", None),
            drains=getattr(self.conf, "devprof_drains", None))
        if self.batcher.pipeline is not None:
            self.devprof.clock = self.batcher.pipeline.devclock
        self.devprof.start()
        self.global_mgr = GlobalManager(
            self.conf.behaviors, self, self.metrics, log,
            health=self.conf.health)
        # failure detector handle (net/health.py), installed by whoever
        # runs the node (daemon.py / cluster.py); introspection reads it
        self.monitor = None
        if self.mesh_mode:
            self._picker = MeshShardPicker.for_mesh(self.engine.mesh,
                                                    mesh_peers)
        else:
            self._picker: ConsistentHashRing[PeerClient] = ConsistentHashRing()
        self.mesh_peers = list(mesh_peers) if mesh_peers else None
        self.health = HealthCheckResp(status=HEALTHY, peer_count=0)
        self.advertise_address = self.conf.advertise_address
        # dynamic mesh GLOBAL registration (reference analog: GLOBAL keys
        # are accepted on first use, global.go:62-68): process 0 is the
        # registrar that totally orders registrations mesh-wide
        self._greg_lock = asyncio.Lock()
        self._greg_inflight: Dict[str, asyncio.Future] = {}
        # registrar-side: keys whose TWO-PHASE registration completed on
        # every process.  Deliberately not the registrar's own
        # engine.global_ready: a partial phase-2 failure leaves a key active
        # here but pending elsewhere, and the retry must re-run both phases
        # (idempotent) to heal the stuck host.
        self._greg_done: set = set()

    @property
    def standalone(self) -> bool:
        """No peer ring and not a mesh: this node owns every key (the gate
        for the native RPC lane, re-checked again on the engine thread via
        pipeline.rpc_enabled — see server.py / core/pipeline.py)."""
        return not self.mesh_mode and self._picker.size() == 0

    # ------------------------------------------------------------ public API

    def add_to_server(self, server, *, v1: bool = True,
                      peers: bool = True) -> None:
        """Embed this instance's gRPC services onto a CALLER-OWNED
        grpc.aio.Server (the reference's GRPCServers embedding hook,
        config.go:30-31): the caller keeps ownership of the server's
        lifecycle, ports, interceptors and TLS; this just registers the
        pb.gubernator.V1 and/or pb.gubernator.PeersV1 handlers backed by
        this instance.

        `v1`/`peers` select which service to mount — one process can host
        two instances on ONE server by splitting the services between them
        (front-door V1 on one engine, peer traffic on another).  gRPC
        generic handlers match in registration order, so mounting the SAME
        service from two instances leaves the first registration serving
        all of its RPCs.
        """
        # deferred import: server.py imports Instance from this module
        from gubernator_tpu.api.grpc_api import (add_peers_servicer,
                                                 add_v1_servicer)
        from gubernator_tpu.server import _PeersServicer, _V1Servicer

        if v1:
            add_v1_servicer(server, _V1Servicer(self))
        if peers:
            add_peers_servicer(server, _PeersServicer(self))

    async def get_rate_limits(
        self, requests: Sequence[RateLimitReq],
        deadline: Optional[float] = None,
        client_id: Optional[str] = None,
    ) -> List[RateLimitResp]:
        """deadline: absolute monotonic deadline propagated from the
        transport (gRPC context.time_remaining(), HTTP timeout header) —
        admission sheds requests it cannot serve in time (qos/admission.py).

        client_id: transport-level caller identity (source address) — the
        concurrency-lease book attributes grants to it so stream-close and
        peer-death can release held slots.
        """
        if len(requests) > MAX_BATCH_SIZE:
            raise BatchTooLargeError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'")
        if self.mesh_mode:
            await self._register_first_seen(requests)
        return list(await asyncio.gather(
            *(self._route(r, deadline, client_id=client_id)
              for r in requests)))

    async def _register_first_seen(self, requests) -> None:
        """Register an RPC's first-seen GLOBAL keys mesh-wide in ONE
        registration, before its items are routed: a fill of a thousand new
        keys is then a handful of registrar round trips, which the default
        GUBER_GLOBAL_TIMEOUT holds, instead of one a key behind one lock.
        A failure is left to the items: each retries its own key in
        _route_inner and reports the error in-band."""
        specs = {}
        for r in requests:
            if (r.behavior == Behavior.GLOBAL and r.name and r.unique_key
                    and r.algorithm in (Algorithm.TOKEN_BUCKET,
                                        Algorithm.LEAKY_BUCKET)):
                key = r.hash_key()
                if key not in specs and not self.engine.global_ready(key):
                    specs[key] = (key, r.limit, r.duration, int(r.algorithm))
        if specs:
            try:
                await self._ensure_globals_registered(list(specs.values()))
            except Exception as e:  # noqa: BLE001 — per-item error contract
                log.debug("batched GLOBAL registration failed: %s", e)

    async def _route(self, r: RateLimitReq,
                     deadline: Optional[float] = None,
                     client_id: Optional[str] = None) -> RateLimitResp:
        cap = getattr(getattr(self.conf, "leases", None),
                      "max_per_client", 0)
        if (cap and r.algorithm == Algorithm.CONCURRENCY and r.hits > 0
                and self.leases.count(client_id or "anonymous",
                                      r.hash_key()) + r.hits > cap):
            # GUBER_LEASE_MAX_PER_CLIENT: answer on the host, before the
            # device spends a slot this client is not allowed to hold
            resp = RateLimitResp(status=Status.OVER_LIMIT, limit=r.limit,
                                 remaining=0, reset_time=0)
            self._account_decision(r, resp, client_id)
            return resp
        if (r.algorithm == Algorithm.CONCURRENCY
                and (r.hits < 0
                     or (client_id is not None
                         and self.leases.holds(client_id, r.hash_key())))):
            # QoS exemption: shedding a lease release (or a holder's
            # re-touch) on deadline would leak the held slot until bucket
            # expiry — these always ride through admission undeadlined
            deadline = None
        resp = await self._route_inner(r, deadline)
        self._account_decision(r, resp, client_id)
        return resp

    def _account_decision(self, r: RateLimitReq, resp: RateLimitResp,
                          client_id: Optional[str]) -> None:
        """Post-decision bookkeeping: the per-algorithm decision counter
        and the concurrency-lease book (algorithms/leases.py)."""
        if resp.error:
            return
        self.metrics.observe_algorithm(
            ALGORITHM_NAMES.get(int(r.algorithm), "token_bucket"))
        if r.algorithm != Algorithm.CONCURRENCY or r.hits == 0:
            return
        key = r.hash_key()
        client = client_id or "anonymous"
        if r.hits > 0:
            if resp.status == Status.UNDER_LIMIT:
                self._lease_tmpl[key] = r
                self.leases.acquire(key, client, r.hits,
                                    millisecond_now() + r.duration)
        else:
            self.leases.release(key, client, -r.hits)
            self.metrics.observe_lease_release("explicit", -r.hits)

    async def release_client_leases(self, client_id: str,
                                    reason: str = "stream_close") -> int:
        """Release every lease a vanished client holds: drop the book rows
        and push the matching negative-hits requests through the normal
        decision path so the device free-slot counters recover.  Returns
        the number of slots given back."""
        rows = self.leases.release_client(client_id)
        total = 0
        for key, count in rows:
            tmpl = self._lease_tmpl.get(key)
            if tmpl is None:
                # no template (book restored from a snapshot and the key
                # was never re-touched here): the bucket's expiry column
                # reclaims the slots on-device
                continue
            rel = RateLimitReq(
                name=tmpl.name, unique_key=tmpl.unique_key, hits=-count,
                limit=tmpl.limit, duration=tmpl.duration,
                algorithm=Algorithm.CONCURRENCY, behavior=tmpl.behavior)
            resp = await self._route_inner(rel, None)
            if not resp.error:
                total += count
        if total or rows:
            self.metrics.observe_lease_release(
                reason, sum(c for _, c in rows))
        return total

    async def release_peer_leases(self, host: str) -> int:
        """Peer-death hook (net/health.py): grants are attributed to the
        forwarding peer's source address, so a confirmed-down peer's
        clients get their slots back here."""
        ip = host.rsplit(":", 1)[0]
        total = 0
        for client in (host, ip):
            if self.leases.holds(client):
                total += await self.release_client_leases(
                    client, reason="peer_down")
        return total

    async def _route_inner(self, r: RateLimitReq,
                           deadline: Optional[float] = None
                           ) -> RateLimitResp:
        key = r.hash_key()
        # validation: exact reference strings and order (gubernator.go:102-110)
        if not r.unique_key:
            return RateLimitResp(error="field 'unique_key' cannot be empty")
        if not r.name:
            return RateLimitResp(error="field 'namespace' cannot be empty")
        if r.algorithm not in (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET,
                               Algorithm.GCRA, Algorithm.SLIDING_WINDOW,
                               Algorithm.CONCURRENCY):
            # the reference surfaces this via the apply-error wrapper
            # (gubernator.go:126-131 <- :250)
            return RateLimitResp(error=(
                f"while applying rate limit for '{key}' - "
                f"'invalid rate limit algorithm '{r.algorithm}''"))
        if (r.behavior == Behavior.GLOBAL
                and r.algorithm not in (Algorithm.TOKEN_BUCKET,
                                        Algorithm.LEAKY_BUCKET)):
            # the staged GLOBAL pair-transition replicates only the
            # token/leaky ladders; GCRA/sliding/concurrency state cannot be
            # reconciled through the hits psum, so refuse rather than
            # silently serve stale replicas
            return RateLimitResp(error=(
                f"while applying rate limit for '{key}' - "
                f"'GLOBAL behavior does not support algorithm "
                f"'{r.algorithm}''"))

        # standalone (no peer ring): every key is ours
        if self._picker.size() == 0:
            return await self._local(r, deadline)

        if r.behavior == Behavior.GLOBAL and self.mesh_mode:
            # ownership is irrelevant here: after the window psum EVERY mesh
            # replica is authoritative for GLOBAL keys
            try:
                if not self.engine.global_ready(key):
                    # first sight of this GLOBAL key: register it mesh-wide
                    # through the registrar before serving (reference
                    # analog: GLOBAL keys accepted on first use,
                    # global.go:62-68)
                    await self._ensure_globals_registered(
                        [(key, r.limit, r.duration, int(r.algorithm))])
                return await self.batcher.submit(r, deadline=deadline)
            except Exception as e:
                # per-item failure (e.g. unregistered GLOBAL key failed
                # individually by _take_window) must not abort the whole
                # client batch via the gather in get_rate_limits
                return RateLimitResp(
                    error=f"while applying rate limit for '{key}' - '{e}'")

        try:
            peer = self._picker.get(key)
        except Exception as e:
            return RateLimitResp(
                error=f"while finding peer that owns rate limit '{key}' - '{e}'")

        if peer.is_owner:
            try:
                return await self._local(r, deadline)
            except Exception as e:
                return RateLimitResp(
                    error=f"while applying rate limit for '{key}' - '{e}'")

        if r.behavior == Behavior.GLOBAL:
            try:
                return await self._global_nonowner(r)
            except Exception as e:
                return RateLimitResp(
                    error=f"while applying rate limit for '{key}' - '{e}'")

        # the forward hop is traced (peer_forward) AND staged: the span
        # carries the traceparent to the owner through the peer lane's
        # gRPC metadata (net/peers.py), so the owner's peer_rpc span lands
        # in the same trace — one stitched view of the cross-node hit
        t0 = time.monotonic()
        self.metrics.cluster_forwarded.inc()
        try:
            with self.tracer.span("peer_forward") as span:
                span.set_attr("peer", peer.host)
                resp = await peer.get_peer_rate_limit(r)
        except BreakerOpenError:
            return await self._breaker_fallback(r, peer.host, deadline)
        except Exception as e:
            return RateLimitResp(
                error=f"while fetching rate limit '{key}' from peer - '{e}'")
        finally:
            self.metrics.observe_stage("peer_forward", time.monotonic() - t0)
        # tell the client who coordinates this key (gubernator.go:151)
        resp.metadata = dict(resp.metadata or {}, owner=peer.host)
        return resp

    async def _breaker_fallback(self, r: RateLimitReq, host: str,
                                deadline: Optional[float]) -> RateLimitResp:
        """The owner's circuit breaker is open.  fail_open: answer from the
        LOCAL engine — a non-authoritative decision (this node's window
        state, not the owner's), flagged in metadata so honest clients know
        enforcement is degraded rather than wrong silently.  fail_closed:
        shed in-band with reason breaker_open."""
        fail_open = (self.qos.fail_open if self.qos is not None
                     else self.conf.qos.fail_open)
        if not fail_open:
            if self.qos is not None:
                self.qos.admission.record_shed(SHED_BREAKER_OPEN)
            return shed_response(r, SHED_BREAKER_OPEN)
        resp = await self._local(r, deadline)
        resp.metadata = dict(resp.metadata or {}, owner=host,
                             degraded="true", non_authoritative="true")
        self.metrics.fail_open_served.inc()
        return resp

    async def _local(self, r: RateLimitReq,
                     deadline: Optional[float] = None) -> RateLimitResp:
        """Owner-side decision through the device engine (the reference's
        getRateLimit under the cache mutex, gubernator.go:236-251)."""
        if (r.behavior == Behavior.GLOBAL and self._picker.size() > 0
                and not self.mesh_mode):
            # owner saw a GLOBAL change: schedule an authoritative broadcast
            # (gubernator.go:240-242)
            self.global_mgr.queue_update(r)
        if r.behavior == Behavior.NO_BATCHING:
            # deliberately NOT gated by admission: NO_BATCHING is the
            # jump-the-window lane and keeps working while the batched
            # lane saturates (tests/test_qos.py asserts this)
            return (await self.batcher.submit_now([r]))[0]
        return await self.batcher.submit(r, deadline=deadline)

    async def _global_nonowner(self, r: RateLimitReq) -> RateLimitResp:
        """Non-owner GLOBAL: answer from the local replica, reconcile hits
        asynchronously with the owner (gubernator.go:173-195)."""
        self.global_mgr.queue_hit(r)
        # replica read through the engine's global arena; hits stay out of
        # the mesh psum (they reconcile via the owner instead)
        return await self.batcher.submit(r, accumulate=False)

    # --------------------------------------------- dynamic mesh GLOBAL keys

    async def _ensure_globals_registered(self, specs) -> None:
        """Route first-seen GLOBAL keys' registration — `specs` of (key,
        limit, duration, algorithm), one registrar RPC for all of them —
        through the mesh registrar (process 0) and wait until they are
        servable HERE.  A key another caller is already registering is
        waited for, not sent again."""
        loop = asyncio.get_running_loop()
        mine, waits = [], []
        for spec in specs:
            fut = self._greg_inflight.get(spec[0])
            if fut is None:
                self._greg_inflight[spec[0]] = loop.create_future()
                mine.append(spec)
            else:
                waits.append(fut)
        if mine:
            err = None
            try:
                registrar = self._picker.get_by_host(self.mesh_peers[0])
                if registrar is None:
                    raise RuntimeError("mesh registrar peer is not connected")
                await registrar.register_globals(mine)
            except Exception as e:
                err = e
            for spec in mine:
                fut = self._greg_inflight.pop(spec[0])
                if err is None:
                    fut.set_result(None)
                else:
                    fut.set_exception(err)
                    fut.exception()  # consumed: a key nobody waited for
            if err is not None:
                raise err
        for fut in waits:
            await fut

    async def register_globals(self, specs) -> None:
        """Registrar endpoint (runs on mesh process 0): totally order
        dynamic GLOBAL registrations and two-phase-apply them.  Phase 1
        writes the replicated arena on EVERY process (collective-free, see
        engine.register_global_keys); phase 2 activates serving only after
        every process confirmed phase 1 — so no host ever contributes psum
        hits to a slot some replica hasn't configured."""
        if not self.mesh_mode:
            raise RuntimeError("RegisterGlobals is a mesh-mode RPC")
        async with self._greg_lock:
            todo = list({s[0]: s for s in specs
                         if s[0] not in self._greg_done}.values())
            if not todo:
                return
            now = millisecond_now()
            peers = [self._picker.get_by_host(h) for h in self.mesh_peers]
            if any(p is None for p in peers):
                raise RuntimeError(
                    "mesh peers not all connected; cannot register "
                    "GLOBAL keys")
            await asyncio.gather(*(
                p.apply_global_registration(todo, now, False)
                for p in peers))
            await asyncio.gather(*(
                p.apply_global_registration(todo, now, True) for p in peers))
            self._greg_done.update(s[0] for s in todo)
            self.metrics.global_register_batch.observe(len(todo))

    async def apply_global_registration(self, specs, now: int,
                                        activate: bool) -> None:
        """One registration phase on THIS process (registrar fan-out
        target); engine work runs on the device executor thread."""
        loop = asyncio.get_running_loop()
        if activate:
            keys = [s[0] for s in specs]
            await loop.run_in_executor(
                self.batcher._executor,
                lambda: self.engine.activate_global_keys(keys))
        else:
            await loop.run_in_executor(
                self.batcher._executor,
                lambda: self.engine.register_global_keys(
                    specs, now=now, pending=True))

    # ------------------------------------------------------------ peer plane

    async def get_peer_rate_limits(
            self, requests: Sequence[RateLimitReq],
            client_id: Optional[str] = None) -> List[RateLimitResp]:
        """Batch relay from a peer; we must be authoritative for every key
        (gubernator.go:210-227)."""
        if len(requests) > MAX_BATCH_SIZE:
            raise BatchTooLargeError(
                f"'PeerRequest.rate_limits' list too large; max size is '{MAX_BATCH_SIZE}'")
        valid: List[RateLimitReq] = []
        slots: List[int] = []
        out: List[Optional[RateLimitResp]] = [None] * len(requests)
        for i, r in enumerate(requests):
            if r.algorithm not in (Algorithm.TOKEN_BUCKET,
                                   Algorithm.LEAKY_BUCKET, Algorithm.GCRA,
                                   Algorithm.SLIDING_WINDOW,
                                   Algorithm.CONCURRENCY):
                out[i] = RateLimitResp(
                    error=f"invalid rate limit algorithm '{r.algorithm}'")
                continue
            if r.behavior == Behavior.GLOBAL:
                self.global_mgr.queue_update(r)
            valid.append(r)
            slots.append(i)
        if valid:
            resps = await self.batcher.submit_now(valid)
            for i, resp in zip(slots, resps):
                out[i] = resp
                # leases acquired over the peer lane attribute to the
                # forwarding peer: its death releases them (health.py)
                self._account_decision(requests[i], resp, client_id)
        return [o if o is not None else RateLimitResp() for o in out]

    async def update_peer_globals(self, globals_: Sequence) -> None:
        """Owner pushed authoritative global statuses; upsert our replicas
        (gubernator.go:199-207)."""
        await self.batcher.apply_upserts(list(globals_))

    async def read_global_status(self, probe: RateLimitReq) -> RateLimitResp:
        """Authoritative hits=0 read used by the broadcast loop
        (global.go:199-203)."""
        resp = (await self.batcher.submit_now([probe]))[0]
        if resp.error:
            # the broadcast loop must SKIP this key, not push a zeroed
            # status to every replica as authoritative (submit_now reports
            # per-item failures in-band, so surface them as an exception
            # here where a failure means "don't broadcast")
            raise RuntimeError(resp.error)
        return resp

    async def health_check(self) -> HealthCheckResp:
        """Liveness is more than the last set_peers result: a batcher that
        fail-stopped (lockstep dispatch failure — this host left the mesh)
        or an admission queue pinned at its cap means this node cannot
        serve, whatever the ring looked like when it was built."""
        if self.batcher._failed:
            return HealthCheckResp(
                status=UNHEALTHY,
                message="lockstep dispatch failed; this host left the mesh",
                peer_count=self.health.peer_count)
        if self.qos is not None and self.qos.admission.draining:
            return HealthCheckResp(
                status=UNHEALTHY,
                message="draining: node is departing the ring",
                peer_count=self.health.peer_count)
        if self.qos is not None and self.qos.admission.saturated:
            return HealthCheckResp(
                status=UNHEALTHY,
                message=(f"admission queue saturated "
                         f"({self.qos.admission.pending} pending, "
                         f"cap {self.qos.admission.max_pending})"),
                peer_count=self.health.peer_count)
        return self.health

    # ------------------------------------------------------------ membership

    def get_peer(self, key: str) -> PeerClient:
        return self._picker.get(key)

    def peer_list(self) -> List[PeerClient]:
        return self._picker.peers()

    async def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Rebuild the ring on membership change (gubernator.go:254-292).
        Unlike the reference (which leaks stale PeerClients, :276 TODO) we
        close clients for departed hosts."""
        picker = self._picker.new()
        errs: List[str] = []
        for info in peers:
            client = self._picker.get_by_host(info.address)
            if client is None:
                try:
                    client = PeerClient(self.conf.behaviors, info.address,
                                        qos=self.qos)
                except Exception:
                    errs.append(
                        f"failed to connect to peer '{info.address}'; "
                        f"consistent hash is incomplete")
                    continue
            client.is_owner = info.is_owner
            picker.add(info.address, client)

        old_hosts = {p.host for p in self._picker.peers()}
        new_hosts = {p.host for p in picker.peers()}
        departed = [self._picker.get_by_host(h) for h in old_hosts - new_hosts]

        # gate the native RPC lane CLOSED across the swap: a drain queued
        # between the picker swap and the ring install would otherwise
        # classify against the stale (or empty) C ring and decide keys this
        # node no longer owns; _sync_pipeline_ring re-opens it after the
        # new ring is installed on the engine thread
        if self.batcher.pipeline is not None:
            self.batcher.pipeline.rpc_enabled = False
        self._picker = picker
        self.metrics.cluster_peers.set(picker.size())
        self.health = HealthCheckResp(
            status=UNHEALTHY if errs else HEALTHY,
            message="|".join(errs),
            peer_count=picker.size(),
        )
        await self._sync_pipeline_ring()
        if not self.mesh_mode:
            # mesh mode replicates GLOBAL state through the in-mesh psum;
            # the gRPC async-hits/broadcast loops stay off
            self.global_mgr.start()
        log.info("Peers updated: %s", [p.address for p in peers])
        for client in departed:
            if client is not None:
                await client.close()

    async def _sync_pipeline_ring(self) -> None:
        """Keep the native RPC lane's view of the cluster consistent with
        the picker: standalone => empty ring (everything local); cluster =>
        install the consistent-hash table so the C parser classifies each
        item local-vs-forward (reference analog: the per-item
        owner-vs-forward split, gubernator.go:114-152).  The ring install
        runs on the engine thread, serialized with in-flight drains."""
        pipe = self.batcher.pipeline
        if pipe is None or not pipe.enabled:
            return
        import numpy as np
        loop = asyncio.get_running_loop()
        if self.mesh_mode:
            # the raw-RPC lane routes by shard: it serves a mesh whose
            # shards are all this process's own; with several hosts the
            # full path forwards each item to the host that owns its shard
            pipe.rpc_enabled = not self.engine.multiprocess
            return
        if self._picker.size() == 0:
            await loop.run_in_executor(
                self.batcher._executor, pipe.install_ring,
                np.empty(0, np.uint32), np.empty(0, np.int32), (), -1)
            pipe.rpc_enabled = True
            return
        points, peers = self._picker.ring_table()
        self_idx = next(
            (i for i, p in enumerate(peers) if getattr(p, "is_owner", False)),
            -1)
        if self_idx < 0:
            # cannot identify self on the ring: the lane cannot classify
            pipe.rpc_enabled = False
            return
        await loop.run_in_executor(
            self.batcher._executor, pipe.install_ring,
            np.asarray(points, np.uint32),
            np.arange(len(points), dtype=np.int32), tuple(peers), self_idx)
        pipe.rpc_enabled = True

    # ------------------------------------------------------- self-healing

    async def rehome(self, hosts: Sequence[str],
                     direction: str = "down") -> None:
        """Rebuild the ring around the given membership (the failure
        detector's view) and migrate re-homed resident keys.  The detector
        calls this with the current membership minus a confirmed-down peer
        (its keyspace spreads over the survivors; its own state restarts
        cold there — the hint buffer covers the GLOBAL hits meanwhile) or
        plus a recovered one."""
        old_hosts = [p.host for p in self.peer_list()]
        new_hosts = sorted(set(hosts))
        if sorted(old_hosts) == new_hosts:
            return
        await self.set_peers([
            PeerInfo(address=h, is_owner=(h == self.advertise_address))
            for h in new_hosts])
        try:
            await self.migrate_keys(old_hosts, new_hosts)
        except Exception as e:
            # the ring is already rewired — serving with cold keys on the
            # new owners beats refusing to re-home
            log.error("rehome: migration failed (keys restart cold): %s", e)
        self.metrics.observe_rehome(direction)
        log.warning("ring re-homed (%s): %s -> %s", direction,
                    sorted(old_hosts), new_hosts)

    def on_peer_recovered(self, host: str) -> int:
        """Detector callback: the peer answers probes again — replay its
        hinted GLOBAL payloads (ownership re-resolved at replay time)."""
        return self.global_mgr.replay_hints(host)

    async def drain(self, timeout: float = 5.0,
                    now_fn=time.monotonic, sleep=asyncio.sleep) -> bool:
        """Graceful-departure phase: close admission intake (new work is
        shed in-band with reason `draining`) and wait — bounded by
        `timeout` — for already-admitted decisions to finish.  Returns
        True when the queue emptied in time."""
        if self.qos is not None:
            self.qos.admission.close_intake()
        deadline = now_fn() + timeout
        while self.qos is not None and self.qos.admission.pending > 0:
            if now_fn() >= deadline:
                log.warning("drain: %d decisions still pending at timeout",
                            self.qos.admission.pending)
                return False
            await sleep(0.01)
        return True

    # ------------------------------------------------------- state lifecycle

    async def _quiesced(self, fn):
        """Run engine-mutating work on the batcher's single dispatch
        thread: serialized with every in-flight window, exactly like
        apply_global_registration — the quiesce point for snapshot/restore
        and migration."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self.batcher._executor, fn)

    async def export_snapshot(self, layout: str = "auto", now=None):
        """Quiesced device->host export (state/snapshot.ArenaSnapshot).
        The concurrency-lease book rides along (optional npz keys)."""
        snap = await self._quiesced(
            lambda: self.engine.export_state(now=now, layout=layout))
        snap.leases = self.leases.export_rows()
        return snap

    async def save_snapshot(self, path: str, layout: str = "auto") -> int:
        """Export + atomic write; returns bytes written.  The quiesce pause
        covers only the device->host export — serialization and file I/O
        run off the dispatch thread."""
        import time as _time
        from gubernator_tpu.state import snapshot as snapmod
        start = _time.monotonic()
        snap = await self.export_snapshot(layout)
        size = snapmod.save(snap, path)
        self.metrics.observe_snapshot(_time.monotonic() - start, size,
                                      ok=True)
        log.info("snapshot: %d keys, %d bytes -> %s", snap.total_keys(),
                 size, path)
        return size

    async def export_snapshot_bytes(self, layout: str = "auto") -> bytes:
        from gubernator_tpu.state import snapshot as snapmod
        return snapmod.dumps(await self.export_snapshot(layout))

    async def restore_snapshot_bytes(self, data: bytes,
                                     rebase_to=None) -> int:
        """Parse + quiesced import; returns the number of restored keys.
        Raises SnapshotError on a bad blob (callers decide whether a cold
        start is acceptable — restore-on-boot degrades, an explicit admin
        restore must surface the failure)."""
        from gubernator_tpu.state import snapshot as snapmod
        snap = snapmod.loads(data)
        await self._quiesced(
            lambda: self.engine.import_state(snap, rebase_to=rebase_to))
        if snap.leases:
            self.leases.import_rows(snap.leases)
        return snap.total_keys()

    async def transfer_buckets(self, payload: bytes) -> bytes:
        """Dest side of live migration: import shipped rows, never
        clobbering a fresher local entry (engine.import_rows)."""
        from gubernator_tpu.state import migrate
        regular, global_, leases = migrate.decode_rows(payload)
        now = millisecond_now()
        imp = sk = gimp = gsk = 0
        if regular:
            imp, sk = await self._quiesced(
                lambda: self.engine.import_rows(regular, now=now))
        if global_:
            gimp, gsk = await self._quiesced(
                lambda: self.engine.import_global_rows(global_, now=now))
        if leases:
            # re-register in-flight concurrency leases under the new owner
            # (the device free-slot counters arrived with the arena rows)
            self.leases.import_rows(
                (r[0], r[1], r[2], r[3]) for r in leases)
            for r in leases:
                if len(r) >= 8 and r[4]:
                    self._lease_tmpl[r[0]] = RateLimitReq(
                        name=str(r[4]), unique_key=str(r[5]),
                        limit=int(r[6]), duration=int(r[7]),
                        algorithm=Algorithm.CONCURRENCY)
            log.info("migration import: %d lease rows re-registered",
                     len(leases))
        self.metrics.observe_migration(imported=imp + gimp,
                                       skipped_stale=sk + gsk)
        if imp or gimp or sk or gsk:
            log.info("migration import: %d rows (+%d GLOBAL), "
                     "%d stale skipped", imp, gimp, sk + gsk)
        return migrate.encode_ack(imp, sk, gimp, gsk)

    async def migrate_keys(self, old_hosts: Sequence[str],
                           new_hosts: Sequence[str]) -> dict:
        """Source side of live migration, run after set_peers installed the
        NEW ring: diff old->new ownership over the keys resident here, ship
        each re-homed key's live bucket row to its new owner, then drop the
        moved regular keys locally.  GLOBAL keys re-register on the new
        owner but keep their local replica (every node serves GLOBAL reads).

        Returns {"moved", "gmoved", "imported", "skipped_stale"} totals."""
        from gubernator_tpu.state import migrate
        keys = await self._quiesced(self.engine.local_keys)
        gkeys = await self._quiesced(self.engine.global_keys)
        moved = migrate.ownership_diff(keys, old_hosts, new_hosts)
        gmoved = migrate.ownership_diff(gkeys, old_hosts, new_hosts)
        # keys this node no longer owns move OUT; anything re-homed TO this
        # node is someone else's export
        self_host = self.advertise_address
        totals = {"moved": 0, "gmoved": 0, "imported": 0, "skipped_stale": 0}
        for dest in sorted(set(moved) | set(gmoved)):
            if dest == self_host:
                continue
            dkeys = moved.get(dest, [])
            dgkeys = gmoved.get(dest, [])
            rows = await self._quiesced(
                lambda ks=dkeys: self.engine.export_rows(ks))
            grows = await self._quiesced(
                lambda ks=dgkeys: self.engine.export_global_rows(ks))
            lrows = []
            for key, client, count, expire in self.leases.export_rows(
                    dkeys):
                tmpl = self._lease_tmpl.get(key)
                lrows.append([key, client, count, expire]
                             + ([tmpl.name, tmpl.unique_key, tmpl.limit,
                                 tmpl.duration] if tmpl is not None
                                else ["", "", 0, 0]))
            peer = self._picker.get_by_host(dest)
            if peer is None:
                log.warning("migration: new owner %s not connected; "
                            "%d keys restart cold there", dest,
                            len(dkeys) + len(dgkeys))
                continue
            ack = migrate.decode_ack(await peer.transfer_buckets(
                migrate.encode_rows(rows, grows, lrows)))
            # moved regular keys leave the host table either way: the dest
            # is authoritative now (a stale skip means it was ALREADY
            # fresher), and routing no longer brings them here
            await self._quiesced(
                lambda ks=dkeys: self.engine.remove_keys(ks))
            self.leases.drop_keys(dkeys)
            totals["moved"] += len(dkeys)
            totals["gmoved"] += len(dgkeys)
            totals["imported"] += ack["imported"] + ack["gimported"]
            totals["skipped_stale"] += (ack["skipped_stale"]
                                        + ack["gskipped_stale"])
        self.metrics.observe_migration(moved=totals["moved"]
                                       + totals["gmoved"])
        if totals["moved"] or totals["gmoved"]:
            log.info("migration out: %s", totals)
        return totals

    async def aclose(self) -> None:
        """Async close: flush the GlobalManager FIRST (a clean shutdown
        must not drop queued aggregated hits — the old stop()-only path
        did), then tear down.  `close()` remains for sync embedders and
        keeps the flush-less behavior only because it cannot await."""
        try:
            await self.global_mgr.flush()
        except Exception as e:
            log.error("global flush on close failed: %s", e)
        self.close()

    def close(self) -> None:
        self.global_mgr.stop()
        self.devprof.close()
        self.batcher.close()
