"""The plain reference, put in the program's place, with one guarantee broken.

The configurations state no numeric precision; they state a guarantee: every
decision equals the serial per-key application of the acknowledged requests.
The step that would tempt a later PR is to answer every request of a window
from the rows as they stood before the window (no in-window replay of a hot
key's duplicates) and to commit the window's hits afterwards.  That is
`--mode stale`, the control that `correct` has to fail.  Two more modes plant
the faults a served cell can have: `frozen` answers correctly but leaves the
state unchanged in every fourth window; `altered` changes one answer of each
reply in every fourth window, where it is produced.  `sound` breaks nothing (the reference
served as it is), to show the comparison passes what it should.

Items that ask for Behavior GLOBAL are held to the other guarantee, stale
then consistent, and every mode serves them by its rule (`global_window` of
the reference: all answers of a window read the row as it stood before it,
the window's summed hits land once after it), so for them `stale` is the
sound way.  Three modes break that guarantee the way a later PR would be
tempted to: `lossy` (the summed hits of every fourth window never land: a
dropped psum contribution), `late` (a window's hits land two windows on:
staler than one drain, so a request sent after another's reply was received
does not see it) and `serial` (GLOBAL items answered serially, each showing
its own hit: exact, which is not what the configuration states).

It speaks the daemon's protocol as far as the harness uses it: GetRateLimits
over gRPC, /v1/HealthCheck, /metrics and /v1/admin/debug over HTTP, the
device report in $BENCH_INFO_FILE, SIGTERM to stop.  No JAX, no chip.
"""

import argparse
import asyncio
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import wire  # noqa: E402
from benchmark.reference import serial  # noqa: E402

GLOBAL = 2          # Behavior GLOBAL on the wire


class ControlServer:
    def __init__(self, mode, tick_ms):
        self.mode, self.tick = mode, tick_ms / 1000.0
        self.store = serial.SerialStore()
        self.queue = []
        self.windows = 0
        self.altered = 0
        self.landing = []           # late: summed hits that have yet to land

    async def get_rate_limits(self, data, context):
        fut = asyncio.get_running_loop().create_future()
        self.queue.append((wire.GetRateLimitsReq.FromString(data), fut))
        return await fut

    def window(self, batch):
        """One batching window over every RPC that arrived in the tick."""
        self.windows += 1
        now = time.time_ns() // 1_000_000
        rows = self.store.rows
        faulty = self.windows % 4 == 0
        stale = {} if self.mode == "stale" else None
        undo = {} if (self.mode == "frozen" and faulty) else None
        sums = {}                   # GLOBAL keys: the window's requests
        for msg, fut in batch:
            out = wire.GetRateLimitsResp()
            for r in msg.requests:
                key = (r.name, r.unique_key)
                if undo is not None and key not in undo:
                    old = rows.get(key)
                    undo[key] = old.copy() if old is not None else None
                ask = (r.hits, r.limit, r.duration, r.algorithm)
                if r.behavior == GLOBAL and self.mode != "serial":
                    # the rule's read: the row as it stood before the window
                    # (nothing of the family lands until the reads are done)
                    old = rows.get(key)
                    _, (resp,) = serial.global_window(
                        old.copy() if old is not None else None, [ask], now)
                    sums.setdefault(key, []).append(ask)
                elif stale is not None:
                    if key not in stale:
                        old = rows.get(key)
                        stale[key] = old.copy() if old is not None else None
                    old = stale[key]
                    _, resp = serial.apply(
                        old.copy() if old is not None else None, *ask, now)
                    self.store.hit(key, *ask, now)
                else:
                    resp = self.store.hit(key, *ask, now)
                out.responses.add(status=resp[0], limit=resp[1],
                                  remaining=resp[2], reset_time=resp[3])
            if self.mode == "altered" and faulty and len(out.responses):
                self.altered += 1
                one = out.responses[self.altered % len(out.responses)]
                one.remaining = one.remaining + 1 if one.remaining < one.limit - 1 \
                    else one.remaining - 1
            fut.set_result(out.SerializeToString())
        # the windows' summed hits land: once, after the reads
        if self.mode == "late":
            self.landing.append(sums)
            sums = self.landing.pop(0) if len(self.landing) > 2 else {}
        if not (self.mode == "lossy" and faulty):
            for key, asks in sums.items():
                rows[key], _ = serial.global_window(rows.get(key), asks, now)
        if undo is not None:
            for key, old in undo.items():
                if old is None:
                    rows.pop(key, None)
                else:
                    rows[key] = old

    async def pump(self):
        while True:
            await asyncio.sleep(self.tick)
            if self.queue:
                batch, self.queue = self.queue, []
                self.window(batch)


async def amain(mode, tick_ms):
    import grpc
    from aiohttp import web
    ctl = ControlServer(mode, tick_ms)
    handler = grpc.method_handlers_generic_handler("pb.gubernator.V1", {
        "GetRateLimits": grpc.unary_unary_rpc_method_handler(
            ctl.get_rate_limits, request_deserializer=None,
            response_serializer=None)})
    server = grpc.aio.server(options=[
        ("grpc.max_receive_message_length", -1),
        ("grpc.max_send_message_length", -1)])
    server.add_generic_rpc_handlers((handler,))
    server.add_insecure_port(os.environ["GUBER_GRPC_ADDRESS"])
    await server.start()

    app = web.Application()
    app.router.add_get("/v1/HealthCheck", lambda r: web.json_response(
        {"status": "healthy"}))
    app.router.add_get("/metrics", lambda r: web.Response(
        text=f"guber_tpu_windows_total {ctl.windows}\n"))
    app.router.add_get("/v1/admin/debug", lambda r: web.json_response(
        {"control": mode, "windows": ctl.windows}))
    app.router.add_post("/v1/admin/profile", lambda r: web.json_response(
        {"armed": False, "error": "the control has no device to trace"}))
    runner = web.AppRunner(app)
    await runner.setup()
    host, port = os.environ["GUBER_HTTP_ADDRESS"].rsplit(":", 1)
    await web.TCPSite(runner, host, int(port)).start()

    with open(os.environ["BENCH_INFO_FILE"], "w") as f:
        json.dump({"platform": "control", "kind": "reference:" + mode,
                   "count": 0, "memory_peak_bytes": 0}, f)
    pump = asyncio.create_task(ctl.pump())
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    pump.cancel()
    await server.stop(0.5)
    await runner.cleanup()


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=("sound", "stale", "frozen", "altered",
                                      "lossy", "late", "serial"),
                   required=True)
    p.add_argument("--tick-ms", type=float, default=5.0)
    a = p.parse_args()
    asyncio.run(amain(a.mode, a.tick_ms))
