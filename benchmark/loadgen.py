"""One load-generator process: gRPC clients over real sockets, no JAX.

Started by the harness as `python -m benchmark.loadgen <job.json>`.  The job
names the server, the configuration's keyspace, the traffic mix, the seed and
this process's index.  Modes:

  fill    send ranks [lo, hi) once, 1000 to an RPC, as fast as the server
          answers (set-up: loads the arena); with `fill_more`, further spans
          of ranks after it (the `global` family's keys);
  open    Poisson arrivals at a fixed rate, several RPCs outstanding on a
          connection; latency runs from each RPC's *due* time;
  closed  one RPC outstanding per connection, the next sent on the reply.

Protocol with the parent, one line each way: the child prints `ready` once
its tables are built and its channels are connected, then `started` when
warm-up traffic flows; the parent writes `window <t0> <t1>` (epoch seconds)
and the child measures exactly that span, waits `grace_s` for stragglers,
writes its result file and exits.  `stop` ends a warm-up without a window.

For the comparison that decides `correct`, the child records every answer
given for the ranks the run follows (traffic.sampled_ranks_mask): when it
was sent and received, and what the server said.
"""

import asyncio
import json
import os
import re
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic, wire  # noqa: E402

OVER = 1


class Recorder:
    """Answers for the followed ranks, in growing numpy columns."""

    COLS = (("rank", np.int64), ("sent", np.float64), ("recv", np.float64),
            ("status", np.int8), ("remaining", np.int64),
            ("reset", np.int64), ("hint", np.int64))

    def __init__(self, n=1 << 16):
        self.n = 0
        self.cols = {k: np.zeros(n, dtype=t) for k, t in self.COLS}
        self.tainted = []

    def _grow(self, need):
        size = len(self.cols["rank"])
        while size < need:
            size *= 2
        for k in self.cols:
            self.cols[k] = np.resize(self.cols[k], size)

    def add(self, ranks, pos, responses, sent, recv, hint):
        k = self.n
        if k + len(pos) > len(self.cols["rank"]):
            self._grow(k + len(pos))
        c = self.cols
        st, rem, rs = c["status"], c["remaining"], c["reset"]
        for j, p in enumerate(pos):
            it = responses[p]
            st[k + j] = it.status
            rem[k + j] = it.remaining
            rs[k + j] = it.reset_time
        m = len(pos)
        c["rank"][k:k + m] = ranks
        c["sent"][k:k + m] = sent
        c["recv"][k:k + m] = recv
        c["hint"][k:k + m] = hint
        self.n = k + m

    def arrays(self):
        return {k: v[:self.n] for k, v in self.cols.items()}


class Generator:
    def __init__(self, job):
        self.job = job
        self.mix = job["mix"]
        self.ks = traffic.KeySpace(job["keyspace"])
        self.enc = traffic.ItemEncoder(self.ks)
        self.seed = int(job["seed"])
        self.proc, self.nprocs = int(job["proc"]), int(job["nprocs"])
        self.check = self.mix.get("check", {})
        self.rec = Recorder()
        self.timeout = float(self.mix.get("rpc_timeout_s", 20.0))
        self.grace = float(self.mix.get("grace_s", 10.0))
        # per-RPC rows of the measured window and of the whole run
        self.rpc = {k: [] for k in ("due", "sent", "recv", "ok")}
        self.window = None
        self.stopping = False
        self.win_issued = 0
        self.errors = {}            # what refused answers said, and how often

    # ------------------------------------------------------------ plumbing

    def connect(self):
        import grpc
        opts = [("grpc.use_local_subchannel_pool", 1),
                ("grpc.max_receive_message_length", -1),
                ("grpc.max_send_message_length", -1)]
        n = int(self.job["conns"])
        self.channels = [grpc.aio.insecure_channel(self.job["address"],
                                                   options=opts)
                         for _ in range(n)]
        self.calls = [ch.unary_unary(
            wire.METHOD, request_serializer=None,
            response_deserializer=wire.GetRateLimitsResp.FromString)
            for ch in self.channels]

    def prepare_rows(self, pool):
        """Per pool row: which positions the comparison follows, and a few
        leaky positions of hot ranks whose OVER_LIMIT answer reveals the
        drain's timestamp (a hint for ordering, never a constraint)."""
        self.pool = pool
        mask = traffic.sampled_ranks_mask(pool, self.check, self.seed,
                                          self.ks.population)
        self.row_pos, self.row_ranks, self.row_reveal = [], [], []
        ks = self.ks
        for i in range(pool.shape[0]):
            pos = np.flatnonzero(mask[i])
            self.row_pos.append(pos.tolist())
            self.row_ranks.append(pool[i, pos])
            if len(pos):
                hot = np.flatnonzero((pool[i] <= 64))[:24]
                rv = [(int(p), max(ks.duration_ms // ks.limit(int(pool[i, p])), 1))
                      for p in hot if ks.algo(int(pool[i, p])) == traffic.LEAKY]
                self.row_reveal.append(rv[:6])
            else:
                self.row_reveal.append(())

    async def send(self, call, row, due):
        """One RPC; returns (ok, recv time)."""
        body = self.enc.rpc(self.pool[row].tolist())
        sent = time.time()
        ok, responses = True, None
        try:
            resp = await call(body, timeout=self.timeout)
            recv = time.time()
            responses = resp.responses
            if len(responses) != self.pool.shape[1]:
                ok = False
            else:
                for it in responses:
                    if it.error or it.metadata:
                        ok = False
                        self.note(it.error
                                  or f"metadata {sorted(it.metadata)}".replace("'", ""))
                        break
        except asyncio.CancelledError:
            # never answered within the grace period: may have been applied
            self.rec.tainted.extend(self.row_ranks[row].tolist())
            raise
        except Exception as e:
            recv = time.time()
            ok = False
            self.note(f"{type(e).__name__}: {e}")
        pos = self.row_pos[row]
        if pos:
            if ok:
                hint = 0
                for p, rate in self.row_reveal[row]:
                    it = responses[p]
                    if it.status == OVER and it.reset_time:
                        hint = it.reset_time - rate
                        break
                self.rec.add(self.row_ranks[row], pos, responses,
                             sent * 1e3, recv * 1e3, hint)
            else:
                # an unanswered or refused request may or may not have been
                # applied: its keys leave the comparison
                self.rec.tainted.extend(self.row_ranks[row].tolist())
        r = self.rpc
        r["due"].append(due if due is not None else sent)
        r["sent"].append(sent)
        r["recv"].append(recv)
        r["ok"].append(ok)
        return ok, recv

    def note(self, said):
        said = re.sub(r"'[^']*'", "'*'", said)[:200]    # without the key
        if said in self.errors or len(self.errors) < 8:
            self.errors[said] = self.errors.get(said, 0) + 1

    # --------------------------------------------------------------- modes

    async def run_fill(self):
        spans = [(int(self.job["fill_lo"]), int(self.job["fill_hi"]))]
        spans += [(int(a), int(b)) for a, b in self.job.get("fill_more", ())]
        per = 1000
        starts = [(a, hi) for lo, hi in spans for a in range(lo, hi, per)]
        rows = np.zeros((len(starts), per), dtype=np.int64)
        for i, (a, hi) in enumerate(starts):
            b = min(a + per, hi)
            rows[i, :b - a] = np.arange(a, b)
            rows[i, b - a:] = a          # pad the last row with its first rank
        self.prepare_rows(rows)
        nxt = iter(range(len(starts)))

        async def worker(call):
            for i in nxt:
                await self.send(call, i, None)
        print("started", flush=True)
        await asyncio.gather(*[worker(c) for c in self.calls])

    async def pace(self, offsets, t0, rows, cutoff=None):
        """Open loop: fire each RPC at its due time, never waiting on a
        reply.  `cutoff()` gives the time at which a warm-up schedule ends
        (None while that is not known yet)."""
        loop = asyncio.get_running_loop()
        tasks = []
        ncalls = len(self.calls)
        for i, off in enumerate(offsets):
            due = t0 + float(off)
            while True:
                end = cutoff() if cutoff else None
                if end is not None and due >= end:
                    return tasks
                delay = due - time.time()
                if delay <= 0:
                    break
                # short naps while a warm-up may still be told to end
                await asyncio.sleep(min(delay, 0.05) if cutoff else delay)
            tasks.append(loop.create_task(
                self.send(self.calls[i % ncalls], rows[i % len(rows)], due)))
        return tasks

    async def run_open(self):
        mix, rate = self.mix, float(self.job["rate_rps"])
        nrows = self.pool.shape[0]
        warm = traffic.arrival_offsets(mix, rate, self.seed + 1, self.proc,
                                       self.nprocs,
                                       float(self.job["warm_schedule_s"]))
        nwarm = len(warm)
        t0 = time.time() + 0.2
        print("started", flush=True)

        def cutoff():
            if self.stopping:
                return 0.0
            return self.window[0] if self.window else None
        tasks = await self.pace(warm, t0, np.arange(nwarm) % nrows, cutoff)
        while self.window is None and not self.stopping:
            await asyncio.sleep(0.02)
        if self.window and not self.stopping:
            w0, w1 = self.window
            offs = traffic.arrival_offsets(mix, rate, self.seed, self.proc,
                                           self.nprocs, w1 - w0)
            self.win_issued = len(offs)
            rows = (nwarm + np.arange(len(offs))) % nrows
            tasks += await self.pace(offs, w0, rows)
        if tasks:
            _, late = await asyncio.wait(tasks, timeout=self.grace)
            for t in late:          # never answered: counted by the parent
                t.cancel()
            if late:
                await asyncio.wait(late, timeout=2.0)

    async def run_closed(self):
        nrows = self.pool.shape[0]
        counter = iter(range(1 << 62))
        print("started", flush=True)

        async def worker(call):
            while not self.stopping:
                if self.window and time.time() >= self.window[1]:
                    break
                await self.send(call, next(counter) % nrows, None)
        await asyncio.gather(*[worker(c) for c in self.calls])

    # ---------------------------------------------------------------- main

    def listen(self):
        for line in sys.stdin:
            parts = line.split()
            if not parts:
                continue
            if parts[0] == "window":
                self.window = (float(parts[1]), float(parts[2]))
            elif parts[0] == "stop":
                self.stopping = True
        self.stopping = True

    async def main(self):
        mode = self.job["mode"]
        cpu = self.job.get("cpu")
        if cpu is not None:
            os.sched_setaffinity(0, {int(cpu)})
        self.connect()
        if mode != "fill":
            self.prepare_rows(traffic.rpc_pool(self.ks, self.mix, self.seed,
                                               self.proc, self.nprocs))
        await asyncio.gather(*[ch.channel_ready() for ch in self.channels])
        print("ready", flush=True)
        threading.Thread(target=self.listen, daemon=True).start()
        await {"fill": self.run_fill, "open": self.run_open,
               "closed": self.run_closed}[mode]()
        await asyncio.gather(*[ch.close() for ch in self.channels])
        out = {("op_" + k): v for k, v in self.rec.arrays().items()}
        out["tainted"] = np.asarray(self.rec.tainted, dtype=np.int64)
        for k, v in self.rpc.items():
            out["rpc_" + k] = np.asarray(v, dtype=np.float64)
        out["items_per_rpc"] = np.asarray(self.pool.shape[1])
        out["window"] = np.asarray(self.window or (0.0, 0.0))
        out["win_issued"] = np.asarray(self.win_issued)
        out["errors"] = np.asarray(json.dumps(self.errors))
        np.savez(self.job["out"], **out)


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        asyncio.run(Generator(json.load(f)).main())
