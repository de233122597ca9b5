"""The benchmark's harness: one cell, once, against the served path.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is data, found by the name BENCHMARK.json gives:

  benchmark/configs/<config>.json      sizes, daemon settings, guarantees
  benchmark/reference/<config>.py      that configuration's plain reference
  benchmark/traffic/<traffic>.json     how RPCs are formed and sent
  benchmark/cells/<cell>.json          optional: a cell's own numbers
                                       (the fixed rate of an open-loop cell)
  benchmark/layer_metrics/<base>.json  how a per-layer metric is read; a
                                       metric `<base>.<suffix>` of
                                       BENCHMARK.json uses <base>.json

This process never imports JAX.  The daemon (benchmark/serve.py) is a child
that alone holds the chip; the load generators (benchmark/loadgen.py) are
children pinned to cores of their own; the trace is reduced in a child too
(benchmark/reduce_trace.py), after the daemon has gone.
"""

import importlib.util
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check as checker  # noqa: E402
from benchmark import traffic  # noqa: E402

T_PROCESS_START = time.time()


class BenchError(RuntimeError):
    pass


def say(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- data


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Bench:
    """BENCHMARK.json and the data files it names, under one root."""

    def __init__(self, root=ROOT):
        self.root = root
        self.data = os.path.join(root, "benchmark")
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.workloads = {w["name"]: w for w in self.spec["workloads"]}
        self.configs = {c["name"]: c for c in self.spec["configs"]}

    def cell(self, name):
        if name not in self.workloads:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        w = self.workloads[name]
        cfg = _load_json(os.path.join(self.root, self.configs[w["config"]]["file"]))
        mix = _load_json(os.path.join(self.data, "traffic", w["traffic"] + ".json"))
        own = os.path.join(self.data, "cells", name + ".json")
        if os.path.exists(own):
            mix.update(_load_json(own))
        if mix["loop"] == "open" and "rate_rps" not in mix:
            raise BenchError(f"{name}: an open-loop cell needs rate_rps in "
                             f"benchmark/cells/{name}.json")
        family = cfg["keyspace"].get("global")
        if float(mix.get("global_item_share", 0.0)) > 0 and not family:
            raise BenchError(
                f"{name}: the mix has a global_item_share, and the keyspace "
                f"of configuration {w['config']!r} has no `global` block")
        if family and "global_window" not in self.reference_functions(w["config"]):
            raise BenchError(
                f"{name}: the keyspace has a `global` block, and benchmark/"
                f"reference/{w['config']}.py has no `global_window` rule")
        return {"name": name, "chips": int(w["chips"]), "config": cfg,
                "mix": mix, "config_name": w["config"]}

    def reference_functions(self, config_name):
        """What a configuration's reference module states: `apply`, the
        serial rule, and where it serves GLOBAL keys `global_window`."""
        path = os.path.join(self.data, "reference", config_name + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_reference_" + config_name.replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return {k: getattr(mod, k) for k in ("apply", "global_window")
                if callable(getattr(mod, k, None))}

    def reference(self, config_name):
        return self.reference_functions(config_name)["apply"]

    def metrics_for(self, cell_name, kind):
        """Names of the `end_to_end` or `per_layer` metrics this cell reports."""
        return [m["name"] for m in self.spec[kind]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_file(self, metric):
        for base in (metric, metric.rsplit(".", 1)[0]):
            path = os.path.join(self.data, "layer_metrics", base + ".json")
            if os.path.exists(path):
                return _load_json(path)
        raise BenchError(f"no benchmark/layer_metrics file for {metric!r}")

    def peaks(self, kind):
        table = _load_json(os.path.join(self.data, "peaks.json"))
        if kind not in table["devices"]:
            raise BenchError(f"device kind {kind!r} is not in peaks.json")
        return table["devices"][kind]


# ----------------------------------------------------------------- server


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def http_post(url, body, timeout=30.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read() or b"{}")


def parse_prom(text):
    """Prometheus text -> {(name, (sorted label pairs)): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        name, labels = head, ()
        if "{" in head:
            name, _, rest = head.partition("{")
            pairs = []
            for kv in rest.rstrip("}").split(","):
                if "=" in kv:
                    k, _, v = kv.partition("=")
                    pairs.append((k.strip(), v.strip().strip('"')))
            labels = tuple(sorted(pairs))
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def split_cores(n_gen):
    """Cores for the server, for each generator and for this process:
    disjoint, so the generators never take the server's time."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < n_gen + 3:
        return {"server": cores, "generators": [None] * n_gen, "harness": cores}
    gens = cores[-(n_gen + 1):-1]
    return {"server": cores[:-(n_gen + 1)], "generators": gens,
            "harness": [cores[-1]]}


class Server:
    """The daemon as a child process, and what it says over HTTP."""

    def __init__(self, cfg, workdir, cores, argv=None, extra_env=None):
        self.cfg, self.workdir, self.cores = cfg, workdir, cores
        self.grpc = f"127.0.0.1:{free_port()}"
        self.http = f"127.0.0.1:{free_port()}"
        self.info_file = os.path.join(workdir, "server_info.json")
        self.argv = argv or [sys.executable, os.path.join(HERE, "serve.py")]
        self.extra_env = extra_env or {}
        self.proc = None
        self.log = open(os.path.join(workdir, "server.log"), "wb")

    PLACEHOLDER = re.compile(r"\{([^{}]*)\}")

    def fill_in(self, value):
        """A `daemon_env` value with its placeholders filled for this run:
        {grpc} and {http}, the run's own addresses, and {port}, a fresh free
        port each time it stands.  A value without braces is passed as it is."""
        def one(m):
            if m.group(1) == "grpc":
                return self.grpc
            if m.group(1) == "http":
                return self.http
            if m.group(1) == "port":
                return str(free_port())
            raise BenchError(f"daemon_env: no placeholder {m.group(0)!r} "
                             f"(there are {{grpc}}, {{http}} and {{port}})")
        return self.PLACEHOLDER.sub(one, str(value))

    def start(self):
        env = dict(os.environ)
        env.update({k: self.fill_in(v)
                    for k, v in self.cfg.get("daemon_env", {}).items()})
        env.update(self.extra_env)
        env.update({"GUBER_GRPC_ADDRESS": self.grpc,
                    "GUBER_HTTP_ADDRESS": self.http,
                    "BENCH_INFO_FILE": self.info_file})
        cores = self.cores

        def pin():
            if cores:
                os.sched_setaffinity(0, set(cores))
        self.proc = subprocess.Popen(self.argv, env=env, cwd=ROOT,
                                     stdout=self.log, stderr=self.log,
                                     preexec_fn=pin)

    def info(self):
        try:
            return _load_json(self.info_file)
        except (OSError, ValueError):
            return None

    def wait_ready(self, device_ok, timeout):
        """Wait for the device report, judge it, then wait for the port."""
        end = time.time() + timeout
        judged = False
        while time.time() < end:
            if self.proc.poll() is not None:
                raise BenchError(f"the daemon exited with {self.proc.returncode}"
                                 f" before serving:\n{self.tail()}")
            if not judged:
                info = self.info()
                if info is not None:
                    device_ok(info)
                    judged = True
            else:
                try:
                    http_get(f"http://{self.http}/v1/HealthCheck", 2.0)
                    return
                except Exception:
                    pass
            time.sleep(0.1)
        raise BenchError(f"the daemon did not serve within {timeout}s:\n"
                         f"{self.tail()}")

    def tail(self, n=3000):
        try:
            with open(os.path.join(self.workdir, "server.log"), "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return ""

    def said(self, words):
        """The first line of the daemon's log that holds `words`, or None."""
        try:
            with open(os.path.join(self.workdir, "server.log"), "rb") as f:
                for line in f:
                    if words.encode() in line:
                        return line.decode(errors="replace").strip()
        except OSError:
            pass
        return None

    def debug(self):
        return json.loads(http_get(f"http://{self.http}/v1/admin/debug"))

    def prom(self):
        return parse_prom(http_get(f"http://{self.http}/metrics").decode())

    def cpu_seconds(self):
        with open(f"/proc/{self.proc.pid}/stat") as f:
            parts = f.read().rsplit(")", 1)[1].split()
        return (int(parts[11]) + int(parts[12])) / os.sysconf("SC_CLK_TCK")

    def snapshot(self):
        return {"t": time.time(), "prom": self.prom(), "debug": self.debug(),
                "cpu_s": self.cpu_seconds()}

    def stop(self, timeout=60.0):
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()
        return self.info()


# -------------------------------------------------------------- generators


class Generators:
    """The load-generator children of one phase."""

    def __init__(self, cell, server, seed, workdir, cores, mode, tag,
                 per_proc=None, **job):
        mix = cell["mix"]
        self.n = int(mix["generator_procs"])
        self.procs, self.outs = [], []
        conns = int(mix["connections"]) // self.n
        for i in range(self.n):
            out = os.path.join(workdir, f"gen_{tag}_{i}.npz")
            spec = dict(job, address=server.grpc, mix=mix, seed=int(seed),
                        keyspace=cell["config"]["keyspace"], proc=i,
                        nprocs=self.n, conns=max(conns, 1), mode=mode, out=out,
                        cpu=cores[i] if i < len(cores) else None)
            if per_proc:
                spec.update(per_proc[i])
            path = os.path.join(workdir, f"job_{tag}_{i}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            self.outs.append(out)
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"), path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT,
                text=True))

    def expect(self, word):
        for p in self.procs:
            line = p.stdout.readline().strip()
            if line != word:
                raise BenchError(f"a generator said {line!r}, not {word!r}")

    def tell(self, line):
        for p in self.procs:
            if p.poll() is None:
                p.stdin.write(line + "\n")
                p.stdin.flush()

    def wait(self, timeout):
        end = time.time() + timeout
        for p in self.procs:
            try:
                p.wait(max(1.0, end - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        bad = [p.returncode for p in self.procs if p.returncode]
        if bad:
            raise BenchError(f"generators exited with {bad}")
        return [dict(np.load(o)) for o in self.outs]

    def kill(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()


def merge_errors(results):
    """What refused or failed RPCs said, over all generators of a run."""
    out = {}
    for r in results:
        for said, n in json.loads(str(r["errors"])).items():
            out[said] = out.get(said, 0) + n
    return out


def merge_ops(results):
    keys = [k for k in results[0] if k.startswith("op_")]
    ops = {k[3:]: np.concatenate([r[k] for r in results]) for k in keys}
    tainted = np.concatenate([r["tainted"] for r in results])
    return ops, tainted


# ------------------------------------------------------------------ phases


def fill(cell, server, seed, workdir, cores):
    """Load the arena: ranks 1..fill_keys once, 1000 to an RPC (set-up), and
    after them every key of the `global` family, asked for as the window
    asks for it, so that a key's first use lies in set-up."""
    n = int(cell["config"].get("fill_keys", 0))
    ks = cell["config"]["keyspace"]
    family = int(ks["global"]["keys"]) if ks.get("global") else 0
    if n <= 0 and family <= 0:
        return None, 0.0
    t = time.time()
    mix = cell["mix"]
    procs = int(mix["generator_procs"])
    edges = np.linspace(1, n + 1, procs + 1).astype(int)
    per_proc = [{"fill_lo": int(edges[i]), "fill_hi": int(edges[i + 1])}
                for i in range(procs)]
    if family:
        first = int(ks["population"]) + 1
        edges = np.linspace(first, first + family, procs + 1).astype(int)
        for i in range(procs):
            per_proc[i]["fill_more"] = [[int(edges[i]), int(edges[i + 1])]]
    fcell = dict(cell, mix=dict(mix, connections=int(mix["fill_connections"])))
    gens = Generators(fcell, server, seed, workdir, cores, "fill", "fill",
                      per_proc=per_proc)
    try:
        results = gens.wait(600.0)
    finally:
        gens.kill()
    return results, time.time() - t


def measure(cell, server, seed, seconds, workdir, cores, tag="w"):
    """Warm up under the cell's own traffic for `warm_s`, then measure one
    window."""
    mix = cell["mix"]
    mode = mix["loop"]
    warm_s = float(mix["warm_s"])
    job = {"warm_schedule_s": warm_s + 10.0}
    if mode == "open":
        job["rate_rps"] = float(mix["rate_rps"])
    gens = Generators(cell, server, seed, workdir, cores, mode, tag, **job)
    try:
        gens.expect("ready")
        gens.expect("started")
        time.sleep(warm_s)
        before = server.snapshot()
        w0 = time.time() + 0.5
        w1 = w0 + seconds
        gens.tell(f"window {w0!r} {w1!r}")
        time.sleep(max(0.0, w1 - time.time()))
        after = server.snapshot()
        results = gens.wait(float(mix.get("grace_s", 10.0)) + 40.0)
    finally:
        gens.kill()
    return {"results": results, "before": before, "after": after,
            "window": (w0, w1), "setup_s": w0 - T_PROCESS_START}


def profile_active(server):
    """Is a capture still armed or being written?  While the profiler stops,
    the daemon may not answer at all: that counts as still active."""
    try:
        prof = server.debug().get("profile", {})
        return bool(prof.get("active") or prof.get("remaining", 0) > 0)
    except OSError:
        return True


def trace_after(cell, server, seed, workdir, cores, tag="t"):
    """The device trace, taken once the measured window has closed and its
    last reply is in: the cell's own traffic again, warmed up as the window
    was, then `trace_drains` drains under the profiler.  The profiler's stop
    stalls the engine for seconds, so nothing of this phase enters a metric
    the clients or the counters give, nor the comparison."""
    mix = cell["mix"]
    mode = mix["loop"]
    lead = float(mix["warm_s"])
    job = {"warm_schedule_s": lead + 5.0}
    if mode == "open":
        job["rate_rps"] = float(mix["rate_rps"])
    trace_dir = os.path.join(workdir, f"trace_{tag}")
    gens = Generators(cell, server, seed, workdir, cores, mode, tag, **job)
    try:
        gens.expect("ready")
        gens.expect("started")
        time.sleep(lead)
        http_post(f"http://{server.http}/v1/admin/profile",
                  {"drains": int(mix["trace_drains"]), "dir": trace_dir})
        end = time.time() + 60.0
        while time.time() < end and profile_active(server):
            time.sleep(0.25)
        gens.tell("stop")
        gens.wait(float(mix.get("grace_s", 10.0)) + 40.0)
    finally:
        gens.kill()
    return trace_dir


# ----------------------------------------------------------------- metrics


def pct(values, q):
    if not len(values):
        return None
    return float(np.percentile(values, q, method="higher"))


def client_stats(cell, results, window):
    """What the clients saw in the window, over all its RPCs."""
    w0, w1 = window
    mix = cell["mix"]
    items = int(mix["items_per_rpc"])
    due = np.concatenate([r["rpc_due"] for r in results])
    sent = np.concatenate([r["rpc_sent"] for r in results])
    recv = np.concatenate([r["rpc_recv"] for r in results])
    ok = np.concatenate([r["rpc_ok"] for r in results]) > 0
    out = {}
    if mix["loop"] == "open":
        issued = int(sum(int(r["win_issued"]) for r in results))
        inw = (due >= w0) & (due < w1)
        good = inw & ok
        lat = (recv[good] - due[good]) * 1e3
        failed = issued - int(good.sum())
        # a failed or unanswered RPC is as slow as its deadline
        worst = float(mix.get("rpc_timeout_s", 20.0)) * 1e3
        full = np.concatenate([lat, np.full(max(failed, 0), worst)])
        out.update(attempted=issued, failed=failed,
                   rpc_p50_ms=pct(full, 50), rpc_p95_ms=pct(full, 95),
                   rpc_p99_ms=pct(full, 99),
                   rpc_mean_ms=float(lat.mean()) if len(lat) else None,
                   gen_late_p99_ms=pct((sent[inw] - due[inw]) * 1e3, 99),
                   offered_rps=issued / (w1 - w0),
                   decisions=int(good.sum()) * items)
    else:
        inw = (recv >= w0) & (recv < w1)
        good = inw & ok
        lat = (recv[good] - sent[good]) * 1e3
        out.update(attempted=int(inw.sum()), failed=int((inw & ~ok).sum()),
                   closed_rpc_p50_ms=pct(lat, 50),
                   rpc_mean_ms=float(lat.mean()) if len(lat) else None,
                   decisions=int(good.sum()) * items,
                   decisions_per_s=int(good.sum()) * items / (w1 - w0))
    out["seconds"] = w1 - w0
    return out


def _dig(d, path):
    for part in path.split("."):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d if isinstance(d, (int, float)) else None


def _prom_delta(ctx, term):
    key = (term["prom"], tuple(sorted(term.get("labels", {}).items())))
    a, b = ctx["before"]["prom"].get(key), ctx["after"]["prom"].get(key)
    if b is None:
        return None
    return b - (a or 0.0)


def evaluate(term, ctx):
    """A layer-metric file's expression: a number, a reading, or an
    operation over terms.  Anything that finds nothing to read gives None."""
    if isinstance(term, (int, float)):
        return float(term)
    if "op" in term:
        args = [evaluate(a, ctx) for a in term["args"]]
        if any(a is None for a in args):
            return None
        op = term["op"]
        if op == "div":
            return args[0] / args[1] if args[1] else None
        if op == "mul":
            return float(np.prod(args))
        if op == "add":
            return float(sum(args))
        if op == "sub":
            return args[0] - sum(args[1:])
        raise BenchError(f"unknown op {op!r} in a layer-metric file")
    if "prom" in term:
        return _prom_delta(ctx, term)
    if "debug" in term:
        b = _dig(ctx["after"]["debug"], term["debug"])
        if term.get("how", "end") == "end" or b is None:
            return b
        return b - (_dig(ctx["before"]["debug"], term["debug"]) or 0.0)
    if "client" in term:
        return ctx["client"].get(term["client"])
    if "proc" in term:
        return ctx["after"]["cpu_s"] - ctx["before"]["cpu_s"]
    if "trace" in term:
        return (ctx.get("trace") or {}).get(term["trace"])
    if "server" in term:
        return (ctx.get("server_info") or {}).get(term["server"])
    if "peak" in term:
        return (ctx.get("peaks") or {}).get(term["peak"])
    raise BenchError(f"a layer-metric term names no reading: {term}")


def reduce_trace(trace_dir, workdir):
    """The trace, reduced by benchmark/reduce_trace.py in a process of its
    own (it loads JAX's reader, on the CPU, after the daemon has gone)."""
    out = os.path.join(workdir, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "reduce_trace.py"),
                        trace_dir, out], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        raise BenchError(f"the trace could not be reduced:\n{r.stderr[-2000:]}")
    got = _load_json(out)
    if got["busy_s"] <= 0:
        raise BenchError("the traced window holds no device operation")
    return got


# --------------------------------------------------------------------- run


def compare(bench, cell, ops, tainted, client):
    """Every number compared, beside its limit."""
    mix = cell["mix"]
    lim = mix["check"]
    ks = traffic.KeySpace(cell["config"]["keyspace"])
    ref = bench.reference_functions(cell["config_name"])
    got = checker.check(ops, tainted, ks, ref["apply"],
                        global_window=ref.get("global_window"))
    followed = max(got["followed_decisions"], 1)
    numbers = {
        "mismatched_keys": (got["mismatched_keys"], 0, "max"),
        "checked_decisions": (got["checked_decisions"],
                              int(lim["min_checked_decisions"]), "min"),
        "undecided_share": (got["undecided_decisions"] / followed,
                            float(lim["max_undecided_share"]), "max"),
        "failed_share": (client["failed"] / max(client["attempted"], 1),
                         float(lim["max_failed_share"]), "max"),
    }
    if float(mix.get("global_item_share", 0.0)) > 0:
        # a run in which no GLOBAL answer was verified cannot read correct
        numbers["global_checked_decisions"] = (
            got["global_checked_decisions"],
            int(lim["min_global_checked_decisions"]), "min")
    ok = all(v <= l if how == "max" else v >= l
             for v, l, how in numbers.values())
    compared = {k: {"value": v, "limit": l, "holds": how}
                for k, (v, l, how) in numbers.items()}
    return ok, compared, got


def run_cell(bench, name, seed, seconds, trace, device_ok, server_argv=None,
             server_env=None, keep=None, require_device_trace=True):
    """One run of one cell; returns the result line as a dict."""
    cell = bench.cell(name)
    mix = cell["mix"]
    cores = split_cores(int(mix["generator_procs"]))
    own_cores = os.sched_getaffinity(0)
    os.sched_setaffinity(0, set(cores["harness"]))
    workdir = tempfile.mkdtemp(prefix="bench_run_")
    server = Server(cell["config"], workdir, cores["server"], server_argv,
                    server_env)
    info = None
    try:
        server.start()
        server.wait_ready(lambda i: device_ok(i, cell), 1150.0)
        t_ready = time.time() - T_PROCESS_START
        fill_results, fill_s = fill(cell, server, seed, workdir,
                                    cores["generators"])
        t_filled = time.time() - T_PROCESS_START
        m = measure(cell, server, seed, seconds, workdir,
                    cores["generators"])
        trace_dir = trace_after(cell, server, seed, workdir,
                                cores["generators"]) if trace else None
        info = server.stop()
        mesh_said = server.said("mesh mode:")
    except BaseException:
        server.stop()
        say(server.tail())
        if keep is None:
            shutil.rmtree(workdir, ignore_errors=True)
        raise
    finally:
        os.sched_setaffinity(0, own_cores)
    try:
        client = client_stats(cell, m["results"], m["window"])
        ctx = {"before": m["before"], "after": m["after"], "client": client,
               "server_info": info, "peaks": None, "trace": None}
        if info and info.get("platform") == "tpu":
            ctx["peaks"] = bench.peaks(info["kind"])
        if trace:
            try:
                ctx["trace"] = reduce_trace(trace_dir, workdir)
            except BenchError as e:
                if require_device_trace:
                    raise
                ctx["trace_error"] = str(e)
        ops, tainted = merge_ops((fill_results or []) + m["results"])
        ctx["ops"], ctx["tainted"] = ops, tainted
        correct, compared, detail = compare(bench, cell, ops, tainted, client)
    finally:
        if keep is None:
            shutil.rmtree(workdir, ignore_errors=True)

    metrics = {}
    if trace:
        for mname in bench.metrics_for(name, "per_layer"):
            spec = bench.layer_file(mname)
            v = evaluate(spec["read"], ctx)
            if v is not None:
                metrics[mname] = {"value": v, "unit": spec["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in bench.spec["end_to_end"]}
        for mname in bench.metrics_for(name, "end_to_end"):
            v = m["setup_s"] if mname == "setup_s" else client.get(mname)
            if v is not None:
                metrics[mname] = {"value": v, "unit": units[mname]}

    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"],
              "memory_peak_bytes": info.get("memory_peak_bytes", 0)}
    line = {"correct": bool(correct), "attempted": client["attempted"],
            "failed": client["failed"], "metrics": metrics, "device": device}
    if trace and ctx["trace"]:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"][:10],
                             "idle_gaps": ctx["trace"]["idle_gaps"][:10]}
    line["run"] = {"cores": cores, "ready_s": t_ready, "fill_s": fill_s,
                   "warm_s": m["setup_s"] - t_filled,
                   "decisions": client["decisions"], "client": client,
                   "mesh": mesh_said, "told_lag": checker.told_lag(
                       ops, traffic.KeySpace(cell["config"]["keyspace"]),
                       m["window"]),
                   "families": {k: v for k, v in detail.items()
                                if k != "reports"},
                   "errors": merge_errors((fill_results or []) + m["results"])}
    line["compared"] = compared
    say(f"cores: server {cores['server']} generators {cores['generators']} "
        f"harness {cores['harness']}")
    for r in detail["reports"]:
        say("compare:", r)
    say("compared: " + ", ".join(
        f"{k}={v['value']} (limit {v['holds']} {v['limit']})"
        for k, v in compared.items()))
    return line, m, client, ctx
