"""What the instrumentation costs when it is on, measured on the chip.

    python scripts/chip_trace_cost.py sample  --cell <cell> --samples 0,0.01,1.0
    python scripts/chip_trace_cost.py capture --cell <cell> [--root <checkout>]
    python scripts/chip_trace_cost.py gaps    --trace-dir <capture> --gaps N

`sample`: for each GUBER_TRACE_SAMPLE value one daemon (the benchmark's own
set-up: benchmark/harness.py boots it, fills the arena, warms up) and
`--windows` measured windows back to back under the cell's traffic; prints
the cell's end-to-end metric per window.  `capture`: the cell's traffic,
then `POST /v1/admin/profile` as benchmark/harness.py:trace_after does it,
and the longest RPC the clients saw while the profiler started and stopped;
with `--gaps N` also the N longest idle gaps of the device in that capture,
each with its place among the traced drains and the `guber_*` host
annotations that overlap it (read with JAX's reader, on the CPU, in a child,
once the daemon has gone).

`--root` names the checkout whose daemon and harness run (default: this
one), so the same probe reads a parent commit.  It refuses a machine
without a TPU unless `--any-device` (the CPU rehearsal) is given.  One JSON
object per line on standard output.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time


def boot(harness, bench, cell_name, seed, env, any_device):
    cell = bench.cell(cell_name)
    cores = harness.split_cores(int(cell["mix"]["generator_procs"]))
    workdir = tempfile.mkdtemp(prefix="trace_cost_")
    server = harness.Server(cell["config"], workdir, cores["server"],
                            extra_env=env)

    def device_ok(info):
        if not any_device and info["platform"] != "tpu":
            raise harness.BenchError(f"no TPU: {info['platform']!r}")
    server.start()
    server.wait_ready(device_ok, 1150.0)
    harness.fill(cell, server, seed, workdir, cores["generators"])
    return cell, cores, workdir, server


def program_means(before, after, seconds):
    """What the program's own series say of the window: the mean of each
    drain stage (ms), of each request stage (ms), of the handler's RPC
    duration (ms), and the pump's holds as a share of the window (%)."""
    def delta(name, **labels):
        key = (name, tuple(sorted(labels.items())))
        return after.get(key, 0.0) - before.get(key, 0.0)

    def mean(total, count, scale=1.0):
        return total * scale / count if count else None
    out = {}
    for (name, labels) in after:
        lab = dict(labels)
        if name == "guber_tpu_stage_duration_ms_count":
            out["stage_ms." + lab["stage"]] = mean(
                delta("guber_tpu_stage_duration_ms_sum", **lab),
                delta(name, **lab))
        elif name == "guber_tpu_request_stage_requests_total":
            out["request_ms." + lab["stage"]] = mean(
                delta("guber_tpu_request_stage_seconds_total", **lab),
                delta(name, **lab), 1e3)
        elif name == "guber_tpu_pump_hold_seconds_total":
            out["pump_hold_pct." + lab["reason"]] = (
                100.0 * delta(name, **lab) / seconds)
        elif (name == "grpc_request_duration_milliseconds_count"
              and lab["method"].endswith("/GetRateLimits")):
            out["server_rpc_ms"] = mean(
                delta("grpc_request_duration_milliseconds_sum", **lab),
                delta(name, **lab))
    return out


def sample_sweep(harness, bench, a):
    for sample in a.samples.split(","):
        cell, cores, workdir, server = boot(
            harness, bench, a.cell, a.seed,
            {"GUBER_TRACE_SAMPLE": sample}, a.any_device)
        try:
            for i in range(a.windows):
                m = harness.measure(cell, server, a.seed + i, a.seconds,
                                    workdir, cores["generators"], tag=f"w{i}")
                c = harness.client_stats(cell, m["results"], m["window"])
                spans = len(server.debug().get("tracing", {})
                            .get("recent_traces", []))
                print(json.dumps({
                    "probe": "sample", "cell": a.cell, "sample": sample,
                    "window": i, "seconds": a.seconds,
                    "rpc_p50_ms": c.get("rpc_p50_ms"),
                    "decisions_per_s": c.get("decisions_per_s"),
                    "rpc_mean_ms": c.get("rpc_mean_ms"),
                    "failed": c["failed"], "attempted": c["attempted"],
                    "recent_traces": spans,
                    "program": program_means(m["before"]["prom"],
                                             m["after"]["prom"],
                                             c["seconds"])}), flush=True)
        finally:
            server.stop()
            shutil.rmtree(workdir, ignore_errors=True)


def list_gaps(a):
    """The `gaps` probe: the device's longest idle gaps in the capture under
    `--trace-dir`, read with JAX's reader (run with JAX_PLATFORMS=cpu, once
    the daemon that held the chip has gone)."""
    from benchmark import reduce_trace as rt
    planes = []
    for path in rt.find_traces(a.trace_dir):
        planes += rt.read_planes(path)
    mods, host = [], []
    for pname, lines in planes:
        for lname, events in lines:
            for name, start, dur in events:
                if dur <= 0:
                    continue
                if pname.startswith(rt.DEVICE_PLANE) and lname == rt.MODULES:
                    mods.append((start, start + dur))
                elif name.startswith(rt.ANNOTATION_PREFIX):
                    host.append((name, start, start + dur))
    mods.sort()
    gaps = []
    for i, (m0, m1) in enumerate(zip(mods, mods[1:])):
        if m1[0] <= m0[1]:
            continue
        names = {}
        for n, s, e in host:
            o = min(e, m1[0]) - max(s, m0[1])
            if o > 0:
                names[n] = names.get(n, 0) + o / 1e6
        gaps.append({"after_module": i + 1, "of": len(mods),
                     "gap_ms": (m1[0] - m0[1]) / 1e6,
                     "host_ms": {k: round(v, 3) for k, v in names.items()}})
    gaps.sort(key=lambda g: -g["gap_ms"])
    print(json.dumps({
        "modules": len(mods),
        "window_s": (mods[-1][1] - mods[0][0]) / 1e9 if mods else 0.0,
        "idle_s": sum(g["gap_ms"] for g in gaps) / 1e3,
        "longest": gaps[:a.gaps]}), flush=True)


def longest_gaps(root, trace_dir, n):
    import subprocess
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "gaps", "--root", root,
         "--trace-dir", trace_dir, "--gaps", str(n)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    if r.returncode:
        return {"error": r.stderr[-500:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def capture_stall(harness, bench, a):
    import numpy as np
    cell, cores, workdir, server = boot(harness, bench, a.cell, a.seed, {},
                                        a.any_device)
    mix = cell["mix"]
    lead = float(mix["warm_s"])
    job = {"warm_schedule_s": lead + 60.0}
    if mix["loop"] == "open":
        job["rate_rps"] = float(mix["rate_rps"])
    gens = harness.Generators(cell, server, a.seed, workdir,
                              cores["generators"], mix["loop"], "cap", **job)
    try:
        gens.expect("ready")
        gens.expect("started")
        time.sleep(lead)
        t_arm = time.time()
        harness.http_post(f"http://{server.http}/v1/admin/profile",
                          {"drains": int(mix["trace_drains"]),
                           "dir": os.path.join(workdir, "trace")})
        end = time.time() + 60.0
        while time.time() < end and harness.profile_active(server):
            time.sleep(0.05)
        t_done = time.time()
        time.sleep(2.0)
        gens.tell("stop")
        results = gens.wait(float(mix.get("grace_s", 10.0)) + 40.0)
    finally:
        gens.kill()
        server.stop()
    due = np.concatenate([r["rpc_due"] for r in results])
    recv = np.concatenate([r["rpc_recv"] for r in results])
    lat = (recv - due) * 1e3
    before = lat[recv < t_arm]
    during = lat[(recv >= t_arm) & (due <= t_done)]
    print(json.dumps({
        "probe": "capture", "cell": a.cell, "root": a.root,
        "capture_s": t_done - t_arm, "rpcs_before": int(len(before)),
        "rpcs_during": int(len(during)),
        "longest_before_ms": float(before.max()) if len(before) else None,
        "p50_before_ms": float(np.median(before)) if len(before) else None,
        "longest_during_ms": float(during.max()) if len(during) else None,
        "p50_during_ms": float(np.median(during)) if len(during) else None,
        "over_1s_during": int((during > 1000.0).sum()),
        "gaps": (longest_gaps(a.root, os.path.join(workdir, "trace"), a.gaps)
                 if a.gaps else None)}), flush=True)
    shutil.rmtree(workdir, ignore_errors=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("probe", choices=("sample", "capture", "gaps"))
    p.add_argument("--cell")
    p.add_argument("--trace-dir")
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--samples", default="0,0.01,1.0")
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=2800001)
    p.add_argument("--gaps", type=int, default=0)
    p.add_argument("--any-device", action="store_true")
    a = p.parse_args()
    a.root = os.path.abspath(a.root)
    sys.path.insert(0, a.root)
    if a.probe == "gaps":
        return list_gaps(a)
    from benchmark import harness
    bench = harness.Bench(a.root)
    {"sample": sample_sweep, "capture": capture_stall}[a.probe](
        harness, bench, a)


if __name__ == "__main__":
    main()
