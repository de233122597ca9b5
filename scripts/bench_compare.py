"""Bench-regression gate: fresh CPU smoke vs this HOST's best prior run.

`make bench-smoke` runs bench.py on the CPU backend (JAX_PLATFORMS=cpu,
`bench.py --platform cpu`: the small smoke shapes) and diffs the fresh
throughput against the best-of baseline stashed for THIS host
(`.bench_baseline_<fingerprint>.json` next to the BENCH records; the
fingerprint hashes nproc + the CPU model string).  Keying by host keeps
the gate honest when the repo moves between boxes: numbers measured on a
96-core builder must never gate a laptop, and vice versa.

  * first run on a host: the fresh numbers anchor the stash, exit 0;
  * later runs compare against the stash and RAISE it when fresh numbers
    beat it (best-of, so the gate catches a regression even when the
    previous round already regressed);
  * GUBER_BENCH_REBASE=1 re-anchors the stash to the fresh run (after a
    deliberate trade-off or a host change that kept the fingerprint).

A regression past the noise floor (default 10%, CPU smoke numbers
jitter) on either gated metric fails the build loudly:

  * e2e_decisions_per_sec     the serving headline (client -> response)
  * device_decisions_per_sec  the raw drain-window throughput
  * host_decisions_per_sec    the pipelined host path (RPC bytes -> C
                              parse -> stacked dispatch -> C encode)

A fourth gate is ABSOLUTE and box-independent: `kernels_per_window`
(the composed serving arm's executed-kernel census, recorded at the top
level of the BENCH json) must stay within the kernel-ladder budget —
an absolute 24/window, >= 8x below the 192.5/window pre-ladder anchor
(the staged folded-shoulders ladder traces at 20.5/window).  The
census is a property of the traced program, so no fingerprint, no
stash, and no rebase applies to it.

A fifth gate is LOWER-IS-BETTER and host-keyed like the throughput
gates: `measured_ms_per_window` (per-arm device time from the parsed
jax.profiler trace, observability/devprof.py — recorded at the top
level of the BENCH json when the census tier ran with
GUBER_PROBE_MEASURE=1).  Wall-clock device time is a property of the
box, so it compares against the same host's stash only, with its own
looser noise floor (default 50%, GUBER_BENCH_MEASURED_TOLERANCE —
single-digit-ms CPU kernels jitter far more than aggregate
throughput).  The stash keeps the best-of (lowest) per arm and
GUBER_BENCH_REBASE=1 re-anchors it along with the throughput metrics.

Prior BENCH_r*.json rounds are still read (defensively: rc != 0 or an
empty `parsed` is skipped, CPU numbers may live at the top level or
nested under `cpu_smoke`) but only for CONTEXT in the log — they carry
no host fingerprint, so they never gate.

  python scripts/bench_compare.py                    # run + compare
  python scripts/bench_compare.py --fresh-json F     # compare-only (tests)
  python scripts/bench_compare.py --tolerance 0.2    # looser floor

Exit codes: 0 ok / nothing to compare, 1 regression, 2 fresh run broken.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

GATED_METRICS = ("e2e_decisions_per_sec", "device_decisions_per_sec",
                 "host_decisions_per_sec")

# Kernel-ladder budget (box-independent: the census is a property of the
# traced program, identical on every host, so it gates ABSOLUTELY — no
# host fingerprint, no stash, and GUBER_BENCH_REBASE does not bypass it).
# Anchor = the pre-ladder composed serving window: 1257 drain kernels +
# 283 analytics kernels over a K=8 stack = 192.5 kernels/window.  The
# staged folded-shoulders ladder (ISSUE 17: drain grid kernel + GLOBAL
# pair kernel + analytics finisher) traces at 20.5/window, so the gate
# is the ABSOLUTE 24/window budget (>= 8x below the anchor) — any
# regression past it fails the run outright.
CENSUS_ANCHOR_KPW = 192.5
CENSUS_BUDGET_KPW = 24.0


def host_fingerprint() -> tuple[str, str]:
    """(12-hex fingerprint, human-readable description) of this box:
    nproc + the CPU model string.  Containers on the same machine class
    share it; moving to different silicon changes it, detaching the
    stash automatically."""
    import hashlib
    model = "unknown-cpu"
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
        for key in ("model name", "hardware", "cpu model"):
            for line in lines:
                if line.lower().startswith(key) and ":" in line:
                    model = line.split(":", 1)[1].strip() or model
                    break
            if model != "unknown-cpu":
                break
    except OSError:
        pass
    nproc = os.cpu_count() or 1
    desc = f"{nproc}x {model}"
    fp = hashlib.sha256(f"{nproc}|{model}".encode()).hexdigest()[:12]
    return fp, desc


def stash_path(bench_dir: str, fp: str) -> str:
    return os.path.join(bench_dir, f".bench_baseline_{fp}.json")


def load_stash(path: str) -> dict:
    try:
        with open(path) as f:
            rec = json.load(f)
        metrics = rec.get("metrics")
        return rec if isinstance(metrics, dict) else {}
    except (OSError, ValueError):
        return {}


def write_stash(path: str, fp: str, desc: str, metrics: dict,
                measured: dict | None = None) -> None:
    import time
    rec = {"fingerprint": fp, "host": desc,
           "anchored_at": int(time.time()),
           "metrics": {m: float(v) for m, v in metrics.items()
                       if isinstance(v, (int, float)) and v > 0}}
    if measured:
        rec["measured_ms_per_window"] = {
            a: float(v) for a, v in measured.items()
            if isinstance(v, (int, float)) and v > 0}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")


def extract_cpu(parsed: dict | None) -> dict:
    """The CPU-smoke tier of one bench record, wherever it lives."""
    if not parsed:
        return {}
    nested = parsed.get("cpu_smoke")
    if isinstance(nested, dict) and nested:
        return nested
    if parsed.get("backend") == "cpu":
        return parsed
    return {}


def best_baseline(bench_dir: str) -> tuple[dict, list[str]]:
    """Best-of per gated metric across all readable prior rounds (best-of,
    not latest: the gate must catch a regression even when the previous
    round already regressed)."""
    best: dict = {}
    used: list[str] = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if rec.get("rc") not in (0, None):
            continue
        cpu = extract_cpu(rec.get("parsed"))
        took = False
        for m in GATED_METRICS:
            v = cpu.get(m)
            if isinstance(v, (int, float)) and v > 0 and v > best.get(m, 0):
                best[m] = float(v)
                took = True
        if took:
            used.append(os.path.basename(path))
    return best, used


def run_fresh(budget_s: float) -> dict:
    """One CPU smoke bench.py run; returns its single-line JSON result."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--platform", "cpu"],
        cwd=repo, env=env, capture_output=True, text=True,
        timeout=budget_s + 120)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench.py failed (rc={proc.returncode}); stderr tail:\n"
            + proc.stderr[-2000:])
    # bench.py prints ONE JSON line on stdout; scan from the end in
    # case a library printed above it
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise RuntimeError(
        f"bench.py produced no JSON (rc={proc.returncode}); stderr tail:\n"
        + proc.stderr[-2000:])


def compare(baseline: dict, fresh_cpu: dict, tolerance: float) -> list[str]:
    """Regression lines past the noise floor (empty == gate passes)."""
    failures = []
    for m in GATED_METRICS:
        base = baseline.get(m)
        new = fresh_cpu.get(m)
        if not base:
            print(f"  {m}: no baseline — skipped")
            continue
        if not isinstance(new, (int, float)) or new <= 0:
            failures.append(f"{m}: fresh run reported {new!r} "
                            f"(baseline {base:,.0f})")
            continue
        ratio = new / base
        verdict = "OK" if ratio >= 1.0 - tolerance else "REGRESSION"
        print(f"  {m}: {new:,.0f} vs best {base:,.0f} "
              f"({(ratio - 1.0) * 100.0:+.1f}%) {verdict}")
        if verdict != "OK":
            failures.append(
                f"{m}: {new:,.0f} < {base:,.0f} * {1.0 - tolerance:.2f} "
                f"({(ratio - 1.0) * 100.0:+.1f}%)")
    return failures


def census_gate(fresh: dict) -> list[str]:
    """Absolute kernels-per-window budget on the composed serving arms
    (bench.py records them at the TOP level — box-independent).  Gates
    the headline `kernels_per_window` AND every composed_* arm in the
    per-arm census — including composed_mixed_algos, the window with all
    five wire algorithms live at once: the algorithm plane must ride the
    ladder as select-chain depth, never as extra kernels."""
    checks: dict = {}
    kpw = fresh.get("kernels_per_window")
    if isinstance(kpw, (int, float)) and kpw > 0:
        checks["kernels_per_window"] = float(kpw)
    per_arm = fresh.get("census_kernels_per_window")
    if isinstance(per_arm, dict):
        for arm in sorted(per_arm):
            v = per_arm[arm]
            if (arm.startswith("composed")
                    and isinstance(v, (int, float)) and v > 0):
                checks[f"kernels_per_window[{arm}]"] = float(v)
    if not checks:
        print("  kernels_per_window: absent — census gate skipped")
        return []
    failures = []
    for label, v in checks.items():
        verdict = "OK" if v <= CENSUS_BUDGET_KPW else "REGRESSION"
        print(f"  {label}: {v:.1f} vs absolute budget "
              f"{CENSUS_BUDGET_KPW:.1f} (anchor {CENSUS_ANCHOR_KPW:.1f}, "
              f">= 8x fold) {verdict}")
        if verdict != "OK":
            failures.append(
                f"{label}: {v:.1f} > {CENSUS_BUDGET_KPW:.1f} — composed "
                "serving ladder regressed past the absolute staged budget")
    return failures


def extract_measured(fresh: dict) -> dict:
    """Per-arm measured ms/window from the fresh BENCH record (top level;
    only present when the census tier ran with GUBER_PROBE_MEASURE=1)."""
    m = fresh.get("measured_ms_per_window")
    if not isinstance(m, dict):
        return {}
    return {a: float(v) for a, v in m.items()
            if isinstance(v, (int, float)) and v > 0}


def measured_compare(baseline_ms: dict, fresh_ms: dict,
                     tolerance: float) -> list[str]:
    """Lower-is-better device-time diff per arm (empty == gate passes).
    Arms absent on either side are skipped, not failed: a cold stash or
    a run without the measured pass must not trip the gate."""
    failures = []
    for arm in sorted(baseline_ms):
        base = baseline_ms[arm]
        new = fresh_ms.get(arm)
        if not isinstance(new, (int, float)) or new <= 0:
            print(f"  measured_ms[{arm}]: fresh value absent — skipped")
            continue
        ratio = new / base
        verdict = "OK" if ratio <= 1.0 + tolerance else "REGRESSION"
        print(f"  measured_ms[{arm}]: {new:.4f} vs best {base:.4f} "
              f"({(ratio - 1.0) * 100.0:+.1f}%) {verdict}")
        if verdict != "OK":
            failures.append(
                f"measured_ms[{arm}]: {new:.4f} > {base:.4f} * "
                f"{1.0 + tolerance:.2f} ({(ratio - 1.0) * 100.0:+.1f}%)")
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--bench-dir",
                   default=os.path.dirname(
                       os.path.dirname(os.path.abspath(__file__))),
                   help="directory holding BENCH_r*.json (default: repo root)")
    p.add_argument("--fresh-json", default="",
                   help="compare-only: read the fresh result from this file "
                   "instead of running bench.py")
    p.add_argument("--tolerance", type=float,
                   default=float(os.environ.get("GUBER_BENCH_TOLERANCE",
                                                "0.10")),
                   help="allowed fractional drop before failing "
                   "(default 0.10)")
    p.add_argument("--measured-tolerance", type=float,
                   default=float(os.environ.get(
                       "GUBER_BENCH_MEASURED_TOLERANCE", "0.50")),
                   help="allowed fractional device-time rise before "
                   "failing the measured gate (default 0.50)")
    p.add_argument("--budget", type=float, default=480.0,
                   help="wall budget (s) for the fresh bench.py run")
    args = p.parse_args(argv)

    fp, desc = host_fingerprint()
    path = stash_path(args.bench_dir, fp)
    stash = load_stash(path)
    rebase = os.environ.get("GUBER_BENCH_REBASE") == "1"

    legacy, used = best_baseline(args.bench_dir)
    if legacy and used:
        print(f"bench gate: prior rounds {', '.join(used)} "
              "(context only — unkeyed, measured on unknown hosts)")

    if args.fresh_json:
        with open(args.fresh_json) as f:
            fresh = json.load(f)
    else:
        try:
            fresh = run_fresh(args.budget)
        except Exception as e:  # noqa: BLE001 — broken run != regression
            print(f"bench gate BROKEN: {e}", file=sys.stderr)
            return 2
    if fresh.get("error"):
        print(f"bench gate BROKEN: fresh run error: {fresh['error']}",
              file=sys.stderr)
        return 2
    fresh_cpu = extract_cpu(fresh)
    if not fresh_cpu:
        print("bench gate BROKEN: fresh result has no CPU tier "
              f"(backend={fresh.get('backend')!r})", file=sys.stderr)
        return 2
    gated = {m: float(fresh_cpu[m]) for m in GATED_METRICS
             if isinstance(fresh_cpu.get(m), (int, float))
             and fresh_cpu[m] > 0}

    # census gate first: absolute, host-independent, not rebasable
    print("bench gate: kernel-census budget (box-independent)")
    census_failures = census_gate(fresh)
    if census_failures:
        print("bench gate FAILED:", file=sys.stderr)
        for f_ in census_failures:
            print(f"  {f_}", file=sys.stderr)
        return 1

    fresh_ms = extract_measured(fresh)

    if rebase or not stash:
        if not gated:
            print("bench gate BROKEN: fresh run reported no gated metrics",
                  file=sys.stderr)
            return 2
        write_stash(path, fp, desc, gated, measured=fresh_ms)
        why = ("GUBER_BENCH_REBASE=1" if rebase
               else "first run on this host")
        print(f"bench gate: anchored baseline for {desc} "
              f"(fp {fp}) — {why}")
        for m, v in gated.items():
            print(f"  {m}: {v:,.0f}")
        for a, v in sorted(fresh_ms.items()):
            print(f"  measured_ms[{a}]: {v:.4f}")
        return 0

    baseline = stash["metrics"]
    baseline_ms = stash.get("measured_ms_per_window")
    if not isinstance(baseline_ms, dict):
        baseline_ms = {}
    print(f"bench gate: baseline for {desc} (fp {fp})")
    failures = compare(baseline, fresh_cpu, args.tolerance)
    if baseline_ms or fresh_ms:
        print("bench gate: measured device time (lower is better)")
        if not baseline_ms:
            print("  measured_ms: no stash baseline — anchoring only")
        failures += measured_compare(baseline_ms, fresh_ms,
                                     args.measured_tolerance)
    if failures:
        print("bench gate FAILED:", file=sys.stderr)
        for f_ in failures:
            print(f"  {f_}", file=sys.stderr)
        print("  (a deliberate trade-off? re-anchor with "
              "GUBER_BENCH_REBASE=1)", file=sys.stderr)
        return 1
    merged = dict(baseline)
    raised = []
    for m, v in gated.items():
        if v > merged.get(m, 0.0):
            merged[m] = v
            raised.append(m)
    # best-of for device time is the LOWEST per arm; new arms anchor
    merged_ms = dict(baseline_ms)
    for a, v in fresh_ms.items():
        if a not in merged_ms or v < merged_ms[a]:
            merged_ms[a] = v
            raised.append(f"measured_ms[{a}]")
    if raised:
        write_stash(path, fp, desc, merged, measured=merged_ms)
        print(f"bench gate: baseline raised for {', '.join(raised)}")
    print("bench gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
