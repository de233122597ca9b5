"""The benchmark's own reduction of a profiler trace to numbers.

    python benchmark/reduce_trace.py <trace dir> <out.json>

Reads every `.xplane.pb` under the directory with jax.profiler.ProfileData
(JAX's reader only; run with JAX_PLATFORMS=cpu, after the process that held
the chip has gone) and writes:

  window_s      first `XLA Modules` event's start to the last one's end on
                the fullest device: the traced drains, without the profiler's
                own start and stop
  busy_s        union of the `XLA Modules` events, averaged over devices
  modules       number of `XLA Modules` events on the fullest device
  module_s      their summed duration on that device
  device_ops    [[name, seconds], ...] from the `XLA Ops` line, by self time
                (an op's time less the ops nested in it), largest first
  idle_gaps     [[what the host was doing, seconds], ...]: the device's idle
                time inside the window, split by the host annotation that
                overlaps it (`guber_fetch`, `guber_*`, else `unattributed`)

One line per quantity: `XLA Modules` for busy time, `XLA Ops` for the
breakdown.  Adding lines together counts device time twice.
"""

import json
import os
import re
import sys

DEVICE_PLANE = "/device:"
MODULES, OPS = "XLA Modules", "XLA Ops"
ANNOTATION_PREFIX = "guber_"


def find_traces(trace_dir):
    out = []
    for base, _, files in os.walk(trace_dir):
        out += [os.path.join(base, f) for f in files if f.endswith(".xplane.pb")]
    return sorted(out)


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def overlap(gaps, spans):
    """Seconds of `gaps` covered by `spans` (both merged, ns)."""
    total, j = 0, 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def subtract(gaps, spans):
    """`gaps` minus `spans` (both merged)."""
    out = []
    j = 0
    for a, b in gaps:
        cur = a
        while j < len(spans) and spans[j][1] <= cur:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            if spans[k][0] > cur:
                out.append([cur, spans[k][0]])
            cur = max(cur, spans[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def short_name(hlo):
    """`%fusion.618 = (u32[10485760]{...}, ...) fusion(...)` -> the op's
    name and result shapes, without layouts or operands."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name[:120]
    shapes = re.sub(r"\{[^}]*\}", "", rest)
    shapes = re.sub(r"/\*[^*]*\*/", "", shapes)
    depth, end = 0, len(shapes)
    for i, ch in enumerate(shapes):
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            end = i
            break
    return (name + " " + shapes[:end].replace(" ", ""))[:120]


def self_times(events):
    """{name: self seconds-in-ns} for one line's (name, start, dur) events:
    an event's duration less that of the events nested directly in it."""
    out = {}
    stack = []          # [end, name, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            _, n, own = stack.pop()
            out[n] = out.get(n, 0) + own
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    for _, n, own in stack:
        out[n] = out.get(n, 0) + own
    return out


def reduce_planes(planes):
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    t_lo, t_hi = None, None
    devices = []
    host = {}
    for pname, lines in planes:
        dev = ({"modules": [], "ops": []} if pname.startswith(DEVICE_PLANE)
               else None)
        for lname, events in lines:
            for name, start, dur in events:
                if dur <= 0:
                    continue
                t_lo = start if t_lo is None else min(t_lo, start)
                t_hi = start + dur if t_hi is None else max(t_hi, start + dur)
                if dev is not None:
                    if lname == MODULES:
                        dev["modules"].append((start, start + dur))
                    elif lname == OPS:
                        dev["ops"].append((name, start, dur))
                elif name.startswith(ANNOTATION_PREFIX):
                    host.setdefault(name, []).append((start, start + dur))
        if dev is not None and (dev["modules"] or dev["ops"]):
            devices.append(dev)
    if t_lo is None or not devices:
        return None
    all_lo, all_hi = t_lo, t_hi
    busy = [union(d["modules"]) for d in devices]
    busy_ns = [sum(b - a for a, b in u) for u in busy]
    full = max(range(len(devices)), key=lambda i: busy_ns[i])
    if not busy_ns[full]:
        return None
    ops = {}
    for d in devices:
        for k, v in self_times(d["ops"]).items():
            k = short_name(k)
            ops[k] = ops.get(k, 0) + v / len(devices)
    # idle gaps of the fullest device, named by what the host was doing
    t_lo, t_hi = busy[full][0][0], busy[full][-1][1]
    gaps = subtract([[t_lo, t_hi]], busy[full])
    idle = []
    names = sorted(host, key=lambda n: (n != "guber_fetch", n))
    for n in names:
        spans = union(host[n])
        got = overlap(gaps, spans)
        if got:
            idle.append([n, got / 1e9])
        gaps = subtract(gaps, spans)
    rest = sum(b - a for a, b in gaps)
    if rest:
        idle.append(["unattributed", rest / 1e9])
    idle.sort(key=lambda kv: -kv[1])
    return {
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "trace_s": (all_hi - all_lo) / 1e9,
        "modules": len(devices[full]["modules"]),
        "module_s": sum(b - a for a, b in devices[full]["modules"]) / 1e9,
        "devices": len(devices),
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle[:10],
    }


def read_planes(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                      for ev in line.events]))
        out.append((plane.name, lines))
    return out


def reduce_dir(trace_dir):
    planes = []
    for path in find_traces(trace_dir):
        planes += read_planes(path)
    return reduce_planes(planes)


def what_is_there(trace_dir):
    """For the one who has to find out why a trace reduced to nothing: its
    files, their planes and each plane's lines with their event counts."""
    out = []
    for path in find_traces(trace_dir):
        out.append(f"{os.path.relpath(path, trace_dir)} "
                   f"({os.path.getsize(path)} bytes):")
        for pname, lines in read_planes(path):
            out.append(f"  {pname}: " + ", ".join(
                f"{lname} ({len(ev)})" for lname, ev in lines[:12]))
    return "\n".join(out[:60]) or "no .xplane.pb file"


if __name__ == "__main__":
    got = reduce_dir(sys.argv[1])
    if got is None:
        sys.exit(f"no device events in the trace under {sys.argv[1]}:\n"
                 + what_is_there(sys.argv[1]))
    with open(sys.argv[2], "w") as f:
        json.dump(got, f)
