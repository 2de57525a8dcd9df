"""Reduction from the profiler's trace (``.xplane.pb``) to what the metrics
read: the traced window, device busy time, time per device operation and per
compiled program, and the longest idle gaps named by what the host was doing. Reads the file with ``jax.profiler.ProfileData`` alone.

The traced window runs from the first device event to the last: what the
profiler takes to start and stop is not the program's idle time.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals):
    """Merge ``(start, end)`` pairs; returns the merged list, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged) -> int:
    return sum(e - s for s, e in merged)


def op_label(name: str) -> str:
    """``fusion.12 f32[2,4096,14336]`` from the event's HLO text: the
    operation's name and the type and shape of what it produces."""
    head, _, rest = name.lstrip("%").partition(" = ")
    shape = rest.split("{")[0].split(" ")[0].strip("(")
    return f"{head.strip()} {shape}".strip()


def is_container(name: str) -> bool:
    """Control flow whose event spans the operations of its body, which are
    listed themselves: counted for busy time, left out of the breakdown."""
    return name.lstrip("%").startswith(("while", "conditional", "call"))


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, hosts = [], []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name.split(":")[-1].isdigit():
            devices.append(plane)
        elif name.startswith("/host:"):
            hosts.append(plane)
    devices.sort(key=lambda p: int(p.name.split(":")[-1]))
    return devices, hosts


def line_events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
    return []


def reduce(trace_dir: str, chips: int = 1, max_gaps: int = 400) -> dict:
    devices, hosts = read_planes(find_xplane(trace_dir))
    if not devices:
        raise RuntimeError("the trace holds no /device:TPU plane: no operation ran on the device")
    devices = devices[:chips]
    per_device = []
    lo, hi = None, None
    for plane in devices:
        ops = line_events(plane, "XLA Ops")
        modules = line_events(plane, "XLA Modules")
        per_device.append((ops, modules))
        for s, e, _ in ops + modules:
            lo = s if lo is None or s < lo else lo
            hi = e if hi is None or e > hi else hi
    host_events = [
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for plane in hosts for line in plane.lines for ev in line.events
    ]
    if lo is None:
        raise RuntimeError("no operation ran on the device inside the trace")
    window_ns = hi - lo

    busy_ns, op_time, module_time, modules_all = [], defaultdict(int), defaultdict(list), []
    for ops, modules in per_device:
        merged = union([(s, e) for s, e, _ in ops])
        busy_ns.append(covered(merged))
        for s, e, name in ops:
            if not is_container(name):
                op_time[op_label(name)] += e - s
        for s, e, name in modules:
            module_time[name.split("(")[0]].append((e - s) / 1e9)
    if not any(busy_ns):
        raise RuntimeError("no operation ran on the device inside the traced window")

    # idle gaps on the first device, named by the shortest host event that
    # spans the middle of the gap (the innermost frame of whatever ran)
    merged0 = union([(s, e) for s, e, _ in per_device[0][0]])
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged0, merged0[1:]) if b[0] > a[1]]
    if merged0:
        gaps.append((merged0[0][0] - lo, lo, merged0[0][0]))
        gaps.append((hi - merged0[-1][1], merged0[-1][1], hi))
    gaps = sorted((g for g in gaps if g[0] > 0), reverse=True)[:max_gaps]
    named = defaultdict(int)
    if gaps:
        mids = sorted((s + (e - s) // 2, i) for i, (_, s, e) in enumerate(gaps))
        keys = [m for m, _ in mids]
        best = {}
        for s, e, name in host_events:
            a = bisect.bisect_left(keys, s)
            b = bisect.bisect_right(keys, e)
            for _, i in mids[a:b]:
                if i not in best or e - s < best[i][0]:
                    best[i] = (e - s, name)
        for i, (length, _, _) in enumerate(gaps):
            named[best[i][1] if i in best else "(no host event)"] += length
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy_ns],
        "device_ops": [[n, t / 1e9 / len(per_device)] for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])],
        "module_s": dict(module_time),
        "idle_gaps": [[n, t / 1e9] for n, t in sorted(named.items(), key=lambda kv: -kv[1])],
        "host_events": len(host_events),
    }
