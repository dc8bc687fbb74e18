"""From the profiler's `.xplane.pb` to the few numbers the benchmark keeps.

Read with `jax.profiler.ProfileData` and nothing else. What comes out:

- `window_s`: the traced window. The harness marks it on the host with a
  `jax.profiler.TraceAnnotation(WINDOW)`; where the mark is missing, the span
  from the first device event to the last stands in.
- `busy_s`: seconds in which an operation ran on the device (the union of the
  op intervals on each device's "XLA Ops" line, cut to the window), averaged
  over the devices that ran anything.
- `device_ops`: the ten operations that took most device time, by XLA's own
  names cut to "%name opcode", with their seconds (summed over devices);
  loops and branches are left out, since their time is their bodies'.
- `idle_gaps`: the device's idle seconds in the window (first device), booked
  to whichever of the benchmark's own host annotations covers most of each
  gap ("unannotated" where none does), ten names at most.

Checked on a small trace recorded on a TPU v5e (tests/benchmark/data).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
_OPS_LINE = "XLA Ops"
# a gap this short is the device's own turn-around between two operations,
# not the host's doing: booked as "between_ops", never looked up
_SHORT_GAP_NS = 20_000
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_op_name(name: str) -> str:
    """XLA's own name of an operation, without its operands' types:
    "%fusion.12 = bf16[..] fusion(..), kind=kLoop, .." -> "%fusion.12 fusion"."""
    lhs, _, rhs = name.partition(" = ")
    opcode = re.search(r"(?:^|\s)([a-z][\w\-.]*)\(", rhs)
    return (lhs + " " + opcode.group(1) if opcode else lhs)[:96]


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "host" not in name.lower()


def _ops_line(plane):
    lines = list(plane.lines)
    for line in lines:
        if line.name == _OPS_LINE:
            return line
    for line in lines:
        if "ops" in line.name.lower():
            return line
    return None


def reduce_profile(profile, host_names=()):
    """`profile`: a `jax.profiler.ProfileData`. Returns the dict described in
    the module's docstring, or None where no device ran an operation."""
    device_events = {}            # plane name -> [(start, end, op name)]
    host_events = []              # (start, end, name) of our annotations
    window = None
    wanted = set(host_names)
    for plane in profile.planes:
        if _is_device_plane(plane.name):
            line = _ops_line(plane)
            if line is None:
                continue
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events if e.duration_ns > 0]
            if evs:
                device_events[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in wanted:
                        host_events.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not device_events:
        return None
    if window is None:
        window = (min(s for evs in device_events.values() for s, _, _ in evs),
                  max(e for evs in device_events.values() for _, e, _ in evs))
    lo, hi = window

    busy, by_op, first_busy = [], {}, None
    for name in sorted(device_events):
        evs = _clip([(s, e) for s, e, _ in device_events[name]], lo, hi)
        merged = _union(evs)
        if first_busy is None:
            first_busy = merged
        busy.append(sum(e - s for s, e in merged))
        for s, e, op in device_events[name]:
            s, e = max(s, lo), min(e, hi)
            op = short_op_name(op)
            # a loop or a branch holds other operations: its time is theirs
            if e > s and op.split(" ")[-1] not in _CONTAINERS:
                by_op[op] = by_op.get(op, 0.0) + (e - s)

    gaps, cursor = [], lo
    for s, e in first_busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    host_events.sort()
    by_host = {}
    for gs, ge in gaps:
        if ge - gs < _SHORT_GAP_NS:
            by_host["between_ops"] = by_host.get("between_ops", 0.0) \
                + (ge - gs)
            continue
        best, best_cover = "unannotated", 0.0
        for hs, he, name in host_events:
            if hs >= ge:
                break
            cover = min(he, ge) - max(hs, gs)
            if cover > best_cover:
                best, best_cover = name, cover
        by_host[best] = by_host.get(best, 0.0) + (ge - gs)

    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "device_ops": top(by_op),
            "idle_gaps": top(by_host),
            "devices": sorted(device_events),
            "device_events": sum(len(v) for v in device_events.values())}


def reduce_xplane(path: str, host_names=()):
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          host_names)
