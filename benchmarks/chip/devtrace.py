"""Reduce a profiler trace (`.xplane.pb`) to device busy, idle and collective time.

The window is the span of the harness's own host annotations (`wave`) in the
trace. On each device plane, the operations on its
"XLA Ops" line are clipped to the window:

- busy: the union of their intervals; idle is the rest of the window;
- collective only: time in which a collective runs and no other operation;
- top operations: device seconds by HLO instruction name (`%fusion.3`),
  summed over devices, of the operations that hold no other: a `while` spans
  the operations of its body on the same line, which would count them twice;
- idle gaps: each stretch between operations, labelled with the innermost
  event on the host thread that holds the annotations and covers the gap's
  middle, so that a gap reads as what the host was doing; summed by label.

Busy and collective seconds are averaged over the devices.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

ANNOTATIONS = ("wave",)
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
                        r"|allreduce|allgather|reducescatter", re.I)
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> float:
    """Length of merged intervals `a` not covered by merged intervals `b`."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def short(name: str) -> str:
    """An operation's HLO instruction name: `%fusion.3 = bf16[8] fusion(...)` -> `%fusion.3`."""
    return name.split(" = ", 1)[0]


def leaves(events):
    """The (name, start, end) events that contain no other event: on one line
    an event that holds others is followed, in order of start, by one of them."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt[1] < ev[2] and nxt[2] <= ev[2])]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_xplane(path: str, annotations=ANNOTATIONS) -> dict | None:
    """Busy, idle, collective and top-operation seconds of the annotated window;
    None when the trace holds no annotated window or no device operation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append((plane.name, list(_events(ops[0]))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = list(_events(ln))
                if any(n in annotations for n, _, _ in evs):
                    host = evs
    return reduce_events(host, devices, annotations)


def reduce_events(host, devices, annotations=ANNOTATIONS) -> dict | None:
    """The reduction itself. `host`: (name, start_ns, end_ns) events of the host
    thread that holds the annotations; `devices`: (plane name, its operations
    as (name, start_ns, end_ns))."""
    spans = [(s, e) for n, s, e in host if n in annotations]
    if not spans or not any(ev for _, ev in devices):
        return None
    w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)

    per_device, top, gaps = [], defaultdict(float), defaultdict(float)
    for name, evs in devices:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]
        busy = union((s, e) for _, s, e in clipped)
        coll = union((s, e) for n, s, e in clipped if COLLECTIVE.search(n))
        other = union((s, e) for n, s, e in clipped if not COLLECTIVE.search(n))
        for n, s, e in leaves(clipped):
            top[short(n)] += (e - s) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                covering = [(he - hs, n) for n, hs, he in host if hs <= mid <= he]
                gaps[min(covering)[1] if covering else "no host event"] += (e - s) / 1e9
        per_device.append({"device": name, "busy_s": length(busy) / 1e9,
                           "collective_only_s": subtract(coll, other) / 1e9})
    n = len(per_device)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "collective_only_s": sum(d["collective_only_s"] for d in per_device) / n,
        "devices": per_device,
        "device_ops": sorted(([k, v] for k, v in top.items()), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(([k, v / n] for k, v in gaps.items()), key=lambda kv: -kv[1])[:TOP],
    }
