"""From a profiler trace to device busy time, idle time and a breakdown.

A trace is read into plain tuples, ``[(plane, [(line, [(name, start_ns,
duration_ns), ...]), ...]), ...]``, so the reduction can be checked on a
small recorded trace without the chip.

* The window is the host annotation ``bench.window`` (the harness opens
  it around the measured interval).
* Device planes are ``/device:TPU:<n>``.  Busy time is the union of the
  intervals of the operations on the plane's ``XLA Ops`` line (``XLA
  Modules`` where a plane has no such line), clipped to the window,
  averaged over the device planes that ran anything.
* ``device_ops``: total time by operation name; ``idle_gaps``: the
  longest gaps between device operations inside the window, each named
  by the innermost host event that covers its midpoint, waits aside.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")     # the first a plane has
WINDOW = "bench.window"
# host events that say a thread waited, not what the host was doing
WAITS = re.compile(r"sleep|wait|acquire|select|poll|recv|accept|futex",
                   re.IGNORECASE)


def load(path: str) -> list:
    """Planes of the ``.xplane.pb`` under ``path`` (a file or a trace
    directory), as plain tuples."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, float(e.start_ns),
                                  float(e.duration_ns)) for e in ln.events])
                      for ln in p.lines])
            for p in pd.planes]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(planes: list, marker: str = WINDOW):
    """(start_ns, end_ns) of the host annotation ``marker``."""
    for name, lines in planes:
        if not name.startswith("/host"):
            continue
        for _, events in lines:
            for ev, s, d in events:
                if ev == marker:
                    return s, s + d
    return None


def reduce(planes: list, marker: str = WINDOW, top: int = 10) -> dict:
    win = window_of(planes, marker)
    if win is None:
        raise ValueError(f"no host annotation {marker!r} in the trace")
    w0, w1 = win
    busy, per_op, unions = [], {}, []
    for name, lines in planes:
        if not DEVICE_PLANE.match(name):
            continue
        ivs = []
        names = [ln for ln, _ in lines]
        use = next((n for n in OPS_LINES if n in names), None)
        for lname, events in lines:
            if lname != use:
                continue
            for ev, s, d in events:
                s, e = max(s, w0), min(s + d, w1)
                if e > s:
                    ivs.append((s, e))
                    op = ev.split(" = ")[0]     # HLO text -> "%fusion.3"
                    per_op[op] = per_op.get(op, 0.0) + (e - s)
        if ivs:
            u = _union(ivs)
            unions.append(u)
            busy.append(sum(e - s for s, e in u))
    host = [(ev, s, s + d) for name, lines in planes
            if name.startswith("/host") for _, events in lines
            for ev, s, d in events
            if d > 0 and ev != marker and not WAITS.search(ev)]
    gaps = []
    if unions:
        u = unions[0]
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    named = []
    for g, a, b in gaps[:top]:
        mid = 0.5 * (a + b)
        cover = [(e - s, ev) for ev, s, e in host if s <= mid <= e]
        named.append([min(cover)[1] if cover else "host idle", g * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "n_devices": len(busy),
        "device_ops": [[k, v * 1e-9 / max(len(busy), 1)] for k, v in ops],
        "idle_gaps": named,
    }
