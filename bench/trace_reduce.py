"""Reduce a JAX profiler trace of the measured window to device metrics.

``extract`` reads the ``.xplane.pb`` the profiler wrote and keeps what the
metrics need, as plain lists: the window (the harness's ``bench_window``
annotation), each TPU's device operations (its ``XLA Ops`` line) and the
host events.  ``reduce`` turns that cut into busy time (the union of the
device operations' intervals inside the window, averaged over the chips),
summed operation time, the operations that took most time, and the
longest idle gaps named by the innermost host event around each.  The
cut is JSON-able, so a small recorded one tests ``reduce`` on any host.

The ``XLA Ops`` line nests the operations of a loop's body inside the
loop's own event; operation time counts each top-level event once.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
TOP = 10


def extract(trace_dir: str) -> dict:
    """The cut of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    window, devices, host = None, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                [ev.name, ev.start_ns, ev.duration_ns]
                for line in plane.lines if line.name == DEVICE_LINE
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = [ev.start_ns, ev.start_ns + ev.duration_ns]
                    elif ev.duration_ns > 0:
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"window": window, "devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _top_level(events: list) -> list:
    """The events that no earlier event on the line encloses."""
    out, end = [], float("-inf")
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if start + dur > end:
            out.append((name, start, dur))
            end = start + dur
    return out


def short_name(hlo: str) -> str:
    """``%fusion.5 = s32[...] fusion(...)`` -> ``fusion.5``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def reduce(cut: dict) -> dict | None:
    """Busy, operation and window seconds with the breakdown; None when
    the cut has no window or no device operation inside it."""
    if cut.get("window") is None or not cut.get("devices"):
        return None
    w0, w1 = cut["window"]
    busy, op_ns, per_op, gaps = [], 0.0, {}, []
    for events in cut["devices"].values():
        spans = []
        for name, start, dur in _top_level(events):
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                spans.append((s, e))
                op_ns += e - s
                key = short_name(name)
                per_op[key] = per_op.get(key, 0.0) + (e - s)
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not per_op:
        return None
    n = len(cut["devices"])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "op_s": op_ns / n / 1e9,
        "device_ops": [[name, ns / 1e9] for name, ns in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_host_doing(cut["host"], s, e), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
    }


def _host_doing(host: list, s: float, e: float) -> str:
    """The shortest host event that covers the gap's middle."""
    mid = (s + e) / 2
    best = None
    for name, start, dur in host:
        if start <= mid < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "no host event"
