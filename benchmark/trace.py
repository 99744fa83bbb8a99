"""Reduction of a profiler trace (`.xplane.pb`) to the device's busy
time, the window's length, the device operations that took most time,
and the device's idle time by what the host was doing.

The window is the span of the host annotation ``bench.window`` that the
harness opens around the measured cycles. Busy time is the union of the
operations' intervals on each TPU's "XLA Ops" line (every line of the
plane where it has none), clipped to the window and averaged over the
chips. The idle time (the stretches of the window in which no operation
ran) is charged, piece by piece, to the innermost ``bench.``/``action.``/
``session.``/``kbt.`` host annotation open over each piece, or to "host"
where none was, and summed per annotation.
Operations are named by their HLO kind (``fusion``, ``copy-done``,
``tpu_custom_call`` for a Pallas kernel).
"""

from __future__ import annotations

import bisect
import re

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "action.", "session.", "kbt.")
OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_-]*?)(?:\.\d+)?(?: =|$)")


def op_name(event_name: str) -> str:
    """The HLO instruction's kind: "%fusion.12 = f32[...] fusion(...)" is
    "fusion"; a name that is not HLO text is kept whole."""
    m = OP_NAME.match(event_name)
    return m.group(1) if m else event_name
WINDOW = "bench.window"


def events_from_xplane(path: str):
    """(device planes {name: [(start_ns, end_ns, op)]}, host annotations
    [(start_ns, end_ns, name)]) from a profiler file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == OPS_LINE] or lines
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                for l in ops for e in l.events
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return devices, host


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(annotations: list, t: float) -> str:
    """The latest-opened annotation open at ``t``; "host" where none is."""
    best = None
    for s, e, n in annotations:
        if s > t:
            break
        if e > t and (best is None or s >= best[0]):
            best = (s, n)
    return best[1] if best else "host"


def reduce(devices: dict, host: list, top: int = 10) -> dict | None:
    """busy_s, window_s, idle share and the breakdown; None where the
    trace holds no window or no device."""
    windows = [(s, e) for s, e, n in host if n == WINDOW]
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    window_s = (w1 - w0) / 1e9
    busy, op_time, gaps = [], {}, []
    annotations = sorted((s, e, n) for s, e, n in host if n != WINDOW)
    for events in devices.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events if e > w0 and s < w1]
        for s, e, n in clipped:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    busy_s = sum(busy) / len(busy)

    # each idle stretch is cut at every annotation boundary inside it and
    # each piece charged to the innermost annotation open over it
    cuts = sorted({t for s, e, _ in annotations for t in (s, e)})
    idle_by: dict[str, float] = {}
    for g0, g1 in gaps:
        pts = [g0, *cuts[bisect.bisect_right(cuts, g0):bisect.bisect_left(cuts, g1)], g1]
        for a, b in zip(pts, pts[1:]):
            name = _innermost(annotations, (a + b) / 2)
            idle_by[name] = idle_by.get(name, 0.0) + (b - a) / 1e9 / len(devices)

    idle = sorted(idle_by.items(), key=lambda kv: kv[1], reverse=True)[:top]
    ops = sorted(op_time.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "breakdown": {
            "device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in idle],
        },
    }
