"""Reduce a profiler trace (``.xplane.pb``) to the run's device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else.  The window is the
benchmark's own ``bench.window`` host span.  On each TPU device plane the
operations are the events of its ``XLA Ops`` line; busy time is the union of
their intervals inside the window.  Each idle gap (the window minus that
union) is named by the benchmark span (``bench.*``) on the host that overlaps
it most, the innermost one where two overlap it alike.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def load(path: str | Path):
    """(device ops by plane, host benchmark spans) of one trace file.

    Device ops are ``(start_ns, end_ns, name)`` per TPU plane; host spans are
    ``(start_ns, end_ns, name)`` of the events whose name starts ``bench.``.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return ops, spans


def reduce(ops: dict[str, list], spans: list) -> dict:
    """Busy and window seconds, the top device operations and the longest
    idle gaps, as ``{"busy_s", "window_s", "devices", "device_ops",
    "idle_gaps"}``.  Busy seconds are averaged over the device planes."""
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    if not ops:
        raise ValueError("the trace holds no TPU device plane with XLA ops")
    inner = [(a, b, n) for a, b, n in spans if n != WINDOW_SPAN]
    busy_ns, op_ns, gaps = [], defaultdict(int), []
    for plane, evs in sorted(ops.items()):
        busy = _union(_clip([(a, b) for a, b, _ in evs], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        for a, b, name in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_ns[name] += b - a
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _name_gap(g0, g1, inner)))
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy_ns),
        "device_ops": [[n, v / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for v, n in gaps[:TOP]],
    }


def _name_gap(g0: int, g1: int, spans: list) -> str:
    best, key = "no bench span", (0, 0)
    for a, b, name in spans:
        ov = min(b, g1) - max(a, g0)
        if ov > 0 and (ov, -(b - a)) > key:
            best, key = name, (ov, -(b - a))
    return best


def reduce_file(path: str | Path) -> dict:
    return reduce(*load(path))


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
