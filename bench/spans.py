"""Put a traced window's device idle time down to the program's own spans.

This extends ``bench/trace.py`` and leaves its reduction as it is.  It reads
the same device planes, ``XLA Ops`` lines and ``bench.window``, and computes
``busy_s``, ``window_s`` and ``devices`` as ``trace.reduce`` does.  What it
adds is the host side of the program.  The selection path opens a ``milo.*``
span (``jax.profiler.TraceAnnotation``) at each layer boundary; the spans
land on the ``/host:`` plane of the same trace, on the device's clock, and
nest by time on the calling thread.  Each carries its counts as arguments
(``bytes``, ``n_c``, ``prep_seed`` ...).

Every idle nanosecond inside the window, on each device, goes to the
innermost ``milo.*`` span open at that moment, or to ``untraced``; the
pieces sum to the window less the busy time.  A piece is keyed by the chain
of span names from the outermost down (``milo.build/milo.preprocess/...``).
Spans around dispatches time the host's enqueue only; the wait for the
device is the ``milo.fetch`` spans.

The run record carries no path to its trace, so the readers take the newest
trace under ``bench.run``'s output directory and check that it is the run's
own by its window and busy time.
"""
from __future__ import annotations

import heapq
from collections import Counter
from pathlib import Path

from bench import trace

PREFIX = "milo."
UNTRACED = "untraced"
BUILD = "milo.build"
PREPROCESS = "milo.preprocess"
FETCH = "milo.fetch"
# the layers of PERF.md that the idle is put down to: the preprocessor's host
# loop is milo.preprocess and every span below it; the session is the rest
# of a traced build (milo.build's own time and milo.fingerprint)
HOST_LOOP = "preprocessor host loop"
SESSION = "session"

_cache: dict = {}


def load(path: str | Path):
    """``(ops, spans, events)`` of one trace file, read in one pass.

    ``ops`` are the ``(start_ns, end_ns)`` of each TPU plane's operations
    (without the names ``trace.load`` keeps, which are long and not needed
    here); ``spans`` are the ``bench.*`` host spans as ``trace.load`` gives
    them; ``events`` are the ``milo.*`` host events as
    ``(start_ns, end_ns, name, args, thread)``.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[str, list] = {}
    spans: list = []
    events: list = []
    for plane in pd.planes:
        if trace._is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    name = ev.name
                    if name.startswith(trace.SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, name))
                    elif name.startswith(PREFIX):
                        events.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, name,
                                       dict(ev.stats), (plane.name, i)))
    return ops, spans, events


def chains(events: list) -> list[tuple[str, ...]]:
    """For each event, the names of the events it nests in on its thread,
    outermost first, and its own name last."""
    out: list = [None] * len(events)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][4], events[i][0], -events[i][1]))
    stack: list = []
    for i in order:
        a, b, name, _, thread = events[i]
        while stack and (events[stack[-1]][4] != thread
                         or events[stack[-1]][1] <= a):
            stack.pop()
        out[i] = (out[stack[-1]] if stack else ()) + (name,)
        stack.append(i)
    return out


def owners(events: list, lo, hi) -> list:
    """``[lo, hi]`` cut into ``(t0, t1, i)`` pieces, ``i`` the innermost event
    open over the piece (the latest started, the shortest on a tie), or -1."""
    points = sorted({lo, hi} | {t for ev in events for t in ev[:2]
                                if lo < t < hi})
    starts = sorted(range(len(events)), key=lambda i: events[i][0])
    open_: list = []
    j, out = 0, []
    for t0, t1 in zip(points, points[1:]):
        while j < len(starts) and events[starts[j]][0] <= t0:
            i = starts[j]
            heapq.heappush(open_, (-events[i][0], events[i][1] - events[i][0], i))
            j += 1
        while open_ and events[open_[0][2]][1] <= t0:
            heapq.heappop(open_)
        owner = open_[0][2] if open_ else -1
        if out and out[-1][2] == owner:
            out[-1] = (out[-1][0], t1, owner)
        else:
            out.append((t0, t1, owner))
    return out


def split_idle(idle: list, pieces: list, keys: list) -> Counter:
    """Sum each idle interval's overlap with each owner piece, by the owner's
    key (``UNTRACED`` for -1).  Both lists are sorted and non-overlapping."""
    out: Counter = Counter()
    p = 0
    for g0, g1 in idle:
        while p < len(pieces) and pieces[p][1] <= g0:
            p += 1
        q = p
        while q < len(pieces) and pieces[q][0] < g1:
            t0, t1, i = pieces[q]
            ov = min(t1, g1) - max(t0, g0)
            if ov > 0:
                out[keys[i] if i >= 0 else UNTRACED] += ov
            q += 1
    return out


def reduce(ops: dict[str, list], spans: list, events: list) -> dict:
    """``busy_s``, ``window_s`` and ``devices`` as ``trace.reduce`` gives
    them for the same ``(start_ns, end_ns)`` intervals (they identify the
    run's trace and give the shares their denominator), and:

    - ``idle_ns``: the window's idle nanoseconds summed over the device
      planes, by the chain of the innermost ``milo.*`` span open over them
      (``UNTRACED`` where none is); they sum to ``devices`` x window less
      the busy time;
    - ``builds``: for each ``milo.build`` that lies whole inside the window,
      its arguments and its count of ``milo.fetch`` spans.
    """
    lo, hi = next((a, b) for a, b, n in spans if n == trace.WINDOW_SPAN)
    if not ops:
        raise ValueError("the trace holds no TPU device plane with XLA ops")
    events = [ev for ev in events if ev[1] > lo and ev[0] < hi]
    keys = ["/".join(c) for c in chains(events)]
    pieces = owners(events, lo, hi)
    busy_ns, idle_ns = [], Counter()
    for plane, evs in sorted(ops.items()):
        busy = trace._union(trace._clip(evs, lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        idle_ns.update(split_idle(idle, pieces, keys))
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy_ns),
        "idle_ns": dict(idle_ns),
        "builds": builds(events, lo, hi),
    }


def builds(events: list, lo, hi) -> list[dict]:
    """The arguments of each ``milo.build`` whole inside ``[lo, hi]``, with
    its count of ``milo.fetch`` spans (``fetches``)."""
    out = []
    for a, b, name, args, thread in sorted(events, key=lambda ev: ev[0]):
        if name != BUILD or a < lo or b > hi:
            continue
        fetches = sum(1 for ev in events if ev[2] == FETCH
                      and ev[4] == thread and a <= ev[0] and ev[1] <= b)
        out.append(dict(args, fetches=fetches))
    return out


def layer(key: str) -> str:
    """The layer an ``idle_ns`` key belongs to."""
    if key == UNTRACED:
        return UNTRACED
    return HOST_LOOP if PREPROCESS in key.split("/") else SESSION


def trace_file() -> Path | None:
    """The newest trace under the harness's output directory, where
    ``bench.run`` keeps a run's trace while the metric readers run."""
    from bench.run import OUT_DIR

    found = sorted(OUT_DIR.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return found[-1] if found else None


def for_run(run: dict) -> dict | None:
    """This module's reduction of the trace a ``--trace 1`` run has just
    reduced with ``trace.reduce`` (``run["trace"]``), read once per file for
    all the readers.  None for an untraced run, or where the trace holds no
    complete ``milo.build``, as a program without the spans gives.  Raises
    where a traced run's own trace is not the newest under the harness's
    output directory (none there, or another run's): its metrics would
    otherwise vanish as if the program had no spans.
    """
    t = run.get("trace")
    if not t:
        return None
    path = trace_file()
    if path is None:
        raise FileNotFoundError(
            "no trace under the harness's output directory; the milo.* "
            "readers read a traced run's trace only there (not under "
            "--trace-dir)")
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce(*load(path))
    red = _cache[key]
    if (red["window_s"], red["busy_s"]) != (t["window_s"], t["busy_s"]):
        raise ValueError(
            f"{path} is not this run's trace: window {red['window_s']} s, "
            f"busy {red['busy_s']} s, against {t['window_s']} s and "
            f"{t['busy_s']} s")
    return red if red["builds"] else None


def idle_share(red: dict | None, of_layer: str) -> float | None:
    """Percent of the window the devices sat idle under ``of_layer``'s
    spans, or None without a reduction."""
    if red is None:
        return None
    ns = sum(v for k, v in red["idle_ns"].items() if layer(k) == of_layer)
    return 100.0 * ns / (red["devices"] * red["window_s"] * 1e9)
