"""Reduction of a profiler trace to device busy time, op time and idle gaps.

A traced run records its window with ``jax.profiler`` and wraps each call
into a layer in a host annotation named ``bench.<layer>``; the window itself
is ``bench.window``.  :func:`load` turns the ``.xplane.pb`` into plain event
lists (``[name, start_ns, end_ns]``), and :func:`reduce_events` works on
those lists only, so tests check it on traces written out by hand;
:func:`trim` cuts a chip trace small enough to keep as a recorded one.

* device busy: the union of the intervals in which an op ran on a device,
  clipped to the window, averaged over the devices;
* op time by name: summed op durations inside the window, averaged likewise;
* per host span name: calls, host seconds and the device-busy seconds that
  fall inside those spans;
* idle gaps: the complement of the first device's busy union inside the
  window, each named by the innermost benchmark span around its middle.
"""

from __future__ import annotations

import bisect
import re

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
#: device lines that aggregate other lines (not ops) and are never counted
_AGGREGATE_LINES = ("XLA Modules", "Steps", "Framework Ops", "Framework Name Scope",
                    "Source code", "XLA TraceMe", "Launch Stats")
OPS_LINE = "XLA Ops"
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """``%while.17 (while)`` from an op event's HLO text."""
    head, sep, _ = text.partition(" = ")
    m = _OPCODE.search(text) if sep else None
    return f"{head} ({m.group(1)})" if m else head[:120]


def load(path: str) -> dict:
    """Plain event lists from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: dict[str, dict[str, list]] = {}
    host: list[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {}
            for line in plane.lines:
                evs = [[op_name(e.name), e.start_ns, e.end_ns] for e in line.events]
                if evs:
                    lines[line.name] = evs
            if lines:
                device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, e.start_ns, e.end_ns])
    return {"device": device, "host": host}


def ops_line(lines: dict[str, list]) -> list:
    """The line holding one event per op executed on a device."""
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    rest = [v for k, v in lines.items() if k not in _AGGREGATE_LINES]
    return max(rest, key=len) if rest else []


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(busy: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by the sorted disjoint intervals ``busy``."""
    starts = [s for s, _ in busy]
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while i < len(busy) and busy[i][0] < b:
        s, e = busy[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


def _window(host: list) -> tuple[float, float]:
    w = [(s, e) for n, s, e in host if n == WINDOW]
    if not w:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    return min(s for s, _ in w), max(e for _, e in w)


def reduce_events(events: dict, top: int = 10) -> dict:
    """Busy/idle, op time, per-span device time and idle gaps of one trace."""
    host = events["host"]
    w0, w1 = _window(host)
    window_ns = w1 - w0
    planes = sorted(events["device"])
    if not planes:
        raise ValueError("trace holds no device plane")
    spans = [(n, s, e) for n, s, e in host if n != WINDOW]
    busy_by_plane = []
    op_ns: dict[str, float] = {}
    for p in planes:
        evs = ops_line(events["device"][p])
        clipped = []
        for name, s, e in evs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                clipped.append((s, e))
                op_ns[name] = op_ns.get(name, 0.0) + (e - s)
        busy_by_plane.append(union(clipped))
    n = len(planes)
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_by_plane) / n

    per_span: dict[str, dict] = {}
    for name, s, e in spans:
        d = per_span.setdefault(name, {"calls": 0, "host_s": 0.0, "device_s": 0.0})
        d["calls"] += 1
        d["host_s"] += (e - s) / 1e9
        d["device_s"] += sum(overlap(b, s, e) for b in busy_by_plane) / n / 1e9

    gaps = []
    prev = w0
    for s, e in busy_by_plane[0] + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        around = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        label = min(around)[1] if around else "outside benchmark calls"
        named.append([label, (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    ops = sorted(([k, v / n / 1e9] for k, v in op_ns.items()), key=lambda o: -o[1])
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "devices": n,
        "spans": per_span,
        "device_ops": ops[:top],
        "idle_gaps": named[:top],
    }


def trim(events: dict, max_events: int = 200) -> dict:
    """A small copy of a trace (for the recorded test fixture)."""
    host = [h for h in events["host"] if h[0] == WINDOW]
    w0, _ = _window(events["host"])
    device = {}
    end = None
    for p, lines in events["device"].items():
        evs = sorted(ops_line(lines), key=lambda ev: ev[1])[:max_events]
        device[p] = {OPS_LINE: evs}
        if evs:
            end = evs[-1][2] if end is None else max(end, evs[-1][2])
    end = end if end is not None else w0
    host = [[WINDOW, w0, end]] + [h for h in events["host"]
                                  if h[0] != WINDOW and h[1] >= w0 and h[2] <= end]
    return {"device": device, "host": host}
