"""The program's own spans in a profiler trace, beside the benchmark's.

Under a tracer with the profiler sink (``repro.obs.Tracer(path=None,
profiler=True)``) the oracle's query path names its work in the profiler's
host plane: ``advisor.decompose``, ``estimator.features``, ``network.pack``,
``network.launch`` and ``forest.launch``.  They share the clock of the device
ops and of the benchmark's ``bench.*`` spans, so a device gap inside a
benchmark call can be put down to the part of the host path that ran in it.

:func:`load` is :func:`bench.trace_reduce.load` plus the program spans, each
with its thread line; :func:`reduce_events` is
:func:`bench.trace_reduce.reduce_events`, whose keys it leaves as they are,
plus ``program``: for each span name its ``calls``, ``host_s``, ``self_s``
(``host_s`` less what child program spans on the same line cover) and
``device_s`` (device busy inside it).  Its ``idle_gaps`` are named by the
innermost span of either kind around each gap's middle.
"""

from __future__ import annotations

from bench import trace_reduce
from bench.trace_reduce import WINDOW, ops_line, overlap, union

#: name prefixes of the program's spans (no JAX runtime event starts so)
PROGRAM_PREFIXES = ("advisor.", "estimator.", "network.", "forest.")


def load(path: str) -> dict:
    """:func:`bench.trace_reduce.load` plus ``program``: ``[name, start_ns,
    end_ns, line]`` of every program span in the host planes."""
    from jax.profiler import ProfileData

    events = trace_reduce.load(path)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        program.append([e.name, e.start_ns, e.end_ns, line.name])
    return {**events, "program": program}


def _child_ns(program: list) -> list[float]:
    """Per span, the time its direct children on the same line cover."""
    child = [0.0] * len(program)
    lines: dict[str, list[int]] = {}
    for i, (_, _, _, line) in enumerate(program):
        lines.setdefault(line, []).append(i)
    for idx in lines.values():
        idx.sort(key=lambda i: (program[i][1], -program[i][2]))
        open_spans: list[int] = []
        for i in idx:
            _, s, e, _ = program[i]
            while open_spans and program[open_spans[-1]][2] <= s:
                open_spans.pop()
            if open_spans and e <= program[open_spans[-1]][2]:
                child[open_spans[-1]] += e - s
            open_spans.append(i)
    return child


def reduce_events(events: dict, top: int = 10) -> dict:
    """:func:`bench.trace_reduce.reduce_events` with ``program`` and the idle
    gaps named by program spans too."""
    out = trace_reduce.reduce_events(events, top)
    program = events.get("program", [])
    host = events["host"]
    w0, w1 = trace_reduce._window(host)
    busy_by_plane = []
    for p in sorted(events["device"]):
        busy_by_plane.append(union([(max(s, w0), min(e, w1))
                                    for _, s, e in ops_line(events["device"][p])]))
    n = len(busy_by_plane)

    per_name: dict[str, dict] = {}
    for (name, s, e, _), child in zip(program, _child_ns(program)):
        d = per_name.setdefault(name, {"calls": 0, "host_s": 0.0, "self_s": 0.0,
                                       "device_s": 0.0})
        d["calls"] += 1
        d["host_s"] += (e - s) / 1e9
        d["self_s"] += (e - s - child) / 1e9
        d["device_s"] += sum(overlap(b, s, e) for b in busy_by_plane) / n / 1e9

    spans = [(nm, s, e) for nm, s, e in host if nm != WINDOW]
    spans += [(nm, s, e) for nm, s, e, _ in program]
    gaps = []
    prev = w0
    for s, e in busy_by_plane[0] + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        around = [(e - s, nm) for nm, s, e in spans if s <= mid <= e]
        named.append([min(around)[1] if around else "outside benchmark calls",
                      (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    return {**out, "program": per_name, "idle_gaps": named[:top]}
