"""Megabytes the program copies host to device per ``autotune`` call of the
hybrid mesh search: the window's increase of its ``jax.network.h2d_bytes``
counter over the calls of the benchmark's ``bench.search`` span (profiler
trace)."""

SPAN = "bench.search"


def read(run):
    n = run.delta["counters"].get("jax.network.h2d_bytes")
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    return n / s["calls"] / 1e6 if n and s and s["calls"] else None
