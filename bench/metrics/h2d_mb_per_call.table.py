"""Megabytes the program copies host to device per ``PerfOracle.predict``
call of the latency table: the window's increase of its
``jax.forest.h2d_bytes`` counter over the calls of the benchmark's
``bench.table`` span (profiler trace)."""

SPAN = "bench.table"


def read(run):
    n = run.delta["counters"].get("jax.forest.h2d_bytes")
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    return n / s["calls"] / 1e6 if n and s and s["calls"] else None
