"""Device-busy milliseconds inside the benchmark's span around each
``PerfOracle.predict`` call of the latency table, per call (profiler trace)."""

SPAN = "bench.table"


def read(run):
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    return s["device_s"] / s["calls"] * 1e3 if s and s["calls"] else None
