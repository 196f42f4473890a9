"""Host milliseconds per ``autotune`` call of the hybrid mesh search: the
benchmark's span around the call less the device-busy time inside it (from
the profiler trace)."""

SPAN = "bench.search"


def read(run):
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    return (s["host_s"] - s["device_s"]) / s["calls"] * 1e3 if s and s["calls"] else None
