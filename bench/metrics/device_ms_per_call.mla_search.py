"""Device-busy milliseconds inside the benchmark's span around each
``autotune`` call of the latent-attention mesh search, per call (from the
profiler trace)."""

SPAN = "bench.search"


def read(run):
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    return s["device_s"] / s["calls"] * 1e3 if s and s["calls"] else None
