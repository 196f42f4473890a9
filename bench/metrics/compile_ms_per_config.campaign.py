"""Compile milliseconds per measured config: the increase of the platform's
``xla_cpu.compile_s`` histogram over the window, per shape it counted."""


def read(run):
    n = run.delta["counters"].get("xla_cpu.shapes", 0)
    count, total = run.delta["histograms"].get("xla_cpu.compile_s", (0, 0.0))
    return total / n * 1e3 if n and count else None
