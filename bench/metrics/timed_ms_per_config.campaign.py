"""Timed-loop milliseconds per measured config: the increase of the
platform's ``xla_cpu.timed_s`` histogram over the window, per shape."""


def read(run):
    n = run.delta["counters"].get("xla_cpu.shapes", 0)
    count, total = run.delta["histograms"].get("xla_cpu.timed_s", (0, 0.0))
    return total / n * 1e3 if n and count else None
