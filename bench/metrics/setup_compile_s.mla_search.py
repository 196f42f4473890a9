"""Seconds JAX spent compiling before the window: the total of the
program's ``jax.compile_s`` histogram (tracing, lowering and backend
compile of each program, from its first compiled call on) after the
window, less its increase over the window."""

from bench.common import program_counters


def read(run):
    count, total = program_counters()["histograms"].get("jax.compile_s", (0, 0.0))
    _, window = run.delta["histograms"].get("jax.compile_s", (0, 0.0))
    return total - window if count else None
