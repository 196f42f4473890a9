"""Shared experiment helpers: CSV emission + scale control.

Every experiment prints ``name,us_per_call,derived`` rows (harness contract).
``us_per_call`` is the mean wall-time of the experiment's unit operation in
microseconds; ``derived`` carries the headline metric (e.g. ``mape=1.23%``).

REPRO_BENCH_SCALE=full reproduces paper-scale sample counts (~9000); the
default "ci" scale keeps the full suite under a few minutes on one CPU core.
"""

from __future__ import annotations

import os
import time


def scale() -> str:
    return os.environ.get("REPRO_BENCH_SCALE", "ci")


def sizes_for_curves() -> list[int]:
    if scale() == "full":
        return [250, 500, 1000, 2000, 4000, 9000]
    return [250, 500, 1000, 2000]


def table1_size() -> int:
    return 9000 if scale() == "full" else 2000


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0

    def us(self, n_calls: int = 1) -> float:
        return self.seconds / max(1, n_calls) * 1e6
