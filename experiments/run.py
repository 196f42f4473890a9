"""The paper's accuracy experiments -- one function per table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``us_per_call`` is CPU wall
clock, not a device metric (the on-chip benchmark is ``bench/``).

  PYTHONPATH=src python -m experiments.run            # every suite, CI scale
  REPRO_BENCH_SCALE=full PYTHONPATH=src python -m experiments.run   # paper scale
  PYTHONPATH=src python -m experiments.run table1 fig2 ...          # subset
"""

from __future__ import annotations

import sys
import time
import traceback

from experiments import (
    fig2_sweeps,
    fig4to7_curves,
    table1_single_layer,
    table2_whole_network,
    table3_sota,
)

SUITES = {
    "fig2": fig2_sweeps.main,
    "table1": table1_single_layer.main,
    "fig4to7": fig4to7_curves.main,
    "table2": table2_whole_network.main,
    "table3": table3_sota.main,
}


def main() -> None:
    names = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        t0 = time.perf_counter()
        try:
            SUITES[name]()
        except Exception as e:  # keep the harness running; report the failure
            failures += 1
            print(f"{name},0.000,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
        print(f"{name}.total,{(time.perf_counter() - t0) * 1e6:.0f},done")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
