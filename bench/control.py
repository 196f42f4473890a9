"""Readings that set the limits of ``correct``: the program's and the control's.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, one run of the cell in this process (set-up, a window at the
cell's own load, the check), then the control in the program's place on the
same inputs, computed one precision below the one the configuration states:
the plain reference in float32 for the float64 traversal, and the
platform's dense program at precision ``high`` (three bfloat16 passes) for
the float32 product at ``highest``.  Prints one JSON line per seed with the program's
compared numbers (``checks``) and the control's (``control``).  The limit of
each number lies between the largest program reading and the smallest
control reading.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(CHECKOUT), str(CHECKOUT / "src")] + [p for p in sys.path if p != here]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / "bench" / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    from bench.common import Cell
    from bench.run import execute

    for seed in (int(s) for s in args.seeds.split(",")):
        cell = Cell(args.workload)
        result, _ = execute(cell, seed, args.seconds, False, t_start=time.perf_counter(),
                            control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": result["correct"], "checks": result["checks"],
                          "control": result["control"], "metrics": result["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
