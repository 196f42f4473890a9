"""Fig. 2 analog: parameter sweeps exhibit step-wise behavior; PRs detected.

For each platform x layer x parameter: run the sweep, run Algorithm 1, and
report the detected step width (the PRs are the last point of each step).
"""

from __future__ import annotations

from experiments.common import Timer, emit
from repro.accelerators import TPUv5eSim, UltraTrailSim, VTASim
from repro.core import steps, sweeps


CASES = [
    (UltraTrailSim(), "conv1d", ("C", "K", "C_w")),
    (VTASim(), "fully_connected", ("in", "out")),
    (VTASim(), "conv2d", ("C", "K")),
    (TPUv5eSim(knowledge="black", noise=0.002), "dense", ("tokens", "d_in", "d_out")),
    (TPUv5eSim(knowledge="black", noise=0.002), "moe_gemm", ("tokens", "d_ff")),
    (TPUv5eSim(knowledge="black", noise=0.002), "attention_decode", ("S_kv",)),
    (TPUv5eSim(knowledge="black", noise=0.002), "ssd_scan", ("S",)),
]


def main() -> None:
    for platform, layer, params in CASES:
        with Timer() as t:
            sw = sweeps.run_sweeps(platform, layer, params=params, n_points=256)
            widths = steps.determine_step_widths(sw)
        n_meas = sum(len(x) for x, _ in sw.values())
        detected = ";".join(f"{p}:w={widths[p]}" for p in params)
        emit(f"fig2_sweep[{platform.name}/{layer}]", t.us(n_meas), detected)


if __name__ == "__main__":
    main()
