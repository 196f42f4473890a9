"""PR estimator as a deployment tool: predict step times and rank configs.

  PYTHONPATH=src python examples/estimate_and_tune.py

1. Builds PR-trained layer estimators for the TPU-v5e platform (~1 min).
2. Predicts the train_4k step time of each assigned architecture on the
   production mesh -- milliseconds per query instead of minutes per compile.
3. Runs the advisor (the paper's NAS use-case): ranks (dp, tp, microbatch)
   candidates for qwen3-moe and prints the recommended launch config.
"""

from repro.accelerators import TPUv5eSim
from repro.configs import ARCHS, get_config
from repro.core.advisor import autotune, default_candidates
from repro.core.network import decompose
from repro.models.config import SHAPES, shape_applicable

import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from experiments.table2_whole_network import build_network_estimator  # noqa: E402


def main() -> None:
    platform = TPUv5eSim(knowledge="gray", noise=0.001)
    print("building PR-trained layer estimators (800 samples per layer type)...")
    net = build_network_estimator(platform, 800)

    print("\npredicted train_4k step time on the 16x16 production mesh:")
    for arch in ARCHS:
        cfg = get_config(arch)
        shape = SHAPES["train_4k"]
        blocks = decompose(cfg, shape, dp=16, tp=16)
        t = net.predict_network(blocks)
        print(f"  {arch:24s} {t*1e3:9.2f} ms/step")

    print("\nadvisor ranking for qwen3-moe-235b-a22b train_4k (256 chips):")
    ranking = autotune(net, get_config("qwen3-moe-235b-a22b"), SHAPES["train_4k"],
                       default_candidates(256))
    for cand, t in ranking[:5]:
        print(f"  {str(cand):28s} est {t*1e3:9.2f} ms/step")
    print(f"\nrecommended: {ranking[0][0]}")


if __name__ == "__main__":
    main()
