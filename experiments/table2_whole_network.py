"""Table 2: whole-network execution-time estimation.

The paper estimates MobileNet/ResNet18 on two platforms (0.68%-19.66% error).
Here the "networks" are the assigned LM architectures decomposed into
building blocks (core/network.py) on the sharded TPU-v5e platform; ground
truth is the platform's overlapped block execution (Eq. 9 max rule for
compute/DMA/ICI overlap).  Estimators are PR-trained per layer type; block
fusing factors (Eq. 10/11) are fitted on ~120 random block configurations.
"""

from __future__ import annotations

import numpy as np

from experiments.common import Timer, emit, scale
from repro.accelerators import TPUv5eSim
from repro.api import Campaign, CampaignSpec, PerfOracle
from repro.configs import get_config
from repro.core.blocks import Block, fit_fusing_model
from repro.core.network import decompose, simulate_network
from repro.models.config import SHAPES

ARCH_SHAPES = [
    ("qwen2-1.5b", "train_4k"),
    ("internlm2-1.8b", "train_4k"),
    ("granite-20b", "train_4k"),
    ("mamba2-780m", "train_4k"),
    ("zamba2-2.7b", "train_4k"),
    ("olmoe-1b-7b", "train_4k"),
    ("qwen2-1.5b", "decode_32k"),
    ("mamba2-780m", "long_500k"),
]


def _block_training_set(blocks_per_kind: int, rng) -> list[Block]:
    """Random MLP/attn block configs for fusing-factor fitting."""
    out = []
    for _ in range(blocks_per_kind):
        t = int(rng.choice([8192, 16384, 65536]))
        d = int(rng.choice([1536, 2048, 2560]))
        f = int(rng.choice([512, 560, 640, 1536]))
        out.append(
            Block(
                kind="mlp",
                layers=(
                    ("dense", {"tokens": t, "d_in": d, "d_out": f}),
                    ("dense", {"tokens": t, "d_in": d, "d_out": f}),
                    ("dense", {"tokens": t, "d_in": f, "d_out": d}),
                ),
            )
        )
    return out


def build_network_estimator(platform, n_per_layer: int = 1200) -> PerfOracle:
    """Campaign over every TPU layer type -> PerfOracle with fusing models."""
    layer_types = ("dense", "attention_prefill", "attention_decode", "moe_gemm", "ssd_scan", "embed")
    spec = CampaignSpec(
        platform=platform.name,
        layer_types=layer_types,
        sampling="pr",
        n_samples=n_per_layer,
        seed=0,
    )
    campaign = Campaign(spec, platform=platform)
    oracle = campaign.run()
    rng = np.random.default_rng(0)
    oracle.fusing = {
        "mlp": fit_fusing_model(campaign.platform, oracle.estimators, _block_training_set(60, rng))
    }
    oracle.launch_overhead_s = platform.chip.launch_overhead_s  # documented (gray box)
    return oracle


def main() -> None:
    platform = TPUv5eSim(knowledge="gray", noise=0.001)
    n = 2500 if scale() == "full" else 800
    with Timer() as t_build:
        net_est = build_network_estimator(platform, n)
    emit("table2[build_estimators]", t_build.us(6 * n), f"n_per_layer={n}")

    errs = []
    for arch, shape_name in ARCH_SHAPES:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        blocks = decompose(cfg, shape, dp=16, tp=16)
        with Timer() as t:
            t_est = net_est.predict_network(blocks)
        t_true = simulate_network(platform, blocks)
        err = abs(t_est - t_true) / t_true * 100
        errs.append(err)
        emit(
            f"table2[{arch}/{shape_name}]",
            t.us(),
            f"meas_ms={t_true*1e3:.3f};est_ms={t_est*1e3:.3f};err={err:.2f}%",
        )
    emit("table2[mean]", 0.0, f"mean_err={np.mean(errs):.2f}%;max_err={np.max(errs):.2f}%")


if __name__ == "__main__":
    main()
