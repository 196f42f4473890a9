"""Table 1: best single-layer estimation accuracy per platform x layer type.

PR-sampled training sets (paper: <=9000 points; CI scale: 2000), evaluated on
realistic held-out layer configurations; reports RMSPE / MAPE, the mean
measurement time per benchmark point (the cost the PR method saves), and the
campaign cache's unique-measurement count.

Runs entirely through ``repro.api`` (CampaignSpec -> Campaign -> PerfOracle).
"""

from __future__ import annotations

from experiments.common import Timer, emit, table1_size
from repro.api import Campaign, CampaignSpec

# Realistic test layers per platform/layer type (the paper uses TC-ResNet8 and
# Keras-zoo layers; here: TC-ResNet8 for UltraTrail, VGG/ResNet-ish for VTA,
# and the assigned LM architectures' layer shapes for the TPU platform).
TCRESNET8 = [
    {"C": 40, "C_w": 101, "K": 16, "F": 3, "s": 1, "pad": 1},
    {"C": 16, "C_w": 101, "K": 24, "F": 9, "s": 2, "pad": 4},
    {"C": 24, "C_w": 51, "K": 24, "F": 9, "s": 1, "pad": 4},
    {"C": 16, "C_w": 101, "K": 24, "F": 2, "s": 2, "pad": 0},
    {"C": 24, "C_w": 51, "K": 32, "F": 9, "s": 2, "pad": 4},
    {"C": 32, "C_w": 26, "K": 32, "F": 9, "s": 1, "pad": 4},
    {"C": 32, "C_w": 26, "K": 48, "F": 9, "s": 2, "pad": 4},
    {"C": 48, "C_w": 13, "K": 48, "F": 9, "s": 1, "pad": 4},
]

VTA_CONV = [
    {"C": 64, "C_h": 56, "C_w": 56, "K": 64, "F": 3, "s": 1, "pad": 1},
    {"C": 128, "C_h": 28, "C_w": 28, "K": 128, "F": 3, "s": 1, "pad": 1},
    {"C": 96, "C_h": 14, "C_w": 14, "K": 160, "F": 3, "s": 1, "pad": 1},
    {"C": 192, "C_h": 14, "C_w": 14, "K": 192, "F": 1, "s": 1, "pad": 1},
]
VTA_FC = [
    {"in": 512, "out": 1000},
    {"in": 576, "out": 120},
    {"in": 768, "out": 512},
    {"in": 1000, "out": 730},
]

# layer shapes of the assigned LM archs (per-device, dp=16 tp=16, train_4k)
TPU_DENSE = [
    {"tokens": 65536, "d_in": 1536, "d_out": 560},    # qwen2 mlp shard
    {"tokens": 65536, "d_in": 2048, "d_out": 512},    # internlm2
    {"tokens": 65536, "d_in": 6144, "d_out": 1536},   # granite
    {"tokens": 65536, "d_in": 2560, "d_out": 640},    # zamba2
    {"tokens": 4096, "d_in": 4096, "d_out": 9496},    # lm head shard
]
TPU_ATTN = [
    {"B": 16, "S": 4096, "H": 3, "Dh": 128, "kv_ratio": 4},
    {"B": 2, "S": 32768, "H": 4, "Dh": 128, "kv_ratio": 4},
]
TPU_MOE = [
    {"tokens": 4096, "d_model": 2048, "d_ff": 1024, "E": 4, "topk": 8},
    {"tokens": 4096, "d_model": 4096, "d_ff": 1536, "E": 8, "topk": 8},
]
TPU_SSD = [
    {"B": 16, "S": 4096, "H": 3, "P": 64, "N": 128},
    {"B": 16, "S": 4096, "H": 5, "P": 64, "N": 64},
]

# (platform name, platform kwargs, layer type, test configs, budget fraction)
CASES = [
    ("ultratrail", {}, "conv1d", TCRESNET8, 1.0),
    ("vta", {}, "conv2d", VTA_CONV, 1.0),
    ("vta", {}, "fully_connected", VTA_FC, 1.0),
    ("tpu_v5e", {"knowledge": "gray", "noise": 0.002}, "dense", TPU_DENSE, 1.0),
    ("tpu_v5e", {"knowledge": "gray", "noise": 0.002}, "attention_prefill", TPU_ATTN, 1.0),
    ("tpu_v5e", {"knowledge": "gray", "noise": 0.002, "moe_experts": 8}, "moe_gemm", TPU_MOE, 0.5),
    ("tpu_v5e", {"knowledge": "black", "noise": 0.002}, "ssd_scan", TPU_SSD, 0.5),
    ("xla_cpu", {"repeats": 3}, "dense",
     [{"tokens": 96, "d_in": 384, "d_out": 160}, {"tokens": 160, "d_in": 96, "d_out": 320}],
     0.05),  # real measurements are expensive: tiny training set
]


def main() -> None:
    n_base = table1_size()
    for platform_name, platform_kwargs, layer, test, frac in CASES:
        n = max(100, int(n_base * frac))
        spec = CampaignSpec(
            platform=platform_name,
            layer_types=(layer,),
            sampling="pr",
            n_samples=n,
            seed=0,
            platform_kwargs=platform_kwargs,
        )
        campaign = Campaign(spec)
        with Timer() as t:
            oracle = campaign.run()
            m = oracle.evaluate(campaign.platform, layer, test)
        est = oracle.estimators[layer]
        stats = campaign.stats()
        emit(
            f"table1[{campaign.platform.name}/{layer}]",
            t.us(n),
            f"n={n};rmspe={m['rmspe']:.2f}%;mape={m['mape']:.2f}%;"
            f"meas_time_s={est.mean_measure_seconds:.2e};sweep_pts={est.n_sweep};"
            f"unique_meas={stats['unique_measurements']};cache_hits={stats['hits']}",
        )


if __name__ == "__main__":
    main()
