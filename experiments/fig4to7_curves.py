"""Figs. 4-7: estimation accuracy vs training-set size, PR vs random sampling.

One curve pair per platform (UltraTrail/VTA/TPUv5e-gray/TPUv5e-black), the
paper's headline comparison: PR sampling reaches a given MAPE with far fewer
samples than random sampling of the complete parameter space.

Runs through ``repro.api``: one Campaign per platform, so step widths are
discovered once per layer type and every training-set size reuses them (the
``saved`` column counts the sweep measurements this avoids), and the
measurement cache deduplicates benchmark points across sizes and sampling
policies.
"""

from __future__ import annotations

from experiments.common import Timer, emit, sizes_for_curves
from experiments.table1_single_layer import TCRESNET8, TPU_DENSE, VTA_FC
from repro.api import Campaign, CampaignSpec

CASES = [
    ("fig4[ultratrail/conv1d]", "ultratrail", {}, "conv1d", TCRESNET8),
    ("fig5[vta/fully_connected]", "vta", {}, "fully_connected", VTA_FC),
    ("fig6[tpu_v5e-gray/dense]", "tpu_v5e", {"knowledge": "gray", "noise": 0.002}, "dense", TPU_DENSE),
    ("fig7[tpu_v5e-black/dense]", "tpu_v5e", {"knowledge": "black", "noise": 0.002}, "dense", TPU_DENSE),
]


def main() -> None:
    for name, platform_name, platform_kwargs, layer, test in CASES:
        campaign = Campaign(
            CampaignSpec(platform=platform_name, layer_types=(layer,), seed=0,
                         platform_kwargs=platform_kwargs)
        )
        for sampling in ("pr", "random"):
            with Timer() as t:
                curve = campaign.sampling_curve(
                    layer, sizes_for_curves(), test, sampling=sampling, seed=0
                )
            points = [f"{p['n']}:{p['mape']:.2f}%" for p in curve]
            saved = curve[-1]["sweeps_saved"]
            emit(
                f"{name}/{sampling}",
                t.us(len(points)),
                ";".join(points) + f";sweeps_saved={saved}",
            )


if __name__ == "__main__":
    main()
