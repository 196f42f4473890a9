"""End-to-end behaviour of the paper's system (Fig. 1 pipeline).

sweeps -> Algorithm 1 -> PR set -> PR benchmarking -> Random Forest ->
PR mapping -> single-layer estimates -> building blocks -> whole network.
"""

import numpy as np

from repro.accelerators import TPUv5eSim, UltraTrailSim
from repro.api import PerfOracle, train_layer_estimator
from repro.core import prs
from repro.core.network import decompose, simulate_network
from repro.configs import get_config
from repro.models.config import SHAPES


def test_full_pipeline_ultratrail():
    """White-box path: documented widths -> PR sampling -> accurate estimates."""
    ut = UltraTrailSim()
    est = train_layer_estimator(ut, "conv1d", 1200, sampling="pr", seed=0)
    # TC-ResNet8-style layers (the paper's UltraTrail test set)
    layers = [
        {"C": 40, "C_w": 101, "K": 16, "F": 3, "s": 1, "pad": 1},
        {"C": 16, "C_w": 101, "K": 24, "F": 9, "s": 2, "pad": 4},
        {"C": 48, "C_w": 13, "K": 48, "F": 9, "s": 1, "pad": 4},
    ]
    m = est.evaluate(ut, layers)
    assert m["mape"] < 10.0


def test_full_pipeline_blackbox_to_whole_network():
    """Black-box path on the TPU sim, through to a whole-model estimate."""
    tpu = TPUv5eSim(knowledge="black", noise=0.001)
    layer_types = ("dense", "attention_prefill", "ssd_scan", "embed")
    ests = {lt: train_layer_estimator(tpu, lt, 500, sampling="pr", seed=1) for lt in layer_types}
    # discovered widths include the MXU quantisation
    assert ests["dense"].widths["d_in"] == 128

    net = PerfOracle(estimators=ests)
    cfg = get_config("mamba2-780m")
    blocks = decompose(cfg, SHAPES["train_4k"], dp=16, tp=16)
    t_est = net.predict_network(blocks)
    t_sim = simulate_network(tpu, blocks)
    assert t_est > 0 and t_sim > 0
    # whole-model estimate within 2x of the simulated ground truth even
    # without fusing-factor correction (tightened by the benchmarks)
    assert 0.5 < t_est / t_sim < 2.0


def test_pr_sampling_needs_fewer_samples_than_random():
    """The paper's headline claim, as a regression test."""
    ut = UltraTrailSim()
    space = ut.param_space("conv1d")
    rng = np.random.default_rng(7)
    test = prs.sample_random_configs(space, 50, rng)
    pr_small = train_layer_estimator(ut, "conv1d", 600, sampling="pr", seed=2)
    rand_big = train_layer_estimator(ut, "conv1d", 1200, sampling="random", seed=2)
    assert pr_small.evaluate(ut, test)["mape"] < rand_big.evaluate(ut, test)["mape"]
