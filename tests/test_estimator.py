"""Single-layer estimators: PR sampling, mapping, accuracy."""

import numpy as np
import pytest

from repro.accelerators import TPUv5eSim, UltraTrailSim
from repro.api import train_layer_estimator
from repro.core import prs


@pytest.fixture(scope="module")
def ut_estimator():
    return train_layer_estimator(UltraTrailSim(), "conv1d", 1500, sampling="pr", seed=0)


def test_same_step_same_prediction(ut_estimator):
    """Configs inside one step map to the same PR -> identical estimate."""
    base = {"C": 17, "K": 33, "C_w": 101, "F": 3, "s": 1, "pad": 1}
    p1 = ut_estimator.predict_one(base)
    p2 = ut_estimator.predict_one({**base, "C": 20, "K": 38})
    assert p1 == p2


def test_accuracy_on_realistic_layers(ut_estimator):
    layers = [
        {"C": 40, "C_w": 101, "K": 16, "F": 3, "s": 1, "pad": 1},
        {"C": 16, "C_w": 101, "K": 24, "F": 9, "s": 2, "pad": 4},
        {"C": 32, "C_w": 26, "K": 48, "F": 9, "s": 2, "pad": 4},
    ]
    m = ut_estimator.evaluate(UltraTrailSim(), layers)
    assert m["mape"] < 8.0  # paper reaches 0.33% at 9000 samples; 1500 here


def test_pr_beats_random_on_regular_platform():
    ut = UltraTrailSim()
    rng = np.random.default_rng(0)
    space = ut.param_space("conv1d")
    test = prs.sample_random_configs(space, 60, rng)
    est_pr = train_layer_estimator(ut, "conv1d", 1200, sampling="pr", seed=1)
    est_rand = train_layer_estimator(ut, "conv1d", 1200, sampling="random", seed=1)
    m_pr = est_pr.evaluate(ut, test)["mape"]
    m_rand = est_rand.evaluate(ut, test)["mape"]
    assert m_pr < m_rand


def test_estimator_bookkeeping():
    tpu = TPUv5eSim(knowledge="gray")
    est = train_layer_estimator(tpu, "dense", 400, sampling="pr", seed=0)
    assert est.n_train == 400
    assert est.n_sweep > 0  # gray box swept to confirm/discover widths
    assert est.mean_measure_seconds >= 0
    assert est.widths["d_in"] == 128


def test_tpu_dense_estimator_accuracy():
    tpu = TPUv5eSim(knowledge="white")
    est = train_layer_estimator(tpu, "dense", 1500, sampling="pr", seed=0)
    test = [
        {"tokens": 4096, "d_in": 2048, "d_out": 5504},
        {"tokens": 1024, "d_in": 1536, "d_out": 8960},
        {"tokens": 8192, "d_in": 4096, "d_out": 1536},
    ]
    m = est.evaluate(tpu, test)
    assert m["mape"] < 15.0
