"""Building blocks (Eq. 9-12) and whole-model decomposition."""

import numpy as np
import pytest

from repro.accelerators import TPUv5eSim
from repro.api import PerfOracle, train_layer_estimator
from repro.configs import ARCHS, get_config
from repro.core import prs
from repro.core.blocks import Block, FusingModel, block_ops, fit_fusing_model
from repro.core.network import decompose, simulate_network
from repro.models.config import SHAPES, shape_applicable


@pytest.fixture(scope="module")
def tpu():
    return TPUv5eSim(knowledge="white")


@pytest.fixture(scope="module")
def dense_est(tpu):
    return {"dense": train_layer_estimator(tpu, "dense", 800, sampling="pr", seed=0)}


def _mlp_blocks(n, rng):
    out = []
    for _ in range(n):
        t = int(rng.choice([512, 1024, 2048, 4096]))
        d = int(rng.choice([512, 1024, 2048]))
        f = int(rng.choice([1024, 2048, 4096]))
        out.append(
            Block(
                kind="mlp",
                layers=(
                    ("dense", {"tokens": t, "d_in": d, "d_out": f}),
                    ("dense", {"tokens": t, "d_in": f, "d_out": d}),
                ),
            )
        )
    return out


def test_fusing_factor_reduces_block_error(tpu, dense_est):
    rng = np.random.default_rng(0)
    train_blocks = _mlp_blocks(120, rng)
    fusing = fit_fusing_model(tpu, dense_est, train_blocks)
    est_plain = PerfOracle(estimators=dense_est)
    est_fused = PerfOracle(estimators=dense_est, fusing={"mlp": fusing})
    test_blocks = _mlp_blocks(40, np.random.default_rng(1))
    err_plain, err_fused = [], []
    for b in test_blocks:
        t_true = tpu.measure_block(list(b.layers))
        err_plain.append(abs(est_plain.predict_block(b) - t_true) / t_true)
        err_fused.append(abs(est_fused.predict_block(b) - t_true) / t_true)
    # the naive sum over-estimates overlapped blocks systematically; the
    # Eq. 10/11 correction must not make things worse on held-out blocks
    assert abs(np.mean(np.array(err_fused))) <= abs(np.mean(np.array(err_plain))) * 1.05
    assert fusing.n_fit == 120


def test_eq9_max_rule():
    ests = {}
    est = PerfOracle(estimators=ests, overlap_kinds=frozenset({"ov"}))

    class Fake:
        def predict_one(self, cfg):
            return cfg["t"]

    est = PerfOracle(estimators={"x": Fake()}, overlap_kinds=frozenset({"ov"}))
    b = Block(kind="ov", layers=(("x", {"t": 3.0}), ("x", {"t": 5.0})))
    assert est.predict_block(b) == 5.0  # max, not sum
    b2 = Block(kind="seq", layers=b.layers)
    assert est.predict_block(b2) == 8.0


def test_block_ops_positive():
    b = Block(kind="mlp", layers=(("dense", {"tokens": 10, "d_in": 4, "d_out": 8}),))
    assert block_ops(b) == 2.0 * 10 * 4 * 8


@pytest.mark.parametrize("arch", ARCHS)
def test_decompose_all_cells(arch, tpu):
    """Every (arch x applicable shape) decomposes into measurable blocks."""
    cfg = get_config(arch)
    for shape in SHAPES.values():
        if not shape_applicable(cfg, shape):
            continue
        blocks = decompose(cfg, shape, dp=16, tp=16)
        assert blocks, (arch, shape.name)
        t = simulate_network(tpu, blocks)
        assert np.isfinite(t) and t > 0, (arch, shape.name)


def test_decompose_moe_has_moe_block():
    blocks = decompose(get_config("olmoe-1b-7b"), SHAPES["train_4k"], 16, 16)
    assert any(b.kind == "moe" for b in blocks)
    assert not any(b.kind == "ssd" for b in blocks)


def test_decompose_hybrid_has_both():
    blocks = decompose(get_config("zamba2-2.7b"), SHAPES["train_4k"], 16, 16)
    kinds = {b.kind for b in blocks}
    assert "ssd" in kinds and "attn" in kinds


class _NoMeasureBlockPlatform:
    """Duck-typed platform missing measure_block entirely."""

    name = "no-measure-block"


def test_evaluate_networks_raises_without_measure_block():
    """A platform without measure_block must raise, not return nan/inf.

    The old ternary silently accumulated 0.0 ground truth, making mape
    divide by zero and report nan/inf as if it were a result.
    """
    class Fake:
        def predict_one(self, cfg):
            return cfg["t"]

    est = PerfOracle(estimators={"x": Fake()})
    net = [Block(kind="seq", layers=(("x", {"t": 1.0}),))]
    with pytest.raises(TypeError, match="measure_block"):
        est.evaluate_networks(_NoMeasureBlockPlatform(), [net])


def test_fit_fusing_model_raises_without_measure_block(dense_est):
    with pytest.raises(TypeError, match="measure_block"):
        fit_fusing_model(_NoMeasureBlockPlatform(), dense_est, _mlp_blocks(3, np.random.default_rng(0)))


def test_fit_fusing_model_measures_with_collectives(tpu, dense_est):
    """f_beta must be fitted against collectives-inclusive block times, the
    same ground truth simulate_network/evaluate_networks measure."""
    rng = np.random.default_rng(2)
    blocks = []
    for b in _mlp_blocks(40, rng):
        blocks.append(Block(kind=b.kind, layers=b.layers, collective_bytes=2e8))
    got = fit_fusing_model(tpu, dense_est, blocks)

    # expected fit computed directly against collectives-inclusive times
    from repro.core.blocks import block_ops
    f_targets, ops = [], []
    for b in blocks:
        t_meas = tpu.measure_block(list(b.layers), collective_bytes=b.collective_bytes)
        t_sum = sum(dense_est["dense"].predict_one(cfg) for _, cfg in b.layers)
        f_targets.append(t_sum - t_meas)
        ops.append(block_ops(b))
    A = np.stack([np.asarray(ops), np.ones(len(ops))], axis=1)
    coef, *_ = np.linalg.lstsq(A, np.asarray(f_targets), rcond=None)
    assert got.w == pytest.approx(float(coef[0]), rel=1e-12, abs=1e-30)
    assert got.c == pytest.approx(float(coef[1]), rel=1e-12, abs=1e-30)

    # and collectives change the fit: ignoring them would mis-fit c_beta
    plain = fit_fusing_model(tpu, dense_est, _mlp_blocks(40, np.random.default_rng(2)))
    assert got.c != pytest.approx(plain.c, rel=1e-6)
