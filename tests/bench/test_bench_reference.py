"""The plain references against the program's jax path at small sizes, and
the controls that must fail the comparison (bench/reference.py)."""

import numpy as np
import pytest

from bench import estimators, forests, generate, reference
from bench.common import BENCH, Cell, load_json

CONFIG = Cell("olmoe.layer_table").config
GAP_LIMIT = CONFIG["limits"]["max_rel_gap"]
DENSE_LIMIT = load_json(BENCH / "configs" / "dense-wallclock.v5e.json")["limits"]["dense_err"]


def test_node_visits_and_bytes_hand_counted():
    i32, f64 = np.int32, np.float64
    # tree 0: x0 <= 1.5 ? 10 : 20        tree 1: x1 <= 0.5 ? 1 : (x0 <= 2.5 ? 2 : 3)
    t0 = (np.array([0, -1, -1], i32), np.array([1.5, 0, 0], f64), np.array([1, 0, 0], i32),
          np.array([2, 0, 0], i32), np.array([0, 10, 20], f64))
    t1 = (np.array([1, -1, 0, -1, -1], i32), np.array([0.5, 0, 2.5, 0, 0], f64),
          np.array([1, 0, 3, 0, 0], i32), np.array([2, 0, 4, 0, 0], i32),
          np.array([0, 1, 0, 2, 3], f64))
    forest = reference.Forest("dense", ["a", "b"], {}, {"a": [0, 9], "b": [0, 9]}, [t0, t1])
    X = np.array([[1.0, 0.0], [2.0, 1.0], [3.0, 1.0]])
    y, visits = forest.raw(X)
    # row 0: t0 1 visit -> 10; t1 1 visit -> 1.   row 1: 1 -> 20; 2 -> 2.
    # row 2: 1 -> 20; 2 -> 3.   visits = 3 + 5
    assert y.tolist() == [5.5, 11.0, 11.5]
    assert visits == 8
    ops, bytes_ = reference.traversal_cost(visits, pairs=6)
    assert (ops, bytes_) == (14.0, 8 * 24 + 6 * 8)


@pytest.mark.parametrize("layer_type", list(CONFIG["layer_types"]))
def test_snap_and_features_match_the_program(layer_type, tiny_hub):
    from repro.api import EstimatorHub
    from repro.core.batch import ConfigBatch

    path, platform, made = tiny_hub
    est = EstimatorHub(str(path)).load(platform, layer_type)
    cols = generate.uniform_columns(CONFIG["layer_types"][layer_type], 500,
                                    np.random.default_rng(3))
    forest = made[layer_type]
    ours = reference.features(layer_type, reference.snap(cols, forest.widths, forest.ranges),
                              forest.params)
    np.testing.assert_array_equal(ours, est._features(ConfigBatch.from_columns(cols)))


@pytest.mark.parametrize("layer_type", list(CONFIG["layer_types"]))
def test_jax_path_agrees_and_float32_control_fails(layer_type, tiny_hub):
    path, platform, made = tiny_hub
    oracle = estimators.load_oracle(path, platform)
    from repro.core.batch import ConfigBatch

    cols = generate.uniform_columns(CONFIG["layer_types"][layer_type], 700,
                                    np.random.default_rng(4))
    forest = made[layer_type]
    ref, _ = forest.predict(cols)
    got = oracle.predict(layer_type, ConfigBatch.from_columns(cols), backend="jax")
    assert reference.max_rel_gap(got, ref) <= GAP_LIMIT
    low, _ = forest.predict(cols, np.float32)
    assert reference.max_rel_gap(low, ref) > GAP_LIMIT


def test_mesh_search_agrees_and_float32_control_fails(tiny_hub):
    from repro.core.advisor import autotune, default_candidates
    from repro.models.config import InputShape, ModelConfig

    path, platform, made = tiny_hub
    oracle = estimators.load_oracle(path, platform)
    launch = CONFIG["launch_overhead_s"]
    shapes = Cell("olmoe.mesh_search").traffic["shapes"]
    for name, chips in (("train_4k", 256), ("decode_32k", 1024)):
        ranking = autotune(oracle, ModelConfig(**CONFIG["model"]),
                           InputShape(name=name, **shapes[name]), default_candidates(chips))
        got = {(c.dp, c.tp, c.microbatches): s for c, s in ranking}
        ref = reference.score_search(CONFIG["model"], shapes[name], chips, made, launch)
        assert set(got) == set(ref)
        keys = sorted(ref)
        assert reference.max_rel_gap([got[k] for k in keys], [ref[k] for k in keys]) <= GAP_LIMIT
        low = reference.score_search(CONFIG["model"], shapes[name], chips, made, launch,
                                     np.float32)
        assert reference.max_rel_gap([low[k] for k in keys], [ref[k] for k in keys]) > GAP_LIMIT


def _bf16_passes(a, b, passes):
    """``a @ b`` as the TPU multiplies float32 in bfloat16 passes: one pass
    (XLA's default precision) or three (precision ``high``), summed in float32."""
    def split(x):
        hi = reference.rounded(x, "bfloat16").astype(np.float32)
        return hi, reference.rounded(x - hi, "bfloat16").astype(np.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    return ah @ bh if passes == 1 else ah @ bh + (ah @ bl + al @ bh)


def test_dense_comparison_admits_float32_and_fails_bf16_passes():
    """The configuration states float32 at precision ``highest``: a float32
    product passes; three bfloat16 passes (the control, ``high``) and one
    (XLA's default precision on the TPU) fail."""
    rng = np.random.default_rng(5)
    a = rng.standard_normal((64, 256)).astype(np.float32)
    b = rng.standard_normal((256, 96)).astype(np.float32)
    assert reference.dense_error(a, b, a @ b) <= DENSE_LIMIT
    assert reference.dense_error(a, b, _bf16_passes(a, b, 3)) > DENSE_LIMIT
    assert reference.dense_error(a, b, _bf16_passes(a, b, 1)) > DENSE_LIMIT


@pytest.mark.parametrize("layer_type", list(CONFIG["layer_types"]))
def test_forest_tables_have_a_fitted_forest_size(layer_type):
    """At the configuration's size every tree has the node count and depth
    of a fitted one, and the same build seed makes the same tables."""
    cfg = {**CONFIG, "layer_types": {layer_type: CONFIG["layer_types"][layer_type]},
           "forest": {**CONFIG["forest"], "trees": 4}}
    one, two = forests.make(cfg)[layer_type], forests.make(cfg)[layer_type]
    assert all(np.array_equal(x, y) for s, t in zip(one.trees, two.trees) for x, y in zip(s, t))
    for feature, threshold, left, right, value in one.trees:
        inner = feature >= 0
        assert 2000 <= len(feature) <= 3000 and np.all(np.isfinite(value))
        assert (~inner).sum() == inner.sum() + 1
        depth = np.zeros(len(feature), dtype=int)
        for i in np.flatnonzero(inner):
            assert left[i] > i and right[i] == left[i] + 1
            depth[left[i]] = depth[right[i]] = depth[i] + 1
        assert 12 <= depth.max() <= 20


def test_missing_answers_read_infinite():
    assert reference.max_rel_gap([1.0, 2.0], [1.0]) == float("inf")
    assert reference.max_rel_gap([1.0, float("inf")], [1.0, 2.0]) == float("inf")
    assert reference.max_rel_gap([1.0, float("inf")], [1.0, float("inf")]) == 0.0
