"""Bitwise parity of the vectorized fit engine vs the frozen scalar builder.

The golden digests below were generated by the PRE-refactor scalar
``RandomForestRegressor.fit`` (per-node argsorts, Python feature loop) on
fixed seeds.  The vectorized engine (:mod:`repro.core.forest_fit`) must keep
node tables and predictions bitwise identical — these tests are the engine's
hard gate.

Three layers of evidence:

* frozen sha256 digests of node tables + prediction bytes for campaigns on
  all four platforms' feature shapes, plus hyper-parameter branches;
* a tiny forest with its full node tables embedded as literals;
* live equality against the in-repo frozen reference ``_build_tree`` on
  randomized data (duplicated columns, low-cardinality ties, constant-target
  pockets) — plus a hypothesis property for the split search.
"""

from __future__ import annotations

import hashlib
import json
import os
import zipfile

import numpy as np
import pytest

from repro.core.forest import RandomForestRegressor, _Tree, _build_tree

TREE_FIELDS = ("feature", "threshold", "left", "right", "value")

# sha256 digests produced by the pre-refactor scalar fit (fixed seeds).
GOLDEN_PLATFORM = {
    # platform, ctor kwargs, layer type, n_features, tree digest, pred digest
    "ultratrail": (
        {}, "conv1d", 9,
        "a4373b3cf560de7fbd0ccc170d21b2468463125c1fe60885d898bbd6c8be03db",
        "9a7a2b72ddb02754c47bbe65ba2b6a2e7c498cb45ede698da973e8df75d4be7b",
    ),
    "vta": (
        {}, "conv2d", 8,
        "9414959c6c3c712adf2cd8623383ca58e8ff17cf8318f65dde1e1da8d5120fe4",
        "f2a0804c677ba173cfc6eeb2a38dc66c27f2860ce8c785b136fc3ebe51179bd8",
    ),
    "tpu_v5e": (
        {"knowledge": "white"}, "dense", 6,
        "d3f3f2f7b6abab256d72fd6a724bad6112961100883dbb996dab25c6d5049c69",
        "dd9f0fc393540f0756e7f7e4db8e33fffc1004541eab1cbcdbc2d62145cc6c9f",
    ),
    "xla_cpu": (
        {"synthetic": True}, "dense", 6,
        "240a31ac77a16ec0fd08fcb1b441555d18f1f1f4b80ec379e165c19169c97282",
        "8f5dd79eab840081a5b1ca28da3dedb2a9fa62dd490938d42736217ba225ad03",
    ),
}

GOLDEN_HYPER = {
    "default": (
        {},
        "021b62b86bf50b0fa521fea6d35cf7e88d1f6acc638994994c39a371ef130c03",
        "e3f4c817b11e475c860d2bd2d2f850249d08046445d65fe6dcbcddb3a4ccf1b0",
    ),
    "sqrt": (
        {"max_features": "sqrt"},
        "4c716b81b3982677e99be1085b523e538495b34a0d763a9428c347d1fd8de457",
        "17d3e9cd5e703d992db84a9dd38086194a8854b961f2da2cd94658797e828b5b",
    ),
    "one_int": (
        {"max_features": 1},
        "23290bbf292961435d6482ac4736b42735c44c50bc4b79086614d1129b4dc26e",
        "4bbd2e8b87256205aaf551481e35167a29ed618c47a533f9f2ef797b7e1de103",
    ),
    "leaf4": (
        {"min_samples_leaf": 4},
        "8272424db43c6f9836af0bb6bbcaf408435bb7b2d38b572c07f1bfe894e84d64",
        "97fd9d1069c20461a70cff821498fc391252bc35cae60f56dfb1a85a47ab04b2",
    ),
    "nobootstrap": (
        {"bootstrap": False},
        "574af6b567ff8f96e272a709043f2f785365317a3cb4a6466dda6ef92153c1f3",
        "e63d455292c3a7eebc3a457305569df71eb77d14341161a8dbb204cea0260c9a",
    ),
}


def forest_digest(forest: RandomForestRegressor) -> str:
    h = hashlib.sha256()
    for t in forest._trees:
        for f in TREE_FIELDS:
            a = getattr(t, f)
            h.update(f.encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def pred_digest(y: np.ndarray) -> str:
    return hashlib.sha256(np.asarray(y, dtype=np.float64).tobytes()).hexdigest()


def reference_fit(kw: dict, X: np.ndarray, y: np.ndarray) -> list[_Tree]:
    """The pre-refactor fit loop, verbatim, over the frozen scalar builder."""
    f = RandomForestRegressor(**kw)
    rng = np.random.default_rng(f.seed)
    n = X.shape[0]
    mf = f._n_features_per_split(X.shape[1])
    trees = []
    for _ in range(f.n_estimators):
        idx = rng.integers(0, n, size=n) if f.bootstrap else np.arange(n)
        trees.append(_build_tree(X[idx], y[idx], rng, f.max_depth, f.min_samples_leaf, mf))
    return trees


def hyper_data():
    rng = np.random.default_rng(7)
    X = rng.integers(1, 64, size=(400, 5)).astype(np.float64)
    y = (X[:, 0] * X[:, 1] + 3.0 * X[:, 2] + rng.normal(0, 0.5, size=400)) + 100.0
    return X, y


# --------------------------------------------------------------- frozen goldens
@pytest.mark.parametrize("platform_name", sorted(GOLDEN_PLATFORM))
def test_platform_shape_goldens(platform_name):
    """Campaign-trained forests on each platform's feature shape match the
    frozen pre-refactor node tables and prediction bytes."""
    from repro.api.campaign import train_layer_estimator
    from repro.api import get_platform
    from repro.core import prs

    kwargs, layer_type, n_feat, tree_hex, pred_hex = GOLDEN_PLATFORM[platform_name]
    platform = get_platform(platform_name, **kwargs)
    est = train_layer_estimator(
        platform, layer_type, 300, sampling="pr", seed=0,
        forest_kwargs={"n_estimators": 8, "max_depth": 14},
    )
    rng = np.random.default_rng(123)
    queries = prs.sample_random_batch(platform.param_space(layer_type), 64, rng)
    assert est._features(queries).shape[1] == n_feat
    assert forest_digest(est.forest) == tree_hex
    assert pred_digest(est.predict(queries)) == pred_hex


@pytest.mark.parametrize("tag", sorted(GOLDEN_HYPER))
def test_hyperparameter_goldens(tag):
    kw, tree_hex, pred_hex = GOLDEN_HYPER[tag]
    X, y = hyper_data()
    f = RandomForestRegressor(n_estimators=6, max_depth=10, seed=3, **kw).fit(X, y)
    assert forest_digest(f) == tree_hex
    assert pred_digest(f.predict(X[:50])) == pred_hex


def test_tiny_forest_literal_node_tables():
    """A 2-tree forest whose full pre-refactor node tables are embedded."""
    rng = np.random.default_rng(11)
    X = rng.integers(0, 6, size=(24, 2)).astype(np.float64)
    y = X[:, 0] * 2.0 + X[:, 1]
    f = RandomForestRegressor(n_estimators=2, max_depth=3, seed=5).fit(X, y)
    expect = [
        {
            "feature": [0, 0, 1, 1, 1, -1, -1, -1, -1, 1, 1, -1, -1, -1, -1],
            "threshold": [3.5, 0.5, 2.5, 0.5, 3.5, 0.0, 0.0, 0.0, 0.0, 1.5, 1.5, 0.0, 0.0, 0.0, 0.0],
            "left": [1, 9, 3, 7, 5, -1, -1, -1, -1, 13, 11, -1, -1, -1, -1],
            "right": [2, 10, 4, 8, 6, -1, -1, -1, -1, 14, 12, -1, -1, -1, -1],
            "value": [8.333333333333334, 5.181818181818182, 11.0, 9.625, 13.2,
                      13.0, 14.0, 8.0, 10.166666666666666, 1.0, 6.75,
                      5.333333333333333, 7.6, 0.0, 3.0],
        },
        {
            "feature": [0, 0, 1, 1, 0, -1, -1, -1, -1, 1, 1, -1, -1, -1, -1],
            "threshold": [3.5, 0.5, 3.0, 1.5, 4.5, 0.0, 0.0, 0.0, 0.0, 0.5, 1.5, 0.0, 0.0, 0.0, 0.0],
            "left": [1, 9, 3, 7, 5, -1, -1, -1, -1, 13, 11, -1, -1, -1, -1],
            "right": [2, 10, 4, 8, 6, -1, -1, -1, -1, 14, 12, -1, -1, -1, -1],
            "value": [8.041666666666666, 4.461538461538462, 12.272727272727273,
                      10.4, 13.833333333333334, 13.0, 14.666666666666666, 11.0,
                      10.0, 0.8, 6.75, 5.333333333333333, 7.6, 0.0, 1.0],
        },
    ]
    assert len(f._trees) == 2
    for tree, exp in zip(f._trees, expect):
        for field in TREE_FIELDS:
            assert getattr(tree, field).tolist() == exp[field], field


# -------------------------------------------------- live reference equality
def assert_forests_equal(ref_trees, trees):
    assert len(ref_trees) == len(trees)
    for a, b in zip(ref_trees, trees):
        for field in TREE_FIELDS:
            assert np.array_equal(getattr(a, field), getattr(b, field)), field


def test_randomized_equality_vs_reference():
    """Randomized shapes, ties, duplicated columns, constant-target pockets."""
    rng = np.random.default_rng(99)
    for trial in range(12):
        n = int(rng.integers(5, 300))
        n_feat = int(rng.integers(1, 12))
        kw = dict(
            n_estimators=3,
            max_depth=int(rng.integers(1, 24)),
            min_samples_leaf=int(rng.choice([1, 1, 1, 2, 4])),
            seed=int(rng.integers(0, 100)),
            bootstrap=bool(rng.integers(0, 2)),
        )
        X = rng.integers(0, max(2, int(rng.integers(2, 30))), size=(n, n_feat)).astype(np.float64)
        if n_feat >= 2:
            X[:, -1] = X[:, 0]  # duplicated column: cross-feature gain ties
        y = (X @ rng.normal(size=n_feat)) + rng.normal(size=n) * 0.01
        if trial % 4 == 0:
            y = np.round(y)  # constant-target pockets
        ref = reference_fit(kw, X, y)
        f = RandomForestRegressor(**kw).fit(X, y)
        assert_forests_equal(ref, f._trees)


@pytest.mark.parametrize("workload", ["snapped_conv1d", "dense_grid"])
def test_campaign_shaped_fits_equal_reference(workload):
    """Node-for-node equality on the two regimes a campaign fit meets: PR-
    snapped UltraTrail features (low cardinality, many mid-size nodes) and a
    dense high-cardinality 9-feature grid."""
    if workload == "snapped_conv1d":
        from repro.api import get_platform, train_layer_estimator
        from repro.core import prs

        platform = get_platform("ultratrail")
        est = train_layer_estimator(
            platform, "conv1d", 200, seed=0,
            forest_kwargs={"n_estimators": 2, "max_depth": 6},
        )
        rng = np.random.default_rng(0)
        configs = prs.sample_pr_batch(platform.param_space("conv1d"), est.widths, 600, rng)
        y = np.log(platform.measure_batch("conv1d", configs))
        X = est._features(configs, snap=True)
    else:
        rng = np.random.default_rng(0)
        X = rng.integers(1, 512, size=(600, 9)).astype(np.float64)
        y = np.log(X[:, 0] * X[:, 1] * X[:, 2] + X[:, 3] * 100 + 1.0)
    kw = dict(n_estimators=4, max_depth=18, seed=0)
    assert_forests_equal(reference_fit(kw, X, y), RandomForestRegressor(**kw).fit(X, y)._trees)


def test_midpoint_rounds_onto_right_neighbour_with_larger_values():
    """Adjacent doubles make the midpoint threshold round up onto the right
    neighbour; when larger values remain in the node, the reference's
    ``<= thr`` mask extends the left child across the tied value and still
    splits — the engine must do the same, in both the numpy and the tiny-node
    scalar paths (regression for a premature-leaf bug)."""
    a = 27.70888466262316
    b = float(np.nextafter(a, np.inf))
    assert 0.5 * (a + b) == b  # the degenerate rounding this test is about
    y = np.array([0.0] * 8 + [10.0] * 12)
    # numpy path (m = 20 root) and scalar path (children <= 7) both covered
    col = np.array([a] * 8 + [b] * 2 + [2 * b] * 10)
    for n_feat in (1, 3):
        X = np.tile(col[:, None], (1, n_feat))
        kw = dict(n_estimators=2, max_depth=4, seed=0, bootstrap=False)
        ref = reference_fit(kw, X, y)
        vec = RandomForestRegressor(**kw).fit(X, y)
        assert_forests_equal(ref, vec._trees)
        assert len(ref[0].feature) > 1  # the reference does split here
    # and the swallow case (no value above thr) still leafs like the reference
    col2 = np.array([a] * 8 + [b] * 12)
    X2 = col2[:, None]
    ref2 = reference_fit(dict(n_estimators=1, max_depth=4, seed=0, bootstrap=False), X2, y)
    vec2 = RandomForestRegressor(n_estimators=1, max_depth=4, seed=0, bootstrap=False).fit(X2, y)
    assert_forests_equal(ref2, vec2._trees)


def test_scalar_smallnode_exactness_primitives():
    """The scalar fast path's float contract: n<8 numpy sums/cumsums are
    sequential left folds and python ``**`` matches numpy scalar pow."""
    rng = np.random.default_rng(3)
    for n in (2, 3, 5, 7):
        for _ in range(2000):
            v = [float(x) for x in rng.normal(size=n) * 10.0 ** rng.integers(-100, 100, size=n)]
            arr = np.asarray(v)
            acc = v[0]
            for x in v[1:]:
                acc = acc + x
            assert arr.sum() == acc
            cs = arr.cumsum()
            a2 = v[0]
            for i in range(1, n):
                a2 = a2 + v[i]
                assert cs[i] == a2
    for _ in range(5000):
        x = float(rng.normal() * 10.0 ** rng.integers(-100, 100))
        assert float(np.float64(x) ** 2) == x**2


# ---------------------------------------------------------- hub byte-identity
def _zip_member_bytes(path: str) -> dict[str, bytes]:
    with zipfile.ZipFile(path) as z:
        return {name: z.read(name) for name in sorted(z.namelist())}


def test_hub_checkpoints_byte_identical_pre_post(tmp_path):
    """Estimators trained by the vectorized engine persist to checkpoints
    whose payload bytes equal a checkpoint of the reference-built forest."""
    from repro.api.hub import EstimatorHub
    from repro.core.estimator import LayerEstimator
    from repro.core.prs import ParamSpace

    X, y = hyper_data()
    kw = dict(n_estimators=4, max_depth=8, seed=1)

    def make_est(trees):
        forest = RandomForestRegressor(**kw)
        forest._trees = trees
        return LayerEstimator(
            layer_type="toy",
            params=("a", "b", "c", "d", "e"),
            widths={"a": 1, "b": 2, "c": 4, "d": 1, "e": 1},
            space=ParamSpace(ranges={p: (1, 64) for p in ("a", "b", "c", "d", "e")}),
            forest=forest,
            n_train=400,
            log_target=False,
        )

    est_vec = make_est(RandomForestRegressor(**kw).fit(X, y)._trees)
    est_ref = make_est(reference_fit(kw, X, y))

    hub_vec = EstimatorHub(str(tmp_path / "vec"))
    hub_ref = EstimatorHub(str(tmp_path / "ref"))
    p_vec = hub_vec.save("plat", est_vec)
    p_ref = hub_ref.save("plat", est_ref)

    # payload bytes (array contents + manifest) are identical; only zip
    # timestamps may differ, so compare member-by-member decompressed bytes
    vec_members = _zip_member_bytes(os.path.join(p_vec, "arrays.npz"))
    ref_members = _zip_member_bytes(os.path.join(p_ref, "arrays.npz"))
    assert vec_members == ref_members
    with open(os.path.join(p_vec, "manifest.json")) as fh:
        man_vec = json.load(fh)
    with open(os.path.join(p_ref, "manifest.json")) as fh:
        man_ref = json.load(fh)
    assert man_vec == man_ref

    # and a save -> load round trip stays bitwise identical
    loaded = hub_vec.load("plat", "toy")
    assert_forests_equal(est_vec.forest._trees, loaded.forest._trees)
    q = X[:37]
    assert np.array_equal(est_vec.predict(
        [dict(zip(("a", "b", "c", "d", "e"), map(int, row))) for row in q]
    ), loaded.predict(
        [dict(zip(("a", "b", "c", "d", "e"), map(int, row))) for row in q]
    ))


# ------------------------------------------------------------ hypothesis property
def test_split_search_property_hypothesis():
    """Vectorized and reference builders choose identical (feature, threshold)
    at the root split on random data (hypothesis-generated)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        n=st.integers(4, 80),
        n_feat=st.integers(1, 6),
        cardinality=st.integers(2, 12),
        msl=st.sampled_from([1, 1, 2]),
    )
    def check(seed, n, n_feat, cardinality, msl):
        rng = np.random.default_rng(seed)
        X = rng.integers(0, cardinality, size=(n, n_feat)).astype(np.float64)
        y = (X @ rng.normal(size=n_feat)) + rng.normal(size=n) * 0.1
        kw = dict(n_estimators=1, max_depth=1, min_samples_leaf=msl,
                  seed=seed, bootstrap=False)
        # max_depth=1: the forests are exactly the root split search
        ref = reference_fit(kw, X, y)
        vec = RandomForestRegressor(**kw).fit(X, y)._trees
        assert np.array_equal(ref[0].feature, vec[0].feature)
        assert np.array_equal(ref[0].threshold, vec[0].threshold)
        assert np.array_equal(ref[0].value, vec[0].value)

    check()
