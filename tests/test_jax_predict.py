"""Jitted predict path: backend selection, parity, and serving integration.

The contract under test (see src/repro/core/jax_predict.py):

* layer predictions and the four platforms' analytical measurement kernels
  are **bitwise** identical across backends;
* whole-network predictions are bitwise except when a log-target ``exp``
  runs inside the compiled call (rtol 1e-12 there);
* ``jax`` is an error when jax is not installed, ``auto`` is numpy only then,
  and an installed jax whose API breaks raises instead of serving numpy;
* a compiled entry point declines (numpy path) only requests that need
  scalar semantics;
* importing the library never imports jax (the numpy-only CI leg).
"""

import subprocess
import sys

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.api import Campaign, CampaignSpec, PerfOracle
from repro.core import jax_predict
from repro.core.batch import BlockBatch, ConfigBatch
from repro.core.blocks import Block
from repro.core.forest import RandomForestRegressor, _Tree
from repro.registry import get_platform

FAST_FOREST = {"n_estimators": 8, "max_depth": 10}

def _oracle(platform, layer_types, **platform_kwargs) -> PerfOracle:
    spec = CampaignSpec(
        platform=platform,
        layer_types=layer_types,
        n_samples=64,
        seed=0,
        forest_kwargs=FAST_FOREST,
        platform_kwargs=platform_kwargs or None,
    )
    return Campaign(spec).run()


@pytest.fixture(scope="module")
def toy_oracle() -> PerfOracle:
    return _oracle("stepped_sim", ("toy",))


@pytest.fixture(scope="module")
def tpu_oracle() -> PerfOracle:
    return _oracle("tpu_v5e", ("dense", "attention_decode", "embed"))


def _sample_batch(space, n, seed=0) -> ConfigBatch:
    rng = np.random.default_rng(seed)
    cols = {p: rng.integers(lo, hi + 1, size=n) for p, (lo, hi) in space.ranges.items()}
    for p, v in getattr(space, "fixed", {}).items():
        cols[p] = np.full(n, v)
    return ConfigBatch.from_columns(cols)


# ---------------------------------------------------------------- selection
def test_bucket_rows():
    assert jax_predict.bucket_rows(0) == 64
    assert jax_predict.bucket_rows(1) == 64
    assert jax_predict.bucket_rows(64) == 64
    assert jax_predict.bucket_rows(65) == 128
    assert jax_predict.bucket_rows(333) == 512
    assert jax_predict.bucket_rows(4096) == 4096


def test_resolve_backend_env(monkeypatch):
    monkeypatch.delenv(jax_predict._ENV_VAR, raising=False)
    assert jax_predict.resolve_backend() == "numpy"
    assert jax_predict.resolve_backend("numpy") == "numpy"
    monkeypatch.setenv(jax_predict._ENV_VAR, "numpy")
    assert jax_predict.resolve_backend() == "numpy"
    with pytest.raises(ValueError, match="unknown predict backend"):
        jax_predict.resolve_backend("tensorflow")
    monkeypatch.setenv(jax_predict._ENV_VAR, "tensorflow")
    with pytest.raises(ValueError, match="unknown predict backend"):
        jax_predict.resolve_backend()


def test_resolve_backend_jax_and_auto(monkeypatch):
    assert jax_predict.resolve_backend("jax") == "jax"
    assert jax_predict.resolve_backend("auto") == "jax"
    monkeypatch.setenv(jax_predict._ENV_VAR, "jax")
    assert jax_predict.resolve_backend() == "jax"


def test_fallback_when_jax_unavailable(monkeypatch, toy_oracle):
    """No jax installed: 'jax' raises, 'auto' serves numpy; a broken jax raises."""
    import jax

    cfgs = [{"a": i % 40 + 1, "b": i % 20 + 1} for i in range(17)]
    y_np = toy_oracle.predict("toy", cfgs)

    with monkeypatch.context() as m:
        m.setattr(jax_predict, "_modules_cache", None)
        m.setattr(jax_predict, "_import_failed", True)
        assert not jax_predict.jax_available()
        with pytest.raises(RuntimeError, match="jax is not installed"):
            jax_predict.resolve_backend("jax")
        with pytest.raises(RuntimeError, match="jax is not installed"):
            toy_oracle.predict("toy", cfgs, backend="jax")
        assert jax_predict.resolve_backend("auto") == "numpy"
        assert np.array_equal(y_np, toy_oracle.predict("toy", cfgs, backend="auto"))

    # An installed jax whose API no longer matches raises; it is not numpy.
    monkeypatch.delattr(jax, "enable_x64")
    assert jax_predict.resolve_backend("auto") == "jax"
    with pytest.raises(AttributeError, match="enable_x64"):
        toy_oracle.predict("toy", cfgs, backend="auto")


def test_no_eager_jax_import():
    """The numpy-only leg: importing the library must not import jax."""
    code = (
        "import sys\n"
        "import repro.api, repro.serving\n"
        "import repro.obs, repro.obs.report, repro.obs.metrics, repro.obs.trace\n"
        "import repro.core.jax_predict, repro.core.steps, repro.core.sweeps\n"
        "import repro.accelerators.jax_kernels\n"
        "import repro.accelerators.tpu_v5e, repro.accelerators.ultratrail\n"
        "import repro.accelerators.vta, repro.accelerators.xla_cpu\n"
        "import repro.analysis\n"
        "assert 'jax' not in sys.modules, 'jax imported eagerly'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True)


def test_worker_pool_refused_for_attached_accelerator():
    """One process per chip: no worker pool over a platform measuring on one."""
    from repro.runtime import MeasurementRuntime, RuntimeSpec
    from repro.runtime.testing import SteppedSimPlatform

    class OnChip(SteppedSimPlatform):
        def measures_accelerator(self) -> bool:
            return True

    with pytest.raises(ValueError, match="one process per chip"):
        MeasurementRuntime(RuntimeSpec(workers=2), OnChip())
    with MeasurementRuntime(RuntimeSpec(workers=1), OnChip()) as rt:
        assert rt.executor.workers == 1


# ------------------------------------------------------------ forest parity
@pytest.mark.parametrize("n", [0, 1, 5, 64, 333])
def test_forest_predict_bitwise(n):
    rng = np.random.default_rng(3)
    Xtr = rng.uniform(0, 100, size=(200, 4))
    ytr = Xtr @ np.array([1e-6, 2e-6, 5e-7, 1e-7]) + rng.normal(0, 1e-8, 200)
    forest = RandomForestRegressor(n_estimators=10, max_depth=8, seed=0)
    forest.fit(Xtr, ytr)
    X = rng.uniform(-10, 120, size=(n, 4))
    assert np.array_equal(forest.predict(X), forest.predict(X, backend="jax"))


def test_forest_engine_invalidated_on_refit():
    rng = np.random.default_rng(4)
    X = rng.uniform(0, 10, size=(100, 2))
    forest = RandomForestRegressor(n_estimators=5, max_depth=6, seed=0)
    forest.fit(X, X.sum(axis=1))
    y1 = forest.predict(X, backend="jax")
    forest.fit(X, X.prod(axis=1))  # refit resets the stack and its device tables
    y2 = forest.predict(X, backend="jax")
    assert np.array_equal(y2, forest.predict(X))
    assert not np.array_equal(y1, y2)


def _counters(*names):
    from repro.obs.metrics import metrics

    c = metrics().snapshot()["counters"]
    return {n: c.get(n, 0) for n in names}


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


_RESIDENT = ("jax.resident.uploads", "jax.resident.bytes", "jax.resident.path_bytes")
_FORMS = ("jax.traverse.dense_groups", "jax.traverse.descent_groups")


def test_resident_tables_keep_their_dtypes_and_values():
    import jax

    rng = np.random.default_rng(5)
    X = rng.uniform(0, 10, size=(80, 3))
    forest = RandomForestRegressor(n_estimators=4, max_depth=5, seed=0)
    forest.fit(X, X.sum(axis=1))
    stack = forest._stacked()
    tables = jax_predict.resident_forest(stack)
    assert tables is jax_predict.resident_forest(stack)  # cached on the stack
    assert isinstance(tables, jax_predict.DenseForest)
    host = jax_predict._dense_tables(stack)
    dtypes = (np.float64, np.int32, np.int32, np.int8, np.int32, np.float64, np.float64)
    for dev, ref, dtype in zip(tables, host, dtypes):
        assert isinstance(dev, jax.Array)
        assert dev.dtype == ref.dtype == dtype
        assert np.array_equal(np.asarray(dev), ref)
    assert float(tables.n_trees) == 4.0
    # each real leaf column: its left turns are its +1 entries, its value a
    # leaf value of its tree; padded columns are never reached
    paths, turns = np.asarray(tables.paths), np.asarray(tables.left_turns)
    for t in range(4):
        leaves = stack.feature[t] < 0
        n_leaves = int(leaves[: len(forest._trees[t].feature)].sum())
        assert np.array_equal(turns[t, :n_leaves], (paths[t, :, :n_leaves] == 1).sum(axis=0))
        assert np.all(turns[t, n_leaves:] == -1)
        assert np.array_equal(np.asarray(tables.leaf_value)[t, :n_leaves],
                              forest._trees[t].value[forest._trees[t].feature < 0])


def test_refit_forest_uploads_again():
    rng = np.random.default_rng(6)
    X = rng.uniform(0, 10, size=(100, 2))
    forest = RandomForestRegressor(n_estimators=5, max_depth=6, seed=0)
    forest.fit(X, X.sum(axis=1))
    before = _counters(*_RESIDENT)
    forest.predict(X, backend="jax")
    forest.predict(X[:7], backend="jax")
    once = _counters(*_RESIDENT)
    host = jax_predict._dense_tables(forest._stacked())
    assert _delta(before, once) == {
        "jax.resident.uploads": 1,
        "jax.resident.bytes": sum(np.asarray(a).nbytes for a in host),
        "jax.resident.path_bytes": host.paths.nbytes,
    }
    forest.fit(X, X.prod(axis=1))
    y = forest.predict(X, backend="jax")
    assert _delta(once, _counters(*_RESIDENT))["jax.resident.uploads"] == 1
    assert np.array_equal(y, forest.predict(X))


def _one_leaf_tree(value: float) -> _Tree:
    return _Tree(np.array([-1], np.int32), np.zeros(1), np.zeros(1, np.int32),
                 np.zeros(1, np.int32), np.array([value]))


def _network_raw(forest, X):
    """The forest's raw prediction through the network program: one layer a
    block and one block a network, no launch overhead, nothing fused."""
    n, d = X.shape
    nb = jax_predict.bucket_rows(n)
    Xp = np.zeros((nb, d))
    Xp[:n] = X
    at = np.arange(nb)
    ints = np.concatenate([at, at, [nb], at, np.zeros(2 * nb)]).astype(np.int64)
    floats = np.concatenate([np.ones(nb), np.zeros(3 * nb), np.ones(nb), Xp.ravel()])
    groups = (jax_predict.resident_forest(forest._stacked()),)
    fn = jax_predict._network_fn((False,))
    with jax_predict.x64():
        out = fn(groups, ints, floats, np.float64(0.0), (nb, nb, nb, ((nb, d),)))
    return np.asarray(out)[:n]


def _parity_rows(case, stack, rng):
    inner = stack.feature >= 0
    if case == "on_threshold":
        # every value is one of its feature's thresholds: the row goes left there
        X = rng.uniform(0, 10, size=(200, 3))
        for f in range(3):
            X[:, f] = rng.choice(stack.threshold[inner & (stack.feature == f)], size=200)
        return X
    X = rng.uniform(-1, 11, size=(333 if case == "multi_chunk" else 50, 3))
    if case == "nan":
        X[rng.random(X.shape) < 0.3] = np.nan
    return X


@pytest.mark.parametrize("case", ["bucket64", "on_threshold", "nan", "multi_chunk",
                                  "only_leaves"])
def test_dense_traversal_bitwise(case, monkeypatch):
    """The dense form equals the numpy descent bit for bit, through a forest
    call and through the network program: trees of different node counts and
    a one-leaf tree in one stack, rows on a threshold (left), NaN (right), the
    64-row bucket and a bucket traversed in several chunks of trees."""
    rng = np.random.default_rng(7)
    Xtr = rng.uniform(0, 10, size=(150, 3))
    forest = RandomForestRegressor(n_estimators=7, max_depth=7, seed=1)
    forest.fit(Xtr, Xtr @ np.array([1.0, 2.0, 0.5]) + 1.0)
    trees = [_one_leaf_tree(0.25 * (i + 1)) for i in range(3)]
    if case != "only_leaves":
        trees = forest._trees[:3] + trees[:1] + forest._trees[3:]
    forest._trees = trees
    stack = forest._stacked()
    assert len({int((t.feature >= 0).sum()) for t in trees}) > 1 or case == "only_leaves"
    tables = jax_predict.resident_forest(stack)
    assert isinstance(tables, jax_predict.DenseForest)
    X = _parity_rows(case, stack, rng)
    if case == "multi_chunk":
        # chunks of two of the eight trees at this bucket
        leaves = tables.paths.shape[2]
        monkeypatch.setattr(jax_predict, "_CHUNK_ELEMENTS", 2 * 512 * leaves)
        assert jax_predict._tree_chunk(len(trees), 512, leaves) == 2
        jax_predict._forest_fn.cache_clear()
        jax_predict._network_fn.cache_clear()
    try:
        ref = forest.predict(X)
        assert np.array_equal(forest.predict(X, backend="jax"), ref)
        assert np.array_equal(_network_raw(forest, X), ref)
    finally:
        if case == "multi_chunk":
            jax_predict._forest_fn.cache_clear()
            jax_predict._network_fn.cache_clear()


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "over_budget"])
def test_traversal_form_follows_the_budget(dense, toy_oracle, monkeypatch):
    """A forest whose path matrices exceed the budget keeps the descent and
    still matches numpy; the counters count the form each forest call and
    each network group ran, and the path bytes uploaded."""
    import dataclasses

    if not dense:
        monkeypatch.setattr(jax_predict, "DENSE_BUDGET_BYTES", 0)
    ests = {}
    for lt, e in toy_oracle.estimators.items():
        forest = RandomForestRegressor()
        forest._trees = e.forest._trees  # a new stack: uploaded afresh
        ests[lt] = dataclasses.replace(e, forest=forest, log_target=False)
    oracle = dataclasses.replace(toy_oracle, estimators=ests)
    cfgs = [{"a": (i * 7) % 64 + 1, "b": (i * 3) % 32 + 1} for i in range(100)]
    names = (*_RESIDENT, *_FORMS)
    before = _counters(*names)
    assert np.array_equal(oracle.predict("toy", cfgs, backend="jax"),
                          oracle.predict("toy", cfgs))
    assert np.array_equal(oracle.predict_networks(_toy_nets(), backend="jax"),
                          oracle.predict_networks(_toy_nets()))
    d = _delta(before, _counters(*names))
    stack = ests["toy"].forest._stacked()
    form = jax_predict.DenseForest if dense else jax_predict.DescentForest
    assert isinstance(jax_predict.resident_forest(stack), form)
    paths = jax_predict.resident_forest(stack).paths.nbytes if dense else 0
    assert d["jax.resident.uploads"] == 1
    assert d["jax.resident.path_bytes"] == paths
    # one forest call and one group (the toy layer type) in the network call
    assert d["jax.traverse.dense_groups"] == (2 if dense else 0)
    assert d["jax.traverse.descent_groups"] == (0 if dense else 2)


def test_layer_predict_bitwise_including_ragged(toy_oracle):
    cfgs = [{"a": (i * 7) % 64 + 1, "b": (i * 3) % 32 + 1} for i in range(333)]
    assert np.array_equal(
        toy_oracle.predict("toy", cfgs),
        toy_oracle.predict("toy", cfgs, backend="jax"),
    )
    # ragged key sets (an extra key on one row) take the row fallback on both
    ragged = [{"a": 5, "b": 3}, {"a": 9, "b": 2, "extra": 7}]
    assert np.array_equal(
        toy_oracle.predict("toy", ragged),
        toy_oracle.predict("toy", ragged, backend="jax"),
    )
    assert toy_oracle.predict("toy", [], backend="jax").shape == (0,)


# --------------------------------------------------- measurement kernel parity
PLATFORMS = [
    ("tpu_v5e", {}),
    ("ultratrail", {}),
    ("vta", {}),
    ("xla_cpu", {"synthetic": True, "repeats": 1}),
]


@pytest.mark.parametrize("name,kwargs", PLATFORMS)
def test_measure_batch_bitwise(name, kwargs):
    plat = get_platform(name, **kwargs)
    for lt in plat.layer_types():
        for n in (1, 64, 257):
            batch = _sample_batch(plat.param_space(lt), n, seed=n)
            y_np = plat.measure_batch(lt, batch)
            plat.predict_backend = "jax"
            y_jx = plat.measure_batch(lt, batch)
            plat.predict_backend = None
            assert np.array_equal(y_np, y_jx), f"{name}/{lt} n={n}"


def test_noisy_tpu_stays_numpy():
    """Per-config hash-seeded noise is scalar semantics; jax must not engage."""
    from repro.accelerators import jax_kernels

    plat = get_platform("tpu_v5e", noise=0.01)
    plat.predict_backend = "jax"
    batch = _sample_batch(plat.param_space("dense"), 16)
    assert jax_kernels.tpu_measure_batch(plat, "dense", batch) is None
    ref = get_platform("tpu_v5e", noise=0.01).measure_batch("dense", batch)
    assert np.array_equal(plat.measure_batch("dense", batch), ref)


def test_wallclock_xla_cpu_stays_numpy():
    from repro.accelerators import jax_kernels

    plat = get_platform("xla_cpu", synthetic=False)
    plat.predict_backend = "jax"
    batch = _sample_batch(plat.param_space("dense"), 4)
    assert jax_kernels.xla_cpu_measure_batch(plat, "dense", batch) is None


# ------------------------------------------------------------ network parity
def _toy_nets():
    return [
        [
            Block(kind="k", layers=(("toy", {"a": 4, "b": 2}), ("toy", {"a": 8, "b": 4})), repeat=3),
            Block(kind="k", layers=(("toy", {"a": 16, "b": 8}),), collective_bytes=128.0),
        ],
        [Block(kind="k", layers=(("toy", {"a": 32, "b": 16}),))],
        [],
    ]


def test_predict_networks_tolerance_log_target(toy_oracle):
    """log-target exp runs inside the compiled call: rtol 1e-12 applies."""
    assert all(e.log_target for e in toy_oracle.estimators.values())
    p_np = toy_oracle.predict_networks(_toy_nets())
    p_jx = toy_oracle.predict_networks(_toy_nets(), backend="jax")
    np.testing.assert_allclose(p_jx, p_np, rtol=1e-12, atol=0.0)


def test_predict_networks_bitwise_without_log_target(toy_oracle):
    import dataclasses

    ests = {
        lt: dataclasses.replace(e, log_target=False)
        for lt, e in toy_oracle.estimators.items()
    }
    oracle = dataclasses.replace(toy_oracle, estimators=ests)
    p_np = oracle.predict_networks(_toy_nets())
    p_jx = oracle.predict_networks(_toy_nets(), backend="jax")
    assert np.array_equal(p_np, p_jx)


def test_predict_networks_platform_oracles(tpu_oracle):
    nets = [
        [
            Block(kind="embed", layers=(("embed", {"tokens": 512, "vocab": 32000, "d_model": 1024}),), repeat=2),
            Block(
                kind="attn",
                layers=(
                    ("dense", {"tokens": 512, "d_in": 1024, "d_out": 3072}),
                    ("attention_decode", {"B": 8, "S_kv": 2048, "H": 16, "Dh": 128, "kv_ratio": 1}),
                ),
                collective_bytes=64.0,
            ),
        ],
        [Block(kind="mlp", layers=(("dense", {"tokens": 512, "d_in": 1024, "d_out": 4096}),))],
    ]
    p_np = tpu_oracle.predict_networks(nets)
    p_jx = tpu_oracle.predict_networks(nets, backend="jax")
    np.testing.assert_allclose(p_jx, p_np, rtol=1e-12, atol=0.0)


def test_predict_network_batch_jax_matches_columnar(toy_oracle):
    nets = _toy_nets()
    flat = [b for net in nets for b in net]
    batch = BlockBatch.from_blocks(flat)
    net_id = np.repeat(np.arange(len(nets)), [len(n) for n in nets])
    y = jax_predict.predict_network_batch_jax(toy_oracle, batch, net_id, len(nets))
    assert y is not None
    np.testing.assert_allclose(
        y, toy_oracle.predict_networks(nets), rtol=1e-12, atol=0.0
    )


def test_predict_network_batch_falls_back_for_stub_estimators():
    class Stub:
        def predict(self, configs):
            return np.full(len(configs), 2.5e-6)

    oracle = PerfOracle(estimators={"toy": Stub()})
    nets = [[Block(kind="k", layers=(("toy", {"a": 4, "b": 2}),))]]
    # jax route declines stubs on both backends -> identical numpy answers
    assert np.array_equal(
        oracle.predict_networks(nets), oracle.predict_networks(nets, backend="jax")
    )


def test_empty_overlap_block_raises(toy_oracle):
    import dataclasses

    oracle = dataclasses.replace(toy_oracle, overlap_kinds=frozenset({"k"}))
    nets = [[Block(kind="k", layers=())]]
    with pytest.raises(ValueError, match="overlap block with zero layers"):
        oracle.predict_networks(nets)
    with pytest.raises(ValueError, match="overlap block with zero layers"):
        oracle.predict_networks(nets, backend="jax")


# ----------------------------------------------------------------- autotune
def test_autotune_parity_across_backends_and_paths(tpu_oracle):
    import dataclasses as dc

    from repro.configs import get_config
    from repro.core.advisor import autotune
    from repro.models.config import InputShape

    cfg = get_config("qwen2-1.5b")
    shape = InputShape(name="t", seq_len=1024, global_batch=8, kind="decode")

    ranked = autotune(tpu_oracle, cfg, shape, chips=16)

    class ManyOnly:
        """Forces the predict_networks fallback path."""

        def __init__(self, oracle):
            self._o = oracle

        def predict_networks(self, networks):
            return self._o.predict_networks(networks)

    class OneOnly:
        """Forces the per-candidate predict_network loop."""

        def __init__(self, oracle):
            self._o = oracle

        def predict_network(self, blocks):
            return float(self._o.predict_networks([blocks])[0])

    for shim in (ManyOnly(tpu_oracle), OneOnly(tpu_oracle)):
        alt = autotune(shim, cfg, shape, chips=16)
        assert [c for c, _ in alt] == [c for c, _ in ranked]
        np.testing.assert_allclose(
            [s for _, s in alt], [s for _, s in ranked], rtol=0, atol=0
        )

    jax_oracle = dc.replace(tpu_oracle, predict_backend="jax")
    ranked_jx = autotune(jax_oracle, cfg, shape, chips=16)
    assert [c for c, _ in ranked_jx] == [c for c, _ in ranked]
    np.testing.assert_allclose(
        [s for _, s in ranked_jx], [s for _, s in ranked], rtol=1e-12
    )


def test_autotune_uploads_each_forest_once():
    """A warm oracle copies only the call's own tables: each forest goes to
    the device on the first call, nothing on the second, and a repeated
    shape compiles no new network program."""
    import dataclasses as dc

    from repro.configs import get_config
    from repro.core.advisor import autotune
    from repro.models.config import InputShape

    oracle = _oracle("tpu_v5e", ("dense", "attention_decode", "embed"))
    cfg = get_config("qwen2-1.5b")
    shape = InputShape(name="t", seq_len=1024, global_batch=8, kind="decode")
    ranked = autotune(oracle, cfg, shape, chips=16)

    jax_oracle = dc.replace(oracle, predict_backend="jax")
    names = (*_RESIDENT, "jax.network.calls", "jax.network.traces")
    before = _counters(*names)
    first = autotune(jax_oracle, cfg, shape, chips=16)
    mid = _counters(*names)
    second = autotune(jax_oracle, cfg, shape, chips=16)
    d1, d2 = _delta(before, mid), _delta(mid, _counters(*names))
    assert d1["jax.resident.uploads"] == len(oracle.estimators)
    assert d1["jax.network.calls"] == 1
    assert d2 == {"jax.resident.uploads": 0, "jax.resident.bytes": 0,
                  "jax.resident.path_bytes": 0,
                  "jax.network.calls": 1, "jax.network.traces": 0}
    assert first == second
    assert [c for c, _ in first] == [c for c, _ in ranked]
    np.testing.assert_allclose(
        [s for _, s in first], [s for _, s in ranked], rtol=1e-12
    )


def test_network_program_per_bucket_signature(toy_oracle):
    """Networks of other sizes inside the same buckets reuse the compiled
    program and still match numpy."""
    nets = _toy_nets()
    other = [[Block(kind="k", layers=(("toy", {"a": 2 * i + 1, "b": i + 1}),) * (i % 2 + 1),
                    repeat=i % 4 + 1)
              for i in range(j, j + 3)]
             for j in range(5)]
    toy_oracle.predict_networks(nets, backend="jax")
    before = _counters("jax.network.calls", "jax.network.traces")
    y = toy_oracle.predict_networks(other, backend="jax")
    assert _delta(before, _counters("jax.network.calls", "jax.network.traces")) == {
        "jax.network.calls": 1, "jax.network.traces": 0}
    np.testing.assert_allclose(y, toy_oracle.predict_networks(other), rtol=1e-12, atol=0.0)


def test_launch_overhead_change_reaches_the_device(tpu_oracle):
    import dataclasses as dc

    nets = [[Block(kind="mlp", layers=(
        ("dense", {"tokens": 512, "d_in": 1024, "d_out": 4096}),
        ("dense", {"tokens": 512, "d_in": 4096, "d_out": 1024}),
    ))]]
    oracle = dc.replace(tpu_oracle, launch_overhead_s=0.0)
    y0 = oracle.predict_networks(nets, backend="jax")
    oracle.launch_overhead_s = 1e-3
    y1 = oracle.predict_networks(nets, backend="jax")
    assert y1[0] != y0[0]
    np.testing.assert_allclose(y1, oracle.predict_networks(nets), rtol=1e-12, atol=0.0)


# ---------------------------------------------------------- decompose_batch
def test_decompose_batch_matches_from_blocks():
    from repro.configs import ARCHS, get_config
    from repro.core.network import decompose, decompose_batch
    from repro.models.config import SHAPES, shape_applicable

    checked = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if not shape_applicable(cfg, shape):
                continue
            for dp, tp in ((1, 1), (4, 2), (16, 16)):
                ref = BlockBatch.from_blocks(decompose(cfg, shape, dp, tp))
                got = decompose_batch(cfg, shape, dp, tp)
                assert ref.kinds == got.kinds
                assert np.array_equal(ref.collective_bytes, got.collective_bytes)
                assert np.array_equal(ref.repeat, got.repeat)
                assert np.array_equal(ref.block_id, got.block_id)
                assert np.array_equal(ref.group_of, got.group_of)
                assert np.array_equal(ref.row_of, got.row_of)
                assert ref.group_types == got.group_types
                for a, b in zip(ref.group_configs, got.group_configs):
                    assert a.params == b.params
                    assert np.array_equal(a.values, b.values)
                checked += 1
    assert checked >= 50


# --------------------------------------------------- batched steps and sweeps
def test_determine_step_widths_matches_scalar():
    from repro.core.steps import determine_step_widths, find_step_width

    rng = np.random.default_rng(11)
    for trial in range(40):
        sweeps = {}
        for j in range(int(rng.integers(1, 6))):
            n = int(rng.choice([8, 24, 48, 96, 97]))
            x = np.arange(1, n + 1, dtype=float)
            kind = rng.integers(0, 4)
            if kind == 0:
                y = 3e-6 * x + 1e-6
            elif kind == 1:
                y = 2e-6 * np.ceil(x / int(rng.integers(2, 9)))
            elif kind == 2:
                y = 2e-6 * np.ceil(x / int(rng.integers(2, 9))) + rng.normal(0, 5e-9, n)
            else:
                y = np.full(n, 4e-6)
            sweeps[f"p{j}"] = (x, y)
        batched = determine_step_widths(sweeps)
        scalar = {p: find_step_width(x, y) for p, (x, y) in sweeps.items()}
        assert batched == scalar
        assert list(batched) == list(sweeps)  # original param order


def test_run_sweeps_grouped_matches_per_window():
    from repro.core.sweeps import run_sweeps, sweep_window

    plat = get_platform("ultratrail")
    out = run_sweeps(plat, "conv1d", n_points=96)
    space = plat.param_space("conv1d")
    defaults = plat.defaults("conv1d")
    anchor = space.with_fixed(defaults)
    assert list(out) == list(space.params)
    for p in space.params:
        lo, hi = space.ranges[p]
        xs = sweep_window(lo, hi, defaults.get(p, lo), 96)
        base_cfg = dict(anchor)
        base_cfg.setdefault(p, int(xs[0]))
        batch = ConfigBatch.from_anchor(base_cfg, len(xs)).replace(p, xs)
        ys = plat.measure_batch("conv1d", batch)
        got_x, got_y = out[p]
        assert np.array_equal(got_x, xs)
        assert np.array_equal(got_y, ys)


# ----------------------------------------------------------------- serving
def test_served_equals_direct_with_jax_backend(toy_oracle):
    from repro.serving import OracleServer, ServeSpec

    cfgs = [{"a": (i * 5) % 64 + 1, "b": (i * 7) % 32 + 1} for i in range(50)]
    nets = _toy_nets()[:2]
    spec = ServeSpec(window_s=0.001, predict_backend="jax")
    with OracleServer(oracles={"stepped_sim": toy_oracle}, spec=spec) as srv:
        r = srv.handle(
            {"op": "predict", "platform": "stepped_sim", "layer_type": "toy", "configs": cfgs}
        )
        assert r["ok"], r
        direct = toy_oracle.predict("toy", cfgs, backend="jax")
        assert np.array_equal(np.asarray(r["result"]), direct)
        # repeat: answered from cache, still the same bits
        r2 = srv.handle(
            {"op": "predict", "platform": "stepped_sim", "layer_type": "toy", "configs": cfgs}
        )
        assert np.array_equal(np.asarray(r2["result"]), direct)

        rn = srv.handle(
            {
                "op": "predict_networks",
                "platform": "stepped_sim",
                "networks": [[_payload(b) for b in net] for net in nets],
            }
        )
        assert rn["ok"], rn
        direct_n = toy_oracle.predict_networks(nets, backend="jax")
        assert np.array_equal(np.asarray(rn["result"]), direct_n)
    # the injected oracle object was never mutated
    assert toy_oracle.predict_backend is None


def _payload(block: Block) -> dict:
    return {
        "kind": block.kind,
        "layers": [[lt, dict(cfg)] for lt, cfg in block.layers],
        "collective_bytes": block.collective_bytes,
        "repeat": block.repeat,
    }


def test_network_cache_keys_are_backend_scoped(toy_oracle):
    """A numpy-warmed network cache entry must not serve a jax-backend oracle
    (answers can differ by an ulp via the compiled log-target exp); layer
    entries stay shared because layer parity is bitwise."""
    import dataclasses as dc

    from repro.serving import OracleServer, ServeSpec

    assert any(e.log_target for e in toy_oracle.estimators.values())
    srv = OracleServer(oracles={"stepped_sim": toy_oracle}, spec=ServeSpec())
    assert srv._network_key_scope(toy_oracle) == ()
    assert srv._network_key_scope(dc.replace(toy_oracle, predict_backend="jax")) == ("jax",)
    # bitwise network parity (no log target) -> key sharing is allowed
    ests = {lt: dc.replace(e, log_target=False) for lt, e in toy_oracle.estimators.items()}
    linear = dc.replace(toy_oracle, estimators=ests, predict_backend="jax")
    assert srv._network_key_scope(linear) == ()
    srv.close()

    nets = _toy_nets()[:1]
    poison = 123.456
    spec = ServeSpec(window_s=0.001, predict_backend="jax")
    with OracleServer(oracles={"stepped_sim": toy_oracle}, spec=spec) as srv:
        oracle = srv._oracle("stepped_sim")
        numpy_keys = [("stepped_sim",) + k for k in oracle.network_keys(nets)]
        srv.cache.put_many(numpy_keys, [poison])  # what a numpy server would warm
        r = srv.handle(
            {
                "op": "predict_networks",
                "platform": "stepped_sim",
                "networks": [[_payload(b) for b in nets[0]]],
            }
        )
        assert r["ok"], r
        assert r["result"][0] != poison  # scoped key -> recomputed, not served
        np.testing.assert_allclose(
            r["result"], toy_oracle.predict_networks(nets, backend="jax"), rtol=1e-12
        )


def test_network_cache_keys_unscoped_on_numpy_backend(toy_oracle):
    from repro.serving import OracleServer, ServeSpec

    nets = _toy_nets()[:1]
    poison = 123.456
    with OracleServer(oracles={"stepped_sim": toy_oracle}, spec=ServeSpec(window_s=0.001)) as srv:
        keys = [("stepped_sim",) + k for k in toy_oracle.network_keys(nets)]
        srv.cache.put_many(keys, [poison])
        r = srv.handle(
            {
                "op": "predict_networks",
                "platform": "stepped_sim",
                "networks": [[_payload(b) for b in nets[0]]],
            }
        )
        assert r["ok"], r
        assert r["result"][0] == poison  # same backend -> cache hit by design
