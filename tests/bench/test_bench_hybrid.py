"""The hybrid mesh-search cell: the plain restatement of a layer-pattern
model (bench/hybrid_reference.py) against the program's decomposition,
features and jax path, the counters the cell reads, and a whole run that is
correct only when sound (bench/drivers/hybrid_search.py)."""

import dataclasses
import shutil
import time

import numpy as np
import pytest

from bench import estimators, generate, hybrid_reference, reference
from bench.common import Cell
from bench.run import execute

CELL = "nemotron3.hybrid_search"
CONFIG = Cell(CELL).config
TRAFFIC = Cell(CELL).traffic
GAP_LIMIT = CONFIG["limits"]["max_rel_gap"]
LAUNCH = CONFIG["launch_overhead_s"]


@pytest.fixture(scope="module")
def hybrid_hub():
    """The configuration's forests grown on 80 samples a type, and the hub
    the program loads them from: ``(path, forests)``."""
    from bench.drivers import hybrid_search

    config = {**CONFIG, "forest": {**CONFIG["forest"], "samples": 80}}
    path, made = hybrid_search.build(config)
    yield path, made
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def oracle(hybrid_hub):
    return estimators.load_oracle(hybrid_hub[0], CONFIG["platform"])


def _tiny_model() -> dict:
    from repro.models.config import ModelConfig, reduced

    return dataclasses.asdict(reduced(ModelConfig(**CONFIG["model"])))


def _program_blocks(m, shape, dp, tp):
    from repro.core.network import decompose
    from repro.models.config import InputShape, ModelConfig

    return [(b.kind, [(lt, dict(c)) for lt, c in b.layers], b.repeat)
            for b in decompose(ModelConfig(**m), InputShape(name="s", **shape), dp, tp)]


@pytest.mark.parametrize("model", ["published", "tiny"])
@pytest.mark.parametrize("shape", sorted(TRAFFIC["shapes"]))
def test_restatement_gives_the_programs_blocks(model, shape):
    m = CONFIG["model"] if model == "published" else _tiny_model()
    s = TRAFFIC["shapes"][shape]
    for dp, tp in ((64, 1), (32, 2), (16, 4), (4, 16), (1, 64), (1, 1024)):
        ref = hybrid_reference.pattern_blocks(m, s, dp, tp)
        assert ref == _program_blocks(m, s, dp, tp)
        kinds = {kind: rep for kind, _, rep in ref}
        rep = 3.0 if s["kind"] == "train" else 1.0
        pattern = m["layer_pattern"]
        assert (kinds["ssd"], kinds["moe"], kinds["attn"]) == (
            pattern.count("M") * rep, pattern.count("E") * rep, pattern.count("*") * rep)
        mixers = [lt for _, layers, _ in ref for lt, _ in layers if lt.startswith("ssd")]
        assert mixers == ["ssd_decode" if s["kind"] == "decode" else "ssd_scan"]
        moe = next(layers for kind, layers, _ in ref if kind == "moe")
        assert moe[1][1]["mats"] == 2 and len(moe) == 4


@pytest.mark.parametrize("layer_type", list(CONFIG["layer_types"]))
def test_snap_and_features_match_the_program(layer_type, hybrid_hub):
    from repro.api import EstimatorHub
    from repro.core.batch import ConfigBatch

    path, made = hybrid_hub
    est = EstimatorHub(str(path)).load(CONFIG["platform"], layer_type)
    cols = generate.uniform_columns(CONFIG["layer_types"][layer_type], 500,
                                    np.random.default_rng(3))
    forest = made[layer_type]
    ours = hybrid_reference.features(
        layer_type, reference.snap(cols, forest.widths, forest.ranges), forest.params)
    np.testing.assert_array_equal(ours, est._features(ConfigBatch.from_columns(cols)))


@pytest.mark.parametrize("model", ["published", "tiny"])
def test_autotune_on_the_jax_path_equals_the_restatement(model, oracle, hybrid_hub):
    from repro.core.advisor import autotune, default_candidates
    from repro.models.config import InputShape, ModelConfig

    made = hybrid_hub[1]
    m = CONFIG["model"] if model == "published" else _tiny_model()
    for name, chips in ((n, c) for n in sorted(TRAFFIC["shapes"]) for c in TRAFFIC["chips"]):
        shape = TRAFFIC["shapes"][name]
        ranking = autotune(oracle, ModelConfig(**m), InputShape(name=name, **shape),
                           default_candidates(chips))
        got = {(c.dp, c.tp, c.microbatches): s for c, s in ranking}
        ref = hybrid_reference.score_search(m, shape, chips, made, LAUNCH)
        assert set(got) == set(ref) and 21 <= len(ref) <= 33
        keys = sorted(ref)
        assert reference.max_rel_gap([got[k] for k in keys], [ref[k] for k in keys]) <= 1e-12
        low = hybrid_reference.score_search(m, shape, chips, made, LAUNCH, np.float32)
        assert reference.max_rel_gap([low[k] for k in keys], [ref[k] for k in keys]) > GAP_LIMIT


def test_counters_count_what_a_network_call_packs(oracle):
    from repro.core.advisor import autotune, candidates_block_batch, default_candidates
    from repro.core.jax_predict import bucket_rows
    from repro.models.config import InputShape, ModelConfig
    from repro.obs.metrics import metrics

    model = ModelConfig(**CONFIG["model"])
    names = ("jax.network.rows", "jax.network.pad_rows", "jax.network.rows.ssm",
             "jax.network.calls")
    for name in sorted(TRAFFIC["shapes"]):
        shape = InputShape(name=name, **TRAFFIC["shapes"][name])
        cands = default_candidates(256)
        before = {k: metrics().snapshot()["counters"].get(k, 0) for k in names}
        ranking = autotune(oracle, model, shape, cands)
        after = {k: metrics().snapshot()["counters"].get(k, 0) for k in names}
        scored = [c for c, s in ranking if np.isfinite(s)]
        batch, _ = candidates_block_batch(model, shape, scored)
        per_type = {lt: len(cfgs) for lt, cfgs in zip(batch.group_types, batch.group_configs)}
        rows = sum(per_type.values())
        assert {k: after[k] - before[k] for k in names} == {
            "jax.network.rows": rows,
            "jax.network.pad_rows": sum(bucket_rows(n) - n for n in per_type.values()),
            "jax.network.rows.ssm": per_type.get("ssd_scan", 0) + per_type.get("ssd_decode", 0),
            "jax.network.calls": 1,
        }
        assert 0 < per_type.get("ssd_scan", per_type.get("ssd_decode", 0)) < rows


class _Run:
    def __init__(self, counters):
        self.delta = {"counters": counters, "histograms": {}}


@pytest.mark.parametrize("metric,counters,value", [
    ("pad_row_share.hybrid_search",
     {"jax.network.rows": 300, "jax.network.pad_rows": 100, "jax.network.rows.ssm": 60}, 25.0),
    ("ssm_row_share.hybrid_search",
     {"jax.network.rows": 300, "jax.network.pad_rows": 100, "jax.network.rows.ssm": 60}, 20.0),
    ("pad_row_share.hybrid_search", {"jax.network.h2d_bytes": 10}, None),
    ("ssm_row_share.hybrid_search", {"jax.network.h2d_bytes": 10}, None),
])
def test_row_shares_read_the_counters_and_nothing_without_them(metric, counters, value):
    assert Cell(CELL).metric_reader(metric).read(_Run(counters)) == value


@pytest.mark.parametrize("layer_type", ["ssd_scan", "ssd_decode"])
def test_mamba_forests_have_a_fitted_forest_size(layer_type):
    cfg = {**CONFIG, "layer_types": {layer_type: CONFIG["layer_types"][layer_type]},
           "forest": {**CONFIG["forest"], "trees": 4}}
    one, two = (hybrid_reference.make(cfg)[layer_type] for _ in range(2))
    assert all(np.array_equal(x, y) for s, t in zip(one.trees, two.trees) for x, y in zip(s, t))
    for feature, _, left, right, value in one.trees:
        inner = feature >= 0
        assert 1000 <= len(feature) <= 3000 and np.all(np.isfinite(value))
        assert (~inner).sum() == inner.sum() + 1
        depth = np.zeros(len(feature), dtype=int)
        for i in np.flatnonzero(inner):
            depth[left[i]] = depth[right[i]] = depth[i] + 1
        assert 10 <= depth.max() <= 24


def _patch_traverse(monkeypatch, broken):
    from repro.core import jax_predict

    monkeypatch.setattr(jax_predict, "_traverse", broken(jax_predict._traverse))
    jax_predict._forest_fn.cache_clear()
    jax_predict._network_fn.cache_clear()


def answer_altered(monkeypatch):
    """The compiled traversal returns one row's answer changed."""
    _patch_traverse(monkeypatch, lambda traverse: lambda jnp, *a: (
        traverse(jnp, *a).at[0].multiply(1.0 + 1e-6)))


def half_the_trees(monkeypatch):
    """The compiled traversal descends half of the trees."""
    def broken(traverse):
        def half(jnp, lax, feature, threshold, left, right, value, X, n_trees):
            h = feature.shape[0] // 2
            return traverse(jnp, lax, feature[:h], threshold[:h], left[:h], right[:h],
                            value[:h], X, n_trees / 2)
        return half

    _patch_traverse(monkeypatch, broken)


def decode_scans_one_chunk(monkeypatch):
    """The decomposition prices a decode step's Mamba-2 mixer as a scan of
    one token, as the program did before ``ssd_decode``."""
    from repro.core import network

    plan = network._decompose_plan

    def scanning(*args, **kwargs):
        for kind, layers, coll, repeat in plan(*args, **kwargs):
            yield kind, tuple(("ssd_scan", {**c, "S": 1}) if lt == "ssd_decode" else (lt, c)
                              for lt, c in layers), coll, repeat

    monkeypatch.setattr(network, "_decompose_plan", scanning)


@pytest.fixture
def fresh_kernels():
    from repro.core import jax_predict

    yield
    jax_predict._forest_fn.cache_clear()
    jax_predict._network_fn.cache_clear()


@pytest.mark.parametrize("fault", [None, answer_altered, half_the_trees, decode_scans_one_chunk],
                         ids=lambda f: f.__name__ if f else "sound")
def test_run_is_correct_only_when_sound(fault, tiny_cell, monkeypatch, fresh_kernels):
    if fault is not None:
        fault(monkeypatch)
    cell = tiny_cell(CELL, check_steps=3)
    result, checks = execute(cell, 2**31 + 11, 1.0, False, require_chip=False,
                             t_start=time.perf_counter(), control=True)
    assert result["correct"] is (fault is None), checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"networks_per_s", "setup_s"}
    assert result["control"]["max_rel_gap"] > GAP_LIMIT


def test_a_program_without_layer_patterns_fails_before_any_work(tiny_cell):
    from bench.drivers import hybrid_search

    cell = tiny_cell(CELL)
    cell.config["model"]["no_such_field"] = 1

    class Run:
        state: dict = {}

    run = Run()
    run.cell = cell
    with pytest.raises(TypeError, match="no_such_field"):
        hybrid_search.setup(run, print)
    assert run.state == {}
