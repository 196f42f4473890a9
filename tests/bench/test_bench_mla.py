"""The latent-attention mesh-search cell: the plain restatement of
DeepSeek-V3's decomposition (bench/mla_reference.py) against the program's
decomposition, features and jax path, the row counter the cell reads, and a
whole run that is correct only when sound (bench/drivers/mla_search.py)."""

import dataclasses
import shutil
import time

import numpy as np
import pytest

from bench import estimators, generate, mla_reference, reference
from bench.common import Cell
from bench.run import execute

CELL = "deepseek3.mla_search"
CONFIG = Cell(CELL).config
TRAFFIC = Cell(CELL).traffic
GAP_LIMIT = CONFIG["limits"]["max_rel_gap"]
LAUNCH = CONFIG["launch_overhead_s"]
#: the cell's shapes and a training step, which runs the MTP module
SHAPES = {**TRAFFIC["shapes"],
          "train_4k": {"seq_len": 4096, "global_batch": 1024, "kind": "train"}}


@pytest.fixture(scope="module")
def mla_hub():
    """The configuration's forests grown on 80 samples a type, and the hub
    the program loads them from: ``(path, forests)``."""
    from bench.drivers import mla_search

    config = {**CONFIG, "forest": {**CONFIG["forest"], "samples": 80}}
    path, made = mla_search.build(config)
    yield path, made
    shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="module")
def oracle(mla_hub):
    return estimators.load_oracle(mla_hub[0], CONFIG["platform"])


def _tiny_model() -> dict:
    from repro.models.config import ModelConfig, reduced

    return dataclasses.asdict(reduced(ModelConfig(**CONFIG["model"])))


def _program_blocks(m, shape, dp, tp):
    from repro.core.network import decompose
    from repro.models.config import InputShape, ModelConfig

    return [(b.kind, [(lt, dict(c)) for lt, c in b.layers], b.repeat)
            for b in decompose(ModelConfig(**m), InputShape(name="s", **shape), dp, tp)]


@pytest.mark.parametrize("model", ["published", "tiny"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_restatement_gives_the_programs_blocks(model, shape):
    m = CONFIG["model"] if model == "published" else _tiny_model()
    s = SHAPES[shape]
    for dp, tp in ((256, 1), (64, 4), (16, 16), (4, 64), (1, 128), (1, 1024)):
        ref = mla_reference.mla_blocks(m, s, dp, tp)
        assert ref == _program_blocks(m, s, dp, tp)
        cores = [lt for _, layers, _ in ref for lt, _ in layers if lt.startswith("mla")]
        n_mla = 2 if s["kind"] == "train" else 1  # the body's and the MTP module's
        assert cores == ["mla_decode" if s["kind"] == "decode" else "mla_prefill"] * n_mla


@pytest.mark.parametrize("layer_type", list(CONFIG["layer_types"]))
def test_snap_and_features_match_the_program(layer_type, mla_hub):
    from repro.api import EstimatorHub
    from repro.core.batch import ConfigBatch

    path, made = mla_hub
    est = EstimatorHub(str(path)).load(CONFIG["platform"], layer_type)
    cols = generate.uniform_columns(CONFIG["layer_types"][layer_type], 500,
                                    np.random.default_rng(3))
    forest = made[layer_type]
    ours = mla_reference.features(
        layer_type, reference.snap(cols, forest.widths, forest.ranges), forest.params)
    np.testing.assert_array_equal(ours, est._features(ConfigBatch.from_columns(cols)))


@pytest.mark.parametrize("model", ["published", "tiny"])
def test_autotune_on_the_jax_path_equals_the_restatement(model, oracle, mla_hub):
    from repro.core.advisor import autotune, default_candidates
    from repro.models.config import InputShape, ModelConfig

    made = mla_hub[1]
    m = CONFIG["model"] if model == "published" else _tiny_model()
    control = 0.0
    for name, chips in ((n, c) for n in sorted(SHAPES) for c in TRAFFIC["chips"]):
        shape = SHAPES[name]
        ranking = autotune(oracle, ModelConfig(**m), InputShape(name=name, **shape),
                           default_candidates(chips))
        got = {(c.dp, c.tp, c.microbatches): s for c, s in ranking}
        ref = mla_reference.score_search(m, shape, chips, made, LAUNCH)
        assert set(got) == set(ref) and 21 <= len(ref) <= 33
        keys = sorted(ref)
        assert reference.max_rel_gap([got[k] for k in keys], [ref[k] for k in keys]) <= 1e-12
        low = mla_reference.score_search(m, shape, chips, made, LAUNCH, np.float32)
        control = max(control, reference.max_rel_gap([low[k] for k in keys],
                                                     [ref[k] for k in keys]))
    # the cell's check takes the widest gap of its answers: the float32
    # reference's fails the limit
    assert control > GAP_LIMIT


def test_counters_count_what_a_network_call_packs(oracle):
    from repro.core.advisor import autotune, candidates_block_batch, default_candidates
    from repro.core.jax_predict import bucket_rows
    from repro.models.config import InputShape, ModelConfig
    from repro.obs.metrics import metrics

    model = ModelConfig(**CONFIG["model"])
    names = ("jax.network.rows", "jax.network.pad_rows", "jax.network.rows.mla",
             "jax.network.rows.ssm", "jax.network.calls")
    for name in sorted(TRAFFIC["shapes"]):
        shape = InputShape(name=name, **TRAFFIC["shapes"][name])
        before = {k: metrics().snapshot()["counters"].get(k, 0) for k in names}
        ranking = autotune(oracle, model, shape, default_candidates(512))
        after = {k: metrics().snapshot()["counters"].get(k, 0) for k in names}
        scored = [c for c, s in ranking if np.isfinite(s)]
        batch, _ = candidates_block_batch(model, shape, scored)
        per_type = {lt: len(cfgs) for lt, cfgs in zip(batch.group_types, batch.group_configs)}
        rows = sum(per_type.values())
        core = "mla_decode" if shape.kind == "decode" else "mla_prefill"
        assert {k: after[k] - before[k] for k in names} == {
            "jax.network.rows": rows,
            "jax.network.pad_rows": sum(bucket_rows(n) - n for n in per_type.values()),
            "jax.network.rows.mla": per_type[core],
            "jax.network.rows.ssm": 0,
            "jax.network.calls": 1,
        }
        assert 0 < per_type[core] == len(scored) < rows


class _Run:
    def __init__(self, counters):
        self.delta = {"counters": counters, "histograms": {}}


@pytest.mark.parametrize("counters,value", [
    ({"jax.network.rows": 400, "jax.network.pad_rows": 100, "jax.network.rows.mla": 25}, 6.25),
    ({"jax.network.rows": 400, "jax.network.rows.mla": 0}, 0.0),
    ({"jax.network.rows": 400, "jax.network.rows.ssm": 60}, None),
    ({"jax.network.h2d_bytes": 10}, None),
])
def test_mla_row_share_reads_the_counters_and_nothing_without_them(counters, value):
    assert Cell(CELL).metric_reader("mla_row_share.mla_search").read(_Run(counters)) == value


@pytest.mark.parametrize("layer_type", ["mla_prefill", "mla_decode"])
def test_mla_forests_have_a_fitted_forest_size(layer_type):
    cfg = {**CONFIG, "layer_types": {layer_type: CONFIG["layer_types"][layer_type]},
           "forest": {**CONFIG["forest"], "trees": 4}}
    one, two = (mla_reference.make(cfg)[layer_type] for _ in range(2))
    assert all(np.array_equal(x, y) for s, t in zip(one.trees, two.trees) for x, y in zip(s, t))
    for feature, _, left, right, value in one.trees:
        inner = feature >= 0
        assert 1000 <= len(feature) <= 3000 and np.all(np.isfinite(value))
        assert (~inner).sum() == inner.sum() + 1


def _patch_plan(monkeypatch, edit_cfg=None, edit_layers=None):
    """Plant a fault in the program's decomposition: ``edit_cfg(cfg)`` gives
    the model the plan sees, ``edit_layers(layers, tp)`` each block's layers."""
    from repro.core import network

    plan = network._decompose_plan

    def faulty(cfg, kind, seq_len, global_batch, dp, tp, *args):
        cfg = edit_cfg(cfg) if edit_cfg else cfg
        for k, layers, coll, repeat in plan(cfg, kind, seq_len, global_batch, dp, tp, *args):
            yield k, (edit_layers(layers, tp) if edit_layers else layers), coll, repeat

    monkeypatch.setattr(network, "_decompose_plan", faulty)


def latent_sharded_over_tp(monkeypatch):
    """Decode prices each chip's share of the latent, as if tp split it."""
    _patch_plan(monkeypatch, edit_layers=lambda layers, tp: tuple(
        (lt, {**c, "kv_lora": np.maximum(1, c["kv_lora"] // tp)}) if lt == "mla_decode"
        else (lt, c) for lt, c in layers))


def decode_priced_as_prefill(monkeypatch):
    """Decode prices its core as the unabsorbed attention of one token."""
    _patch_plan(monkeypatch, edit_layers=lambda layers, tp: tuple(
        ("mla_prefill", {"B": c["B"], "S": 1, "H": c["H"], "nope": c["nope"],
                         "rope": c["rope"], "dv": c["dv"]}) if lt == "mla_decode"
        else (lt, c) for lt, c in layers))


def kv_b_left_out(monkeypatch):
    """Prefill and training skip the latent's decompression (kv_b)."""
    _patch_plan(monkeypatch, edit_layers=lambda layers, tp: tuple(
        layer for i, layer in enumerate(layers)
        if i + 1 == len(layers) or layers[i + 1][0] != "mla_prefill"))


def mtp_dropped(monkeypatch):
    """Training runs without the MTP module."""
    _patch_plan(monkeypatch, edit_cfg=lambda cfg: dataclasses.replace(cfg, mtp_layers=0))


@pytest.fixture
def fresh_kernels():
    from repro.core import jax_predict

    yield
    jax_predict._forest_fn.cache_clear()
    jax_predict._network_fn.cache_clear()


def _run(tiny_cell, model):
    cell = tiny_cell(CELL, shapes=SHAPES, chips=[256], check_steps=1000)
    if model == "tiny":
        cell.config["model"] = _tiny_model()
    result, checks = execute(cell, 2**31 + 13, 1.0, False, require_chip=False,
                             t_start=time.perf_counter(), control=True)
    assert result["attempted"] >= len(SHAPES) and result["failed"] == 0
    assert set(result["metrics"]) == {"networks_per_s", "setup_s"}
    assert result["control"]["max_rel_gap"] > GAP_LIMIT
    return result, checks


@pytest.mark.parametrize("model", ["published", "tiny"])
def test_sound_run_is_correct(model, tiny_cell, fresh_kernels):
    result, checks = _run(tiny_cell, model)
    assert result["correct"] is True, checks


@pytest.mark.parametrize("fault", [latent_sharded_over_tp, decode_priced_as_prefill,
                                   kv_b_left_out, mtp_dropped], ids=lambda f: f.__name__)
def test_each_fault_makes_the_run_incorrect(fault, tiny_cell, monkeypatch, fresh_kernels):
    fault(monkeypatch)
    result, checks = _run(tiny_cell, "tiny")
    assert result["correct"] is False, checks


def test_a_program_without_latent_attention_fails_before_any_work(tiny_cell):
    from bench.drivers import mla_search

    cell = tiny_cell(CELL)
    cell.config["model"]["no_such_field"] = 1

    class Run:
        state: dict = {}

    run = Run()
    run.cell = cell
    with pytest.raises(TypeError, match="no_such_field"):
        mla_search.setup(run, print)
    assert run.state == {}
