"""Batch/scalar parity for the columnar config engine.

The columnar refactor carries one hard invariant: the batched path must be
*numerically identical* to the historical scalar path — same RNG draws, same
cache hit/miss accounting, bitwise-equal measurements, features and forest
predictions.  The scalar reference implementations below are frozen copies of
the pre-refactor per-config code, so these tests pin the batched engine to the
old semantics rather than to itself.
"""

import math

import numpy as np
import pytest

from repro.api.cache import CachedPlatform, MeasurementCache, batch_keys, config_key
from repro.api.campaign import Campaign, CampaignSpec
from repro.registry import get_platform
from repro.core import prs
from repro.core.batch import ConfigBatch
from repro.core.features import derived_features, derived_features_batch
from repro.core.forest import RandomForestRegressor


# --------------------------------------------------------------------------- refs
def _ref_map_to_pr(cfg, widths, space=None):
    """Frozen pre-refactor scalar map_to_pr (Eq. 7/8)."""
    out = dict(cfg)
    for p, w in widths.items():
        if p in out and w > 1:
            snapped = int(math.ceil(out[p] / w)) * w
            if space is not None and p in space.ranges:
                lo, hi = space.ranges[p]
                top = int(math.floor(hi / w)) * w
                first = max(w, int(math.ceil(lo / w)) * w)
                if top < first:
                    snapped = hi
                else:
                    snapped = min(max(snapped, first), top)
            out[p] = snapped
    return out


def _ref_sample_pr(space, widths, n, rng):
    """Frozen pre-refactor per-config/per-param PR sampler."""
    per_param = {p: prs.pr_values(lo, hi, widths.get(p, 1)) for p, (lo, hi) in space.ranges.items()}
    out = []
    for _ in range(n):
        cfg = {p: int(rng.choice(vals)) for p, vals in per_param.items()}
        out.append(space.with_fixed(cfg))
    return out


def _ref_sample_random(space, n, rng):
    """Frozen pre-refactor per-config/per-param uniform sampler."""
    out = []
    for _ in range(n):
        cfg = {p: int(rng.integers(lo, hi + 1)) for p, (lo, hi) in space.ranges.items()}
        out.append(space.with_fixed(cfg))
    return out


PLATFORMS = [
    ("ultratrail", {}),
    ("vta", {}),
    ("tpu_v5e", {"knowledge": "white"}),
    ("tpu_v5e", {"knowledge": "gray", "noise": 0.05}),
]


def _sampled_batch(platform, layer_type, n=64, seed=0):
    space = platform.param_space(layer_type)
    widths = platform.known_step_widths(layer_type) or {p: 3 for p in space.params}
    rng = np.random.default_rng(seed)
    return prs.sample_random_batch(space, n, rng), widths, space


# --------------------------------------------------------------------- ConfigBatch
class TestConfigBatch:
    def test_dict_roundtrip(self):
        configs = [{"a": 1, "b": 2}, {"a": 3, "b": 4}]
        batch = ConfigBatch.from_dicts(configs)
        assert batch.to_dicts() == configs
        assert batch.params == ("a", "b")
        assert np.array_equal(batch.column("b"), [2, 4])
        assert len(batch) == 2

    def test_heterogeneous_keys_rejected(self):
        with pytest.raises(ValueError):
            ConfigBatch.from_dicts([{"a": 1}, {"b": 2}])

    def test_non_integer_values_rejected_not_truncated(self):
        with pytest.raises(ValueError):
            ConfigBatch.from_dicts([{"a": 7.5}])

    def test_non_integer_configs_fall_back_to_scalar_paths(self):
        # map_to_pr keeps the historical float behavior via its scalar branch
        space = prs.ParamSpace(ranges={"C": (1, 56), "W": (3, 256)})
        out = prs.map_to_pr({"C": 7.5, "W": 3.25}, {"C": 8, "W": 1}, space)
        assert out == {"C": 8, "W": 3.25}
        # measure_many degrades to the per-config loop instead of truncating
        platform = get_platform("ultratrail")
        cfg = {"C": 24, "K": 24, "C_w": 101.0 + 0.5, "F": 3, "s": 1, "pad": 1}
        y = platform.measure_many("conv1d", [cfg])
        assert y[0] == platform.measure("conv1d", cfg)

    def test_concat_and_take(self):
        b1 = ConfigBatch.from_dicts([{"a": 1, "b": 2}])
        b2 = ConfigBatch.from_dicts([{"a": 3, "b": 4}, {"a": 5, "b": 6}])
        cat = ConfigBatch.concat([b1, b2])
        assert len(cat) == 3
        assert cat.take(np.array([2, 0])).to_dicts() == [{"a": 5, "b": 6}, {"a": 1, "b": 2}]

    def test_dedup_first_occurrence_order(self):
        batch = ConfigBatch.from_dicts(
            [{"a": 5}, {"a": 1}, {"a": 5}, {"a": 2}, {"a": 1}]
        )
        unique, first_rows, inverse = batch.dedup()
        assert unique.to_dicts() == [{"a": 5}, {"a": 1}, {"a": 2}]
        assert list(first_rows) == [0, 1, 3]
        assert np.array_equal(unique.values[inverse], batch.values)

    def test_with_fixed_appends_missing_only(self):
        batch = ConfigBatch.from_dicts([{"a": 1}]).with_fixed({"a": 9, "c": 7})
        assert batch.to_dicts() == [{"a": 1, "c": 7}]


# ------------------------------------------------------------------ sampling parity
class TestSamplingParity:
    @pytest.mark.parametrize("seed", [0, 1, 17])
    def test_pr_sampling_matches_scalar_rng_stream(self, seed):
        space = prs.ParamSpace(ranges={"C": (1, 56), "K": (1, 56), "W": (3, 256)}, fixed={"s": 1})
        widths = {"C": 8, "K": 8, "W": 1}
        ref = _ref_sample_pr(space, widths, 200, np.random.default_rng(seed))
        got = prs.sample_pr_configs(space, widths, 200, np.random.default_rng(seed))
        assert got == ref

    @pytest.mark.parametrize("seed", [0, 5])
    def test_random_sampling_matches_scalar_rng_stream(self, seed):
        space = prs.ParamSpace(ranges={"a": (3, 3), "b": (1, 9), "c": (100, 4096)})
        ref = _ref_sample_random(space, 300, np.random.default_rng(seed))
        got = prs.sample_random_configs(space, 300, np.random.default_rng(seed))
        assert got == ref

    def test_single_value_pr_grid(self):
        # len(pr_values)==1 columns must consume the bitstream like rng.choice.
        space = prs.ParamSpace(ranges={"a": (1, 5), "b": (1, 64)})
        widths = {"a": 8, "b": 4}  # hi < w: "a" has the single PR value 5
        ref = _ref_sample_pr(space, widths, 100, np.random.default_rng(2))
        got = prs.sample_pr_configs(space, widths, 100, np.random.default_rng(2))
        assert got == ref


class TestMapToPrParity:
    def test_matches_scalar_reference_on_platform_spaces(self):
        for name, kwargs in PLATFORMS:
            platform = get_platform(name, **kwargs)
            for lt in platform.layer_types():
                batch, widths, space = _sampled_batch(platform, lt, n=128, seed=3)
                got = prs.map_to_pr_batch(batch, widths, space).to_dicts()
                ref = [_ref_map_to_pr(c, widths, space) for c in batch.to_dicts()]
                assert got == ref

    def test_scalar_wrapper_is_one_row_batch(self):
        space = prs.ParamSpace(ranges={"p": (9, 9)})
        assert prs.map_to_pr({"p": 4}, {"p": 8}, space) == _ref_map_to_pr(
            {"p": 4}, {"p": 8}, space
        )


# ------------------------------------------------------------------- measure parity
class TestMeasureBatchParity:
    def test_bitwise_equal_to_scalar_measure(self):
        for name, kwargs in PLATFORMS:
            platform = get_platform(name, **kwargs)
            for lt in platform.layer_types():
                batch, _, _ = _sampled_batch(platform, lt, n=96, seed=11)
                got = platform.measure_batch(lt, batch)
                ref = np.array([platform.measure(lt, c) for c in batch.to_dicts()])
                assert np.array_equal(got, ref), (name, lt)

    def test_default_fallback_for_scalar_only_platforms(self):
        from repro.accelerators.base import Platform

        class ScalarOnly(Platform):
            name = "scalar_only"

            def layer_types(self):
                return ("toy",)

            def param_space(self, layer_type):
                return prs.ParamSpace(ranges={"a": (1, 8)})

            def defaults(self, layer_type):
                return {"a": 4}

            def measure(self, layer_type, cfg):
                return float(cfg["a"]) * 1e-6

        p = ScalarOnly()
        batch = ConfigBatch.from_dicts([{"a": 2}, {"a": 7}])
        assert np.array_equal(p.measure_batch("toy", batch), [2e-6, 7e-6])


# ------------------------------------------------------------------- forest parity
class TestForestParity:
    @pytest.mark.parametrize("seed", [0, 4])
    def test_stacked_predict_bitwise_equals_per_tree_loop(self, seed):
        rng = np.random.default_rng(seed)
        X = rng.uniform(0, 100, size=(400, 5))
        y = X[:, 0] * 2 + np.sin(X[:, 1]) + rng.normal(0, 0.1, 400)
        forest = RandomForestRegressor(n_estimators=16, max_depth=10, seed=seed).fit(X, y)
        Xq = rng.uniform(-10, 120, size=(257, 5))
        acc = np.zeros(Xq.shape[0])
        for t in forest._trees:
            acc += t.predict(Xq)
        assert np.array_equal(forest.predict(Xq), acc / len(forest._trees))

    def test_stack_invalidated_when_trees_replaced(self):
        rng = np.random.default_rng(0)
        X = rng.uniform(0, 10, size=(50, 2))
        f1 = RandomForestRegressor(n_estimators=4, seed=0).fit(X, X[:, 0])
        f1.predict(X)  # builds the stack
        f2 = RandomForestRegressor(n_estimators=4, seed=0).fit(X, X[:, 1])
        f1._trees = f2._trees  # what EstimatorHub.load does
        assert np.array_equal(f1.predict(X), f2.predict(X))


# -------------------------------------------------------------------- cache batching
class TestCacheBatching:
    def test_numpy_int_keys_hit_plain_int_entries(self):
        """Regression: np.int64-valued configs must hit int-keyed entries."""
        cache = MeasurementCache()
        cache.store("p", "toy", {"a": 8, "b": 3}, 1.5e-6)
        a, b = np.arange(8, 9)[0], np.arange(3, 4)[0]
        assert config_key("toy", {"a": a, "b": b}) == config_key("toy", {"a": 8, "b": 3})
        assert cache.lookup("p", "toy", {"a": a, "b": b}) == 1.5e-6

    def test_batch_keys_match_config_key(self):
        batch = ConfigBatch.from_dicts([{"b": 2, "a": 1}, {"b": 4, "a": 3}])
        assert batch_keys("toy", batch) == [
            config_key("toy", {"a": 1, "b": 2}),
            config_key("toy", {"a": 3, "b": 4}),
        ]

    def test_stats_parity_with_scalar_replay_on_duplicates(self):
        platform = get_platform("ultratrail")
        rows = _sampled_batch(platform, "conv1d", n=40, seed=7)[0].to_dicts()
        rows = rows + rows[:10]  # in-batch duplicates
        # scalar replay
        scalar = CachedPlatform(get_platform("ultratrail"))
        y_ref = np.array([scalar.measure("conv1d", c) for c in rows])
        # batched transaction
        batched = CachedPlatform(get_platform("ultratrail"))
        y = batched.measure_batch("conv1d", ConfigBatch.from_dicts(rows))
        assert np.array_equal(y, y_ref)
        assert batched.cache.hits == scalar.cache.hits
        assert batched.cache.misses == scalar.cache.misses
        assert batched.cache.n_unique == scalar.cache.n_unique

    def test_batch_and_scalar_paths_share_entries(self):
        cp = CachedPlatform(get_platform("ultratrail"))
        cfg = {"C": 24, "K": 24, "C_w": 101, "F": 3, "s": 1, "pad": 1}
        t = cp.measure("conv1d", cfg)
        y = cp.measure_batch("conv1d", ConfigBatch.from_dicts([cfg]))
        assert y[0] == t
        assert cp.cache.misses == 1 and cp.cache.hits == 1


# --------------------------------------------------------------- end-to-end parity
class TestCampaignParity:
    def test_campaign_is_deterministic_and_batched_end_to_end(self):
        """Two fresh campaigns with one seed agree bitwise (training configs,
        cache accounting and predictions all flow through the batch path)."""
        def run():
            spec = CampaignSpec(
                platform="vta",
                layer_types=("fully_connected",),
                n_samples=80,
                seed=5,
                forest_kwargs={"n_estimators": 4, "max_depth": 8},
            )
            campaign = Campaign(spec)
            oracle = campaign.run()
            queries = prs.sample_random_configs(
                campaign.platform.param_space("fully_connected"), 50, np.random.default_rng(9)
            )
            return oracle.predict("fully_connected", queries), campaign.stats()

        (p1, s1), (p2, s2) = run(), run()
        assert np.array_equal(p1, p2)
        s1.pop("measure_seconds"), s2.pop("measure_seconds")  # wall clock
        assert s1 == s2
        # gray box: 2 sweep windows of <=384 points + 80 training samples
        assert s1["unique_measurements"] <= 2 * 384 + 80

    def test_config_path_matches_scalar_reference_end_to_end(self):
        """PR sampling -> cached measurement -> snap -> features -> forest on a
        trained estimator equals the per-config scalar loops, bit for bit."""
        campaign = Campaign(
            CampaignSpec(
                platform="tpu_v5e",
                layer_types=("dense",),
                n_samples=200,
                seed=0,
                forest_kwargs={"n_estimators": 8, "max_depth": 12},
            )
        )
        campaign.run()
        est = campaign.estimators["dense"]
        space = get_platform("tpu_v5e").param_space("dense")

        batch = prs.sample_pr_batch(space, est.widths, 300, np.random.default_rng(0))
        configs = _ref_sample_pr(space, est.widths, 300, np.random.default_rng(0))
        assert batch.to_dicts() == configs
        scalar = CachedPlatform(get_platform("tpu_v5e"))
        batched = CachedPlatform(get_platform("tpu_v5e"))
        assert np.array_equal(
            batched.measure_batch("dense", batch),
            np.array([scalar.measure("dense", c) for c in configs]),
        )

        queries = prs.sample_random_batch(space, 300, np.random.default_rng(1))
        for rows in (batch, queries):
            snapped = [prs.map_to_pr(c, est.widths, est.space) for c in rows.to_dicts()]
            derived = np.array(
                [list(derived_features("dense", c).values()) for c in snapped],
                dtype=np.float64,
            )
            X = np.concatenate([prs.configs_to_matrix(snapped, est.params), derived], axis=1)
            assert np.array_equal(est._features(rows, snap=True), X)
            acc = np.zeros(X.shape[0])
            for t in est.forest._trees:
                acc += t.predict(X)
            y = acc / len(est.forest._trees)
            assert np.array_equal(est.predict(rows), np.exp(y) if est.log_target else y)

    def test_features_batch_matches_scalar_dicts(self):
        for name, kwargs in PLATFORMS:
            platform = get_platform(name, **kwargs)
            for lt in platform.layer_types():
                batch, _, _ = _sampled_batch(platform, lt, n=64, seed=1)
                got = derived_features_batch(lt, batch)
                ref = np.array(
                    [list(derived_features(lt, c).values()) for c in batch.to_dicts()],
                    dtype=np.float64,
                )
                if ref.size == 0:
                    assert got.size == 0
                else:
                    assert np.array_equal(got, ref), (name, lt)

    def test_run_sweeps_with_param_missing_from_defaults(self):
        """Regression: platforms may omit a swept param from defaults()."""
        from repro.accelerators.base import Platform
        from repro.core import sweeps

        class SparseDefaults(Platform):
            name = "sparse_defaults"

            def layer_types(self):
                return ("toy",)

            def param_space(self, layer_type):
                return prs.ParamSpace(ranges={"a": (1, 20), "b": (1, 10)})

            def defaults(self, layer_type):
                return {"a": 8}  # no "b"

            def measure(self, layer_type, cfg):
                return 1e-6 * (cfg["a"] + cfg.get("b", 0))

        out = sweeps.run_sweeps(SparseDefaults(), "toy")
        assert set(out) == {"a", "b"}
        assert len(out["b"][0]) == 10

    def test_predict_empty_config_list(self):
        """Regression: empty queries must return an empty array, not KeyError."""
        spec = CampaignSpec(
            platform="ultratrail",
            n_samples=30,
            forest_kwargs={"n_estimators": 2, "max_depth": 6},
        )
        campaign = Campaign(spec)
        est = campaign.train("conv1d")
        assert est.predict([]).shape == (0,)

    def test_fixed_only_space_sampling(self):
        """Regression: a ranges-free space still yields n fixed-only configs
        (the pre-refactor scalar loops did)."""
        space = prs.ParamSpace(ranges={}, fixed={"a": 3})
        rng = np.random.default_rng(0)
        assert prs.sample_pr_configs(space, {}, 4, rng) == [{"a": 3}] * 4
        assert prs.sample_random_configs(space, 4, rng) == [{"a": 3}] * 4

    def test_sampling_curve_handles_missing_widths_entry(self, monkeypatch):
        """Regression: a None widths-cache entry must not crash sampling_curve."""
        spec = CampaignSpec(
            platform="ultratrail",
            n_samples=40,
            forest_kwargs={"n_estimators": 2, "max_depth": 6},
        )
        campaign = Campaign(spec)
        monkeypatch.setattr(campaign.cache, "lookup_widths", lambda *a, **k: None)
        test = [{"C": 24, "K": 24, "C_w": 50, "F": 3, "s": 1, "pad": 1}]
        curve = campaign.sampling_curve("conv1d", [20, 30], test)
        assert len(curve) == 2
        # white box: widths are free, so nothing was spent and nothing saved
        assert curve[0]["n_sweep"] == 0 and curve[1]["sweeps_saved"] == 0


# -------------------------------------------------------------- hypothesis parity
# Guarded per-test (not importorskip) so the deterministic parity suite above
# still runs where hypothesis is unavailable.
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal environments
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        lo=st.integers(1, 64),
        span=st.integers(0, 200),
        w=st.integers(1, 32),
        seed=st.integers(0, 10_000),
    )
    def test_property_map_to_pr_batch_matches_scalar_reference(lo, span, w, seed):
        hi = lo + span
        space = prs.ParamSpace(ranges={"p": (lo, hi)})
        rng = np.random.default_rng(seed)
        vals = rng.integers(max(0, lo - 2 * w), hi + 2 * w + 1, size=50)
        batch = ConfigBatch.from_columns({"p": vals})
        got = prs.map_to_pr_batch(batch, {"p": w}, space).to_dicts()
        assert got == [_ref_map_to_pr({"p": int(v)}, {"p": w}, space) for v in vals]

    @settings(max_examples=40, deadline=None)
    @given(
        w_a=st.integers(1, 16),
        w_b=st.integers(1, 16),
        n=st.integers(0, 60),
        seed=st.integers(0, 10_000),
    )
    def test_property_pr_sampler_matches_scalar_rng_stream(w_a, w_b, n, seed):
        space = prs.ParamSpace(ranges={"a": (1, 48), "b": (2, 77)}, fixed={"f": 9})
        widths = {"a": w_a, "b": w_b}
        ref = _ref_sample_pr(space, widths, n, np.random.default_rng(seed))
        got = prs.sample_pr_configs(space, widths, n, np.random.default_rng(seed))
        assert got == ref

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000), n=st.integers(1, 80))
    def test_property_measure_batch_matches_scalar(seed, n):
        platform = get_platform("tpu_v5e", knowledge="white")
        batch, _, _ = _sampled_batch(platform, "dense", n=n, seed=seed)
        got = platform.measure_batch("dense", batch)
        ref = np.array([platform.measure("dense", c) for c in batch.to_dicts()])
        assert np.array_equal(got, ref)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_property_forest_predict_matches_per_tree_loop(seed):
        rng = np.random.default_rng(seed)
        X = rng.uniform(0, 50, size=(120, 3))
        y = X @ np.array([1.0, -2.0, 0.5]) + rng.normal(0, 0.2, 120)
        forest = RandomForestRegressor(n_estimators=6, max_depth=8, seed=seed).fit(X, y)
        Xq = rng.uniform(0, 50, size=(64, 3))
        acc = np.zeros(64)
        for t in forest._trees:
            acc += t.predict(Xq)
        assert np.array_equal(forest.predict(Xq), acc / 6)
