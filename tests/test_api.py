"""repro.api: campaigns, measurement cache, estimator hub, oracle, registry."""

import math

import numpy as np
import pytest

from repro.accelerators.base import Platform
from repro.api import (
    CachedPlatform,
    Campaign,
    CampaignSpec,
    EstimatorHub,
    MeasurementCache,
    PerfOracle,
    get_platform,
    list_platforms,
)
from repro.core import prs
from repro.core.blocks import Block
from repro.core.prs import ParamSpace


class CountingPlatform(Platform):
    """Black-box staircase platform that counts every measure() call."""

    name = "counting_stub"
    knowledge = "black"

    def __init__(self) -> None:
        self.calls: dict[tuple, int] = {}

    def layer_types(self):
        return ("toy",)

    def param_space(self, layer_type):
        return ParamSpace(ranges={"a": (1, 64), "b": (1, 32)})

    def defaults(self, layer_type):
        return {"a": 16, "b": 8}

    def measure(self, layer_type, cfg):
        key = (layer_type, tuple(sorted(cfg.items())))
        self.calls[key] = self.calls.get(key, 0) + 1
        return 1e-6 * (math.ceil(cfg["a"] / 8) * math.ceil(cfg["b"] / 4) + 1)


FAST_FOREST = {"n_estimators": 8, "max_depth": 12}


def _toy_campaign(n_samples=120, **kwargs):
    spec = CampaignSpec(
        platform="counting_stub",
        layer_types=("toy",),
        n_samples=n_samples,
        seed=0,
        forest_kwargs=FAST_FOREST,
        **kwargs,
    )
    stub = CountingPlatform()
    return Campaign(spec, platform=stub), stub


class TestMeasurementCache:
    def test_campaign_measures_each_unique_config_at_most_once(self):
        """Acceptance: sweeps + training + evaluation share one measurement."""
        campaign, stub = _toy_campaign()
        oracle = campaign.run()
        # Evaluate on configs that certainly overlap the PR training grid.
        rng = np.random.default_rng(1)
        space = stub.param_space("toy")
        widths, _ = campaign.discover_widths("toy")
        test = prs.sample_pr_configs(space, widths, 50, rng)
        oracle.evaluate(campaign.platform, "toy", test)
        # Re-train at another size: same PR grid, same cache.
        campaign.train("toy", n_samples=60)
        assert stub.calls, "stub was never measured"
        assert max(stub.calls.values()) == 1
        assert campaign.stats()["hits"] > 0

    def test_cached_platform_hits(self):
        stub = CountingPlatform()
        cp = CachedPlatform(stub)
        cfg = {"a": 9, "b": 5}
        t1 = cp.measure("toy", cfg)
        t2 = cp.measure("toy", dict(cfg))
        assert t1 == t2
        assert stub.calls[("toy", tuple(sorted(cfg.items())))] == 1
        assert cp.cache.hits == 1 and cp.cache.misses == 1

    def test_cache_roundtrip_json(self, tmp_path):
        cache = MeasurementCache()
        cache.store("p", "toy", {"a": 3, "b": 4}, 1.5e-6)
        cache.store_widths("p", "toy", 0.02, 384, {"a": 8, "b": 4}, 123)
        path = str(tmp_path / "cache.json")
        cache.save(path)
        loaded = MeasurementCache.load(path)
        assert loaded.lookup("p", "toy", {"b": 4, "a": 3}) == 1.5e-6
        assert loaded.lookup_widths("p", "toy", 0.02, 384) == ({"a": 8, "b": 4}, 123)


class TestWidthReuse:
    def test_sampling_curve_discovers_widths_once(self):
        campaign, stub = _toy_campaign()
        test = [{"a": 40, "b": 16}, {"a": 9, "b": 30}]
        curve = campaign.sampling_curve("toy", [60, 90, 120], test)
        assert curve[0]["n_sweep"] > 0  # black box: first size pays the sweeps
        assert curve[1]["n_sweep"] == 0 and curve[2]["n_sweep"] == 0
        assert curve[2]["sweeps_saved"] == 2 * curve[0]["n_sweep"]

    def test_widths_memoized_across_trainings(self):
        campaign, _ = _toy_campaign()
        w1, spent1 = campaign.discover_widths("toy")
        w2, spent2 = campaign.discover_widths("toy")
        assert w1 == w2
        assert spent1 > 0 and spent2 == 0


class TestEstimatorHub:
    def test_save_load_bitwise_identical_predictions(self, tmp_path):
        campaign, stub = _toy_campaign()
        est = campaign.train("toy")
        hub = EstimatorHub(str(tmp_path))
        hub.save(stub.name, est)
        loaded = hub.load(stub.name, "toy")
        rng = np.random.default_rng(7)
        queries = prs.sample_random_configs(stub.param_space("toy"), 64, rng)
        assert np.array_equal(est.predict(queries), loaded.predict(queries))
        assert loaded.widths == dict(est.widths)
        assert loaded.space.ranges == dict(est.space.ranges)
        assert loaded.sampling == est.sampling

    def test_oracle_save_load_roundtrip(self, tmp_path):
        from repro.core.blocks import FusingModel

        campaign, stub = _toy_campaign()
        oracle = campaign.run()
        oracle.fusing = {"mlp": FusingModel(w=1e-12, c=2e-7, n_fit=60)}
        oracle.overlap_kinds = frozenset({"attn"})
        oracle.launch_overhead_s = 3e-6
        hub = EstimatorHub(str(tmp_path))
        oracle.save(hub)
        again = PerfOracle.load(hub, stub.name)
        assert set(again.estimators) == {"toy"}
        q = [{"a": 17, "b": 9}, {"a": 64, "b": 32}]
        assert np.array_equal(oracle.predict("toy", q), again.predict("toy", q))
        # combination params survive the round trip (Eq. 9-11 state)
        assert again.fusing["mlp"].w == 1e-12 and again.fusing["mlp"].c == 2e-7
        assert again.overlap_kinds == frozenset({"attn"})
        assert again.launch_overhead_s == 3e-6
        # "plain" has no fusing model (op_count doesn't know "toy" layers);
        # the round trip still exercises overlap_kinds and launch_overhead_s.
        blocks = [
            Block(kind="plain", layers=(("toy", {"a": 8, "b": 4}), ("toy", {"a": 16, "b": 8}))),
            Block(kind="attn", layers=(("toy", {"a": 24, "b": 12}),)),
        ]
        assert oracle.predict_network(blocks) == again.predict_network(blocks)

    def test_empty_hub_load_raises(self, tmp_path):
        hub = EstimatorHub(str(tmp_path))
        with pytest.raises(FileNotFoundError):
            PerfOracle.load(hub, "nothing_here")

    def test_load_missing_raises(self, tmp_path):
        hub = EstimatorHub(str(tmp_path))
        with pytest.raises(FileNotFoundError):
            hub.load("nope", "toy")


class TestPerfOracle:
    def test_batched_network_prediction_matches_legacy_path(self):
        """The batched network path equals Eq. 12's definition, block by block."""
        campaign, _ = _toy_campaign()
        oracle = campaign.run()
        blocks = [
            Block(kind="x", layers=(("toy", {"a": 10, "b": 5}), ("toy", {"a": 33, "b": 17}))),
            Block(kind="x", layers=(("toy", {"a": 64, "b": 32}),), repeat=3),
        ]
        eq12 = sum(oracle.predict_block(b) * b.repeat for b in blocks)
        assert oracle.predict_network(blocks) == pytest.approx(eq12, rel=1e-12)

    def test_overlap_and_repeat(self):
        campaign, _ = _toy_campaign()
        oracle = campaign.run()
        oracle.overlap_kinds = frozenset({"par"})
        layers = (("toy", {"a": 10, "b": 5}), ("toy", {"a": 64, "b": 32}))
        seq = Block(kind="seq", layers=layers)
        par = Block(kind="par", layers=layers)
        times = [oracle.predict_one("toy", c) for _, c in layers]
        assert oracle.predict_block(seq) == pytest.approx(sum(times))
        assert oracle.predict_block(par) == pytest.approx(max(times))
        assert oracle.predict_network([seq]) * 2 == pytest.approx(
            oracle.predict_network([Block(kind="seq", layers=layers, repeat=2)])
        )


class TestRegistry:
    def test_builtin_platforms_registered(self):
        assert {"ultratrail", "vta", "tpu_v5e", "xla_cpu"} <= set(list_platforms())

    def test_get_platform_kwargs(self):
        p = get_platform("tpu_v5e", knowledge="black", noise=0.0)
        assert p.knowledge == "black"
        assert p.name == "tpu_v5e[black]"

    def test_unknown_platform(self):
        with pytest.raises(KeyError):
            get_platform("not_a_platform")


class TestPrGridConsistency:
    """Deterministic analogue of the hypothesis property in test_prs.py."""

    def test_map_to_pr_lands_on_pr_grid_exhaustive(self):
        for lo, hi, w in [
            (1, 56, 8),    # normal range
            (3, 256, 1),   # width 1: identity
            (1, 5, 8),     # hi < w: only PR is hi
            (57, 60, 8),   # lo beyond the last in-range multiple: only PR is hi
            (20, 60, 32),  # lo > w
            (8, 8, 8),     # degenerate single-point range on the grid
            (9, 9, 8),     # degenerate single-point range off the grid
        ]:
            space = prs.ParamSpace(ranges={"p": (lo, hi)})
            grid = set(prs.pr_values(lo, hi, w).tolist())
            # Quantized (w>1) params must land on the grid even for
            # out-of-range query values; w==1 params pass through unsnapped.
            v_lo = max(1, lo - 2 * w) if w > 1 else lo
            v_hi = hi + 2 * w if w > 1 else hi
            for v in range(v_lo, v_hi + 1):
                snapped = prs.map_to_pr({"p": v}, {"p": w}, space)["p"]
                assert snapped in grid, (lo, hi, w, v, snapped, sorted(grid))

    def test_sampled_pr_configs_are_fixed_points(self):
        """PR samples snap to themselves (they already lie on the grid)."""
        space = prs.ParamSpace(ranges={"a": (1, 64), "b": (5, 7)})
        widths = {"a": 8, "b": 16}
        rng = np.random.default_rng(0)
        for cfg in prs.sample_pr_configs(space, widths, 50, rng):
            assert prs.map_to_pr(cfg, widths, space) == cfg
