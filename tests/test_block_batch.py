"""Columnar block engine: BlockBatch + cached/sharded measure_block (Eq. 9-12).

The PR-2-style hard invariant under test: a whole-network calibration +
evaluation + autotune run through the columnar block path (``BlockBatch`` ->
``measure_block_batch`` -> block cache -> runtime scheduler) is **bitwise
identical** to the frozen scalar ``measure_block``/``predict_one`` loops, for
any worker count — plus frozen sha256 goldens so future refactors can't
silently move the numbers, in-batch duplicate-block cache semantics, journal
resume mid-calibration, and a hypothesis round-trip property for
``BlockBatch.from_blocks``/``to_blocks``.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.accelerators import TPUv5eSim
from repro.accelerators.ultratrail import UltraTrailSim
from repro.accelerators.vta import VTASim
from repro.accelerators.xla_cpu import XLACPUPlatform
from repro.api import (
    BlockBatch,
    CachedPlatform,
    Campaign,
    CampaignSpec,
    MeasurementCache,
    PerfOracle,
    RuntimeSpec,
)
from repro.core.advisor import autotune, default_candidates, estimate_candidate
from repro.core.batch import BlockBatchBuilder, ConfigBatch
from repro.core.blocks import (
    Block,
    block_ops,
    block_ops_batch,
    fit_fusing_model,
    measure_block_many,
    op_count,
    op_count_batch,
)
from repro.core.network import simulate_network, simulate_networks
from repro.runtime import (
    JournalCorruptionWarning,
    MeasurementError,
    MeasurementJournal,
    MeasurementScheduler,
    SerialExecutor,
)
from repro.runtime.scheduler import DEFAULT_CHUNK_SIZE
from repro.runtime.testing import SteppedSimPlatform

FAST_FOREST = {"n_estimators": 4, "max_depth": 10}


# --------------------------------------------------------------- block corpora
def _dense_blocks(n: int, seed: int, collectives: bool = True) -> list[Block]:
    r = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = int(r.choice([512, 1024, 2048, 4096]))
        d = int(r.choice([512, 1024, 2048]))
        f = int(r.choice([1024, 2048, 4096]))
        out.append(
            Block(
                kind="mlp",
                layers=(
                    ("dense", {"tokens": t, "d_in": d, "d_out": f}),
                    ("dense", {"tokens": t, "d_in": f, "d_out": d}),
                ),
                collective_bytes=float(r.choice([0.0, 2e8])) if collectives else 0.0,
                repeat=int(r.integers(1, 4)),
            )
        )
    return out


def _tpu_blocks(n: int, seed: int) -> list[Block]:
    blocks = _dense_blocks(n - 2, seed)
    blocks.append(
        Block(
            kind="attn",
            layers=(
                ("dense", {"tokens": 512, "d_in": 1024, "d_out": 3072}),
                ("attention_prefill", {"B": 2, "S": 512, "H": 8, "Dh": 128, "kv_ratio": 4}),
                ("dense", {"tokens": 512, "d_in": 1024, "d_out": 1024}),
            ),
            collective_bytes=1e7,
        )
    )
    blocks.append(Block(kind="empty", layers=()))
    return blocks


def _ultratrail_blocks(n: int, seed: int) -> list[Block]:
    r = np.random.default_rng(seed)
    return [
        Block(
            kind="conv",
            layers=tuple(
                ("conv1d", {"C": int(r.integers(1, 57)), "K": int(r.integers(1, 57)),
                            "C_w": int(r.integers(3, 257)), "F": 3, "s": 1, "pad": 1})
                for _ in range(int(r.integers(1, 4)))
            ),
        )
        for _ in range(n)
    ]


def _vta_blocks(n: int, seed: int) -> list[Block]:
    r = np.random.default_rng(seed)
    return [
        Block(
            kind="conv_fc",
            layers=(
                ("conv2d", {"C": int(r.integers(1, 257)), "C_h": 28, "C_w": 28,
                            "K": int(r.integers(1, 257)), "F": 3, "s": 1, "pad": 1}),
                ("fully_connected", {"in": int(r.integers(1, 1025)), "out": 384}),
            ),
        )
        for _ in range(n)
    ]


def _xla_blocks(n: int, seed: int) -> list[Block]:
    r = np.random.default_rng(seed)
    return [
        Block(
            kind="dense",
            layers=tuple(
                ("dense", {"tokens": int(r.integers(16, 257)),
                           "d_in": int(r.integers(32, 769)), "d_out": 256})
                for _ in range(2)
            ),
        )
        for _ in range(n)
    ]


def _toy_blocks(n: int, seed: int) -> list[Block]:
    r = np.random.default_rng(seed)
    return [
        Block(
            kind="toy",
            layers=tuple(
                ("toy", {"a": int(r.integers(1, 65)), "b": int(r.integers(1, 33))})
                for _ in range(int(r.integers(1, 4)))
            ),
        )
        for _ in range(n)
    ]


# --------------------------------------------------- frozen scalar references
def _scalar_block_times(platform, blocks) -> np.ndarray:
    """The pre-refactor path: one measure_block call per block."""
    return np.array(
        [
            platform.measure_block(list(b.layers), collective_bytes=b.collective_bytes)
            for b in blocks
        ],
        dtype=np.float64,
    )


def _scalar_fit(platform, estimators, blocks) -> tuple[float, float]:
    """Frozen scalar fusing fit: per-block measure + per-layer predict_one."""
    f_targets, ops = [], []
    for b in blocks:
        t_meas = platform.measure_block(
            list(b.layers), collective_bytes=b.collective_bytes
        )
        t_sum = sum(estimators[lt].predict_one(cfg) for lt, cfg in b.layers)
        f_targets.append(t_sum - t_meas)
        ops.append(block_ops(b))
    A = np.stack([np.asarray(ops), np.ones(len(ops))], axis=1)
    coef, *_ = np.linalg.lstsq(A, np.asarray(f_targets), rcond=None)
    return float(coef[0]), float(coef[1])


def _scalar_simulate(platform, blocks) -> float:
    t = 0.0
    for b in blocks:
        t += platform.measure_block(
            list(b.layers), collective_bytes=b.collective_bytes
        ) * b.repeat
    return t


def _scalar_predict_network(oracle, blocks) -> float:
    """Per-layer predict_one + per-block combine (pre-batching oracle path)."""
    total = 0.0
    for b in blocks:
        times = [oracle.estimators[lt].predict_one(cfg) for lt, cfg in b.layers]
        total += oracle._combine(b, times) * b.repeat
    return total


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(np.asarray(p, dtype=np.float64).tobytes())
    return h.hexdigest()[:16]


class _StubEstimator:
    """Deterministic analytic estimator (predict_one only, like test stubs)."""

    def predict_one(self, cfg) -> float:
        return 1e-6 * float(sum(v for v in cfg.values()))


PLATFORM_CASES = {
    "tpu_v5e": (lambda: TPUv5eSim(knowledge="white"), lambda: _tpu_blocks(40, 3)),
    "tpu_v5e_noise": (
        lambda: TPUv5eSim(knowledge="gray", noise=0.01),
        lambda: _tpu_blocks(40, 3),
    ),
    "ultratrail": (UltraTrailSim, lambda: _ultratrail_blocks(30, 4)),
    "vta": (VTASim, lambda: _vta_blocks(30, 5)),
    "xla_cpu": (
        lambda: XLACPUPlatform(synthetic=True),
        lambda: _xla_blocks(30, 6),
    ),
}

#: frozen goldens: sha256[:16] of (block times, fusing w/c, eval mape/rmspe)
#: measured on the scalar reference path — the columnar engine must reproduce
#: them bit for bit (regenerate deliberately via _make_goldens() below).
GOLDENS = {
    "tpu_v5e": "c3aac302099699a1",
    "tpu_v5e_noise": "d5266a9ec5acfc89",
    "ultratrail": "713dc60677bd6eed",
    "vta": "68f6dce59e3458f3",
    "xla_cpu": "0401dd35c7587dc2",
}


def _scalar_reference_bundle(name: str):
    """(block_times, (w, c), metrics) on the frozen scalar path."""
    make_platform, make_blocks = PLATFORM_CASES[name]
    platform = make_platform()
    blocks = make_blocks()
    times = _scalar_block_times(platform, blocks)
    layer_types = {lt for b in blocks for lt, _ in b.layers}
    estimators = {lt: _StubEstimator() for lt in layer_types}
    w, c = _scalar_fit(platform, estimators, blocks)
    oracle = PerfOracle(estimators=estimators, fusing={})
    networks = [blocks[: max(2, len(blocks) // 3)], blocks[len(blocks) // 3 :]]
    networks = [[b for b in net if b.layers] for net in networks]
    y_true = np.asarray([_scalar_simulate(platform, net) for net in networks])
    y_pred = np.asarray([_scalar_predict_network(oracle, net) for net in networks])
    from repro.core.forest import mape, rmspe

    metrics = (mape(y_true, y_pred), rmspe(y_true, y_pred))
    return platform, blocks, estimators, oracle, networks, times, (w, c), metrics


def _make_goldens() -> dict[str, str]:
    """Regeneration helper (run manually when the corpora change)."""
    out = {}
    for name in PLATFORM_CASES:
        _, _, _, _, _, times, wc, metrics = _scalar_reference_bundle(name)
        out[name] = _digest(times, wc, metrics)
    return out


# --------------------------------------------------------------- round trips
class TestBlockBatchStructure:
    def test_round_trip_deterministic(self):
        blocks = _tpu_blocks(20, 0)
        batch = BlockBatch.from_blocks(blocks)
        assert batch.to_blocks() == blocks  # dataclass eq; int repeat == float ok
        assert len(batch) == 20
        assert batch.n_layers == sum(len(b.layers) for b in blocks)

    def test_payload_round_trip(self):
        batch = BlockBatch.from_blocks(_tpu_blocks(12, 1))
        import json

        payload = json.loads(json.dumps(batch.to_payload()))  # JSON-clean
        assert BlockBatch.from_payload(payload).to_blocks() == batch.to_blocks()

    def test_take_preserves_blocks(self):
        blocks = _tpu_blocks(15, 2)
        batch = BlockBatch.from_blocks(blocks)
        rows = np.array([4, 0, 14, 4])
        assert batch.take(rows).to_blocks() == [blocks[i] for i in rows.tolist()]

    def test_concat(self):
        a, b = _dense_blocks(5, 7), _ultratrail_blocks(4, 8)
        merged = BlockBatch.concat(
            [BlockBatch.from_blocks(a), BlockBatch.from_blocks(b)]
        )
        assert merged.to_blocks() == a + b

    def test_concat_matches_block_object_round_trip(self):
        """Columnar concat vs. rebuilding through Block objects: identical.

        ``concat`` merges the ragged tables directly (no ``to_blocks`` /
        ``from_blocks`` round trip), so every derived field — group layout,
        row indices, memoized fingerprints — must come out exactly as the
        reference construction produces them.
        """
        batches = [
            BlockBatch.from_blocks(_dense_blocks(5, 7)),
            BlockBatch.from_blocks(_toy_blocks(6, 8)),
            BlockBatch.from_blocks([]),
            BlockBatch.from_blocks(_dense_blocks(3, 9)),
            BlockBatch.from_blocks(_ultratrail_blocks(4, 10)),
        ]
        reference = BlockBatch.from_blocks(
            [blk for bb in batches for blk in bb.to_blocks()]
        )
        merged = BlockBatch.concat(batches)
        assert merged.to_blocks() == reference.to_blocks()
        assert merged.kinds == reference.kinds
        assert np.array_equal(merged.block_id, reference.block_id)
        assert np.array_equal(merged.group_of, reference.group_of)
        assert np.array_equal(merged.row_of, reference.row_of)
        assert np.array_equal(merged.repeat, reference.repeat)
        assert np.array_equal(merged.collective_bytes, reference.collective_bytes)
        assert merged.group_types == reference.group_types
        assert len(merged.group_configs) == len(reference.group_configs)
        for g_m, g_r in zip(merged.group_configs, reference.group_configs):
            assert g_m.params == g_r.params
            assert np.array_equal(g_m.values, g_r.values)
        assert merged.fingerprints() == reference.fingerprints()

    def test_concat_stitches_memoized_fingerprints(self):
        parts = [
            BlockBatch.from_blocks(_dense_blocks(4, 11)),
            BlockBatch.from_blocks(_toy_blocks(3, 12)),
        ]
        expected = [fp for bb in parts for fp in bb.fingerprints()]  # memoize
        merged = BlockBatch.concat(parts)
        assert merged._fingerprints is not None  # stitched, not recomputed
        assert merged.fingerprints() == expected

    def test_concat_single_and_empty_inputs(self):
        one = BlockBatch.from_blocks(_toy_blocks(4, 13))
        assert BlockBatch.concat([one]) is one
        empty = BlockBatch.concat([])
        assert len(empty) == 0 and empty.to_blocks() == []
        assert BlockBatch.concat([empty, one]).to_blocks() == one.to_blocks()

    def test_dedup_first_occurrence(self):
        base = _dense_blocks(6, 9)
        # duplicates (same measurement) differing only in kind/repeat collapse
        dupes = [
            Block(kind="other", layers=base[2].layers,
                  collective_bytes=base[2].collective_bytes, repeat=99)
        ]
        batch = BlockBatch.from_blocks(base + dupes + base[:3])
        unique, first_rows, inverse = batch.dedup()
        assert len(unique) == 6
        assert first_rows.tolist() == [0, 1, 2, 3, 4, 5]
        assert inverse.tolist() == [0, 1, 2, 3, 4, 5, 2, 0, 1, 2]
        fps = batch.fingerprints()
        assert [fps[i] for i in first_rows.tolist()] == unique.fingerprints()

    def test_from_blocks_rejects_non_integer(self):
        bad = Block(kind="x", layers=(("dense", {"tokens": 7.5, "d_in": 8, "d_out": 8}),))
        with pytest.raises(ValueError):
            BlockBatch.from_blocks([bad])

    @pytest.mark.parametrize("tokens", [7.5, np.array([8.0, 7.5]), "x"],
                             ids=["scalar", "column", "text"])
    def test_builder_rejects_what_from_blocks_rejects(self, tokens):
        builder = BlockBatchBuilder()
        with pytest.raises(ValueError):
            builder.add_steps([("x", (("dense", {"tokens": tokens, "d_in": 8}),), 0.0, 1.0)], 2)
            builder.build()
        builder = BlockBatchBuilder()  # whole floats are taken, as from_blocks takes them
        builder.add_steps([("x", (("dense", {"tokens": np.array([8.0, 16.0]), "d_in": 8}),),
                            0.0, 1.0)], 2)
        assert builder.build().group_configs[0].values.tolist() == [[8, 8], [16, 8]]

    def test_empty(self):
        batch = BlockBatch.from_blocks([])
        assert len(batch) == 0 and batch.n_layers == 0
        assert batch.to_blocks() == []
        unique, first, inv = batch.dedup()
        assert len(unique) == 0 and first.size == 0 and inv.size == 0

    def test_hypothesis_round_trip(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        cfg_st = st.dictionaries(
            st.sampled_from(["a", "b", "c", "d"]),
            st.integers(min_value=0, max_value=2**40),
            min_size=1,
            max_size=4,
        )
        layer_st = st.tuples(st.sampled_from(["lt1", "lt2", "lt3"]), cfg_st)
        block_st = st.builds(
            Block,
            kind=st.sampled_from(["k1", "k2"]),
            layers=st.lists(layer_st, max_size=4).map(tuple),
            collective_bytes=st.floats(
                min_value=0.0, max_value=1e12, allow_nan=False
            ),
            repeat=st.integers(min_value=1, max_value=8),
        )

        @hyp.given(st.lists(block_st, max_size=12))
        @hyp.settings(deadline=None, max_examples=60)
        def round_trip(blocks):
            batch = BlockBatch.from_blocks(blocks)
            back = batch.to_blocks()
            assert len(back) == len(blocks)
            for orig, rebuilt in zip(blocks, back):
                assert rebuilt.kind == orig.kind
                assert rebuilt.layers == orig.layers
                assert rebuilt.collective_bytes == orig.collective_bytes
                assert rebuilt.repeat == orig.repeat
            # payload survives a JSON cycle too
            import json

            payload = json.loads(json.dumps(batch.to_payload()))
            assert BlockBatch.from_payload(payload).to_blocks() == back

        round_trip()


# ------------------------------------------------------------ backend parity
class TestBackendParity:
    @pytest.mark.parametrize("name", sorted(PLATFORM_CASES))
    def test_columnar_matches_scalar_and_golden(self, name):
        platform, blocks, estimators, oracle, networks, times, wc, metrics = (
            _scalar_reference_bundle(name)
        )
        # batched == scalar, bit for bit
        batched = platform.measure_block_batch(BlockBatch.from_blocks(blocks))
        assert np.array_equal(batched, times)
        # batched fusing fit + evaluation reproduce the scalar reference
        got = fit_fusing_model(platform, estimators, blocks)
        assert (got.w, got.c) == wc
        ev = oracle.evaluate_networks(platform, networks)
        assert (ev["mape"], ev["rmspe"]) == metrics
        # and the whole bundle matches the frozen golden
        assert _digest(times, wc, metrics) == GOLDENS[name]

    def test_base_fallback_matches_scalar(self):
        """Platforms without a columnar override ride the base scalar loop."""
        blocks = _toy_blocks(10, 0)
        base = SteppedSimPlatform()  # no measure_block_batch override
        assert "measure_block_batch" not in type(base).__dict__
        assert np.array_equal(
            base.measure_block_batch(BlockBatch.from_blocks(blocks)),
            _scalar_block_times(base, blocks),
        )

    def test_op_count_batch_matches_scalar_for_all_layer_types(self):
        r = np.random.default_rng(13)
        cases = {
            "dense": {"tokens": (8, 65536), "d_in": (64, 8192), "d_out": (64, 8192)},
            "attention_prefill": {"B": (1, 64), "S": (128, 32768), "H": (1, 64), "Dh": (32, 256)},
            "attention_decode": {"B": (1, 256), "S_kv": (128, 65536), "H": (1, 64), "Dh": (32, 256)},
            "moe_gemm": {"tokens": (64, 65536), "topk": (1, 8), "d_model": (128, 4096), "d_ff": (128, 8192)},
            "ssd_scan": {"B": (1, 64), "S": (128, 32768), "H": (1, 128), "P": (32, 256), "N": (16, 256)},
            "embed": {"tokens": (8, 131072), "d_model": (128, 8192)},
            "conv1d": {"C": (1, 56), "K": (1, 56), "C_w": (3, 256), "F": (2, 9), "s": (1, 3), "pad": (0, 4)},
            "conv2d": {"C": (1, 256), "C_h": (7, 64), "C_w": (7, 64), "K": (1, 256), "F": (1, 5), "s": (1, 2), "pad": (0, 2)},
            "fully_connected": {"in": (1, 1024), "out": (1, 1024)},
        }
        for lt, ranges in cases.items():
            cols = {p: r.integers(lo, hi + 1, 64) for p, (lo, hi) in ranges.items()}
            batch = ConfigBatch.from_columns(cols)
            got = op_count_batch(lt, batch)
            ref = np.array([op_count(lt, cfg) for cfg in batch.to_dicts()])
            assert np.array_equal(got, ref), lt
        # defaulted pad/s come from `get` fallbacks, identically to cfg.get
        partial = ConfigBatch.from_columns(
            {"C": np.array([5, 40]), "K": np.array([8, 16]),
             "C_w": np.array([64, 100]), "F": np.array([3, 5])}
        )
        got = op_count_batch("conv1d", partial)
        ref = np.array([op_count("conv1d", c) for c in partial.to_dicts()])
        assert np.array_equal(got, ref)

    def test_block_ops_batch_matches_scalar(self):
        blocks = _tpu_blocks(25, 14)
        batch = BlockBatch.from_blocks(blocks)
        assert np.array_equal(
            block_ops_batch(batch), np.array([block_ops(b) for b in blocks])
        )

    def test_fit_accepts_block_batch_bitwise(self):
        platform = TPUv5eSim(knowledge="white")
        estimators = {"dense": _StubEstimator()}
        blocks = _dense_blocks(30, 15)
        from_list = fit_fusing_model(platform, estimators, blocks)
        from_batch = fit_fusing_model(
            platform, estimators, BlockBatch.from_blocks(blocks)
        )
        assert (from_batch.w, from_batch.c, from_batch.n_fit) == (
            from_list.w, from_list.c, from_list.n_fit,
        )

    def test_measure_block_many_scalar_fallback_non_integer(self):
        platform = TPUv5eSim(knowledge="white")
        blocks = [
            Block(kind="x", layers=(("dense", {"tokens": 64.5, "d_in": 64, "d_out": 64}),))
        ]
        y = measure_block_many(platform, blocks)
        assert y[0] == platform.measure_block(list(blocks[0].layers), collective_bytes=0.0)


# ---------------------------------------------- golden whole-network pipeline
@pytest.fixture(scope="module")
def tpu_campaign():
    spec = CampaignSpec(
        platform="tpu_v5e",
        layer_types=("dense",),
        n_samples=200,
        seed=0,
        forest_kwargs=FAST_FOREST,
        platform_kwargs={"knowledge": "white"},
    )
    campaign = Campaign(spec)
    campaign.run()
    return campaign


class TestGoldenPipeline:
    """Calibration + evaluation + autotune: batched == scalar, all worker counts."""

    def test_calibration_eval_autotune_bitwise(self, tpu_campaign):
        campaign = tpu_campaign
        raw = campaign.platform.inner
        train = _dense_blocks(60, 1)
        networks = [_dense_blocks(8, 10), _dense_blocks(5, 11)]

        # --- frozen scalar reference (pre-refactor loops on the raw platform)
        ref_w, ref_c = _scalar_fit(raw, campaign.estimators, train)
        ref_truth = [_scalar_simulate(raw, net) for net in networks]

        # --- batched path through the campaign's block cache
        fusing = campaign.calibrate_fusing({"mlp": train})["mlp"]
        assert (fusing.w, fusing.c) == (ref_w, ref_c)

        oracle = PerfOracle(
            estimators=dict(campaign.estimators), fusing={"mlp": fusing}
        )
        ref_pred = [_scalar_predict_network(oracle, net) for net in networks]
        from repro.core.forest import mape, rmspe

        ref_metrics = {
            "mape": mape(np.asarray(ref_truth), np.asarray(ref_pred)),
            "rmspe": rmspe(np.asarray(ref_truth), np.asarray(ref_pred)),
        }
        ev = campaign.evaluate_networks(oracle, networks)
        assert ev == ref_metrics
        assert simulate_networks(campaign.platform, networks) == ref_truth
        assert np.array_equal(
            oracle.predict_networks(networks), np.asarray(ref_pred)
        )

    @pytest.mark.parametrize("workers", [1, 2, 4])
    def test_worker_counts_bitwise_identical(self, tpu_campaign, workers, tmp_path):
        """Same calibration through the runtime at any worker count."""
        spec = tpu_campaign.spec
        campaign = Campaign(spec)
        campaign.estimators = dict(tpu_campaign.estimators)  # skip re-training
        train = _dense_blocks(60, 1)
        fusing = campaign.calibrate_fusing(
            {"mlp": train},
            runtime=RuntimeSpec(
                workers=workers, chunk_size=8,
                journal_path=str(tmp_path / "blocks.jsonl"),
            ),
        )["mlp"]
        serial = tpu_campaign.calibrate_fusing({"mlp": train})["mlp"]
        assert (fusing.w, fusing.c, fusing.n_fit) == (serial.w, serial.c, serial.n_fit)
        stats = campaign.cache.stats()
        assert stats["block_misses"] + stats["block_replayed"] > 0
        assert campaign.last_run_stats["measured"] == stats["block_misses"]

    def test_autotune_matches_scalar_reference(self):
        platform = TPUv5eSim(knowledge="white")
        estimators = {lt: _StubEstimator() for lt in platform.layer_types()}
        oracle = PerfOracle(estimators=estimators)
        from repro.configs import get_config
        from repro.models.config import SHAPES

        cfg = get_config("qwen2-1.5b")
        shape = SHAPES["train_4k"]
        rank = autotune(oracle, cfg, shape, chips=64)
        valid = []
        for c in default_candidates(64):
            if c.dp > max(1, shape.global_batch):
                continue
            if cfg.d_ff and cfg.d_ff % c.tp not in (0,) and cfg.moe_experts == 0:
                continue
            valid.append((c, estimate_candidate(oracle, cfg, shape, c)))
        assert rank == sorted(valid, key=lambda x: x[1])


# ------------------------------------------------------- block cache semantics
class _CountingPlatform(SteppedSimPlatform):
    """Counts how many blocks actually reach the timing model."""

    def __init__(self):
        super().__init__()
        self.blocks_measured = 0

    def measure_block_batch(self, batch):
        # Count at batch level only (the base fallback would re-enter the
        # counting measure_block per block and double-count).
        self.blocks_measured += len(batch)
        mb = super().measure_block
        return np.array(
            [
                mb(list(b.layers), collective_bytes=b.collective_bytes)
                for b in batch.to_blocks()
            ],
            dtype=np.float64,
        )

    def measure_block(self, layers, **kwargs):
        self.blocks_measured += 1
        return super().measure_block(layers, **kwargs)


class TestBlockCacheSemantics:
    def test_in_batch_duplicates_measured_once(self):
        inner = _CountingPlatform()
        cached = CachedPlatform(inner)
        blocks = _toy_blocks(8, 1)
        batch = BlockBatch.from_blocks(blocks + blocks[:4] + blocks)  # dups
        y = cached.measure_block_batch(batch)
        assert inner.blocks_measured == 8  # unique blocks only
        assert cached.cache.block_misses == 8
        assert cached.cache.block_hits == len(batch) - 8
        ref = _scalar_block_times(SteppedSimPlatform(), blocks)
        assert np.array_equal(y, np.concatenate([ref, ref[:4], ref]))

    def test_cross_stage_reuse(self):
        """Calibration, evaluation and autotune share one block pool."""
        inner = _CountingPlatform()
        cached = CachedPlatform(inner)
        blocks = _toy_blocks(10, 2)
        measure_block_many(cached, blocks)
        assert inner.blocks_measured == 10
        simulate_networks(cached, [blocks[:5], blocks[5:]])  # all cached
        assert inner.blocks_measured == 10
        # scalar entry point shares the same keys
        b = blocks[0]
        cached.measure_block(list(b.layers), collective_bytes=b.collective_bytes)
        assert inner.blocks_measured == 10

    def test_kind_and_repeat_do_not_split_cache_entries(self):
        inner = _CountingPlatform()
        cached = CachedPlatform(inner)
        b = _toy_blocks(1, 3)[0]
        twin = Block(kind="different", layers=b.layers,
                     collective_bytes=b.collective_bytes, repeat=7)
        measure_block_many(cached, [b, twin])
        assert inner.blocks_measured == 1

    def test_collective_bytes_split_cache_entries(self):
        tpu = TPUv5eSim(knowledge="white")
        cached = CachedPlatform(tpu)
        b = _dense_blocks(1, 4, collectives=False)[0]
        heavy = Block(kind=b.kind, layers=b.layers, collective_bytes=1e12)
        y = measure_block_many(cached, [b, heavy])
        assert cached.cache.block_misses == 2
        assert y[1] > y[0]

    def test_unknown_kwargs_bypass_cache(self):
        class KwargPlatform(SteppedSimPlatform):
            def measure_block(self, layers, scale=1.0, **kwargs):
                return super().measure_block(layers, **kwargs) * scale

        cached = CachedPlatform(KwargPlatform())
        layers = [("toy", {"a": 4, "b": 4})]
        t1 = cached.measure_block(layers, scale=2.0)
        t2 = cached.measure_block(layers, scale=3.0)
        assert t2 == pytest.approx(t1 * 1.5)
        assert cached.cache.block_misses == 0  # never cached

    def test_save_load_round_trips_block_times(self, tmp_path):
        cached = CachedPlatform(SteppedSimPlatform())
        blocks = _toy_blocks(6, 5)
        y = measure_block_many(cached, blocks)
        path = str(tmp_path / "cache.json")
        cached.cache.save(path)
        reloaded = MeasurementCache.load(path)
        assert reloaded.n_unique_blocks == cached.cache.n_unique_blocks
        warm = CachedPlatform(_CountingPlatform(), cache=reloaded)
        y2 = measure_block_many(warm, blocks)
        assert warm.inner.blocks_measured == 0
        assert np.array_equal(y, y2)


# ------------------------------------------------------------- journal resume
class _CrashingBlockTPU(TPUv5eSim):
    """Fails once a block-measurement budget is exhausted (mid-run kill)."""

    def __init__(self, fail_after_blocks: int) -> None:
        super().__init__(knowledge="white")
        self._remaining = fail_after_blocks

    def measure_block_batch(self, batch):
        if self._remaining < len(batch):
            raise RuntimeError("injected crash")
        self._remaining -= len(batch)
        return super().measure_block_batch(batch)


class TestBlockJournalResume:
    def _campaign(self, platform=None):
        spec = CampaignSpec(
            platform="tpu_v5e",
            layer_types=("dense",),
            platform_kwargs={"knowledge": "white"},
        )
        campaign = Campaign(spec, platform=platform)
        campaign.estimators = {"dense": _StubEstimator()}
        return campaign

    def test_mid_calibration_crash_resumes_with_zero_duplicates(self, tmp_path):
        journal = str(tmp_path / "measurements.jsonl")
        train = _dense_blocks(40, 7)

        crashed = self._campaign(_CrashingBlockTPU(fail_after_blocks=20))
        with pytest.raises(MeasurementError):
            crashed.calibrate_fusing(
                {"mlp": train},
                runtime=RuntimeSpec(
                    workers=1, chunk_size=8, max_retries=0, journal_path=journal
                ),
            )
        journaled = sum(
            len(r["seconds"])
            for r in MeasurementJournal(journal).iter_records()
        )
        assert 0 < journaled <= 20

        resumed = self._campaign()
        fusing = resumed.calibrate_fusing(
            {"mlp": train},
            runtime=RuntimeSpec(workers=1, chunk_size=8, journal_path=journal),
        )["mlp"]
        control = self._campaign()
        control_fusing = control.calibrate_fusing({"mlp": train})["mlp"]
        assert (fusing.w, fusing.c) == (control_fusing.w, control_fusing.c)
        # zero duplicate measurements: replayed + new == one full run's misses
        assert resumed.cache.block_replayed == journaled
        assert (
            resumed.cache.block_misses
            == control.cache.block_misses - journaled
        )

    def test_pool_journal_resume_measures_no_block_again(self, tmp_path):
        """A calibration journaled by a 2-worker pool resumes serially from
        its journal: every block is replayed and the fit is unchanged."""
        journal = str(tmp_path / "measurements.jsonl")
        train = _dense_blocks(40, 7)
        pool = self._campaign()
        pool_fusing = pool.calibrate_fusing(
            {"mlp": train},
            runtime=RuntimeSpec(workers=2, chunk_size=8, journal_path=journal),
        )["mlp"]
        resumed = self._campaign()
        resumed_fusing = resumed.calibrate_fusing(
            {"mlp": train}, runtime=RuntimeSpec(workers=1, journal_path=journal)
        )["mlp"]
        assert resumed.cache.block_misses == 0
        assert resumed.cache.block_replayed == pool.cache.block_misses > 0
        assert (resumed_fusing.w, resumed_fusing.c) == (pool_fusing.w, pool_fusing.c)

    def test_block_replay_is_idempotent(self, tmp_path):
        journal_path = str(tmp_path / "j.jsonl")
        batch = BlockBatch.from_blocks(_toy_blocks(5, 8))
        y = SteppedSimPlatform().measure_block_batch(batch)
        with MeasurementJournal(journal_path) as journal:
            journal.append_block_chunk("stepped_sim", batch, y)
        cache = MeasurementCache()
        j = MeasurementJournal(journal_path)
        first = j.replay_into(cache)
        again = j.replay_into(cache)
        assert first["new"] == first["rows"] == len(batch)
        assert again["new"] == 0
        times, miss_rows, _ = cache.lookup_blocks("stepped_sim", batch)
        assert miss_rows.size == 0
        assert np.array_equal(times, y)

    def test_corrupt_block_record_skipped(self, tmp_path):
        journal_path = str(tmp_path / "j.jsonl")
        batch = BlockBatch.from_blocks(_toy_blocks(3, 9))
        y = SteppedSimPlatform().measure_block_batch(batch)
        with MeasurementJournal(journal_path) as journal:
            journal.append_block_chunk("stepped_sim", batch, y)
        with open(journal_path, "a") as f:
            f.write('{"v": 1, "kind": "blocks", "platform": "p"}\n')  # missing keys
            f.write('{"v": 1, "kind": "blocks", "platform": "p", '
                    '"blocks": {"kinds": ["x"]}, "seconds": [1.0]}\n')  # malformed
        cache = MeasurementCache()
        with pytest.warns(JournalCorruptionWarning):
            replay = MeasurementJournal(journal_path).replay_into(cache)
        assert replay == {"records": 1, "rows": 3, "new": 3}

    def test_mixed_config_and_block_records_share_one_journal(self, tmp_path):
        journal_path = str(tmp_path / "j.jsonl")
        cfg_batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 5), "b": np.arange(1, 5)}
        )
        block_batch = BlockBatch.from_blocks(_toy_blocks(4, 10))
        platform = SteppedSimPlatform()
        with MeasurementJournal(journal_path) as journal:
            journal.append_chunk(
                "stepped_sim", "toy", cfg_batch,
                platform.measure_batch("toy", cfg_batch),
            )
            journal.append_block_chunk(
                "stepped_sim", block_batch,
                platform.measure_block_batch(block_batch),
            )
        cache = MeasurementCache()
        replay = MeasurementJournal(journal_path).replay_into(cache)
        assert replay["records"] == 2 and replay["rows"] == 8
        assert cache.n_unique == 4 and cache.n_unique_blocks == 4


# ------------------------------------------------------------ adaptive chunks
class TestAdaptiveChunking:
    def test_defaults_before_any_cost_data(self):
        scheduler = MeasurementScheduler(SerialExecutor(SteppedSimPlatform()))
        assert scheduler.effective_chunk_size() == DEFAULT_CHUNK_SIZE

    def test_explicit_chunk_size_wins(self):
        scheduler = MeasurementScheduler(
            SerialExecutor(SteppedSimPlatform()), chunk_size=7
        )
        scheduler.stats.measured = 1000
        scheduler.stats.measure_seconds = 1000.0
        assert scheduler.effective_chunk_size() == 7

    def test_adapts_toward_target_wall_time(self):
        platform = SteppedSimPlatform(delay_s=0.01)
        scheduler = MeasurementScheduler(SerialExecutor(platform))
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 33), "b": (np.arange(1, 33) % 32) + 1}
        )
        scheduler.measure_batch("stepped_sim", "toy", batch)
        # ~10 ms per config -> ~100 configs for a ~1 s chunk
        size = scheduler.effective_chunk_size()
        assert 40 <= size <= 250, size

    def test_adaptive_and_explicit_chunking_agree_bitwise(self):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 101), "b": (np.arange(1, 101) % 32) + 1}
        )
        blocks = BlockBatch.from_blocks(_toy_blocks(30, 11))
        ref = MeasurementScheduler(SerialExecutor(platform), chunk_size=5)
        adaptive = MeasurementScheduler(SerialExecutor(platform))
        assert np.array_equal(
            adaptive.measure_batch("stepped_sim", "toy", batch),
            ref.measure_batch("stepped_sim", "toy", batch),
        )
        assert np.array_equal(
            adaptive.measure_block_batch("stepped_sim", blocks),
            ref.measure_block_batch("stepped_sim", blocks),
        )

    def test_blocks_are_chunked_for_dispatch_and_journal(self, tmp_path):
        journal = MeasurementJournal(str(tmp_path / "j.jsonl"))
        scheduler = MeasurementScheduler(
            SerialExecutor(SteppedSimPlatform()), journal=journal, chunk_size=4
        )
        batch = BlockBatch.from_blocks(_toy_blocks(10, 12))
        scheduler.measure_block_batch("stepped_sim", batch)
        journal.close()
        records = list(MeasurementJournal(journal.path).iter_records())
        assert len(records) == 3  # ceil(10 / 4)
        assert [len(r["seconds"]) for r in records] == [4, 4, 2]

    def test_path_costs_do_not_cross_contaminate(self):
        """Cheap config measurements must not size block chunks (and vice
        versa): a block costs orders of magnitude more than one config."""
        platform = SteppedSimPlatform()
        scheduler = MeasurementScheduler(SerialExecutor(platform))
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 65), "b": (np.arange(1, 65) % 32) + 1}
        )
        scheduler.measure_batch("stepped_sim", "toy", batch)
        # fake an expensive block history alongside the cheap config one
        scheduler._path_costs["blocks"] = [10, 20.0]  # 2 s per block
        assert scheduler.effective_chunk_size("blocks") == 1
        # and the cheap config history still yields a large config chunk
        assert scheduler.effective_chunk_size("configs") > 100

    def test_unfingerprintable_kwargs_values_bypass_cache(self):
        """Non-int-coercible config values (None, tuples) must fall back to
        the inner platform like the pre-cache path, not raise TypeError."""

        class WeirdPlatform(SteppedSimPlatform):
            def measure_block(self, layers, **kwargs):
                return 42e-6

        cached = CachedPlatform(WeirdPlatform())
        layers = [("toy", {"a": 4, "shape": (3, 3)}), ("toy", {"a": 4, "pad": None})]
        assert cached.measure_block(layers) == 42e-6
        assert cached.cache.block_misses == 0  # bypassed, never cached

    def test_runtime_spec_chunk_size_override(self):
        from repro.runtime import MeasurementRuntime, RuntimeSpec

        with MeasurementRuntime(
            RuntimeSpec(workers=1, chunk_size=13), SteppedSimPlatform()
        ) as runtime:
            assert runtime.scheduler.chunk_size == 13
        with MeasurementRuntime(RuntimeSpec(workers=1), SteppedSimPlatform()) as runtime:
            assert runtime.scheduler.chunk_size is None
            assert runtime.scheduler.effective_chunk_size() == DEFAULT_CHUNK_SIZE
