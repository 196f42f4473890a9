"""Models built by a layer pattern (NemotronH): the configuration, its
decomposition into blocks, the one-token Mamba-2 decode and the expert
matrices on the TPU v5e platform, with the older decompositions and the
platform's older times frozen as they were before patterns existed."""

import dataclasses as dc
import hashlib

import numpy as np
import pytest

from repro.accelerators import TPUv5eSim
from repro.configs import ARCHS, ESTIMATED, get_config
from repro.core.advisor import autotune, candidates_block_batch, default_candidates
from repro.core.blocks import op_count
from repro.core.features import derived_features
from repro.core.batch import BlockBatch
from repro.core.network import decompose, decompose_batch, decompose_steps
from repro.models.config import SHAPES, InputShape, ModelConfig, reduced
from repro.obs import metrics as obs_metrics

NEMOTRON = "nemotron-3-nano-30b-a3b"
TRAIN = InputShape("train_8k", 8192, 512, "train")
DECODE = InputShape("decode_128k", 131072, 64, "decode")
MESHES = ((1, 1), (4, 2), (8, 4), (16, 16), (2, 64))

#: sha256[:16] of the decompositions (block kinds, collectives, repeats and
#: every layer's config in order) and of the platform's block and layer times
#: over MESHES, computed before layer patterns and ``ssd_decode`` existed
FROZEN = {
    "olmoe-1b-7b": (("train_4k", "prefill_32k", "decode_32k"),
                    "1b55176ea1300061", "5b9d06dc0778591e"),
    "zamba2-2.7b": (("train_4k", "prefill_32k"), "410063c959b6e947", "c828459d996298d6"),
    "mamba2-780m": (("train_4k", "prefill_32k"), "9b898837a6d53940", "a256243974600fa6"),
}


def _blocks(arch, shape_names):
    cfg = get_config(arch)
    for name in shape_names:
        for dp, tp in MESHES:
            yield from decompose(cfg, SHAPES[name], dp, tp)


@pytest.mark.parametrize("arch", sorted(FROZEN))
def test_older_decompositions_and_times_unchanged(arch):
    shapes, plan_hex, times_hex = FROZEN[arch]
    h = hashlib.sha256()
    for b in _blocks(arch, shapes):
        h.update(repr((b.kind, b.collective_bytes, b.repeat,
                       [(lt, list(c.items())) for lt, c in b.layers])).encode())
    assert h.hexdigest()[:16] == plan_hex
    tpu = TPUv5eSim(knowledge="white")
    times = []
    for b in _blocks(arch, shapes):
        times.append(tpu.measure_block(list(b.layers), collective_bytes=b.collective_bytes))
        times.extend(tpu.measure(lt, c) for lt, c in b.layers)
    digest = hashlib.sha256(np.asarray(times, dtype=np.float64).tobytes()).hexdigest()[:16]
    assert digest == times_hex


def test_nemotron_is_registered_beside_the_zoo():
    cfg = get_config(NEMOTRON)
    assert NEMOTRON in ESTIMATED and NEMOTRON not in ARCHS
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (52, 2688, 131072)
    assert cfg.layer_counts() == {"M": 23, "E": 23, "*": 6}
    # NemotronH's d_inner: heads x head dim, not expand x d_model
    assert (cfg.ssm_heads, cfg.d_inner, cfg.ssm_expand * cfg.d_model) == (64, 4096, 5376)
    assert 30e9 < cfg.param_count() < 33e9
    assert 3e9 < cfg.active_param_count() < 4e9


def test_a_pattern_must_match_the_depth():
    with pytest.raises(ValueError, match="layer_pattern"):
        ModelConfig(name="x", family="hybrid", n_layers=3, d_model=64, n_heads=2,
                    n_kv_heads=1, d_ff=64, vocab=64, layer_pattern="ME")
    with pytest.raises(ValueError, match="layer_pattern"):
        ModelConfig(name="x", family="hybrid", n_layers=2, d_model=64, n_heads=2,
                    n_kv_heads=1, d_ff=64, vocab=64, layer_pattern="MX")


def test_reduced_keeps_one_layer_of_each_kind():
    cfg = reduced(get_config(NEMOTRON))
    assert cfg.layer_pattern == "ME*" and cfg.n_layers == 3
    assert cfg.d_inner == cfg.ssm_n_heads * cfg.ssm_headdim
    assert cfg.mlp == "relu2" and cfg.moe_shared_d_ff and cfg.ssm_groups > 1
    # a configuration without a pattern keeps the reduction it had
    assert reduced(get_config("zamba2-2.7b")).layer_pattern == ""
    assert reduced(get_config("zamba2-2.7b")).n_layers == 12


@pytest.mark.parametrize("shape", [TRAIN, DECODE], ids=lambda s: s.name)
@pytest.mark.parametrize("dp,tp", [(64, 1), (16, 4), (8, 32)])
def test_pattern_gives_each_kind_its_count(shape, dp, tp):
    blocks = decompose(get_config(NEMOTRON), shape, dp, tp)
    rep = 3.0 if shape.kind == "train" else 1.0
    assert [(b.kind, b.repeat) for b in blocks] == [
        ("embed", rep), ("ssd", 23 * rep), ("moe", 23 * rep), ("attn", 6 * rep), ("mlp", rep)]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_shape_decomposes_into_measurable_blocks(shape):
    from repro.core.network import simulate_network

    blocks = decompose(get_config(NEMOTRON), SHAPES[shape], dp=16, tp=16)
    t = simulate_network(TPUv5eSim(knowledge="white"), blocks)
    assert np.isfinite(t) and t > 0


@pytest.mark.parametrize("arch", [NEMOTRON, "zamba2-2.7b", "mamba2-780m"])
def test_decode_advances_the_state_and_never_scans(arch):
    cfg = get_config(arch)
    for dp, tp in MESHES:
        decode = [(lt, c) for b in decompose(cfg, SHAPES["decode_32k"], dp, tp)
                  for lt, c in b.layers]
        assert not any(lt == "ssd_scan" for lt, _ in decode)
        mixers = [c for lt, c in decode if lt == "ssd_decode"]
        assert mixers and all("S" not in c for c in mixers)
        train = [c for b in decompose(cfg, SHAPES["train_4k"], dp, tp)
                 for lt, c in b.layers if lt == "ssd_scan"]
        assert train and all(c["S"] == 4096 for c in train)


@pytest.mark.parametrize("tp", [1, 2, 8, 16, 64])
def test_mamba_block_holds_this_chips_share(tp):
    cfg = get_config(NEMOTRON)
    ssd = next(b for b in decompose(cfg, DECODE, 1, tp) if b.kind == "ssd")
    (_, proj_in), (lt, mixer), (_, proj_out) = ssd.layers
    di, h, g = 4096 // tp, max(1, 64 // tp), max(1, 8 // tp)
    assert lt == "ssd_decode"
    assert mixer == {"B": 64, "H": h, "P": 64, "N": 128, "G": g}
    assert proj_in == {"tokens": 64, "d_in": 2688, "d_out": 2 * di + 2 * g * 128 + h}
    assert proj_out == {"tokens": 64, "d_in": di, "d_out": 2688}


@pytest.mark.parametrize("tp", [1, 4, 256])
def test_expert_block_is_router_two_matrix_experts_and_shared_expert(tp):
    cfg = get_config(NEMOTRON)
    moe = next(b for b in decompose(cfg, TRAIN, 512 // tp, tp) if b.kind == "moe")
    t = 8192 * tp  # dp = 512 / tp leaves tp sequences here
    assert [lt for lt, _ in moe.layers] == ["dense", "moe_gemm", "dense", "dense"]
    router, experts, shared_in, shared_out = (c for _, c in moe.layers)
    assert router == {"tokens": t, "d_in": 2688, "d_out": 128}
    assert experts == {"tokens": max(1, t // tp), "d_model": 2688, "d_ff": 1856,
                       "E": max(1, 128 // tp), "topk": 6, "mats": 2}
    assert shared_in == {"tokens": t, "d_in": 2688, "d_out": max(1, 3712 // tp)}
    assert shared_out == {"tokens": t, "d_in": max(1, 3712 // tp), "d_out": 2688}
    # a gated model's experts keep the platform's default of three matrices
    olmoe = next(b for b in decompose(get_config("olmoe-1b-7b"), TRAIN, 64, tp)
                 if b.kind == "moe")
    assert "mats" not in olmoe.layers[1][1] and len(olmoe.layers) == 2


def test_attention_keeps_gqa_ratio_under_the_head_policy():
    attn = next(b for b in decompose(get_config(NEMOTRON), DECODE, 64, 1) if b.kind == "attn")
    assert attn.layers[1] == ("attention_decode",
                              {"B": 1, "S_kv": 131072, "H": 32, "Dh": 128, "kv_ratio": 16})
    assert attn.layers[0][1]["d_out"] == (32 + 2 * 2) * 128


def _step_lists():
    """A list whose kinds alternate (a run a step), a list of two runs, and
    each search's full candidate list: the micro-step of every
    ``default_candidates`` layout of 64, 256 and 1024 chips."""
    meshes = ((64, 1), (16, 4), (1, 64), (64, 1))
    yield "alternating", [(shape, dp, tp) for dp, tp in meshes for shape in (TRAIN, DECODE)]
    yield "two runs", [(shape, dp, tp) for shape in (TRAIN, DECODE) for dp, tp in meshes]
    for chips in (64, 256, 1024):
        for shape in (TRAIN, DECODE):
            yield f"search {shape.name} {chips}", (shape, default_candidates(chips))


def _micro_steps(shape, cands):
    return [(dc.replace(shape, global_batch=max(1, shape.global_batch // c.microbatches)),
             c.dp, c.tp) for c in cands]


@pytest.mark.parametrize("arch", ARCHS + ESTIMATED)
def test_many_steps_in_one_batch_equal_the_concatenated_steps(arch):
    cfg = get_config(arch)
    for name, steps in _step_lists():
        if name.startswith("search"):
            # the search's own call, and the same micro-steps given as steps
            got, step_of = candidates_block_batch(cfg, *steps)
            steps = _micro_steps(*steps)
            again, again_of = decompose_steps(cfg, steps)
            assert again.fingerprints() == got.fingerprints(), name
            assert np.array_equal(again_of, step_of), name
        else:
            got, step_of = decompose_steps(cfg, steps)
        parts = [decompose_batch(cfg, *s) for s in steps]
        ref = BlockBatch.concat(parts)
        assert np.array_equal(step_of, np.repeat(np.arange(len(parts)), [len(p) for p in parts]))
        assert step_of.dtype == np.int64, name
        assert got.kinds == ref.kinds and got.group_types == ref.group_types, name
        for a in ("collective_bytes", "repeat", "block_id", "group_of", "row_of"):
            assert np.array_equal(getattr(got, a), getattr(ref, a)), (name, a)
        for a, b in zip(got.group_configs, ref.group_configs):
            assert a.params == b.params and np.array_equal(a.values, b.values), name
        assert got.fingerprints() == ref.fingerprints(), name


class _Counted:
    """Reads the decomposition's counters around a block of code."""

    def __enter__(self):
        self.start = self._read()
        return self

    def __exit__(self, *exc):
        end = self._read()
        self.passes, self.steps = (e - s for e, s in zip(end, self.start))

    @staticmethod
    def _read():
        counters = obs_metrics().snapshot()["counters"]
        return (counters.get("network.decompose.passes", 0),
                counters.get("network.decompose.steps", 0))


class _FlatOracle:
    """Scores every network 1 in one batched call, as the jax path batches."""

    def predict_network_batch(self, batch, net_id, n_nets):
        return np.ones(n_nets)


@pytest.mark.parametrize("arch", [NEMOTRON, "olmoe-1b-7b"])
def test_one_search_evaluates_the_plan_once(arch):
    cfg = get_config(arch)
    with _Counted() as counted:
        ranking = autotune(_FlatOracle(), cfg, TRAIN, default_candidates(256))
    chosen = sum(1 for _, score in ranking if np.isfinite(score))
    assert chosen > 20
    assert (counted.passes, counted.steps) == (1, chosen)


def test_each_run_of_one_kind_is_one_pass():
    cfg = get_config(NEMOTRON)
    kinds = (TRAIN, TRAIN, DECODE, DECODE, DECODE, TRAIN, DECODE)
    with _Counted() as counted:
        decompose_steps(cfg, [(shape, 16, 4) for shape in kinds])
    assert (counted.passes, counted.steps) == (4, len(kinds))
    with _Counted() as counted:
        decompose(cfg, DECODE, 16, 4)
    assert (counted.passes, counted.steps) == (1, 1)


# ------------------------------------------------------------------ platform
def test_ssd_decode_reads_and_writes_the_state_once():
    tpu = TPUv5eSim(knowledge="white")
    cfg = {"B": 64, "H": 64, "P": 64, "N": 128, "G": 8}
    flop_s, mem_s = tpu._terms("ssd_decode", cfg)
    state = 64 * 64 * 64 * 128
    io = 64 * (64 * 64 * 2 + 2 * 8 * 128 + 64)
    assert mem_s == 2.0 * (2 * state + io) / tpu.chip.hbm_bandwidth
    assert flop_s == 5.0 * state / tpu.chip.peak_bf16_flops
    assert mem_s > 100 * flop_s  # memory bound
    # a one-token scan was charged a whole 128-token chunk of compute and
    # never the state: the decode layer is the cheaper, honest price
    scan_s = tpu._terms("ssd_scan", {"B": 64, "S": 1, "H": 64, "P": 64, "N": 128, "G": 8})
    assert scan_s[0] > 50 * flop_s
    # groups widen B and C, in the decode and in the scan
    assert tpu._terms("ssd_decode", dict(cfg, G=1))[1] < mem_s
    assert tpu._terms("ssd_scan", {"B": 1, "S": 512, "H": 8, "P": 64, "N": 128})[1] < (
        tpu._terms("ssd_scan", {"B": 1, "S": 512, "H": 8, "P": 64, "N": 128, "G": 8})[1])


def test_expert_matrices_are_a_fixed_key():
    tpu = TPUv5eSim(knowledge="white")
    cfg = {"tokens": 4096, "d_model": 2048, "d_ff": 1024, "E": 64, "topk": 8}
    assert tpu.measure("moe_gemm", cfg) == tpu.measure("moe_gemm", dict(cfg, mats=3))
    two = tpu._terms("moe_gemm", dict(cfg, mats=2))
    three = tpu._terms("moe_gemm", cfg)
    assert two[0] == pytest.approx(three[0] * 2 / 3, rel=1e-15)
    assert tpu.param_space("moe_gemm").fixed["mats"] == 3
    two_mats = TPUv5eSim(moe_experts=128, moe_topk=6, moe_mats=2)
    assert two_mats.param_space("moe_gemm").fixed == {"E": 128, "topk": 6, "mats": 2}
    assert "mats=2" in two_mats.cache_key()
    assert two_mats.spawn_spec()[1]["moe_mats"] == 2


@pytest.mark.parametrize("layer_type,cfg", [
    ("ssd_decode", {"B": 8, "H": 16, "P": 64, "N": 128, "G": 2}),
    ("moe_gemm", {"tokens": 512, "d_model": 256, "d_ff": 128, "E": 8, "topk": 2, "mats": 2}),
])
def test_features_and_op_counts_follow_the_layer(layer_type, cfg):
    f = derived_features(layer_type, cfg)
    if layer_type == "ssd_decode":
        state = 8 * 16 * 64 * 128
        assert f == {"macs": 2 * state, "bytes": 2 * state + 8 * (2 * 16 * 64 + 2 * 2 * 128)}
        assert op_count(layer_type, cfg) == 4.0 * state
    else:
        assert f["macs"] == 2 * 512 * 2 * 256 * 128 and f["weights"] == 2 * 8 * 256 * 128
        assert op_count(layer_type, cfg) == 2.0 * 2 * 512 * 2 * 256 * 128
