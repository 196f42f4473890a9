"""Multi-head latent attention (DeepSeek-V3): the mathematics of its two
forms against a plain ``jax.numpy`` MLA, the ``mla_prefill`` and
``mla_decode`` layer types of the TPU v5e platform in their scalar,
columnar and jitted forms, and DeepSeek-V3's decomposition into blocks."""

import dataclasses as dc
import json
from pathlib import Path

import numpy as np
import pytest

from repro.accelerators import TPUv5eSim
from repro.configs import ARCHS, ESTIMATED, get_config
from repro.core.advisor import autotune, default_candidates
from repro.core.batch import BlockBatch, ConfigBatch
from repro.core.blocks import op_count, op_count_batch
from repro.core.features import MLA_FIXED, derived_features, derived_features_batch
from repro.core.network import decompose, decompose_batch, decompose_steps
from repro.models.config import InputShape, reduced

DEEPSEEK = "deepseek-v3"
CATALOG = Path(__file__).resolve().parents[1] / "bench" / "configs" / "deepseek-v3.v5e.json"
TRAIN = InputShape("train_4k", 4096, 1024, "train")
PREFILL = InputShape("prefill_32k", 32768, 64, "prefill")
DECODE = InputShape("decode_128k", 131072, 256, "decode")


# --------------------------------------------------------- plain jnp MLA
class Einsum:
    """``jnp.einsum`` that adds up the FLOPs of each contraction it runs:
    two for every point of the product of all its index ranges."""

    def __init__(self):
        self.flops = 0

    def __call__(self, spec, *ops):
        import jax.numpy as jnp

        sizes = {}
        for names, op in zip(spec.split("->")[0].split(","), ops):
            sizes.update(zip(names, op.shape))
        self.flops += 2 * int(np.prod(list(sizes.values())))
        return jnp.einsum(spec, *ops)


def _weights(rng, d, h, q_lora, kv_lora, nope, rope, dv):
    def w(*shape):
        return (rng.standard_normal(shape) / np.sqrt(shape[0])).astype(np.float32)

    return {"dq": w(d, q_lora), "uq": w(q_lora, h, nope + rope), "dkv": w(d, kv_lora + rope),
            "uk": w(kv_lora, h, nope), "uv": w(kv_lora, h, dv)}


def _rope(x, pos):
    """Rotary embedding of the last axis, its two halves as (re, im)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    angle = pos[:, None] / 10000.0 ** (jnp.arange(half) / half)
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2) + angle.shape[1:])
    re, im = x[..., :half], x[..., half:]
    return jnp.concatenate([re * jnp.cos(angle) - im * jnp.sin(angle),
                            re * jnp.sin(angle) + im * jnp.cos(angle)], axis=-1)


def _latents(x, w, nope):
    """Per token: the query of every head (nope then RoPE dims), the cached
    latent c_KV and the RoPE key that all heads share."""
    import jax.numpy as jnp

    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    q = jnp.einsum("tc,chk->thk", x @ w["dq"], w["uq"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos)], axis=-1)
    kv = x @ w["dkv"]
    lora = w["uk"].shape[0]
    return q, kv[:, :lora], _rope(kv[:, lora:], pos)


def _decompressed(c_kv, k_rope, w):
    """kv_b: each head's k (non-RoPE dims from the latent, the shared RoPE
    key) and v."""
    import jax.numpy as jnp

    k_nope = jnp.einsum("tc,chn->thn", c_kv, w["uk"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None, :], k_nope.shape[:2] + k_rope.shape[-1:])], axis=-1)
    return k, jnp.einsum("tc,chv->thv", c_kv, w["uv"])


def causal_attention(q, k, v, ein):
    """The unabsorbed core (``mla_prefill``): softmax(q k^T / sqrt(Dqk)) v
    under a causal mask, every head its own k and v."""
    import jax
    import jax.numpy as jnp

    t = q.shape[0]
    scores = ein("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1]).astype(np.float32)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    return ein("hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), v)


def absorbed_decode(q, c_kv, k_rope, w, nope, ein):
    """The absorbed core (``mla_decode``) of one token's query over the
    cache: each head's query mapped into latent space by W_UK, scored against
    every cached latent and RoPE key, the weighted latents summed and mapped
    out by W_UV."""
    import jax

    q_lat = ein("hn,chn->hc", q[:, :nope], w["uk"])
    scores = (ein("hc,tc->ht", q_lat, c_kv) + ein("hr,tr->ht", q[:, nope:], k_rope)) / np.sqrt(
        q.shape[-1]).astype(np.float32)
    lat = ein("ht,tc->hc", jax.nn.softmax(scores, axis=-1), c_kv)
    return ein("hc,chv->hv", lat, w["uv"])


SMALL = {"d": 64, "h": 4, "q_lora": 24, "kv_lora": 16, "nope": 16, "rope": 8, "dv": 16}


def _small_case(seed, dtype=np.float32):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = _weights(rng, **SMALL)
    x = rng.standard_normal((32, SMALL["d"])).astype(np.float32)
    w = {k: jnp.asarray(v, dtype) for k, v in w.items()}
    return jnp.asarray(x, dtype), w


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_absorbed_decode_equals_the_unabsorbed_last_row(seed):
    import jax
    import jax.numpy as jnp

    nope = SMALL["nope"]
    with jax.default_matmul_precision("highest"):
        x, w = _small_case(seed)
        q, c_kv, k_rope = _latents(x, w, nope)
        k, v = _decompressed(c_kv, k_rope, w)
        full = np.asarray(causal_attention(q, k, v, jnp.einsum))
        last = np.asarray(absorbed_decode(q[-1], c_kv, k_rope, w, nope, jnp.einsum))
        # the same sums in another association order, (q W_UK^T) c against
        # q (W_UK c): in float32 the two differ by a few units in the last
        # place of each of 16-32 terms, ~1e-6 of the row's scale; 1e-5 leaves
        # that room, and a bfloat16 computation misses it by far (below)
        scale = np.max(np.abs(full[-1]))
        np.testing.assert_allclose(last, full[-1], rtol=1e-5, atol=1e-5 * scale)
        # the bound bites: the absorbed form in bfloat16 is outside it
        xb, wb = _small_case(seed, jnp.bfloat16)
        qb, cb, kb = _latents(xb, wb, nope)
        low = np.asarray(absorbed_decode(qb[-1], cb, kb, wb, nope, jnp.einsum), np.float32)
        assert np.max(np.abs(low - full[-1])) > 1e-5 * scale


def test_simulated_flops_are_the_matmuls_of_each_form():
    """At sizes that need no padding (Dqk = 96 + 32 and Dv = 128 whole MXU
    tiles, S a multiple of 128, heads a multiple of 8) the platform's FLOPs
    are those of the einsums above: the causal core half of its two square
    products, the absorbed core all five of its products."""
    import jax

    tpu = TPUv5eSim(knowledge="white")
    peak = tpu.chip.peak_bf16_flops
    dims = {"d": 64, "h": 8, "q_lora": 32, "kv_lora": 64, "nope": 96, "rope": 32, "dv": 128}
    rng = np.random.default_rng(5)
    w = _weights(rng, **dims)
    s = 256
    with jax.default_matmul_precision("highest"):
        q, c_kv, k_rope = _latents(rng.standard_normal((s, 64)).astype(np.float32), w, 96)
        k, v = _decompressed(c_kv, k_rope, w)
        core = Einsum()
        causal_attention(q, k, v, core)
        prefill = {"B": 1, "S": s, "H": 8, "nope": 96, "rope": 32, "dv": 128}
        assert tpu._terms("mla_prefill", prefill)[0] == core.flops / 2 / peak
        assert op_count("mla_prefill", prefill) == core.flops / 2
        core = Einsum()
        for b in range(2):  # two sequences, each its own cache
            absorbed_decode(q[-1 - b], c_kv, k_rope, w, 96, core)
        decode = {"B": 2, "S_kv": s, "H": 8, "nope": 96, "rope": 32, "dv": 128, "kv_lora": 64}
        assert tpu._terms("mla_decode", decode)[0] == core.flops / peak
        assert op_count("mla_decode", decode) == core.flops


# ------------------------------------------------------------ the platform
def _sampled(layer_type, n, seed):
    space = TPUv5eSim().param_space(layer_type)
    rng = np.random.default_rng(seed)
    cols = {p: rng.integers(lo, hi + 1, size=n) for p, (lo, hi) in space.ranges.items()}
    for p, v in space.fixed.items():
        cols[p] = np.full(n, v)
    return ConfigBatch.from_columns(cols)


@pytest.mark.parametrize("layer_type", ["mla_prefill", "mla_decode"])
def test_scalar_columnar_and_jitted_forms_agree_bitwise(layer_type):
    tpu = TPUv5eSim(knowledge="white")
    for n, seed in ((1, 0), (97, 1), (300, 2)):
        batch = _sampled(layer_type, n, seed)
        rows = batch.to_dicts()
        flop, mem = tpu._terms_batch(layer_type, batch)
        scalar = np.array([tpu._terms(layer_type, c) for c in rows])
        assert np.array_equal(flop, scalar[:, 0]) and np.array_equal(mem, scalar[:, 1])
        ref = np.array([tpu.measure(layer_type, c) for c in rows])
        assert np.array_equal(tpu.measure_batch(layer_type, batch), ref)
        tpu.predict_backend = "jax"
        assert np.array_equal(tpu.measure_batch(layer_type, batch), ref)
        tpu.predict_backend = None
        feats = np.array([list(derived_features(layer_type, c).values()) for c in rows])
        assert np.array_equal(derived_features_batch(layer_type, batch), feats)
        ops = np.array([op_count(layer_type, c) for c in rows])
        assert np.array_equal(op_count_batch(layer_type, batch), ops)


def test_the_fixed_keys_default_to_deepseeks_widths():
    tpu = TPUv5eSim(knowledge="white")
    for lt, keys in (("mla_prefill", ("nope", "rope", "dv")), ("mla_decode", tuple(MLA_FIXED))):
        cfg = tpu.defaults(lt)
        assert tpu.param_space(lt).fixed == {k: MLA_FIXED[k] for k in keys}
        bare = {k: v for k, v in cfg.items() if k not in keys}
        assert tpu._terms(lt, bare) == tpu._terms(lt, cfg)
        assert derived_features(lt, bare) == derived_features(lt, cfg)
        assert list(derived_features(lt, cfg)) == ["macs", "bytes"]
    # the older layer types' cache key is the one it was
    assert tpu.cache_key() == "tpu_v5e[white]|noise=0.0|E=64|topk=8|kv=4|mats=3"


def test_decode_reads_the_latent_once_whatever_the_heads():
    tpu = TPUv5eSim(knowledge="white")
    bw = tpu.chip.hbm_bandwidth

    def cache_bytes(h, s):
        cfg = {"B": 4, "S_kv": s, "H": h, **MLA_FIXED}
        head_terms = 2.0 * (h * 512 * (128 + 128) + 4 * h * (128 + 64 + 128))
        return tpu._terms("mla_decode", cfg)[1] * bw - head_terms

    for h in (1, 8, 32, 128):
        assert cache_bytes(h, 131072) == pytest.approx(2.0 * 4 * 131072 * 576, rel=1e-12)
    # with many sequences (the weights' bytes shared) the absorbed decode
    # sits on the v5e's ridge at 128 heads, and is memory bound by about 4x
    # at 32 heads, with the same cache bytes
    for h, ratio in ((128, 1.0), (32, 0.25)):
        f, m = tpu._terms("mla_decode", {"B": 64, "S_kv": 131072, "H": h, **MLA_FIXED})
        assert f / m == pytest.approx(ratio, rel=0.02)


def test_prefill_pads_each_heads_dims_to_the_mxu():
    tpu = TPUv5eSim(knowledge="white")
    cfg = {"B": 2, "S": 4096, "H": 16, **{k: MLA_FIXED[k] for k in ("nope", "rope", "dv")}}
    flop, mem = tpu._terms("mla_prefill", cfg)
    assert flop == 1.0 * 2 * 16 * 4096**2 * (256 + 128) / tpu.chip.peak_bf16_flops
    assert mem == 2.0 * 2 * 16 * 4096 * (2 * 256 + 2 * 128) / tpu.chip.hbm_bandwidth


# ------------------------------------------------------ the decomposition
def test_deepseek_is_registered_with_every_published_width():
    cfg = get_config(DEEPSEEK)
    pub = json.loads(CATALOG.read_text())
    assert DEEPSEEK in ESTIMATED and DEEPSEEK not in ARCHS
    assert (cfg.family, cfg.mla) == ("moe", True)
    assert {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "first_k_dense_replace": cfg.moe_dense_layers, "intermediate_size": cfg.moe_dense_d_ff,
        "moe_intermediate_size": cfg.d_ff, "n_routed_experts": cfg.moe_experts,
        "num_experts_per_tok": cfg.moe_top_k, "num_nextn_predict_layers": cfg.mtp_layers,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
    } == {k: pub[k] for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads",
        "vocab_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
        "n_routed_experts", "num_experts_per_tok", "num_nextn_predict_layers", "rope_theta",
        "rms_norm_eps")}
    assert cfg.moe_shared_d_ff == pub["n_shared_experts"] * pub["moe_intermediate_size"]
    assert pub["model"] == {k: getattr(cfg, k) for k in pub["model"]}
    # 671B in all and 37B a token without the MTP module, as published
    main = dc.replace(cfg, mtp_layers=0)
    assert 670e9 < main.param_count() < 672e9
    assert 37e9 < main.active_param_count() < 38e9
    assert cfg.param_count() > main.param_count()


def test_latent_attention_needs_a_query_latent():
    with pytest.raises(ValueError, match="q_lora_rank"):
        dc.replace(get_config(DEEPSEEK), q_lora_rank=0)


@pytest.mark.parametrize("dp,tp", [(64, 1), (16, 4), (1, 128), (1, 256)])
def test_blocks_of_each_kind(dp, tp):
    cfg = get_config(DEEPSEEK)
    for shape in (PREFILL, DECODE):
        assert [(b.kind, b.repeat) for b in decompose(cfg, shape, dp, tp)] == [
            ("embed", 1.0), ("attn", 61.0), ("mlp", 3.0), ("moe", 58.0), ("mlp", 1.0)]
    # training adds the MTP module: the concat projection, one more MLA and
    # MoE layer, the LM head once more
    train = decompose(cfg, TRAIN, dp, tp)
    assert [(b.kind, b.repeat) for b in train] == [
        ("embed", 3.0), ("attn", 183.0), ("mlp", 9.0), ("moe", 174.0),
        ("mlp", 3.0), ("attn", 3.0), ("moe", 3.0), ("mlp", 6.0)]
    t = max(1, 1024 // dp) * 4096
    assert train[4].layers == (("dense", {"tokens": t, "d_in": 14336, "d_out": 7168}),)
    assert train[5].layers == train[1].layers and train[6].layers == train[3].layers


@pytest.mark.parametrize("tp", [1, 2, 8, 64, 128, 256, 1024])
def test_mla_block_holds_this_chips_heads(tp):
    cfg = get_config(DEEPSEEK)
    h = 128 // tp if 128 % tp == 0 else 128
    dp = 64
    for shape in (PREFILL, DECODE):
        b = max(1, shape.global_batch // dp)
        t = b * (1 if shape.kind == "decode" else shape.seq_len)
        attn = next(blk for blk in decompose(cfg, shape, dp, tp) if blk.kind == "attn")
        layers = [("dense", {"tokens": t, "d_in": 7168, "d_out": 1536}),
                  ("dense", {"tokens": t, "d_in": 7168, "d_out": 576}),
                  ("dense", {"tokens": t, "d_in": 1536, "d_out": h * 192})]
        if shape.kind == "decode":
            layers.append(("mla_decode", {"B": b, "S_kv": 131072, "H": h, **MLA_FIXED}))
        else:
            layers += [("dense", {"tokens": t, "d_in": 512, "d_out": h * 256}),
                       ("mla_prefill", {"B": b, "S": 32768, "H": h, "nope": 128, "rope": 64,
                                        "dv": 128})]
        layers.append(("dense", {"tokens": t, "d_in": h * 128, "d_out": 7168}))
        assert attn.layers == tuple(layers)


def test_ffn_blocks_are_dense_then_routed_and_shared_experts():
    cfg = get_config(DEEPSEEK)
    blocks = decompose(cfg, DECODE, 64, 4)
    (_, dense), (_, moe) = ((b.kind, b.layers) for b in blocks[2:4])
    assert dense == (("dense", {"tokens": 4, "d_in": 7168, "d_out": 4608}),) * 2 + (
        ("dense", {"tokens": 4, "d_in": 4608, "d_out": 7168}),)
    assert moe == (
        ("dense", {"tokens": 4, "d_in": 7168, "d_out": 256}),
        ("moe_gemm", {"tokens": 1, "d_model": 7168, "d_ff": 2048, "E": 64, "topk": 8}),
        ("dense", {"tokens": 4, "d_in": 7168, "d_out": 512}),
        ("dense", {"tokens": 4, "d_in": 7168, "d_out": 512}),
        ("dense", {"tokens": 4, "d_in": 512, "d_out": 7168}),
    )


def test_decode_cache_term_does_not_fall_with_tp():
    """At a fixed dp each chip holds the whole latent of its sequences: the
    mla_decode row's cache term is the same at every tp, and over 61 layers
    it is 9.2 GB a step at dp 256 and 36.8 GB at dp 64 (decode_128k)."""
    cfg = get_config(DEEPSEEK)
    tpu = TPUv5eSim(knowledge="white")
    for dp in (256, 64):
        terms = set()
        for tp in (1, 2, 4, 16, 128):
            core = next(c for b in decompose(cfg, DECODE, dp, tp) for lt, c in b.layers
                        if lt == "mla_decode")
            h = core["H"]
            head_terms = 2.0 * (h * 512 * 256 + core["B"] * h * 320)
            terms.add(tpu._terms("mla_decode", core)[1] * tpu.chip.hbm_bandwidth - head_terms)
        (cache,) = {round(x) for x in terms}
        assert cache * 61 == pytest.approx({256: 9.2e9, 64: 36.8e9}[dp], rel=0.01)


def test_many_steps_in_one_batch_equal_the_steps_one_by_one():
    cfg = get_config(DEEPSEEK)
    steps = [(shape, dp, tp) for dp, tp in ((64, 4), (16, 16), (1, 256))
             for shape in (TRAIN, PREFILL, DECODE)]
    got, step_of = decompose_steps(cfg, steps)
    parts = [decompose_batch(cfg, *s) for s in steps]
    assert got.fingerprints() == BlockBatch.concat(parts).fingerprints()
    assert np.array_equal(step_of, np.repeat(np.arange(len(parts)), [len(p) for p in parts]))
    for part, (shape, dp, tp) in zip(parts, steps):
        assert part.fingerprints() == BlockBatch.from_blocks(
            decompose(cfg, shape, dp, tp)).fingerprints()


def test_reduced_keeps_every_mechanism():
    cfg = reduced(get_config(DEEPSEEK))
    assert cfg.mla and cfg.moe_dense_layers == 1 and cfg.n_layers == 2
    assert cfg.mtp_layers == 1 and cfg.moe_shared_d_ff and cfg.moe_dense_d_ff
    kinds = [b.kind for b in decompose(cfg, TRAIN, 4, 2)]
    assert kinds == ["embed", "attn", "mlp", "moe", "mlp", "attn", "moe", "mlp"]


class _Batched:
    """Scores every network 1 in one batched call and counts the calls."""

    calls = 0

    def predict_network_batch(self, batch, net_id, n_nets):
        self.calls += 1
        self.types = set(batch.group_types)
        return np.ones(n_nets)


@pytest.mark.parametrize("shape,core", [(PREFILL, "mla_prefill"), (DECODE, "mla_decode")])
def test_autotune_ranks_it_in_one_batched_call(shape, core):
    oracle = _Batched()
    ranking = autotune(oracle, get_config(DEEPSEEK), shape, default_candidates(256))
    assert oracle.calls == 1 and len(ranking) in (21, 27)
    assert oracle.types == {"embed", "dense", core, "moe_gemm"}
