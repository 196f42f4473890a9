"""Plain float64 restatement of a mixture-of-experts model with multi-head
latent attention (DeepSeek-V3: MLA in every layer, leading dense layers, a
shared expert and the MTP module), for the latent-attention mesh-search
cell; it imports nothing of the program.

``bench/reference.py`` restates a GQA mixture-of-experts transformer; this
module adds what latent attention needs beside it:

* :func:`features` of the two MLA layer types: ``mla_prefill``, causal
  attention over the decompressed latent (prefill and training), and
  ``mla_decode``, the absorbed form in which every head of a sequence reads
  that sequence's one cached latent; every other layer type is
  ``bench/reference.py``'s;
* :class:`MLAForest` and :func:`make`: the benchmark's forests
  (``bench/forests.py``) grown and read with those features;
* :func:`mla_blocks`: one step's per-device blocks under a (dp, tp) mesh,
  in training, prefill and decode, the MTP module in training only;
* :func:`score_search`: the scores of one mesh search.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference
from bench.common import seed_key
from bench.forests import grow, log_target, pr_grid_columns


def features(layer_type: str, c: dict, params: list) -> np.ndarray:
    """Base parameters then the derived descriptors (MACs, then elements
    moved), as float64 columns."""
    if layer_type == "mla_prefill":
        # causal: QK^T over nope + rope dims and PV over dv, half the square each
        qk, dv = c["nope"] + c["rope"], c["dv"]
        derived = [c["B"] * c["H"] * c["S"] ** 2 * (qk + dv) / 2,
                   c["B"] * c["S"] * c["H"] * (2 * qk + 2 * dv)]
    elif layer_type == "mla_decode":
        # scores against the latent and the RoPE key, the weighted latents,
        # and W_UK, W_UV folded into each head; the cache read once a sequence
        lat, bh = c["kv_lora"] + c["rope"], c["B"] * c["H"]
        derived = [bh * c["S_kv"] * lat + bh * c["S_kv"] * c["kv_lora"]
                   + bh * c["kv_lora"] * (c["nope"] + c["dv"]),
                   c["B"] * c["S_kv"] * lat + c["H"] * c["kv_lora"] * (c["nope"] + c["dv"])
                   + bh * (c["nope"] + c["rope"] + c["dv"])]
    else:
        return reference.features(layer_type, c, params)
    base = [np.asarray(c[p], dtype=np.float64) for p in params]
    return np.stack(base + [np.asarray(d, dtype=np.float64) for d in derived], axis=1)


class MLAForest(reference.Forest):
    """A :class:`bench.reference.Forest` read with :func:`features`."""

    def predict(self, cols: dict, dtype=np.float64) -> tuple[np.ndarray, int]:
        X = features(self.layer_type, reference.snap(cols, self.widths, self.ranges),
                     self.params)
        y, visits = self.raw(X, dtype)
        return np.exp(y), visits


def make(config: dict) -> dict[str, MLAForest]:
    """Every layer type's forest of ``config``, from its build seed, as
    ``bench/forests.py`` makes them."""
    fc = config["forest"]
    out = {}
    for i, (lt, spec) in enumerate(config["layer_types"].items()):
        rng = np.random.default_rng(seed_key(int(fc["seed"]), 11, i))
        params = list(spec["ranges"])
        X = features(lt, pr_grid_columns(spec, int(fc["samples"]), rng), params)
        y = log_target(X, len(params), fc["target"])
        trees = grow(X, y, int(fc["trees"]), int(fc["max_depth"]), tuple(fc["split_quantile"]),
                     rng)
        out[lt] = MLAForest(lt, params, spec["widths"], spec["ranges"], trees)
    return out


def mla_blocks(m: dict, shape: dict, dp: int, tp: int, train_factor: float = 3.0) -> list:
    """Per-device blocks ``(kind, [(layer_type, cfg)], repeat)`` of one step:
    the embedding, the MLA layers, the leading dense FFNs, the MoE FFNs, in
    training the MTP module (concat projection, one more MLA and MoE layer),
    and the LM head, once more for each MTP depth in training."""
    is_train = shape["kind"] == "train"
    is_decode = shape["kind"] == "decode"
    rep = train_factor if is_train else 1.0
    b = max(1, shape["global_batch"] // dp)
    s = 1 if is_decode else shape["seq_len"]
    t = b * s
    d, f, v = m["d_model"], m["d_ff"], m["vocab"]

    def mlp(width: int) -> list:  # SwiGLU: gate and up, then down
        w = max(1, width // tp)
        return [("dense", {"tokens": t, "d_in": d, "d_out": w})] * 2 + [
            ("dense", {"tokens": t, "d_in": w, "d_out": d})]

    # MLA: the heads split over tp where it divides them, else all on each chip
    heads = m["n_heads"]
    h = heads // tp if heads % tp == 0 else heads
    q_lat, lat = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attn = [("dense", {"tokens": t, "d_in": d, "d_out": q_lat}),         # q_a
            ("dense", {"tokens": t, "d_in": d, "d_out": lat + rope}),    # kv_a, k_rope
            ("dense", {"tokens": t, "d_in": q_lat, "d_out": h * (nope + rope)})]  # q_b
    if is_decode:
        attn.append(("mla_decode", {"B": b, "S_kv": shape["seq_len"], "H": h, "nope": nope,
                                    "rope": rope, "dv": dv, "kv_lora": lat}))
    else:
        attn.append(("dense", {"tokens": t, "d_in": lat, "d_out": h * (nope + dv)}))  # kv_b
        attn.append(("mla_prefill", {"B": b, "S": s, "H": h, "nope": nope, "rope": rope,
                                     "dv": dv}))
    attn.append(("dense", {"tokens": t, "d_in": h * dv, "d_out": d}))  # o_proj

    moe = [("dense", {"tokens": t, "d_in": d, "d_out": m["moe_experts"]}),  # router
           ("moe_gemm", {"tokens": max(1, t // tp), "d_model": d, "d_ff": f,
                         "E": max(1, m["moe_experts"] // tp), "topk": m["moe_top_k"]})]
    if m.get("moe_shared_d_ff"):
        moe += mlp(m["moe_shared_d_ff"])

    n, n_dense = m["n_layers"], m["moe_dense_layers"]
    blocks = [("embed", [("embed", {"tokens": t, "vocab": v, "d_model": d})], rep),
              ("attn", attn, n * rep)]
    if n_dense:
        blocks.append(("mlp", mlp(m["moe_dense_d_ff"]), n_dense * rep))
    blocks.append(("moe", moe, (n - n_dense) * rep))
    mtp = m["mtp_layers"] if is_train else 0
    if mtp:
        blocks += [("mlp", [("dense", {"tokens": t, "d_in": 2 * d, "d_out": d})], mtp * rep),
                   ("attn", attn, mtp * rep),
                   ("moe", moe, mtp * rep)]
    blocks.append(("mlp", [("dense", {"tokens": t, "d_in": d, "d_out": max(1, v // tp)})],
                   rep * (1 + mtp)))
    return blocks


def search_plan(m: dict, shape: dict, chips: int) -> list[tuple[tuple, list | None]]:
    """The advisor's candidates in order, each with its blocks, or None where
    the microbatch count does not divide the batch (scored as infinite)."""
    gb = shape["global_batch"]
    out = []
    for dp, tp, micro in reference.candidates(chips):
        if dp > max(1, gb):
            continue
        if gb % (dp * micro) and gb >= dp:
            out.append(((dp, tp, micro), None))
            continue
        micro_shape = dict(shape, global_batch=max(1, gb // micro))
        out.append(((dp, tp, micro), mla_blocks(m, micro_shape, dp, tp)))
    return out


def score_search(m: dict, shape: dict, chips: int, forests: dict, launch_s: float,
                 dtype=np.float64) -> dict:
    """Reference scores ``{(dp, tp, micro): seconds}`` of one mesh search."""
    plan = search_plan(m, shape, chips)
    rows: dict[str, list[dict]] = {}
    for _, blocks in plan:
        for _, layers, _ in blocks or ():
            for lt, cfg in layers:
                rows.setdefault(lt, []).append(cfg)
    times: dict[str, dict] = {}
    for lt, cfgs in rows.items():
        keys = [tuple(sorted(c.items())) for c in cfgs]
        cols = {p: np.array([c[p] for c in cfgs], dtype=np.int64) for p in cfgs[0]}
        y, _ = forests[lt].predict(cols, dtype)
        times[lt] = dict(zip(keys, y.astype(np.float64)))
    layer_time = lambda lt, cfg: times[lt][tuple(sorted(cfg.items()))]  # noqa: E731
    return {cand: math.inf if blocks is None
            else reference.network_time(blocks, layer_time, launch_s) * cand[2]
            for cand, blocks in plan}
