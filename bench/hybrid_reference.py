"""Plain float64 restatement of a model built by a layer pattern
(NemotronH: Mamba-2, mixture-of-experts and attention layers), for the
hybrid mesh-search cell; it imports nothing of the program.

``bench/reference.py`` restates a mixture-of-experts transformer; this
module adds what a pattern model needs beside it:

* :func:`features` of the Mamba-2 layer types (``ssd_scan`` in prefill and
  training, ``ssd_decode`` for one decode token, both with the model's ``G``
  groups) and of an expert layer with ``mats`` matrices; every other layer
  type is ``bench/reference.py``'s;
* :class:`HybridForest` and :func:`make`: the benchmark's forests
  (``bench/forests.py``) grown and read with those features;
* :func:`pattern_blocks`: one step's per-device blocks under a (dp, tp) mesh,
  each kind of layer repeated by its count in the pattern;
* :func:`score_search`: the scores of one mesh search.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference
from bench.common import seed_key
from bench.forests import grow, log_target, pr_grid_columns


def features(layer_type: str, c: dict, params: list) -> np.ndarray:
    """Base parameters then the derived descriptors, as float64 columns."""
    g = lambda k, d: c[k] if k in c else d  # noqa: E731
    if layer_type == "moe_gemm":
        mats = g("mats", 3)
        derived = [mats * c["tokens"] * c["topk"] * c["d_model"] * c["d_ff"],
                   mats * c["E"] * c["d_model"] * c["d_ff"],
                   c["tokens"] * c["topk"] / np.maximum(1, c["E"])]
    elif layer_type == "ssd_scan":
        derived = [c["B"] * c["S"] * c["H"] * c["P"] * (2 * c["N"] + 128),
                   c["B"] * c["S"] * (2 * c["H"] * c["P"] + 2 * g("G", 1) * c["N"])]
    elif layer_type == "ssd_decode":
        # one token: the (H, P, N) state read, updated and written once
        state = c["B"] * c["H"] * c["P"] * c["N"]
        derived = [2 * state,
                   2 * state + c["B"] * (2 * c["H"] * c["P"] + 2 * g("G", 1) * c["N"])]
    else:
        return reference.features(layer_type, c, params)
    base = [np.asarray(c[p], dtype=np.float64) for p in params]
    return np.stack(base + [np.asarray(d, dtype=np.float64) for d in derived], axis=1)


class HybridForest(reference.Forest):
    """A :class:`bench.reference.Forest` read with :func:`features`."""

    def predict(self, cols: dict, dtype=np.float64) -> tuple[np.ndarray, int]:
        X = features(self.layer_type, reference.snap(cols, self.widths, self.ranges),
                     self.params)
        y, visits = self.raw(X, dtype)
        return np.exp(y), visits


def make(config: dict) -> dict[str, HybridForest]:
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
        out[lt] = HybridForest(lt, params, spec["widths"], spec["ranges"], trees)
    return out


def pattern_blocks(m: dict, shape: dict, dp: int, tp: int, train_factor: float = 3.0) -> list:
    """Per-device blocks ``(kind, [(layer_type, cfg)], repeat)`` of one step of
    a pattern model: the embedding, one block of each kind of layer repeated
    by its count in the pattern (in order of first use), the LM head."""
    is_train = shape["kind"] == "train"
    is_decode = shape["kind"] == "decode"
    rep = train_factor if is_train else 1.0
    b = max(1, shape["global_batch"] // dp)
    s = 1 if is_decode else shape["seq_len"]
    t = b * s
    d, f, v = m["d_model"], m["d_ff"], m["vocab"]
    gated = m.get("mlp", "swiglu") == "swiglu"

    def mlp(width: int) -> list:
        w = max(1, width // tp)
        return ([("dense", {"tokens": t, "d_in": d, "d_out": w})] * (2 if gated else 1)
                + [("dense", {"tokens": t, "d_in": w, "d_out": d})])

    # Mamba-2: z, x, B, C and dt column-sharded over tp
    heads, hp, n = m["ssm_n_heads"], m["ssm_headdim"], m["ssm_state"]
    di = max(1, heads * hp // tp)
    h_ssm = max(1, heads // tp)
    groups = max(1, m["ssm_groups"] // tp)
    if is_decode:
        mixer = ("ssd_decode", {"B": b, "H": h_ssm, "P": hp, "N": n, "G": groups})
    else:
        mixer = ("ssd_scan", {"B": b, "S": s, "H": h_ssm, "P": hp, "N": n, "G": groups})
    mamba = [("dense", {"tokens": t, "d_in": d, "d_out": 2 * di + 2 * groups * n + h_ssm}),
             mixer,
             ("dense", {"tokens": t, "d_in": di, "d_out": d})]

    # experts: the router, this chip's experts, the shared expert
    experts = {"tokens": max(1, t // tp), "d_model": d, "d_ff": f,
               "E": max(1, m["moe_experts"] // tp), "topk": m["moe_top_k"]}
    if not gated:
        experts["mats"] = 2
    moe = [("dense", {"tokens": t, "d_in": d, "d_out": m["moe_experts"]}),
           ("moe_gemm", experts)]
    if m.get("moe_shared_d_ff"):
        moe += mlp(m["moe_shared_d_ff"])

    # GQA attention under the head policy of bench/reference.py's moe_blocks
    q, kv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // q
    if tp == 1 or kv % tp == 0:
        h_loc, kv_loc = q // tp, kv // tp
    elif q % tp == 0:
        h_loc, kv_loc = q // tp, kv
    else:
        h_loc, kv_loc = q, kv
    ratio = max(1, h_loc // max(1, kv_loc))
    if is_decode:
        core = ("attention_decode", {"B": b, "S_kv": shape["seq_len"], "H": h_loc, "Dh": hd,
                                     "kv_ratio": ratio})
    else:
        core = ("attention_prefill", {"B": b, "S": s, "H": h_loc, "Dh": hd, "kv_ratio": ratio})
    attn = [("dense", {"tokens": t, "d_in": d, "d_out": (h_loc + 2 * kv_loc) * hd}),
            core,
            ("dense", {"tokens": t, "d_in": h_loc * hd, "d_out": d})]

    layers = {"M": ("ssd", mamba), "E": ("moe", moe), "*": ("attn", attn)}
    pattern = m["layer_pattern"]
    body = [(layers[k][0], layers[k][1], pattern.count(k) * rep) for k in dict.fromkeys(pattern)]
    return ([("embed", [("embed", {"tokens": t, "vocab": v, "d_model": d})], rep)]
            + body
            + [("mlp", [("dense", {"tokens": t, "d_in": d, "d_out": max(1, v // tp)})], rep)])


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
        out.append(((dp, tp, micro), pattern_blocks(m, micro_shape, dp, tp)))
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
