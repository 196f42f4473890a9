"""Plain references that decide ``correct``; they import nothing of the program.

* :class:`Forest` holds the node tables the benchmark made itself
  (``bench/forests.py``); it never reads what the program wrote or loaded.
* :func:`snap` and :func:`features` restate Eq. 7/8 (the PR snap) and the
  derived layer descriptors.
* :meth:`Forest.predict` is a plain descent of every (tree, row) pair,
  tree by tree, summed in tree order; ``dtype`` selects float64 (the
  reference) or float32 (the control that must fail).
* :func:`mesh_search` and :func:`network_time` restate the mesh advisor's
  candidates, the decomposition of a mixture-of-experts model into blocks,
  and the Eq. 9-12 combination.
* :func:`dense_error` compares a dense program's output with the exact
  product of its float32 operands.
"""

from __future__ import annotations

import math

import numpy as np

#: fixed bytes of one node visit and of one (tree, row) leaf read, whatever
#: implements the traversal: feature id (4) + threshold (8) + child index (4)
#: + feature value (8); leaf value (8)
VISIT_BYTES = 24
LEAF_BYTES = 8


class Forest:
    """One layer type's estimator as the benchmark made it (``bench/forests.py``):
    node tables ``(feature, threshold, left, right, value)`` per tree, the PR
    widths and ranges of the snap, and leaves that hold log seconds."""

    def __init__(self, layer_type: str, params: list, widths: dict, ranges: dict,
                 trees: list) -> None:
        self.layer_type = layer_type
        self.params = list(params)
        self.widths = {p: int(w) for p, w in widths.items()}
        self.ranges = {p: (int(lo), int(hi)) for p, (lo, hi) in ranges.items()}
        self.trees = trees

    def raw(self, X: np.ndarray, dtype=np.float64) -> tuple[np.ndarray, int]:
        """Mean leaf value over the trees and the number of node visits."""
        X = np.asarray(X, dtype=dtype)
        n = X.shape[0]
        acc = np.zeros(n, dtype=dtype)
        visits = 0
        rows = np.arange(n)
        for feature, threshold, left, right, value in self.trees:
            thr = threshold.astype(dtype)
            node = np.zeros(n, dtype=np.int64)
            live = rows
            while live.size:
                f = feature[node[live]]
                inner = f >= 0
                live = live[inner]
                if not live.size:
                    break
                visits += live.size
                at = node[live]
                go_left = X[live, feature[at]] <= thr[at]
                node[live] = np.where(go_left, left[at], right[at])
            acc = acc + value.astype(dtype)[node]
        return acc / dtype(len(self.trees)), visits

    def predict(self, cols: dict, dtype=np.float64) -> tuple[np.ndarray, int]:
        """Eq. 7/8 prediction in seconds of configs given as columns, and node visits."""
        y, visits = self.raw(features(self.layer_type, snap(cols, self.widths, self.ranges),
                                      self.params), dtype)
        return np.exp(y), visits


def snap(cols: dict, widths: dict, ranges: dict) -> dict:
    """Eq. 7/8: each quantised parameter to ``ceil(v / w) * w``, kept on the
    PR grid of its range (``hi`` where the range holds no multiple of ``w``)."""
    out = {}
    for p, v in cols.items():
        v = np.asarray(v, dtype=np.int64)
        w = widths.get(p, 1)
        if w > 1:
            v = -(-v // w) * w
            if p in ranges:
                lo, hi = ranges[p]
                top = (hi // w) * w
                first = max(w, -(-lo // w) * w)
                v = np.full_like(v, hi) if top < first else np.clip(v, first, top)
        out[p] = v
    return out


def features(layer_type: str, c: dict, params: list) -> np.ndarray:
    """Base parameters then the derived descriptors, as float64 columns."""
    g = lambda k, d: c[k] if k in c else d  # noqa: E731
    if layer_type == "dense":
        derived = [c["tokens"] * c["d_in"] * c["d_out"],
                   c["tokens"] * (c["d_in"] + c["d_out"]) + c["d_in"] * c["d_out"],
                   c["d_in"] * c["d_out"]]
    elif layer_type == "attention_prefill":
        kvh = np.maximum(1, c["H"] // g("kv_ratio", 4))
        derived = [c["B"] * c["H"] * c["S"] ** 2 * c["Dh"],
                   c["B"] * c["S"] * c["Dh"] * (2 * c["H"] + 2 * kvh)]
    elif layer_type == "attention_decode":
        kvh = np.maximum(1, c["H"] // g("kv_ratio", 4))
        derived = [c["B"] * c["H"] * c["S_kv"] * c["Dh"],
                   c["B"] * kvh * c["S_kv"] * c["Dh"] * 2]
    elif layer_type == "moe_gemm":
        derived = [3 * c["tokens"] * c["topk"] * c["d_model"] * c["d_ff"],
                   3 * c["E"] * c["d_model"] * c["d_ff"],
                   c["tokens"] * c["topk"] / np.maximum(1, c["E"])]
    elif layer_type == "embed":
        td = c["tokens"] * c["d_model"]
        derived = [td, td]
    else:
        raise KeyError(f"no reference features for {layer_type!r}")
    base = [np.asarray(c[p], dtype=np.float64) for p in params]
    return np.stack(base + [np.asarray(d, dtype=np.float64) for d in derived], axis=1)


def traversal_cost(visits: int, pairs: int) -> tuple[float, float]:
    """(operations, bytes) of a forest traversal: one comparison per node
    visit and one addition per (tree, row); bytes in the fixed representation
    of :data:`VISIT_BYTES` and :data:`LEAF_BYTES`."""
    return float(visits + pairs), float(visits * VISIT_BYTES + pairs * LEAF_BYTES)


def max_rel_gap(got, ref) -> float:
    """Largest ``|got - ref| / |ref|``; a missing or non-finite answer where
    the reference has one (or the other way round) reads as infinite."""
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if got.shape != ref.shape:
        return math.inf
    fin = np.isfinite(ref)
    if not np.array_equal(fin, np.isfinite(got)):
        return math.inf
    if not fin.any():
        return 0.0
    return float(np.max(np.abs(got[fin] - ref[fin]) / np.abs(ref[fin])))


# ------------------------------------------------------------ mesh advisor
def candidates(chips: int) -> list[tuple[int, int, int]]:
    """(dp, tp, microbatches) for every power-of-two tp dividing ``chips``."""
    out = []
    tp = 1
    while tp <= chips:
        if chips % tp == 0:
            out.extend((chips // tp, tp, micro) for micro in (1, 2, 4))
        tp *= 2
    return out


def moe_blocks(m: dict, shape: dict, dp: int, tp: int, train_factor: float = 3.0) -> list:
    """Per-device blocks ``(kind, [(layer_type, cfg)], repeat)`` of one step of
    a mixture-of-experts transformer under a (dp, tp) mesh."""
    is_train = shape["kind"] == "train"
    is_decode = shape["kind"] == "decode"
    rep = train_factor if is_train else 1.0
    b = max(1, shape["global_batch"] // dp)
    s = 1 if is_decode else shape["seq_len"]
    t = b * s
    d, f, v = m["d_model"], m["d_ff"], m["vocab"]
    heads, kv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // heads
    if tp == 1 or kv % tp == 0:
        h_loc, kv_loc = heads // tp, kv // tp
    elif heads % tp == 0:
        h_loc, kv_loc = heads // tp, kv
    else:
        h_loc, kv_loc = heads, kv
    ratio = max(1, h_loc // max(1, kv_loc))
    if is_decode:
        attn = ("attention_decode", {"B": b, "S_kv": shape["seq_len"], "H": h_loc, "Dh": hd,
                                     "kv_ratio": ratio})
    else:
        attn = ("attention_prefill", {"B": b, "S": s, "H": h_loc, "Dh": hd, "kv_ratio": ratio})
    n = m["n_layers"]
    return [
        ("embed", [("embed", {"tokens": t, "vocab": v, "d_model": d})], rep),
        ("attn", [("dense", {"tokens": t, "d_in": d, "d_out": (h_loc + 2 * kv_loc) * hd}),
                  attn,
                  ("dense", {"tokens": t, "d_in": h_loc * hd, "d_out": d})], n * rep),
        ("moe", [("dense", {"tokens": t, "d_in": d, "d_out": m["moe_experts"]}),
                 ("moe_gemm", {"tokens": max(1, t // tp), "d_model": d, "d_ff": f,
                               "E": max(1, m["moe_experts"] // tp), "topk": m["moe_top_k"]})],
         n * rep),
        ("mlp", [("dense", {"tokens": t, "d_in": d, "d_out": max(1, v // tp)})], rep),
    ]


def mesh_search(m: dict, shape: dict, chips: int) -> list[tuple[tuple, list | None]]:
    """The advisor's candidates in order, each with its blocks, or None where
    the microbatch count does not divide the batch (scored as infinite)."""
    gb = shape["global_batch"]
    out = []
    for dp, tp, micro in candidates(chips):
        if dp > max(1, gb):
            continue
        if gb % (dp * micro) and gb >= dp:
            out.append(((dp, tp, micro), None))
            continue
        micro_shape = dict(shape, global_batch=max(1, gb // micro))
        out.append(((dp, tp, micro), moe_blocks(m, micro_shape, dp, tp)))
    return out


def network_time(blocks: list, layer_time, launch_s: float) -> float:
    """Eq. 10 (sum less the launches fused away) per block, Eq. 12 over
    blocks; ``layer_time(layer_type, cfg)`` gives one layer's time."""
    total = 0.0
    for _, layers, repeat in blocks:
        times = [layer_time(lt, cfg) for lt, cfg in layers]
        t = sum(times) - launch_s * max(0, len(times) - 1)
        t = max(t, launch_s if times else 0.0)
        total += t * repeat
    return total


def score_search(m: dict, shape: dict, chips: int, forests: dict, launch_s: float,
                 dtype=np.float64) -> dict:
    """Reference scores ``{(dp, tp, micro): seconds}`` of one mesh search."""
    plan = mesh_search(m, shape, chips)
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
    out = {}
    for cand, blocks in plan:
        out[cand] = math.inf if blocks is None else (
            network_time(blocks, layer_time, launch_s) * cand[2])
    return out


# ------------------------------------------------------------ dense program
def dense_error(a: np.ndarray, b: np.ndarray, y: np.ndarray) -> float:
    """Widest gap of ``y`` from the exact product of float32 ``a @ b``, each
    element's gap measured against ``sqrt(sum_k a_ik^2 b_kj^2)``: operands
    rounded to a relative precision ``u`` read a few ``u`` whatever the shape."""
    a64 = np.asarray(a, dtype=np.float64)
    b64 = np.asarray(b, dtype=np.float64)
    gap = np.abs(np.asarray(y, dtype=np.float64) - a64 @ b64)
    return float(np.max(gap / np.sqrt((a64 ** 2) @ (b64 ** 2))))


def rounded(x: np.ndarray, dtype: str) -> np.ndarray:
    """float32 values rounded to a narrower float (``"bfloat16"``,
    ``"float8_e4m3fn"``), returned as float64."""
    import ml_dtypes

    return np.asarray(x, dtype=np.float32).astype(getattr(ml_dtypes, dtype)).astype(np.float64)
