"""The estimators a configuration serves, made by the benchmark from its
build seed: plain node tables, never fitted by the program.

For each layer type the configuration gives its parameter ranges, fixed
keys and PR step widths.  ``samples`` training configs are drawn uniformly
from the PR grid of those ranges; their target is the log of an analytical
time (the configuration's ``target``: two operations per MAC at the peak
rate, four bytes per moved element at the peak bandwidth, and a launch
overhead).  Each of ``trees`` trees takes a bootstrap of the samples and
grows level by level: a node holding more than one distinct config splits
on a random feature at a random quantile of its configs' values (between
the two values on either side of it), until every leaf holds one distinct
config or ``max_depth`` is reached.  A leaf holds the mean target of its
configs.  So the tables have the size and the depth of the forests a
campaign fits (about 2500 nodes a tree, depth 14-17), and every array is
the benchmark's own: the program loads a copy written in its hub format,
and the reference reads the arrays made here.
"""

from __future__ import annotations

import numpy as np

from bench.common import seed_key
from bench.reference import Forest, features


def pr_grid_columns(spec: dict, n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    """``n`` configs drawn uniformly from the PR grid of ``spec``'s ranges
    (multiples of each width inside the range; ``hi`` where it holds none)."""
    cols = {}
    for p, (lo, hi) in spec["ranges"].items():
        w = int(spec["widths"].get(p, 1))
        if w <= 1:
            cols[p] = rng.integers(lo, hi + 1, size=n, dtype=np.int64)
            continue
        first, top = max(w, -(-lo // w) * w), (hi // w) * w
        if top < first:
            cols[p] = np.full(n, hi, dtype=np.int64)
        else:
            cols[p] = first + w * rng.integers(0, (top - first) // w + 1, size=n, dtype=np.int64)
    for p, v in spec.get("fixed", {}).items():
        cols[p] = np.full(n, int(v), dtype=np.int64)
    return cols


def log_target(X: np.ndarray, n_params: int, target: dict) -> np.ndarray:
    """log seconds of the analytical time: the first derived feature as MACs
    (two operations each), the second as elements moved (four bytes each)."""
    macs, moved = X[:, n_params], X[:, n_params + 1]
    t = (2.0 * macs / target["peak_flops"] + 4.0 * moved / target["peak_bytes_per_s"]
         + target["overhead_s"])
    return np.log(t)


def grow(X: np.ndarray, y: np.ndarray, trees: int, max_depth: int, quantile: tuple,
         rng: np.random.Generator) -> list[tuple[np.ndarray, ...]]:
    """``trees`` random-split trees over bootstraps of ``(X, y)``, as node
    tables ``(feature, threshold, left, right, value)`` (feature -1 at a leaf)."""
    n = X.shape[0]
    idx = rng.integers(0, n, size=(trees, n))
    Xb, yb = X[idx], y[idx]  # (T, n, F), (T, n)
    cap = 2 * n
    feature = np.full((trees, cap), -1, dtype=np.int32)
    threshold = np.zeros((trees, cap))
    left = np.zeros((trees, cap), dtype=np.int32)
    right = np.zeros((trees, cap), dtype=np.int32)
    count = np.ones(trees, dtype=np.int64)
    node = np.zeros((trees, n), dtype=np.int64)
    live = np.ones((trees, n), dtype=bool)
    tree_of = np.broadcast_to(np.arange(trees)[:, None], (trees, n))
    for _ in range(max_depth):
        if not live.any():
            break
        g = (tree_of * cap + node)[live]
        pts = Xb[live]
        order = np.argsort(g, kind="stable")
        g, pts = g[order], pts[order]
        starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
        # a node whose configs are all one config is a leaf for good
        spread = np.maximum.reduceat(pts, starts) != np.minimum.reduceat(pts, starts)
        split = spread.any(axis=1)
        # a random feature among those that vary in the node
        pick = np.where(spread, rng.random(spread.shape), -1.0).argmax(axis=1)
        seg = np.repeat(np.arange(len(starts)), np.diff(np.r_[starts, len(g)]))
        v = pts[np.arange(len(g)), pick[seg]]
        order = np.lexsort((v, seg))
        v = v[order]
        counts = np.diff(np.r_[starts, len(g)])
        k = starts + np.floor(rng.uniform(*quantile, size=len(starts)) * (counts - 1)).astype(np.int64)
        # the split lies between two distinct values: move k to the end of
        # its run of equal values, or before its start where that run ends
        # the node
        new_run = np.r_[True, (v[1:] != v[:-1]) | (seg[1:] != seg[:-1])]
        run_start = np.maximum.accumulate(np.where(new_run, np.arange(len(v)), 0))
        run_end = np.r_[np.flatnonzero(new_run)[1:], len(v)] - 1
        run_end = run_end[np.cumsum(new_run) - 1]
        seg_end = starts + counts - 1
        k = np.where(run_end[k] < seg_end, run_end[k], run_start[k] - 1)
        lo, hi = v[np.maximum(k, 0)], v[np.minimum(k + 1, len(v) - 1)]
        thr = (lo + hi) / 2.0
        thr = np.where(thr < hi, thr, lo)
        # children are numbered in each tree after its nodes so far
        t_seg = g[starts] // cap
        local = g[starts] % cap
        rank = np.cumsum(split) - 1
        before = np.r_[0, np.cumsum(split)][np.searchsorted(t_seg, np.arange(trees))]
        child = count[t_seg] + 2 * (rank - before[t_seg])
        ts, ls = t_seg[split], local[split]
        feature[ts, ls] = pick[split]
        threshold[ts, ls] = thr[split]
        left[ts, ls] = child[split]
        right[ts, ls] = child[split] + 1
        count += 2 * np.bincount(t_seg[split], minlength=trees)
        # every config of a split node goes to a child; the others stop
        f_node = feature[tree_of, node]
        inner = live & (f_node >= 0)
        x = np.take_along_axis(Xb, np.maximum(f_node, 0)[..., None], axis=2)[..., 0]
        go_left = x <= threshold[tree_of, node]
        node = np.where(inner, np.where(go_left, left[tree_of, node], right[tree_of, node]), node)
        live = inner
    total = np.zeros((trees, cap))
    hits = np.zeros((trees, cap))
    np.add.at(total, (tree_of, node), yb)
    np.add.at(hits, (tree_of, node), 1.0)
    value = np.where(hits > 0, total / np.maximum(hits, 1.0), 0.0)
    return [(feature[t, :m].copy(), threshold[t, :m].copy(), left[t, :m].copy(),
             right[t, :m].copy(), value[t, :m].copy())
            for t, m in enumerate(count.tolist())]


def make(config: dict) -> dict[str, Forest]:
    """Every layer type's forest of ``config``, from its build seed."""
    fc = config["forest"]
    out = {}
    for i, (lt, spec) in enumerate(config["layer_types"].items()):
        rng = np.random.default_rng(seed_key(int(fc["seed"]), 11, i))
        params = list(spec["ranges"])
        X = features(lt, pr_grid_columns(spec, int(fc["samples"]), rng), params)
        y = log_target(X, len(params), fc["target"])
        trees = grow(X, y, int(fc["trees"]), int(fc["max_depth"]), tuple(fc["split_quantile"]),
                     rng)
        out[lt] = Forest(lt, params, spec["widths"], spec["ranges"], trees)
    return out
