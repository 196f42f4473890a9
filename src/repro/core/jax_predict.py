"""JAX-jitted inference hot path: compiled forest traversal and Eq. 9-12.

The predict path (never the fit path) can run through ``jax.jit``: the stacked
forest traversal, the four analytical timing models, and the whole-network
combination are pure integer and float64 array programs.  This module owns the
backend selection and the compiled kernels for the forest and network paths;
the platform timing kernels live in :mod:`repro.accelerators.jax_kernels`.

Backend selection
-----------------
``resolve_backend(explicit)`` decides per call:

* an explicit argument (``backend=`` on :meth:`PerfOracle.predict` and
  friends, or ``PerfOracle.predict_backend``) wins;
* otherwise the ``REPRO_PREDICT_BACKEND`` environment variable
  (``numpy`` | ``jax`` | ``auto``) decides; unset means ``numpy``;
* ``jax`` raises when jax is not installed; ``auto`` means jax when it is
  installed and numpy only when it is not.  An installed jax whose API does
  not match this module raises at the first compiled call: it never turns
  into numpy.

Compiled entry points return ``None`` only for requests the kernels do not
model (stub estimators, ragged inputs, noisy or wall-clock platforms), and
the caller continues on the numpy path — third-party platforms and estimator
stubs never see the backend at all.

Parity contract (asserted in tests/test_jax_predict.py and in-bench)
--------------------------------------------------------------------
All kernels run in float64 via the scoped ``jax.enable_x64(True)`` context
(never the global flag: flipping ``jax_enable_x64`` process-wide would change
the dtype behaviour of unrelated jax code in the same process).

* **Layer predictions are bitwise identical** to numpy on the CPU, and
  within :data:`DEVICE_RTOL` on a TPU, whose float64 is emulated.  The dense
  traversal does not replay the numpy descent; three things keep it exact.
  The compare is the descent's: a row goes left at a node exactly when its
  float64 value is at most the node's threshold, decided as an int32 compare
  of the row's rank among its feature's sorted thresholds with the
  threshold's own rank (NaN ranks above all, so it goes right, as in the
  descent).  The path sums are integers no larger than the depth, summed
  in int8 x int8 -> int32 products, never a float64 dot; so the leaf found
  is the leaf the descent reaches, and its float64 value is read unchanged.
  The per-tree values are folded in tree order (a left fold, *not*
  ``jnp.sum``, whose pairwise order differs) and divided by a *traced*
  tree-count scalar (XLA strength-reduces division by a compile-time
  constant into multiplication by its reciprocal, a 1-ulp difference; a
  traced divisor keeps the true division).  A forest over the dense budget
  keeps the compiled descent, gather for gather, under the same fold.  The
  log-target inversion stays ``np.exp`` *outside* the jit, so on the CPU
  :meth:`LayerEstimator.predict` is bit-for-bit equal across backends.
* **Platform timing kernels are bitwise identical**: integer tile padding is
  exact arithmetic, and every float hardware constant (peak FLOPs,
  bandwidths, clock rates) is passed as a traced scalar for the same
  reciprocal reason.
* **Whole-network predictions** (:func:`predict_network_batch_jax`) compile
  the traversal *and* the Eq. 9-12 combination as one call, which puts
  ``jnp.exp`` inside the compiled graph for log-target estimators;
  ``jnp.exp`` may differ from ``np.exp`` by 1 ulp, so network results carry
  an rtol≈1e-12 tolerance when any estimator is log-target — and are bitwise
  when none is.  The serving cache scopes its network keys accordingly
  (:meth:`repro.serving.server.OracleServer._network_key_scope`).

Shapes, retracing, residency and donation
-----------------------------------------
Batch rows are padded to power-of-two buckets (min 64) before entering a
kernel and sliced back after, so the admission batcher's variable batch sizes
hit a handful of warm-compiled shapes instead of retracing per request.

A forest goes to the device once (:func:`resident_forest`, cached on its
``_ForestStack``, so a refit retires it), as does the oracle's launch
overhead; a call copies from the host only what it produced itself.  Where
its path matrices (trees x internal nodes x leaves, int8, both padded to
128) fit :data:`DENSE_BUDGET_BYTES`, the forest goes in the dense form
(:class:`DenseForest`: each feature's sorted thresholds, each internal
node's feature and threshold rank, the path matrices, each leaf's left
turns and value), built once in numpy by walking every leaf up to its root;
a larger forest goes as the descent's node tables (:class:`DescentForest`).
The choice follows the forest's shape alone.  A dense traversal takes its
trees in chunks sized from the static row bucket (:data:`_CHUNK_ELEMENTS`),
so its path sums stay bounded at every batch size.  A forest call sends its
padded rows; a network call sends one int64 and one float64 buffer that the
program splits at static offsets given by the bucket sizes, so a call costs
two transfers whatever the number of groups.

The per-call buffers are donated (``donate_argnums``), never the resident
tables; on CPU XLA currently declines input-shaped donations and copies
instead — the donation is kept for device backends and the resulting
"donated buffers were not usable" warning is suppressed, since the padded
copy is ours to give away either way.
"""

from __future__ import annotations

import functools
import os
import threading
import warnings
from typing import NamedTuple

import numpy as np

from repro.obs.metrics import metrics as obs_metrics
from repro.obs.trace import span

_ENV_VAR = "REPRO_PREDICT_BACKEND"
_BACKENDS = ("numpy", "jax", "auto")

#: rows are padded up to the next power of two, at least this many
_MIN_BUCKET = 64

#: relative tolerance of compiled predictions against numpy on a chip with
#: no native float64 (the TPU emulates it, so the per-tree sum differs in the
#: last bits; 1.13e-13 at most on a TPU v5e, see README).  The CPU is bitwise.
DEVICE_RTOL = 1e-12

# Compile/retrace observability: jit caches on argument shapes, so a novel
# shape signature means XLA is compiling right now.  ``jax.*.calls`` vs
# ``jax.*.traces`` in the metrics snapshot is the direct retrace-rate signal —
# ``traces`` growing under steady live traffic means the bucketing is not
# absorbing the batch-size jitter (a bug this repo previously could not see).
# ``jax.*.h2d_bytes`` sums what each call copies from the host: its numpy
# arrays and scalars.  The forests' tables are uploaded once
# (:func:`resident_forest`, counted in ``jax.resident.uploads`` and
# ``jax.resident.bytes``, the path matrices also in
# ``jax.resident.path_bytes``) and passed as device arrays, which copy
# nothing.  ``jax.traverse.dense_groups`` and ``jax.traverse.descent_groups``
# count the forest calls and network groups each form traversed.
# ``jax.network.rows`` counts the block-layer rows a network call sends to its
# forests, ``jax.network.pad_rows`` the rows added to fill each group's
# bucket, ``jax.network.rows.ssm`` the rows of the Mamba-2 groups and
# ``jax.network.rows.mla`` those of the latent-attention groups.

#: layer types of the Mamba-2 mixer, counted in ``jax.network.rows.ssm``
SSM_LAYER_TYPES = frozenset({"ssd_scan", "ssd_decode"})
#: layer types of latent attention, counted in ``jax.network.rows.mla``
MLA_LAYER_TYPES = frozenset({"mla_prefill", "mla_decode"})

_seen_forest_sigs: set[tuple] = set()
_seen_network_sigs: set[tuple] = set()
_upload_lock = threading.Lock()

#: JAX's compile stages, each observed in the ``jax.compile_s`` histogram
_COMPILE_EVENTS = frozenset(
    {
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    }
)


def _host_bytes(args) -> int:
    """Bytes of the numpy arrays and scalars in ``args`` (tuples nest); device
    arrays and static values copy nothing."""
    if isinstance(args, tuple):
        return sum(map(_host_bytes, args))
    return args.nbytes if isinstance(args, (np.ndarray, np.generic)) else 0


def _count_trace(kind: str, seen: set, sig: tuple, args: tuple) -> None:
    reg = obs_metrics()
    reg.inc(f"jax.{kind}.calls")
    reg.inc(f"jax.{kind}.h2d_bytes", _host_bytes(args))
    if sig not in seen:
        seen.add(sig)
        reg.inc(f"jax.{kind}.traces")


def _observe_compile(event: str, duration_s: float, **kwargs) -> None:
    if event in _COMPILE_EVENTS:
        obs_metrics().observe_value("jax.compile_s", duration_s)

_modules_cache: tuple | None = None
_import_failed = False


def jax_modules() -> tuple | None:
    """``(jax, jnp, lax)``, or None when jax is not installed.

    The import is deferred so numpy-only deployments (and the CI leg that
    asserts no eager jax import) never pay for it at module load.  Only a
    missing jax package means None; any other failure raises.
    """
    global _modules_cache, _import_failed
    if _modules_cache is None and not _import_failed:
        try:
            import jax
        except ImportError:
            _import_failed = True
            return None
        import jax.numpy as jnp
        from jax import lax

        jax.monitoring.register_event_duration_secs_listener(_observe_compile)
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        _modules_cache = (jax, jnp, lax)
    return _modules_cache


def jax_available() -> bool:
    return jax_modules() is not None


def x64():
    """Scoped float64 for one compiled call (never the process-wide flag)."""
    return jax_modules()[0].enable_x64(True)


def resolve_backend(backend: str | None = None) -> str:
    """Resolve an explicit/env backend request to ``"numpy"`` or ``"jax"``."""
    choice = backend
    if choice is None:
        choice = os.environ.get(_ENV_VAR, "").strip().lower() or "numpy"
    if choice not in _BACKENDS:
        raise ValueError(
            f"unknown predict backend {choice!r}; expected one of {_BACKENDS}"
        )
    if choice == "numpy":
        return "numpy"
    if jax_available():
        return "jax"
    if choice == "jax":
        raise RuntimeError("predict backend 'jax' requested but jax is not installed")
    return "numpy"


def bucket_rows(n: int) -> int:
    """Warm-shape bucket for ``n`` rows: next power of two, at least 64."""
    if n <= _MIN_BUCKET:
        return _MIN_BUCKET
    return 1 << (int(n) - 1).bit_length()


# --------------------------------------------------------------- forest kernel
#: largest path-matrix table (trees x internal nodes x leaves, one int8 each,
#: both node counts padded to the matrix unit's 128) a forest is traversed
#: densely with; a larger forest keeps the gather descent
DENSE_BUDGET_BYTES = 256 << 20

#: most path sums (rows x leaves x trees, int32) one chunk of trees of a
#: dense traversal holds; the chunk follows from the static row bucket
_CHUNK_ELEMENTS = 1 << 25

_MATRIX_TILE = 128


class DescentForest(NamedTuple):
    """A stacked forest's node tables for the gather descent, on the device."""

    feature: object  # (T, N) int32, -1 at leaves and padding
    threshold: object  # (T, N) float64
    left: object  # (T, N) int32
    right: object  # (T, N) int32
    value: object  # (T, N) float64
    n_trees: object  # () float64


class DenseForest(NamedTuple):
    """A stacked forest as a compare and a path-matrix product.

    Internal node ``i`` of tree ``t`` sends a row left when the row's rank
    in ``edges[feature[t, i]]`` (the count of that feature's distinct
    thresholds below the row's value) is at most ``rank[t, i]``, its own
    threshold's place there: exactly ``x <= threshold``.  ``paths[t, i, l]``
    is +1 where leaf ``l`` lies under ``i``'s left child and -1 under its
    right child, so a row's path sum at ``l`` reaches ``left_turns[t, l]``,
    the left turns on ``l``'s path, at its own leaf only.
    """

    edges: object  # (F, K) float64, each feature's thresholds ascending, +inf padded
    feature: object  # (T, I) int32
    rank: object  # (T, I) int32, -1 for a NaN threshold (never left)
    paths: object  # (T, I, L) int8
    left_turns: object  # (T, L) int32, -1 on padded leaf columns (never reached)
    leaf_value: object  # (T, L) float64
    n_trees: object  # () float64


def _padded(k: int) -> int:
    return max(_MATRIX_TILE, -(-int(k) // _MATRIX_TILE) * _MATRIX_TILE)


def _dense_tables(stack) -> DenseForest | None:
    """Host tables of the dense form of ``stack``, or None when its path
    matrices exceed :data:`DENSE_BUDGET_BYTES`.

    Reachable leaves only: padding slots and the root of a one-leaf tree are
    told apart by having no parent.  Each leaf walks up to the root once,
    all leaves of all trees together, so the loop runs the depth.
    """
    feature, threshold = stack.feature, stack.threshold
    T, N = feature.shape
    inner = feature >= 0
    t, i = np.nonzero(inner)
    parent = np.full((T, N), -1, dtype=np.int64)
    turn = np.zeros((T, N), dtype=np.int8)
    for child, side in ((stack.left, 1), (stack.right, -1)):
        parent[t, child[t, i]] = i
        turn[t, child[t, i]] = side
    leaf = ~inner & (parent >= 0)
    leaf[:, 0] |= ~inner[:, 0]
    I, L = _padded(inner.sum(axis=1).max()), _padded(leaf.sum(axis=1).max())
    if T * I * L > DENSE_BUDGET_BYTES:
        return None
    col = np.cumsum(inner, axis=1) - 1
    node_feature = np.zeros((T, I), dtype=np.int32)
    node_feature[t, col[t, i]] = feature[t, i]
    n_feat = int(feature.max()) + 1 if t.size else 1
    per_feat = [np.unique(threshold[inner & (feature == f)]) for f in range(n_feat)]
    per_feat = [v[~np.isnan(v)] for v in per_feat]
    edges = np.full((n_feat, max(1, max(map(len, per_feat)))), np.inf)
    rank = np.zeros((T, I), dtype=np.int32)
    for f, v in enumerate(per_feat):
        edges[f, :len(v)] = v
        at = feature[t, i] == f
        thr = threshold[t[at], i[at]]
        rank[t[at], col[t[at], i[at]]] = np.where(
            np.isnan(thr), -1, np.searchsorted(v, thr, side="left"))
    lt, node = np.nonzero(leaf)
    lc = (np.cumsum(leaf, axis=1) - 1)[lt, node]
    paths = np.zeros((T, I, L), dtype=np.int8)
    left_turns = np.full((T, L), -1, dtype=np.int32)
    left_turns[lt, lc] = 0
    leaf_value = np.zeros((T, L), dtype=np.float64)
    leaf_value[lt, lc] = stack.value[lt, node]
    while True:
        p = parent[lt, node]
        up = p >= 0
        if not up.any():
            break
        lt, lc, node, p = lt[up], lc[up], node[up], p[up]
        side = turn[lt, node]
        paths[lt, col[lt, p], lc] = side
        left_turns[lt, lc] += side > 0
        node = p
    return DenseForest(edges, node_feature, rank, paths, left_turns, leaf_value,
                       np.float64(T))


def _row_ranks(jnp, edges, X):
    """Each row's rank in each feature's thresholds (how many lie below its
    value), int32; a NaN value ranks above every threshold, so it goes right
    at every node, as in the descent."""
    x = X[:, :edges.shape[0]]
    below = jnp.sum(edges[None, :, :] < x[:, :, None], axis=-1, dtype=jnp.int32)
    return jnp.where(jnp.isnan(x), edges.shape[1], below)


def _tree_chunk(n_trees: int, rows: int, leaves: int) -> int:
    """Trees a chunk of the dense traversal takes: the largest divisor of the
    tree count whose path sums stay within :data:`_CHUNK_ELEMENTS`."""
    fit = max(1, _CHUNK_ELEMENTS // (rows * leaves))
    return max(c for c in range(1, min(n_trees, fit) + 1) if n_trees % c == 0)


def _traverse(jnp, lax, feature, threshold, left, right, value, X, n_trees):
    """Mean over trees of each row's leaf value, folded in tree order.

    Takes one of two forms, told apart by the third table:

    * the descent (:class:`DescentForest`'s first five tables, ``X`` the
      float64 rows): the compiled twin of ``_ForestStack.predict_all``, every
      (tree, row) pair advancing until its node is a leaf;
    * the dense form (:class:`DenseForest`'s ``feature``, ``rank``, ``paths``,
      ``left_turns`` and ``leaf_value``, ``X`` the rows' ranks from
      :func:`_row_ranks`): for a chunk of trees at a time, an integer compare
      of every row at every internal node, an int8 product with the path
      matrices, and the one leaf whose path sum equals its left turns.

    Either way the per-tree values are added in tree order and divided by a
    *traced* tree count (see the module docstring's parity contract).
    """
    n = X.shape[0]
    if left.ndim == 3:
        T, I, L = left.shape
        c = _tree_chunk(T, n, L)

        def chunk(acc, tables):
            f, q, p, d, v = tables
            r = jnp.broadcast_to(X[None, :, 0, None], (c, n, I))
            for k in range(1, X.shape[1]):
                r = jnp.where(f[:, None, :] == k, X[None, :, k, None], r)
            go_left = (r <= q[:, None, :]).astype(jnp.int8)
            sums = jnp.einsum("cni,cil->cnl", go_left, p,
                              preferred_element_type=jnp.int32)
            # one column per row meets its left turns; a max of its index
            # compiles and runs as a plain reduce, where argmax pairs values
            # with indices
            column = lax.broadcasted_iota(jnp.int32, sums.shape, 2)
            leaf = jnp.max(jnp.where(sums == d[:, None, :], column, -1), axis=-1)
            y = jnp.take_along_axis(v, leaf, axis=1)
            for j in range(c):
                acc = acc + y[j]
            return acc, None

        split = tuple(a.reshape((T // c, c) + a.shape[1:])
                      for a in (feature, threshold, left, right, value))
        acc, _ = lax.scan(chunk, jnp.zeros((n,), value.dtype), split)
        return acc / n_trees

    T = feature.shape[0]
    rows = jnp.arange(T)[:, None]
    cols = jnp.arange(n)[None, :]

    def cond(node):
        return jnp.any(feature[rows, node] >= 0)

    def body(node):
        feat = feature[rows, node]
        active = feat >= 0
        x = X[cols, jnp.where(active, feat, 0)]
        go_left = x <= threshold[rows, node]
        nxt = jnp.where(go_left, left[rows, node], right[rows, node])
        return jnp.where(active, nxt, node)

    node = lax.while_loop(cond, body, jnp.zeros((T, n), dtype=jnp.int32))
    per_tree = value[rows, node]
    acc = lax.fori_loop(
        0, T, lambda i, a: a + per_tree[i], jnp.zeros((n,), per_tree.dtype)
    )
    return acc / n_trees


def _forest_rows(jnp, lax, forest, X):
    """:func:`_traverse` of ``X`` (float64 rows) through a resident forest."""
    if isinstance(forest, DenseForest):
        return _traverse(jnp, lax, *forest[1:6], _row_ranks(jnp, forest.edges, X),
                         forest.n_trees)
    return _traverse(jnp, lax, *forest[:5], X, forest.n_trees)


def _count_form(forest) -> None:
    dense = isinstance(forest, DenseForest)
    obs_metrics().inc(f"jax.traverse.{'dense' if dense else 'descent'}_groups")


@functools.lru_cache(maxsize=1)
def _forest_fn():
    jax, jnp, lax = jax_modules()

    def forest_traverse(forest, X):
        return _forest_rows(jnp, lax, forest, X)

    return jax.jit(forest_traverse, donate_argnums=(1,))


def resident_forest(stack) -> DenseForest | DescentForest:
    """A stacked forest's tables on the device, built and uploaded on first
    use and cached on the stack: the dense form where its path matrices fit
    :data:`DENSE_BUDGET_BYTES`, else the descent's node tables.

    A refit builds a new ``_ForestStack``, which retires the old arrays.  The
    copy runs under :func:`x64`, which keeps float64 (outside it ``device_put``
    narrows to float32); integer tables keep their dtypes.  Never donated.
    """
    with _upload_lock:
        tables = getattr(stack, "_jax_resident", None)
        if tables is None:
            host = _dense_tables(stack)
            if host is None:
                host = DescentForest(stack.feature, stack.threshold, stack.left,
                                     stack.right, stack.value,
                                     np.float64(stack.feature.shape[0]))
            with x64():
                tables = jax_modules()[0].device_put(host)
            reg = obs_metrics()
            reg.inc("jax.resident.uploads")
            reg.inc("jax.resident.bytes", _host_bytes(host))
            if isinstance(host, DenseForest):
                reg.inc("jax.resident.path_bytes", host.paths.nbytes)
            stack._jax_resident = tables
    return tables


def _resident_launch(oracle):
    """``oracle.launch_overhead_s`` as a float64 device scalar, cached on the
    oracle and uploaded again only when the value changes."""
    value = float(oracle.launch_overhead_s)
    cached = getattr(oracle, "_jax_launch", None)
    if cached is None or cached[0] != value:
        with x64():
            cached = (value, jax_modules()[0].device_put(np.float64(value)))
        oracle._jax_launch = cached
    return cached[1]


def forest_predict_raw(forest, X: np.ndarray) -> np.ndarray:
    """Jitted ``RandomForestRegressor.predict``: the mean-over-trees raw
    prediction, bitwise equal to the numpy fold."""
    X = np.asarray(X, dtype=np.float64)
    tables = resident_forest(forest._stacked())
    n, d = X.shape
    nb = bucket_rows(n)
    Xp = np.zeros((nb, d), dtype=np.float64)
    Xp[:n] = X
    args = (tables, Xp)
    _count_trace(
        "forest", _seen_forest_sigs,
        tuple(a.shape for a in tables) + ((nb, d),), args,
    )
    _count_form(tables)
    fn = _forest_fn()
    with x64(), span("forest.launch"):
        y = fn(*args)
    return np.asarray(y)[:n]


# -------------------------------------------------------------- network kernel
def _split(buf, sizes):
    """Consecutive slices of ``buf`` of the given static sizes."""
    out, at = [], 0
    for n in sizes:
        out.append(buf[at:at + n])
        at += n
    return out


@functools.lru_cache(maxsize=None)
def _network_fn(log_flags: tuple):
    """One-call Eq. 9-12 kernel for a fixed per-group log-target signature.

    ``log_flags`` decides at trace time which groups exponentiate inside the
    graph.  The call's own tables arrive in two host buffers, split at static
    offsets that follow from ``layout`` ``(Lb, Bb, Nb, X shapes)``, the bucket
    sizes: one int64 (each group's positions, the block and network segment
    ids, the overlap and fused flags as 0/1) and one float64 (counts, w, c,
    ops, repeats, each group's flattened features).  Everything else is traced
    or resident, so shape buckets are the only retrace axis.
    """
    jax, jnp, lax = jax_modules()

    def network_estimate(groups, ints, floats, launch, layout):
        Lb, Bb, Nb, x_shapes = layout
        *pos, block_seg, net_seg, overlap, fused = _split(
            ints, [nb for nb, _ in x_shapes] + [Lb + 1, Bb, Bb, Bb]
        )
        counts, w, c, ops, rep, *Xs = _split(
            floats, [Bb] * 5 + [nb * d for nb, d in x_shapes]
        )
        # Lb + 1 slots: the padded layer table and a dump slot for pad rows
        times = jnp.zeros((Lb + 1,), dtype=jnp.float64)
        for forest, p, X, shape, is_log in zip(groups, pos, Xs, x_shapes, log_flags):
            y = _forest_rows(jnp, lax, forest, X.reshape(shape))
            if is_log:
                y = jnp.exp(y)
            times = times.at[p].set(y)
        # Eq. 10 first term / Eq. 9: per-block left-fold sum and max.  Padded
        # layer rows carry segment id Bb (the dump segment, sliced away).
        sums = jax.ops.segment_sum(times, block_seg, num_segments=Bb + 1)[:Bb]
        maxs = jax.ops.segment_max(times, block_seg, num_segments=Bb + 1)[:Bb]
        t = sums - launch * jnp.maximum(0.0, counts - 1.0)
        t = jnp.where(fused != 0, t - (ops * w + c), t)  # Eq. 10/11
        t = jnp.where(overlap != 0, maxs, t)  # Eq. 9
        t = jnp.maximum(t, jnp.where(counts > 0.0, launch, 0.0))
        # Eq. 12: per-network sum of block time x repeat; padded blocks have
        # rep == 0 and net segment Nb (the dump segment).
        return jax.ops.segment_sum(t * rep, net_seg, num_segments=Nb + 1)

    return jax.jit(network_estimate, static_argnums=(4,), donate_argnums=(2,))


def predict_network_batch_jax(oracle, batch, net_id, n_nets) -> np.ndarray | None:
    """Compiled Eq. 9-12 over a :class:`BlockBatch`; None = use the numpy path.

    Falls back (returns None) for stub estimators, empty forests, and blocks
    with zero layers — the numpy path owns those semantics (including the
    empty-overlap-block ``ValueError``).
    """
    n_blocks = len(batch)
    counts = batch.layer_counts()
    if n_blocks == 0 or np.any(counts == 0):
        return None
    n_nets = int(n_nets)
    with span("network.pack"):
        packed = _pack_network(oracle, batch, counts, net_id, n_nets)
    if packed is None:
        return None
    log_flags, args = packed
    fn = _network_fn(log_flags)
    with x64(), span("network.launch"):
        out = fn(*args)
    return np.asarray(out)[:n_nets]


def _pack_network(oracle, batch, counts, net_id, n_nets: int) -> tuple | None:
    """``(log_flags, args)`` of the network program for ``batch``: the
    resident forests, features, bucket padding, positions, and the block and
    network segment tables, packed into one int64 and one float64 buffer."""
    ests = []
    for lt in batch.group_types:
        try:
            est = oracle.estimators[lt]
        except KeyError:
            return None  # numpy path raises the canonical KeyError
        forest = getattr(est, "forest", None)
        if not hasattr(est, "_features") or forest is None or not getattr(
            forest, "_trees", None
        ):
            return None
        ests.append(est)

    from repro.core.blocks import block_ops_batch

    n_blocks = len(batch)
    L = batch.n_layers
    Lb = bucket_rows(L)
    Bb = bucket_rows(n_blocks)
    net_id = np.asarray(net_id, dtype=np.int64)
    Nb = bucket_rows(max(1, n_nets))

    groups = []
    pos = []
    Xs = []
    log_flags = []
    pad_rows = ssm_rows = mla_rows = 0
    for g, (lt, est, cfgs) in enumerate(zip(batch.group_types, ests, batch.group_configs)):
        X = est._features(cfgs, snap=True)
        ng, d = X.shape
        nb = bucket_rows(ng)
        pad_rows += nb - ng
        if lt in SSM_LAYER_TYPES:
            ssm_rows += ng
        elif lt in MLA_LAYER_TYPES:
            mla_rows += ng
        Xp = np.zeros((nb, d), dtype=np.float64)
        Xp[:ng] = X
        p = np.full(nb, Lb, dtype=np.int64)  # pads write the dump slot
        p[:ng] = np.flatnonzero(batch.group_of == g)
        forest = resident_forest(est.forest._stacked())
        _count_form(forest)
        groups.append(forest)
        pos.append(p)
        Xs.append(Xp)
        log_flags.append(bool(getattr(est, "log_target", False)))

    block_seg = np.full(Lb + 1, Bb, dtype=np.int64)
    block_seg[:L] = batch.block_id
    counts_p = np.zeros(Bb, dtype=np.float64)
    counts_p[:n_blocks] = counts
    overlap = np.zeros(Bb, dtype=bool)
    overlap[:n_blocks] = [k in oracle.overlap_kinds for k in batch.kinds]
    fused = np.zeros(Bb, dtype=bool)
    w = np.zeros(Bb, dtype=np.float64)
    c = np.zeros(Bb, dtype=np.float64)
    for i, kind in enumerate(batch.kinds):
        fm = oracle.fusing.get(kind)
        if fm is not None and kind not in oracle.overlap_kinds:
            fused[i] = True
            w[i] = fm.w
            c[i] = fm.c
    ops = np.zeros(Bb, dtype=np.float64)
    if fused.any():
        ops[:n_blocks] = block_ops_batch(batch)
    rep = np.zeros(Bb, dtype=np.float64)
    rep[:n_blocks] = batch.repeat
    net_seg = np.full(Bb, Nb, dtype=np.int64)
    net_seg[:n_blocks] = net_id

    # Two host buffers per call, in the order network_estimate splits them.
    ints = np.concatenate([*pos, block_seg, net_seg, overlap, fused], dtype=np.int64)
    floats = np.concatenate([counts_p, w, c, ops, rep, *(X.ravel() for X in Xs)])
    layout = (Lb, Bb, Nb, tuple(X.shape for X in Xs))
    args = (tuple(groups), ints, floats, _resident_launch(oracle), layout)
    _count_trace(
        "network", _seen_network_sigs,
        (tuple(log_flags), layout) + tuple(tuple(a.shape for a in g) for g in groups),
        args,
    )
    reg = obs_metrics()
    reg.inc("jax.network.rows", L)
    reg.inc("jax.network.pad_rows", pad_rows)
    reg.inc("jax.network.rows.ssm", ssm_rows)
    reg.inc("jax.network.rows.mla", mla_rows)
    return tuple(log_flags), args
