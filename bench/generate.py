"""Traffic generators: everything a window sends is drawn here from the seed.

Each generator is a pure function of its parameters and the run's seed, so
the same seed gives the same traffic and another seed another order or draw
of it.  Mixes whose work should not change with the seed (the mesh search)
draw a seeded order of one fixed cycle.  No jax import: the serving client
runs these in a process that must never touch the chip.
"""

from __future__ import annotations

import numpy as np

from bench.common import seed_key


def balanced_cycle(items: list, seed: int):
    """Endless stream of ``items`` in which each run of ``len(items)`` holds
    every item once, in a seeded order: every seed does the same work in
    another order."""
    rng = np.random.default_rng(seed_key(seed, 1))
    while True:
        for i in rng.permutation(len(items)):
            yield items[i]


def uniform_columns(space: dict, n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    """``n`` configs drawn uniformly over a parameter space
    (``{"ranges": {p: [lo, hi]}, "fixed": {p: v}}``), as int64 columns."""
    cols = {p: rng.integers(lo, hi + 1, size=n, dtype=np.int64)
            for p, (lo, hi) in space["ranges"].items()}
    for p, v in space.get("fixed", {}).items():
        cols[p] = np.full(n, int(v), dtype=np.int64)
    return cols


def table_step(spaces: dict, layer_types: list, rows: int, seed: int, step: int):
    """The layer type and configs of one latency-table step: types in turn,
    rows drawn afresh for every step from (seed, step)."""
    lt = layer_types[step % len(layer_types)]
    rng = np.random.default_rng(seed_key(seed, 2, step))
    return lt, uniform_columns(spaces[lt], rows, rng)


class KeyUniverse:
    """``n_keys`` distinct configs over several layer types' spaces.

    Key ``k`` is of type ``k % len(types)``; its config is row ``k // len(types)``
    of that type's seeded table.  Zipf ranks map onto keys through a seeded
    permutation, so the hottest keys spread over the types.
    """

    def __init__(self, spaces: dict, layer_types: list, n_keys: int, seed: int) -> None:
        self.types = list(layer_types)
        self.n_keys = int(n_keys)
        per = -(-self.n_keys // len(self.types))
        self.tables = {}
        for i, lt in enumerate(self.types):
            rng = np.random.default_rng(seed_key(seed, 3, i))
            cols = uniform_columns(spaces[lt], per, rng)
            self.tables[lt] = (list(cols), np.stack([cols[p] for p in cols], axis=1))
        self.rank_to_key = np.random.default_rng(seed_key(seed, 4)).permutation(self.n_keys)

    def config(self, key: int) -> tuple[str, dict]:
        lt = self.types[key % len(self.types)]
        names, table = self.tables[lt]
        row = table[key // len(self.types)]
        return lt, {p: int(v) for p, v in zip(names, row)}


def zipf_ranks(n_keys: int, theta: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` ranks in ``[0, n_keys)`` with P(rank r) proportional to 1/(r+1)^theta."""
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), n_keys - 1)


def poisson_arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)``
    arrivals with exponential gaps, scaled to fill ``[0, seconds)``: a Poisson
    stream whose count, and so whose work, is the same for every seed."""
    n = max(1, round(rate * seconds))
    t = np.cumsum(rng.exponential(1.0, size=n + 1))
    return t[:n] * (seconds / t[n])


def serve_schedule(traffic: dict, universe: KeyUniverse, seed: int, seconds: float):
    """Due times and keys of one open-loop serving window."""
    rng = np.random.default_rng(seed_key(seed, 5))
    due = poisson_arrivals(float(traffic["rate_per_s"]), seconds, rng)
    ranks = zipf_ranks(universe.n_keys, float(traffic["zipf_theta"]), len(due), rng)
    return due, universe.rank_to_key[ranks]
