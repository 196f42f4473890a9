"""Shared pieces of the on-chip benchmark: finding a cell's files by name,
seeds, statistics, and the program's counters.

Everything a cell needs is found from ``BENCHMARK.json`` by name:

* ``bench/configs/<config>.json``   -- the configuration's sizes;
* ``bench/traffic/<traffic>.json``  -- the traffic mix's parameters, whose
  ``driver`` key names the general driver in ``bench/drivers/<driver>.py``;
* ``bench/metrics/<metric>.py``     -- the reader of one per-layer metric.

No module here imports jax: the serving client runs in a process that must
never touch the chip.
"""

from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

#: seeds are reduced into this range before they key numpy's generators
_SEED_MOD = 2**63


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = CHECKOUT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import one file by path (metric and driver files are found by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and traffic."""

    def __init__(self, name: str, root: Path = CHECKOUT, overrides: dict | None = None) -> None:
        bench = benchmark(root)
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(by_name)}")
        self.root = root
        self.bench = bench
        self.workload = by_name[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.traffic = load_json(root / "bench" / "traffic" / f"{self.workload['traffic']}.json")
        self.traffic.update(overrides or {})
        self.chips = int(self.workload["chips"])

    def driver(self):
        d = self.traffic["driver"]
        return load_module(self.root / "bench" / "drivers" / f"{d}.py", f"bench_driver_{d}")

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if _applies(m, self.name)]

    def per_layer(self) -> list[dict]:
        """Per-layer metrics read in this cell: those listing it, and those
        without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def metric_reader(self, name: str):
        return load_module(self.root / "bench" / "metrics" / f"{name}.py",
                           "bench_metric_" + name.replace(".", "_"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def seed_key(seed: int, *stream: int) -> list[int]:
    """Entropy for ``numpy.random.default_rng``: the run's seed and a stream."""
    return [int(seed) % _SEED_MOD, *[int(s) for s in stream]]


def derived_seed(seed: int, *stream: int) -> int:
    """A 32-bit seed for program APIs that take an int, from (seed, stream)."""
    import numpy as np

    return int(np.random.SeedSequence(seed_key(seed, *stream)).generate_state(1)[0])


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list (q in 0..100)."""
    if not sorted_values:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * len(sorted_values)))
    return sorted_values[k - 1]


def program_counters() -> dict:
    """Snapshot of the program's process-wide counters and histogram totals."""
    from repro.obs.metrics import metrics

    snap = metrics().snapshot()
    hist = {k: (v.get("count", 0), v.get("total", 0.0))
            for k, v in snap.get("histograms", {}).items()}
    return {"counters": dict(snap.get("counters", {})), "histograms": hist}


def counters_delta(before: dict, after: dict) -> dict:
    c = {k: after["counters"].get(k, 0) - before["counters"].get(k, 0)
         for k in after["counters"]}
    h = {}
    for k, (n, tot) in after["histograms"].items():
        n0, t0 = before["histograms"].get(k, (0, 0.0))
        h[k] = (n - n0, tot - t0)
    return {"counters": c, "histograms": h}
