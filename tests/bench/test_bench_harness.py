"""BENCHMARK.json against its contract, discovery of cells by name, and the
traffic generators' determinism (bench/common.py, bench/generate.py)."""

import hashlib
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import generate
from bench.common import Cell, benchmark, derived_seed, percentile, seed_key

ROOT = Path(__file__).resolve().parents[2]
BENCH = benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/")
        assert ".." not in p.split("/") and (ROOT / p).is_dir()
    assert len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells has to fit its time budget
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    pairs = set()
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = Cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer() and all(m["moves"] in reported for m in cell.per_layer())
        assert (ROOT / "bench" / "drivers" / f"{cell.traffic['driver']}.py").is_file()


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_cell_added_as_files_is_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    before = _digest(tmp_path / "bench")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "olmoe-wide.v5e.json").write_text(
        (ROOT / "bench" / "configs" / "olmoe-1b-7b.v5e.json").read_text())
    (tmp_path / "bench" / "traffic" / "table_large.json").write_text(
        json.dumps({"driver": "table", "rows": 65536, "keep_every": 25, "check_steps": 4,
                    "cost_calls": 1}))
    (tmp_path / "bench" / "metrics" / "rows_per_call.table_large.py").write_text(
        "def read(run):\n    return 65536.0\n")
    bench["configs"].append({"name": "olmoe-wide.v5e", "source": "x", "reduced": [],
                             "file": "bench/configs/olmoe-wide.v5e.json", "why": "x"})
    bench["workloads"].append({"name": "olmoe.table_large", "config": "olmoe-wide.v5e",
                               "traffic": "table_large", "chips": 1, "why": "x"})
    rows = next(m for m in bench["end_to_end"] if m["name"] == "layer_rows_per_s")
    rows["workloads"].append("olmoe.table_large")
    bench["per_layer"].append({"name": "rows_per_call.table_large", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "device", "moves": "layer_rows_per_s",
                               "workloads": ["olmoe.table_large"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell("olmoe.table_large", root=tmp_path)
    assert cell.traffic["rows"] == 65536 and cell.config["model"]["d_model"] == 2048
    assert cell.driver().__name__ == "bench_driver_table"
    assert [m["name"] for m in cell.per_layer()] == ["rows_per_call.table_large"]
    assert cell.metric_reader("rows_per_call.table_large").read(None) == 65536.0
    assert {m["name"] for m in cell.end_to_end()} == {"layer_rows_per_s", "setup_s"}
    after = _digest(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        Cell("no.such_cell")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_generators_repeat_for_a_seed_and_differ_across_seeds(seed):
    cfg = Cell("olmoe.layer_table").config
    spaces = cfg["layer_types"]
    types = list(spaces)
    it_a, it_b, it_c = (generate.balanced_cycle(list(range(6)), s) for s in (seed, seed, seed + 1))
    a, b, c = ([next(it) for _ in range(60)] for it in (it_a, it_b, it_c))
    assert a == b and a != c
    assert all(sorted(a[i:i + 6]) == list(range(6)) for i in range(0, 60, 6))
    lt1, x1 = generate.table_step(spaces, types, 256, seed, 3)
    lt2, x2 = generate.table_step(spaces, types, 256, seed, 3)
    _, x3 = generate.table_step(spaces, types, 256, seed + 1, 3)
    assert lt1 == lt2 == types[3]
    assert all(np.array_equal(x1[p], x2[p]) for p in x1)
    assert not all(np.array_equal(x1[p], x3[p]) for p in x1)
    traffic = {"rate_per_s": 500, "zipf_theta": 0.99}
    u = generate.KeyUniverse(spaces, types, 4096, seed)
    d1, k1 = generate.serve_schedule(traffic, u, seed, 2.0)
    d2, k2 = generate.serve_schedule(traffic, u, seed, 2.0)
    d3, k3 = generate.serve_schedule(traffic, generate.KeyUniverse(spaces, types, 4096, seed + 1),
                                     seed + 1, 2.0)
    assert np.array_equal(d1, d2) and np.array_equal(k1, k2)
    assert len(d1) == len(d3) == 1000 and not np.array_equal(k1, k3)
    assert 0.0 <= d1.min() and d1.max() < 2.0 and np.all(np.diff(d1) >= 0)


def test_key_universe_and_zipf():
    cfg = Cell("olmoe.layer_table").config
    types = list(cfg["layer_types"])
    u = generate.KeyUniverse(cfg["layer_types"], types, 1000, 3)
    lt, c = u.config(7)
    assert lt == types[7 % len(types)]
    space = cfg["layer_types"][lt]
    assert all(space["ranges"][p][0] <= c[p] <= space["ranges"][p][1] for p in space["ranges"])
    assert all(c[p] == v for p, v in space["fixed"].items())
    assert sorted(u.rank_to_key.tolist()) == list(range(1000))
    ranks = generate.zipf_ranks(1000, 0.99, 20000, np.random.default_rng(1))
    assert ranks.min() >= 0 and ranks.max() < 1000
    assert np.mean(ranks == 0) > np.mean(ranks == 10) > np.mean(ranks == 500)


def test_seeds_and_statistics():
    assert seed_key(-1, 2) == [2**63 - 1, 2]
    assert derived_seed(2**40, 1) == derived_seed(2**40, 1) != derived_seed(2**40, 2)
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert percentile(list(range(1, 101)), 99) == 99


@pytest.mark.parametrize("bare", [False, True])
def test_no_chip_or_no_program_means_no_result(tmp_path, bare):
    """Without a TPU the command fails and prints no result; so it does in a
    directory that holds only BENCHMARK.json and the benchmark's paths."""
    root = ROOT
    if bare:
        root = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", root)
        for p in BENCH["paths"]:
            shutil.copytree(ROOT / p, root / p,
                            ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", "olmoe.layer_table",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
