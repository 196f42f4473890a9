"""Closed-loop driver of latency-table fills, one caller.

Each step predicts ``rows`` configs of one layer type (types in turn) with
``PerfOracle.predict`` on the jax path; the configs are drawn afresh for
each step, uniformly over the type's parameter space, from (seed, step).
Set-up predicts one batch of each type, which compiles the row bucket.

Traffic parameters: ``rows``, ``check_steps`` compared after the window,
``cost_calls`` per layer type whose node visits price a traced window.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from bench import estimators
from bench.common import seed_key
from bench.generate import table_step
from bench.reference import max_rel_gap, traversal_cost
from repro.core.batch import ConfigBatch


def setup(run, log) -> None:
    cell = run.cell
    hub, platform, forests = estimators.build(cell)
    oracle = estimators.load_oracle(hub, platform)
    spaces = cell.config["layer_types"]
    types = list(spaces)
    rows = int(cell.traffic["rows"])
    for i in range(len(types)):
        lt, cols = table_step(spaces, types, rows, run.seed ^ 0x5A5A, i)
        oracle.predict(lt, ConfigBatch.from_columns(cols), backend="jax")
    run.state.update(hub=hub, forests=forests, oracle=oracle, types=types,
                     spaces=spaces, rows=rows)


def window(run, seconds: float) -> dict:
    st = run.state
    oracle, types, spaces, rows = st["oracle"], st["types"], st["spaces"], st["rows"]
    rng = np.random.default_rng(seed_key(run.seed, 8))
    keep_every = max(1, int(run.cell.traffic["keep_every"]))
    kept = {}
    step = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        lt, cols = table_step(spaces, types, rows, run.seed, step)
        batch = ConfigBatch.from_columns(cols)
        with run.span("bench.table"):
            y = oracle.predict(lt, batch, backend="jax")
        if step < len(types) or rng.integers(keep_every) == 0:
            kept[step] = y
        step += 1
    elapsed = time.perf_counter() - t0
    st.update(kept=kept, steps=step)
    return {"attempted": step, "failed": 0,
            "e2e": {"layer_rows_per_s": step * rows / elapsed},
            "info": {"steps": step, "window_s": elapsed, "kept": len(kept)}}


def release(run) -> None:
    run.state.pop("oracle", None)
    shutil.rmtree(run.state.get("hub", ""), ignore_errors=True)


def gap(run, control: bool = False) -> float:
    """Widest relative gap of sampled steps' answers from the float64
    reference; with ``control`` the answers are the reference's, in float32."""
    st = run.state
    forests = st["forests"]
    kept = st["kept"]
    rng = np.random.default_rng(seed_key(run.seed, 9))
    steps = sorted(kept)
    n = min(len(steps), int(run.cell.traffic["check_steps"]))
    pick = sorted(set(steps[: len(st["types"])])
                  | set(rng.choice(steps, size=n, replace=False).tolist()))
    worst = 0.0
    for step in pick:
        lt, cols = table_step(st["spaces"], st["types"], st["rows"], run.seed, step)
        ref, _ = forests[lt].predict(cols)
        got = forests[lt].predict(cols, np.float32)[0] if control else kept[step]
        worst = max(worst, max_rel_gap(got, ref))
    return worst


def check(run, log) -> list[tuple[str, float, float]]:
    c = run.delta["counters"]
    out = [
        ("max_rel_gap", gap(run), float(run.cell.config["limits"]["max_rel_gap"])),
        ("window_compiles", float(c.get("jax.forest.traces", 0)), 0.0),
    ]
    if run.trace:
        run.stats["forest_cost"] = forest_cost(run)
    return out


def forest_cost(run) -> dict:
    """Operations and bytes of the window's traversals, from the node visits
    of the reference descent: each layer type's mean over a seeded sample of
    its steps, times its steps."""
    st = run.state
    forests = st["forests"]
    types = st["types"]
    rng = np.random.default_rng(seed_key(run.seed, 10))
    ops = bytes_ = 0.0
    for i, lt in enumerate(types):
        steps = list(range(i, st["steps"], len(types)))
        if not steps:
            continue
        pick = rng.choice(steps, size=min(len(steps), int(run.cell.traffic["cost_calls"])),
                          replace=False)
        o = b = 0.0
        for step in pick:
            _, cols = table_step(st["spaces"], types, st["rows"], run.seed, int(step))
            _, visits = forests[lt].predict(cols)
            oo, bb = traversal_cost(visits, st["rows"] * len(forests[lt].trees))
            o, b = o + oo, b + bb
        ops += o / len(pick) * len(steps)
        bytes_ += b / len(pick) * len(steps)
    return {"ops": ops, "bytes": bytes_, "calls": st["steps"]}


def control(run, log) -> dict:
    return {"max_rel_gap": gap(run, control=True)}
