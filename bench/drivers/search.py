"""Closed-loop driver of mesh searches through the advisor, one caller.

Each step ranks every candidate (dp, tp, microbatches) layout of the
configuration's model for one (input shape, chip count) pair with
``autotune`` on the jax path.  The pairs come as a seeded order of one fixed
cycle, so every seed does the same work.  Every pair is searched once in
set-up, which compiles each shape bucket the window uses.

Traffic parameters: ``shapes`` (name -> seq_len, global_batch, kind),
``chips``, ``check_steps`` compared after the window.
"""

from __future__ import annotations

import itertools
import math
import shutil
import time

import numpy as np

from bench import estimators
from bench.common import seed_key
from bench.generate import balanced_cycle
from bench.reference import max_rel_gap, score_search
from repro.core.advisor import autotune, default_candidates
from repro.models.config import InputShape, ModelConfig


def _pairs(traffic) -> list[tuple[str, int]]:
    return list(itertools.product(sorted(traffic["shapes"]), traffic["chips"]))


def setup(run, log) -> None:
    cell = run.cell
    hub, platform, forests = estimators.build(cell)
    oracle = estimators.load_oracle(hub, platform)
    model = ModelConfig(**cell.config["model"])
    shapes = {name: InputShape(name=name, **s) for name, s in cell.traffic["shapes"].items()}
    for name, chips in _pairs(cell.traffic):
        autotune(oracle, model, shapes[name], default_candidates(chips))
    run.state.update(hub=hub, forests=forests, oracle=oracle, model=model, shapes=shapes)


def window(run, seconds: float) -> dict:
    st = run.state
    oracle, model, shapes = st["oracle"], st["model"], st["shapes"]
    pairs = balanced_cycle(_pairs(run.cell.traffic), run.seed)
    answers = []
    networks = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        name, chips = next(pairs)
        with run.span("bench.search"):
            ranking = autotune(oracle, model, shapes[name], default_candidates(chips))
        networks += sum(1 for _, s in ranking if math.isfinite(s))
        answers.append((name, chips, {(c.dp, c.tp, c.microbatches): s for c, s in ranking}))
    elapsed = time.perf_counter() - t0
    st["answers"] = answers
    return {"attempted": len(answers), "failed": 0,
            "e2e": {"networks_per_s": networks / elapsed},
            "info": {"steps": len(answers), "networks": networks, "window_s": elapsed}}


def release(run) -> None:
    run.state.pop("oracle", None)
    shutil.rmtree(run.state.get("hub", ""), ignore_errors=True)


def _sample(run) -> list:
    answers = run.state["answers"]
    rng = np.random.default_rng(seed_key(run.seed, 7))
    n = min(len(answers), int(run.cell.traffic["check_steps"]))
    pick = set(rng.choice(len(answers), size=n, replace=False).tolist()) if answers else set()
    if answers:
        pick.add(max(range(len(answers)), key=lambda i: len(answers[i][2])))
    return [answers[i] for i in sorted(pick)]


def gap(run, control: bool = False) -> float:
    """Widest relative gap of sampled answers from the float64 reference;
    with ``control`` the answers are the reference's own, in float32."""
    st = run.state
    cell = run.cell
    forests = st["forests"]
    launch = float(cell.config["launch_overhead_s"])
    worst = 0.0
    memo: dict = {}
    for name, chips, got in _sample(run):
        if (name, chips) not in memo:
            args = (cell.config["model"], cell.traffic["shapes"][name], chips, forests, launch)
            memo[(name, chips)] = (score_search(*args),
                                   score_search(*args, np.float32) if control else None)
        ref, low = memo[(name, chips)]
        got = low if control else got
        if set(ref) != set(got):
            return math.inf
        keys = sorted(ref)
        worst = max(worst, max_rel_gap([got[k] for k in keys], [ref[k] for k in keys]))
    return worst


def check(run, log) -> list[tuple[str, float, float]]:
    c = run.delta["counters"]
    traces = c.get("jax.network.traces", 0) + c.get("jax.forest.traces", 0)
    return [
        ("max_rel_gap", gap(run), float(run.cell.config["limits"]["max_rel_gap"])),
        ("window_compiles", float(traces), 0.0),
    ]


def control(run, log) -> dict:
    return {"max_rel_gap": gap(run, control=True)}
