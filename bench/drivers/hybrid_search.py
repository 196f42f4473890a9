"""Closed-loop mesh search of a model built by a layer pattern, one caller.

The window is the mesh-search driver's own (``bench/drivers/search.py``):
each step ranks every candidate (dp, tp, microbatches) layout of one
(input shape, chip count) pair with ``autotune`` on the jax path, the pairs
in a seeded order of one fixed cycle, each searched once in set-up.  Set-up
and the check differ where the model does: the forests are made and read
with the Mamba-2 layer types' features, and the answers are compared with
the plain restatement of the pattern decomposition
(``bench/hybrid_reference.py``).

Traffic parameters: ``shapes`` (name -> seq_len, global_batch, kind),
``chips``, ``check_steps`` compared after the window.
"""

from __future__ import annotations

import math
import tempfile
from pathlib import Path

import numpy as np

from bench import estimators, hybrid_reference
from bench.drivers import search
from bench.reference import max_rel_gap
from repro.core.advisor import autotune, default_candidates
from repro.models.config import InputShape, ModelConfig

window = search.window
release = search.release


def build(config: dict) -> tuple[Path, dict]:
    """A hub in the program's format holding the configuration's forests, as
    ``bench/estimators.py`` writes it: its directory and the benchmark's own
    forests by layer type (the reference's)."""
    from repro.api import EstimatorHub, PerfOracle
    from repro.core.estimator import LayerEstimator
    from repro.core.forest import RandomForestRegressor, _Tree
    from repro.core.prs import ParamSpace

    made = hybrid_reference.make(config)
    fc = config["forest"]
    ests = {}
    for lt, f in made.items():
        spec = config["layer_types"][lt]
        rf = RandomForestRegressor(n_estimators=int(fc["trees"]), max_depth=int(fc["max_depth"]),
                                   seed=int(fc["seed"]))
        rf._trees = [_Tree(*(a.copy() for a in t)) for t in f.trees]
        ests[lt] = LayerEstimator(
            layer_type=lt, params=tuple(f.params), widths=dict(f.widths),
            space=ParamSpace(ranges={p: tuple(r) for p, r in spec["ranges"].items()},
                             fixed=dict(spec["fixed"])),
            forest=rf, n_train=int(fc["samples"]), log_target=True)
    path = Path(tempfile.mkdtemp(prefix="bench-hub-"))
    PerfOracle(estimators=ests, platform_name=config["platform"],
               launch_overhead_s=float(config["launch_overhead_s"])).save(EstimatorHub(str(path)))
    return path, made


def setup(run, log) -> None:
    cell = run.cell
    # first, so that a program without layer patterns fails before any work
    model = ModelConfig(**cell.config["model"])
    hub, forests = build(cell.config)
    oracle = estimators.load_oracle(hub, cell.config["platform"])
    shapes = {name: InputShape(name=name, **s) for name, s in cell.traffic["shapes"].items()}
    for name, chips in search._pairs(cell.traffic):
        autotune(oracle, model, shapes[name], default_candidates(chips))
    run.state.update(hub=hub, forests=forests, oracle=oracle, model=model, shapes=shapes)


def gap(run, control: bool = False) -> float:
    """Widest relative gap of sampled answers from the float64 reference;
    with ``control`` the answers are the reference's own, in float32."""
    cell = run.cell
    forests = run.state["forests"]
    launch = float(cell.config["launch_overhead_s"])
    worst = 0.0
    memo: dict = {}
    for name, chips, got in search._sample(run):
        if (name, chips) not in memo:
            args = (cell.config["model"], cell.traffic["shapes"][name], chips, forests, launch)
            memo[(name, chips)] = (
                hybrid_reference.score_search(*args),
                hybrid_reference.score_search(*args, np.float32) if control else None)
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
