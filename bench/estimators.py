"""The estimators a configuration serves, handed to the program.

Set-up makes every layer type's forest from the configuration's build seed
(``bench/forests.py``) and writes them, with the Eq. 10 launch overhead,
into a hub in the program's own format, through the program's persistence
API, in a temporary directory of the run.  The program loads its oracle
from that hub as a deployment would; the reference keeps the arrays the
benchmark made, and never reads the hub back.
"""

from __future__ import annotations

import tempfile
from pathlib import Path

from bench import forests


def build(cell) -> tuple[Path, str, dict]:
    """A hub of the cell's configuration: its directory, platform name, and
    the benchmark's own forests by layer type (the reference's)."""
    from repro.api import EstimatorHub, PerfOracle
    from repro.core.estimator import LayerEstimator
    from repro.core.forest import RandomForestRegressor, _Tree
    from repro.core.prs import ParamSpace

    config = cell.config
    made = forests.make(config)
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
    platform = config["platform"]
    PerfOracle(estimators=ests, platform_name=platform,
               launch_overhead_s=float(config["launch_overhead_s"])).save(EstimatorHub(str(path)))
    return path, platform, made


def load_oracle(path: Path, platform: str):
    """The program's oracle over the hub, on the compiled (jax) path."""
    from repro.api import EstimatorHub, PerfOracle

    return PerfOracle.load(EstimatorHub(str(path)), platform, predict_backend="jax")
