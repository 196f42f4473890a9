"""repro.api — the public entry point to the PR-benchmarking pipeline.

Typical flow (the paper's Fig. 1, campaign-level)::

    from repro.api import Campaign, CampaignSpec, EstimatorHub, PerfOracle

    spec = CampaignSpec(platform="ultratrail", n_samples=1500, hub_dir="hub/")
    oracle = Campaign(spec).run()                  # sweeps -> PRs -> forest
    oracle.predict("conv1d", [{"C": 40, "K": 16, ...}])

    # later / elsewhere: reload without re-measuring anything
    oracle = PerfOracle.load(EstimatorHub("hub/"), "ultratrail")

See README.md for the end-to-end quickstart.
"""

from repro.api.cache import CachedPlatform, MeasurementCache
from repro.api.campaign import Campaign, CampaignSpec, train_layer_estimator
from repro.api.hub import EstimatorHub
from repro.api.oracle import PerfOracle
from repro.registry import get_platform, list_platforms, register_platform
from repro.core.batch import BlockBatch, ConfigBatch
from repro.runtime import (
    DegradationReport,
    FaultPlan,
    HealthPolicy,
    MeasurementRuntime,
    RunStats,
    RuntimeSpec,
)

__all__ = [
    "BlockBatch",
    "CachedPlatform",
    "Campaign",
    "CampaignSpec",
    "ConfigBatch",
    "DegradationReport",
    "EstimatorHub",
    "FaultPlan",
    "HealthPolicy",
    "MeasurementCache",
    "MeasurementRuntime",
    "PerfOracle",
    "RunStats",
    "RuntimeSpec",
    "get_platform",
    "list_platforms",
    "register_platform",
    "train_layer_estimator",
]
