"""Scout-like cluster evaluation substrate (paper §IV).

The paper evaluates on the Scout dataset (Hsu et al., "Arrow") — 1031 Spark
and Hadoop executions over 69 AWS cluster configurations.  That dataset is
not bundled in this offline container, so this package *emulates* it from the
paper's published structure: the 69-config grid (`nodes`), the 16 jobs of
Table I with their memory categories and GB requirements (`workloads`), and
deterministic cost surfaces exhibiting the Fig. 1 memory cliff (`simulator`).
"""

from repro.cluster.nodes import (
    ClusterConfig,
    NodeType,
    NODE_TYPES,
    enumerate_cluster_configs,
    make_cluster_search_space,
)
from repro.cluster.faults import FaultPlan
from repro.cluster.pricing import (
    CATALOGS,
    PriceCatalog,
    SpotSchedule,
    default_catalogs,
    family_indices,
)
from repro.cluster.workloads import (
    JOBS,
    JobSpec,
    PricingScenario,
    drift_spec,
    failure_scenario_jobs,
    family_constrained_scenarios,
    pricing_scenarios,
    spot_volatility_scenarios,
)
from repro.cluster.catalog import (
    enumerate_catalog,
    make_catalog_space,
)
from repro.cluster.simulator import (
    ClusterSimulator,
    job_cost_table,
    job_runtime_table,
    make_profile_run_fn,
)

__all__ = [
    "CATALOGS",
    "ClusterConfig",
    "ClusterSimulator",
    "FaultPlan",
    "JOBS",
    "JobSpec",
    "NODE_TYPES",
    "NodeType",
    "PriceCatalog",
    "PricingScenario",
    "SpotSchedule",
    "default_catalogs",
    "drift_spec",
    "enumerate_catalog",
    "enumerate_cluster_configs",
    "failure_scenario_jobs",
    "family_constrained_scenarios",
    "family_indices",
    "job_cost_table",
    "job_runtime_table",
    "make_catalog_space",
    "make_cluster_search_space",
    "make_profile_run_fn",
    "pricing_scenarios",
    "spot_volatility_scenarios",
]
