"""Ruya's primary contribution: memory-aware two-phase Bayesian config search.

Pipeline (paper §III): single-machine profiling runs on dataset samples
(`profiler`) → OLS/R² memory-usage categorization (`memory_model`) →
memory-aware search-space split (`search_space`) → GP+EI Bayesian-optimized
iterative search, priority group first (`bayesopt`, `gp`, `acquisition`) —
orchestrated end to end by `tuner`.
"""

from repro.core.acquisition import expected_improvement, probability_of_improvement
from repro.core.bayesopt import (
    BOSettings,
    SearchTrace,
    cherrypick_search,
    ruya_search,
)
from repro.core.gp import (
    GPPosterior,
    fit_gp,
    gp_predict,
    matern52,
    matern52_from_sqdist,
    pairwise_sqdist,
)
from repro.core.memory_model import (
    MemoryCategory,
    MemoryModel,
    fit_memory_model,
)
from repro.core.profiler import ProfileResult, profile_job, schedule_sample_sizes
from repro.core.search_space import (
    Configuration,
    SearchSpace,
    split_priority_mask,
    split_search_space,
)
from repro.core.tuner import RuyaReport, run_cherrypick, run_ruya

__all__ = [
    "BOSettings",
    "Configuration",
    "GPPosterior",
    "MemoryCategory",
    "MemoryModel",
    "ProfileResult",
    "RuyaReport",
    "SearchSpace",
    "SearchTrace",
    "cherrypick_search",
    "expected_improvement",
    "fit_gp",
    "fit_memory_model",
    "gp_predict",
    "matern52",
    "matern52_from_sqdist",
    "pairwise_sqdist",
    "probability_of_improvement",
    "profile_job",
    "ruya_search",
    "run_cherrypick",
    "run_ruya",
    "schedule_sample_sizes",
    "split_priority_mask",
    "split_search_space",
]
