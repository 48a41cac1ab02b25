"""The paper's 69-config grid and its 16 Table I jobs, read from the frozen
data file the configuration names.  The seed does not enter: the paper
evaluates against one fixed dataset.

A space generator returns plain NumPy data that the service (through
`adapter.build_jobs`) and the reference (`reference.py`) both read:

    {"features": (n, d) float64 raw features,
     "total_memory": (n,) float64 bytes, "num_nodes": (n,) float64,
     "jobs": [{"name", "cost": (n,) float64, and for Ruya mode
               "full_input_size", "per_node_overhead", "leeway",
               "flat_fraction", "memory_model", "profile"}]}
"""

from __future__ import annotations

import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make(space: dict, seed: int) -> dict:
    with open(os.path.join(BENCH, space["data"])) as f:
        data = json.load(f)
    cfgs = data["configs"]
    jobs = []
    for j in data["jobs"]:
        job = dict(j)
        job["cost"] = np.asarray(j["cost"], np.float64)
        jobs.append(job)
    return {
        "features": np.asarray(cfgs["features"], np.float64),
        "total_memory": np.asarray(cfgs["total_memory"], np.float64),
        "num_nodes": np.asarray(cfgs["num_nodes"], np.float64),
        "jobs": jobs,
    }
