"""The current-generation EC2 c/m/r catalog and the 16 Table I jobs over
it, built from the frozen data file the configuration names.

The file holds the 126 instance types, the node cap, each job's memory
model, profile and runtime-model parameters, and a data seed.  Every
configuration is (instance type k, nodes m) for m = 1..max_nodes, at index
k * max_nodes + m - 1, encoded by six features: total vCPUs, total memory
(GiB), nodes, GiB per vCPU, processor (Intel 0, AMD 1, Graviton 2) and
generation.  Each job's cost table is its emulated runtime times the
cluster's hourly price (the file's ``runtime_model``), normalized by its
minimum.  The runtime's ruggedness is drawn from the file's data seed, not
from the run's seed: every run sees one landscape, as the paper's
evaluation sees one dataset.  Returns the data `paper_grid.make`
describes.
"""

from __future__ import annotations

import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = float(2**30)
PROCESSOR = {"intel": 0.0, "amd": 1.0, "graviton": 2.0}


def make(space: dict, seed: int) -> dict:
    with open(os.path.join(BENCH, space["data"])) as f:
        data = json.load(f)
    types = data["instance_types"]
    m = int(data["max_nodes"])
    per_type = lambda key: np.repeat(
        np.asarray([t[key] for t in types], np.float64), m)
    nodes = np.tile(np.arange(1, m + 1, dtype=np.float64), len(types))
    vcpus = per_type("vcpus") * nodes
    mem_gib = per_type("memory_gib") * nodes
    features = np.stack([
        vcpus, mem_gib, nodes, per_type("memory_gib") / per_type("vcpus"),
        np.repeat([PROCESSOR[t["processor"]] for t in types], m),
        per_type("generation"),
    ], axis=1)

    rm = data["runtime_model"]
    factor = (np.where(np.repeat([t["processor"] == "graviton"
                                  for t in types], m),
                       rm["graviton_factor"], 1.0)
              * np.where(per_type("generation") == 6, rm["gen6_factor"],
                         1.0))
    price = per_type("usd_per_hour") * nodes
    usable = np.maximum(mem_gib - rm["per_node_overhead_gib"] * nodes, 0.0)
    z = np.random.default_rng(data["data_seed"]).standard_normal(
        (len(data["jobs"]), len(nodes)))
    jobs = []
    for j, spec in enumerate(data["jobs"]):
        p = spec["runtime_model"]
        base = (p["serial_hours"] + p["cpu_hours"] * rm["ref_vcpus"] / vcpus
                + p["io_hours"] * rm["ref_nodes"] / nodes)
        coord = 1.0 + p["coord_per_node"] * (nodes - 1.0)
        req = p["mem_requirement_gib"]
        missing = np.minimum(1.0, (req - usable) / req)
        spill = np.where(usable >= req, 1.0,
                         p["spill_base"] + p["spill_slope"] * missing)
        if p["spill_slope"] == 0.0 and p["spill_base"] <= 1.0:
            spill = np.ones_like(nodes)
        runtime = (base * coord * spill * np.exp(p["rugged_sigma"] * z[j])
                   * factor)
        cost = runtime * price
        job = {k: v for k, v in spec.items() if k != "runtime_model"}
        job["cost"] = cost / cost.min()
        jobs.append(job)
    return {
        "features": features,
        "total_memory": mem_gib * GIB,
        "num_nodes": nodes,
        "jobs": jobs,
    }
