"""The current-generation EC2 c/m/r catalog as a search space.

Source: the AWS EC2 instance-type catalog
(https://aws.amazon.com/ec2/instance-types/), current-generation compute
optimized (c), general purpose (m) and memory optimized (r) families of
generations 6 and 7 on Intel (i), AMD (a) and Graviton (g) processors:
18 families.  Each is taken in the seven sizes every one of them offers,
large to 16xlarge (2–64 vCPUs), with the class's documented memory per
vCPU (2 GiB for c, 4 for m, 8 for r): 126 instance types.  Scale-outs of
1 to ``max_nodes`` nodes give 126 × 1024 = 129,024 configurations.

Prices are us-east-1 Linux on-demand rates, linear in size within each
family, from each family's ``large`` rate.  The rates are recalled
figures, not read from a downloaded price list.

The runtime model is the emulator's (`repro.cluster.simulator`), with
total vCPUs standing in for cores as on the paper's c4/m4/r4 grid, and
two assumed offsets carried by each node type's ``runtime_factor``:
Graviton runs the reference workload `pricing.graviton().perf_factor`
slower per vCPU, and generation 6 runs it `GEN6_RUNTIME_FACTOR` slower
than generation 7.

Each configuration is encoded by six features: the paper's four
(§III-E, as in `nodes.make_cluster_search_space`: total vCPUs, total
memory, nodes, GiB per vCPU), the processor and the generation.  No two
configurations share an encoding.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from repro.cluster.nodes import GiB, ClusterConfig, NodeType
from repro.cluster.pricing import graviton
from repro.core.search_space import Configuration, SearchSpace

__all__ = [
    "FAMILIES",
    "GEN6_RUNTIME_FACTOR",
    "GIB_PER_VCPU",
    "MAX_NODES",
    "PROCESSORS",
    "SIZES",
    "catalog_node_types",
    "enumerate_catalog",
    "make_catalog_space",
]

# family: (class, processor, generation, USD/hour of its `large` size)
FAMILIES: Dict[str, Tuple[str, str, int, float]] = {
    "c6i": ("c", "intel", 6, 0.085),
    "c6a": ("c", "amd", 6, 0.0765),
    "c6g": ("c", "graviton", 6, 0.068),
    "c7i": ("c", "intel", 7, 0.08925),
    "c7a": ("c", "amd", 7, 0.10264),
    "c7g": ("c", "graviton", 7, 0.0725),
    "m6i": ("m", "intel", 6, 0.096),
    "m6a": ("m", "amd", 6, 0.0864),
    "m6g": ("m", "graviton", 6, 0.077),
    "m7i": ("m", "intel", 7, 0.1008),
    "m7a": ("m", "amd", 7, 0.11592),
    "m7g": ("m", "graviton", 7, 0.0816),
    "r6i": ("r", "intel", 6, 0.126),
    "r6a": ("r", "amd", 6, 0.1134),
    "r6g": ("r", "graviton", 6, 0.1008),
    "r7i": ("r", "intel", 7, 0.1323),
    "r7a": ("r", "amd", 7, 0.15215),
    "r7g": ("r", "graviton", 7, 0.1071),
}

# size: vCPUs
SIZES: Dict[str, int] = {
    "large": 2,
    "xlarge": 4,
    "2xlarge": 8,
    "4xlarge": 16,
    "8xlarge": 32,
    "12xlarge": 48,
    "16xlarge": 64,
}

GIB_PER_VCPU: Dict[str, float] = {"c": 2.0, "m": 4.0, "r": 8.0}

# The processor's feature value.
PROCESSORS: Dict[str, float] = {"intel": 0.0, "amd": 1.0, "graviton": 2.0}

MAX_NODES = 1024

# Assumed: generation 6 runs the reference workload this much slower than
# generation 7 (AWS quotes up to 15 % better performance for gen 7).
GEN6_RUNTIME_FACTOR = 1.15


def catalog_node_types() -> List[NodeType]:
    """The 126 instance types, family by family in `FAMILIES` order, each
    family's sizes ascending."""
    arm = graviton().perf_factor
    out = []
    for fam, (cls, proc, gen, large) in FAMILIES.items():
        factor = (arm if proc == "graviton" else 1.0) * (
            GEN6_RUNTIME_FACTOR if gen == 6 else 1.0
        )
        for size, vcpus in SIZES.items():
            out.append(NodeType(
                name=f"{fam}.{size}", family=cls, size=size, cores=vcpus,
                memory_gb=GIB_PER_VCPU[cls] * vcpus,
                price_per_hour=large * vcpus / SIZES["large"],
                processor=proc, generation=gen, runtime_factor=factor,
            ))
    return out


def enumerate_catalog(max_nodes: int = MAX_NODES) -> List[ClusterConfig]:
    """Every (instance type, 1..``max_nodes`` nodes), in a fixed order:
    configuration ``k * max_nodes + (nodes - 1)`` is instance type k of
    `catalog_node_types` at ``nodes`` nodes."""
    if max_nodes < 1:
        raise ValueError(f"max_nodes={max_nodes}: want >= 1")
    return [ClusterConfig(node=nt, scale_out=k)
            for nt in catalog_node_types()
            for k in range(1, max_nodes + 1)]


def make_catalog_space(configs=None) -> SearchSpace:
    """The catalog's `SearchSpace` over ``configs`` (default: all of
    `enumerate_catalog()`), with the six-feature encoding of the module
    docstring."""
    if configs is None:
        configs = enumerate_catalog()
    return SearchSpace([
        Configuration(
            name=c.name,
            features=(
                float(c.total_cores),
                float(c.total_memory_gb),
                float(c.scale_out),
                float(c.node.memory_gb / c.node.cores),
                PROCESSORS[c.node.processor],
                float(c.node.generation),
            ),
            total_memory=c.total_memory_gb * GiB,
            num_nodes=c.scale_out,
            meta=c,
        )
        for c in configs
    ])
