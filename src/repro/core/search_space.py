"""Resource-configuration search space and its memory-aware split (paper §III-D).

A configuration is anything with (a) a feature encoding for the GP surrogate
(CherryPick encodes each config "by its principal features like the number of
cores and the amount of memory"), (b) a total cluster memory, and (c) optional
metadata (node count, prices, mesh/remat details for the TPU tuner, ...).

``split_search_space`` implements the paper's priority-group construction:

  LINEAR  → configs with total memory ≥ the extrapolated requirement
            (+overhead+leeway); if *no* config qualifies, prioritize the
            extremes (very high and very low total memory).
  FLAT    → the 10–20 % of configs with the lowest total memory.
  UNCLEAR → no split (priority group = whole space → plain CherryPick).

``split_priority_mask`` is the same rule vectorized over the space's static
per-config arrays (total memories, node counts), returning the (n,) priority
mask directly — the narrowing then scales with the catalog (one vectorized
float64 comparison + a stable sort instead of a Python loop over 10⁴–10⁵
configs).  It runs in NumPy on the host, where float64 is IEEE: every
comparison and the stable sort are bit-equal to the host rule —
`tests/test_search_space.py` pins mask == list equality, which is what lets
`repro.fleet.session.TuningSession` use the vectorized split while staying
trace-identical to the host-split drivers.  (A TPU has no native float64:
XLA emulates it with float32 pairs, which is not IEEE-exact, so the split
does not run there.)
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.memory_model import MemoryCategory, MemoryModel

__all__ = [
    "Configuration",
    "SearchSpace",
    "split_priority_mask",
    "split_search_space",
]


@dataclasses.dataclass(frozen=True)
class Configuration:
    """One point in the discrete configuration search space."""

    name: str
    features: Tuple[float, ...]  # raw GP features (cores, mem/node, nodes, ...)
    total_memory: float  # bytes of total cluster memory
    num_nodes: int = 1
    meta: Any = None


@dataclasses.dataclass
class SearchSpace:
    configs: List[Configuration]

    def __post_init__(self) -> None:
        if not self.configs:
            raise ValueError("empty search space")
        feats = np.asarray([c.features for c in self.configs], np.float64)
        mean = feats.mean(axis=0)
        std = feats.std(axis=0)
        std = np.where(std > 1e-12, std, 1.0)
        self._encoded = (feats - mean) / std
        # Static per-config arrays, built once: the §III-D split (host or
        # device) reads these instead of looping over Configuration objects.
        self._memories = np.asarray(
            [c.total_memory for c in self.configs], np.float64
        )
        self._num_nodes = np.asarray(
            [c.num_nodes for c in self.configs], np.float64
        )

    def __len__(self) -> int:
        return len(self.configs)

    def encoded(self, indices: Optional[Sequence[int]] = None) -> np.ndarray:
        """Standardized feature matrix (whole space or a subset)."""
        if indices is None:
            return self._encoded
        return self._encoded[np.asarray(indices, np.int64)]

    def memories(self) -> np.ndarray:
        return self._memories

    def num_nodes(self) -> np.ndarray:
        return self._num_nodes


def split_search_space(
    space: SearchSpace,
    model: MemoryModel,
    input_size: float,
    *,
    per_node_overhead: float = 0.0,
    leeway: float = 0.10,
    flat_fraction: float = 1.0 / 7.0,
    extreme_fraction: float = 0.15,
) -> Tuple[List[int], List[int]]:
    """Return (priority_indices, remaining_indices) per the paper's §III-D.

    ``flat_fraction`` defaults to ~1/7 — the paper's evaluation placed the ten
    lowest-memory configs of 69 in the priority group.  ``extreme_fraction``
    controls the very-high/very-low fallback when no config satisfies a linear
    requirement.
    """
    n = len(space)
    all_idx = list(range(n))
    mems = space.memories()

    if model.category is MemoryCategory.UNCLEAR:
        return all_idx, []

    if model.category is MemoryCategory.FLAT:
        k = max(1, int(round(flat_fraction * n)))
        order = np.argsort(mems, kind="stable")
        prio = sorted(int(i) for i in order[:k])
        rest = sorted(set(all_idx) - set(prio))
        return prio, rest

    # LINEAR: require total cluster memory ≥ extrapolated requirement.
    req_base = model.estimate(input_size)
    prio = []
    for i, cfg in enumerate(space.configs):
        requirement = req_base * (1.0 + leeway) + per_node_overhead * cfg.num_nodes
        if cfg.total_memory >= requirement:
            prio.append(i)
    if not prio:
        # Requirement exceeds every config: prioritize the extremes — "some
        # jobs can make use of all memory they are given and others need
        # either enough or none" (paper §III-D).
        k = max(1, int(round(extreme_fraction * n)))
        order = np.argsort(mems, kind="stable")
        prio = sorted({int(i) for i in order[:k]} | {int(i) for i in order[-k:]})
    if len(prio) == n:
        # Requirement met by everything → no reduction (paper observed this
        # for PageRank/Spark "huge"); behave exactly like the baseline.
        return all_idx, []
    rest = sorted(set(all_idx) - set(prio))
    return prio, rest


def split_priority_mask(
    space: SearchSpace,
    model: MemoryModel,
    input_size: float,
    *,
    per_node_overhead: float = 0.0,
    leeway: float = 0.10,
    flat_fraction: float = 1.0 / 7.0,
    extreme_fraction: float = 0.15,
) -> np.ndarray:
    """§III-D priority split, vectorized; returns the (n,) bool mask.

    Bit-equal to `split_search_space` by construction: the per-config
    requirement math is the same float64 IEEE arithmetic elementwise, the
    FLAT / extremes selections use the same stable argsort, and the group
    sizes ``k`` are rounded with the same expressions.  The remaining mask
    is always the complement (`~prio`) — including the all-qualify LINEAR
    case, where the complement of an all-True mask is the host rule's
    empty remainder.

    The static per-config arrays come from the `SearchSpace` cache, so
    narrowing a 10⁴–10⁵-point catalog is one vectorized comparison + sort
    instead of a Python loop over configs.
    """
    n = len(space)
    if model.category is MemoryCategory.UNCLEAR:
        return np.ones(n, bool)
    mems = space.memories()
    order = np.argsort(mems, kind="stable")
    prio = np.zeros(n, bool)
    if model.category is MemoryCategory.FLAT:
        prio[order[: max(1, int(round(flat_fraction * n)))]] = True
        return prio
    requirement = (
        model.estimate(input_size) * (1.0 + leeway)
        + per_node_overhead * space.num_nodes()
    )
    qualify = mems >= requirement
    if qualify.any():
        return qualify
    k = max(1, int(round(extreme_fraction * n)))
    prio[order[:k]] = True
    prio[order[-k:]] = True
    return prio
