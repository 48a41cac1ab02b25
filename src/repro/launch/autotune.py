"""Ruya-for-TPU: memory-aware iterative search over execution configurations.

This is the paper's algorithm (``repro.core``) applied beyond its original
domain: the "cluster configuration" becomes a TPU *execution configuration*
(microbatch count × remat policy × FSDP on/off × activation-sequence
sharding), the "job" is one (architecture × shape cell) on the production
mesh, and a *trial* is an AOT compile whose roofline step-time estimate
(max of the compute/memory/collective terms from the loop-scaled HLO cost
analysis) is the cost.  On real hardware each trial is a short profiled run
at scale — expensive — which is exactly the economics the paper's
search-iteration reduction targets.

The mapping of the paper's phases:

  1. *Profiling on reduced hardware* → compile the SAME model at reduced
     global batches (cheap chip-seconds at scale) and read
     ``memory_analysis().peak``; fit the §III-C OLS memory model of
     peak-bytes vs tokens-per-device per remat policy.
  2. *Categorization* → activations make training cells LINEAR in
     tokens-per-device with a flat params+optimizer offset; decode cells
     come out FLAT.  Unclear readings fall back to plain BO (the paper's
     §III-D fallback).
  3. *Search-space split* → configurations whose predicted peak exceeds the
     16 GiB/chip HBM are deprioritized (memory-bottleneck analogue: on TPU
     the penalty is OOM-or-remat, a hard cliff).
  4. *CherryPick BO with EI* → identical engine, cost = roofline seconds.

Usage:
  PYTHONPATH=src python -m repro.launch.autotune --arch granite-8b \
      --cell train_4k [--budget 10] [--exhaustive]
"""

import argparse
import dataclasses
import json
import math
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

HBM_PER_CHIP = 16 * 2**30  # v5e
PEAKS = {"flops": 197e12, "hbm": 819e9, "ici": 50e9}


@dataclasses.dataclass(frozen=True)
class ExecVariant:
    """One point of the TPU execution-configuration search space."""

    num_microbatches: int
    remat: str  # none | dots | full
    fsdp: bool
    seq_shard: bool  # Megatron-style sequence parallelism on activations

    @property
    def name(self) -> str:
        return (f"micro{self.num_microbatches}-{self.remat}"
                f"{'-fsdp' if self.fsdp else ''}"
                f"{'-seqshard' if self.seq_shard else ''}")

    def features(self) -> Tuple[float, ...]:
        # CherryPick encodes configs "by their principal features".
        return (
            math.log2(self.num_microbatches),
            {"none": 0.0, "dots": 1.0, "full": 2.0}[self.remat],
            1.0 if self.fsdp else 0.0,
            1.0 if self.seq_shard else 0.0,
        )


def variant_space(cell_kind: str) -> List[ExecVariant]:
    if cell_kind != "train":
        # serving has no microbatch/remat axis; sweep sharding choices only
        return [
            ExecVariant(1, "none", fsdp, seq)
            for fsdp in (False, True)
            for seq in (False, True)
        ]
    out = []
    for micro in (1, 2, 4, 8, 16):
        for remat in ("none", "dots", "full"):
            for fsdp in (True, False):
                for seq in (False, True):
                    out.append(ExecVariant(micro, remat, fsdp, seq))
    return out


class TpuTunerEnv:
    """Profiling + trial execution against the AOT dry-run machinery."""

    def __init__(self, arch: str, cell_name: str, multi_pod: bool = False,
                 cache_path: Optional[str] = None) -> None:
        import repro.configs as C
        from repro.launch.mesh import make_production_mesh

        self.C = C
        self.arch = arch
        self.spec = C.get(arch)
        self.cell = C.CELLS[cell_name]
        self.mesh = make_production_mesh(multi_pod=multi_pod)
        self.chips = self.mesh.size
        self.trial_cache: Dict[str, Dict] = {}
        self.cache_path = cache_path
        if cache_path and os.path.exists(cache_path):
            with open(cache_path) as f:
                self.trial_cache = json.load(f)

    # -- shared plumbing -----------------------------------------------------

    def _built(self, variant: ExecVariant, cell=None):
        from repro.launch.build import build_cell, rules_for

        spec = dataclasses.replace(
            self.spec, model=self.spec.model.replace(remat_policy=variant.remat)
        )
        ex = spec.exec.replace(
            num_microbatches=variant.num_microbatches,
            remat=variant.remat,
            fsdp=variant.fsdp,
            seq_shard=variant.seq_shard,  # overrides the arch default
        )
        cell = cell or self.cell
        rules = rules_for(dataclasses.replace(spec, exec=ex), cell, self.mesh)
        return build_cell(spec, cell, self.mesh, rules=rules, exec_override=ex)

    def _compile_peak_and_cost(self, variant: ExecVariant, cell=None):
        from repro.launch.hlo_analysis import analyze_hlo

        built = self._built(variant, cell)
        compiled = built.lower(self.mesh).compile()
        ma = compiled.memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        cost = analyze_hlo(compiled.as_text())
        return peak, cost

    # -- phase 1: profiling runs ----------------------------------------------

    def profile_run_fn(self, variant: ExecVariant):
        """(tokens-per-device) -> (chip_seconds_cost, peak_bytes).

        The Ruya profiler drives this with small sample sizes — here small
        global batches of the full model, the analogue of dataset samples on
        one machine."""

        def run(tokens_per_device: float) -> Tuple[float, float]:
            total = int(tokens_per_device) * self.chips
            seq = min(self.cell.seq_len, max(256, total))
            gb = max(1, total // seq)
            cell = self.C.ShapeCell("profile", seq, gb, self.cell.kind)
            peak, cost = self._compile_peak_and_cost(variant, cell)
            est_seconds = max(cost.flops / PEAKS["flops"],
                              cost.hbm_bytes / PEAKS["hbm"],
                              cost.collective_bytes / PEAKS["ici"])
            return est_seconds * self.chips, float(peak)

        return run

    # -- phase 4: one search trial ---------------------------------------------

    def trial_cost_fn(self, space: List[ExecVariant]):
        def cost(idx: int) -> float:
            v = space[idx]
            if v.name not in self.trial_cache:
                try:
                    peak, c = self._compile_peak_and_cost(v)
                    step_s = max(c.flops / PEAKS["flops"],
                                 c.hbm_bytes / PEAKS["hbm"],
                                 c.collective_bytes / PEAKS["ici"])
                    # memory-bottleneck cliff: configs over HBM pay the
                    # remat/offload penalty (or are simply infeasible)
                    over = max(peak / HBM_PER_CHIP, 1.0)
                    penalty = 1.0 if over <= 1.0 else (2.0 + 4.0 * (over - 1.0))
                    self.trial_cache[v.name] = {
                        "peak_bytes": float(peak),
                        "roofline_s": float(step_s),
                        "cost_chip_s": float(step_s * penalty),
                        "terms": {
                            "compute": c.flops / PEAKS["flops"],
                            "memory": c.hbm_bytes / PEAKS["hbm"],
                            "collective": c.collective_bytes / PEAKS["ici"],
                        },
                    }
                except Exception as e:  # infeasible config = huge cost
                    self.trial_cache[v.name] = {
                        "error": str(e)[:200], "cost_chip_s": 1e9,
                    }
                if self.cache_path:
                    with open(self.cache_path, "w") as f:
                        json.dump(self.trial_cache, f, indent=1)
            return self.trial_cache[v.name]["cost_chip_s"]

        return cost

    def search_space(self):
        from repro.core.search_space import Configuration, SearchSpace

        space = variant_space(self.cell.kind)
        # "total memory" of a config = HBM it leaves for the job: constant
        # per chip — what varies is the REQUIREMENT, predicted per config by
        # the memory model.  We encode available memory so the §III-D split
        # can compare requirement vs availability per config.
        configs = [
            Configuration(
                name=v.name,
                features=v.features(),
                total_memory=float(HBM_PER_CHIP),
                num_nodes=self.chips,
                meta=v,
            )
            for v in space
        ]
        return space, SearchSpace(configs)


def predict_peaks(env: TpuTunerEnv, space: List[ExecVariant]):
    """Paper phases 1–2 for every (remat, fsdp, seq) combination: profile
    peak-vs-tokens at reduced batches, extrapolate to the full cell.

    Returns {variant.name: predicted_peak_bytes} and the fitted models."""
    from repro.core.memory_model import fit_memory_model

    cell = env.cell
    full_tokens_per_dev = cell.tokens / env.chips
    preds: Dict[str, float] = {}
    models = {}
    # Group variants: microbatching divides tokens-per-device per microbatch.
    base_keys = sorted({(v.remat, v.fsdp, v.seq_shard) for v in space})
    for remat, fsdp, seq in base_keys:
        probe = ExecVariant(1, remat, fsdp, seq)
        run = env.profile_run_fn(probe)
        fractions = (0.125, 0.25, 0.5)
        sizes, readings = [], []
        for frac in fractions:
            tpd = full_tokens_per_dev * frac
            _, peak = run(tpd)
            sizes.append(tpd)
            readings.append(peak)
        model = fit_memory_model(sizes, readings)
        models[(remat, fsdp, seq)] = model
        for v in space:
            if (v.remat, v.fsdp, v.seq_shard) != (remat, fsdp, seq):
                continue
            tpd = full_tokens_per_dev / v.num_microbatches
            if model.category.value == "linear":
                preds[v.name] = model.estimate(tpd)
            elif model.category.value == "flat":
                preds[v.name] = float(np.mean(readings))
            else:
                preds[v.name] = float("nan")
    return preds, models


def run_autotune(arch: str, cell: str, *, budget: int = 12,
                 multi_pod: bool = False, seed: int = 0,
                 cache_path: Optional[str] = None,
                 exhaustive: bool = False) -> Dict:
    from repro.core.bayesopt import BOSettings, ruya_search
    from repro.core.search_space import split_search_space
    from repro.core.memory_model import MemoryCategory, MemoryModel

    env = TpuTunerEnv(arch, cell, multi_pod=multi_pod, cache_path=cache_path)
    space, sspace = env.search_space()

    print(f"[autotune] {arch} × {cell}: {len(space)} configurations")
    preds, models = predict_peaks(env, space)

    # §III-D split: prioritize configs predicted to fit the per-chip HBM.
    prio, rest = [], []
    any_unclear = any(math.isnan(p) for p in preds.values())
    if any_unclear:
        prio = list(range(len(space)))  # fallback: plain BO
    else:
        for i, v in enumerate(space):
            (prio if preds[v.name] <= HBM_PER_CHIP * 1.05 else rest).append(i)
        if not prio:  # nothing fits → prioritize minimal-requirement extremes
            order = np.argsort([preds[v.name] for v in space])
            k = max(1, len(space) // 7)
            prio = sorted(int(i) for i in order[:k])
            rest = sorted(set(range(len(space))) - set(prio))
    print(f"[autotune] priority group: {len(prio)}/{len(space)} configs "
          f"predicted to fit {HBM_PER_CHIP/2**30:.0f} GiB/chip")

    cost_fn = env.trial_cost_fn(space)
    settings = BOSettings(max_iters=None if exhaustive else budget,
                          min_observations=min(6, len(prio)))
    trace = ruya_search(
        sspace, cost_fn, np.random.default_rng(seed), prio, rest,
        settings=settings, to_exhaustion=exhaustive,
    )
    best = space[trace.best_index]
    result = {
        "arch": arch,
        "cell": cell,
        "trials": len(trace.tried),
        "best": best.name,
        "best_cost_chip_s": trace.best_cost,
        "tried": [space[i].name for i in trace.tried],
        "costs": trace.costs,
        "priority_size": len(prio),
        "predicted_peaks_gib": {k: v / 2**30 for k, v in preds.items()},
        "trial_details": {space[i].name: env.trial_cache.get(space[i].name)
                          for i in trace.tried},
    }
    print(f"[autotune] best: {best.name} "
          f"(roofline {trace.best_cost:.2f} chip-s/step) "
          f"after {len(trace.tried)} trials")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", default="train_4k")
    ap.add_argument("--budget", type=int, default=12)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache", default=None)
    ap.add_argument("--exhaustive", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    result = run_autotune(
        args.arch, args.cell, budget=args.budget, multi_pod=args.multi_pod,
        seed=args.seed, cache_path=args.cache, exhaustive=args.exhaustive,
    )
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    from repro.hostdevices import force_host_device_count

    force_host_device_count(512)  # the production mesh's placeholder devices
    main()
