"""§Perf iteration helper: measure (peak, roofline terms) for one
(arch × cell) under config/exec/rule overrides — the hypothesis→change→
measure loop's instrument.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch kimi-k2-1t-a32b \
        --cell train_4k --micro 2 --attention chunked --chunk 1024
"""

import argparse
import dataclasses
import json
import time


def measure(arch: str, cell_name: str, *, micro=None, remat=None,
            attention=None, chunk=None, fsdp=None, seq_shard=False,
            multi_pod=False, cache_seq_shard=None) -> dict:
    import jax  # noqa: F401  (device count must be set before init)

    import repro.configs as C
    from repro.launch.build import build_cell, rules_for
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh

    spec = C.get(arch)
    model_kw = {}
    if remat is not None:
        model_kw["remat_policy"] = remat
    if attention is not None:
        model_kw["attention_impl"] = attention
    if chunk is not None:
        model_kw["attention_chunk"] = chunk
    if model_kw:
        spec = spec.replace_model(**model_kw)
    ex = spec.exec
    if micro is not None:
        ex = ex.replace(num_microbatches=micro)
    if remat is not None:
        ex = ex.replace(remat=remat)
    if fsdp is not None:
        ex = ex.replace(fsdp=fsdp)
    spec = dataclasses.replace(spec, exec=ex)

    cell = C.CELLS[cell_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = {}
    if seq_shard:
        overrides["seq"] = "model"
    if cache_seq_shard:
        overrides["cache_seq"] = cache_seq_shard
    rules = rules_for(spec, cell, mesh, overrides=overrides or None)

    t0 = time.time()
    built = build_cell(spec, cell, mesh, rules=rules, exec_override=ex)
    compiled = built.lower(mesh).compile()
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    cost = analyze_hlo(compiled.as_text())
    terms = {
        "compute_s": cost.flops / 197e12,
        "memory_s": cost.hbm_bytes / 819e9,
        "collective_s": cost.collective_bytes / 50e9,
    }
    out = {
        "arch": arch, "cell": cell_name,
        "variant": {"micro": micro, "remat": remat, "attention": attention,
                    "chunk": chunk, "fsdp": fsdp, "seq_shard": seq_shard,
                    "cache_seq_shard": cache_seq_shard,
                    "multi_pod": multi_pod},
        "peak_gib": peak / 2**30,
        **{k: round(v, 3) for k, v in terms.items()},
        "step_s": round(max(terms.values()), 3),
        "dominant": max(terms, key=terms.get),
        "collective_breakdown_gb": {
            k: round(v / 1e9, 1) for k, v in cost.collective_breakdown.items()
        },
        "compile_s": round(time.time() - t0, 1),
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--attention", default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--fsdp", type=lambda s: s == "true", default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--cache-seq-shard", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    out = measure(args.arch, args.cell, micro=args.micro, remat=args.remat,
                  attention=args.attention, chunk=args.chunk, fsdp=args.fsdp,
                  seq_shard=args.seq_shard, multi_pod=args.multi_pod,
                  cache_seq_shard=args.cache_seq_shard)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    from repro.hostdevices import force_host_device_count

    force_host_device_count(512)  # the production mesh's placeholder devices
    main()
