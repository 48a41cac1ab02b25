"""Multi-pod AOT dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(**abstract inputs).compile()`` must succeed on the
single-pod (16, 16) mesh AND the 2-pod (2, 16, 16) mesh for every assigned
architecture × input-shape cell.  For each cell the compiled artifact's
``memory_analysis()`` (bytes per device), ``cost_analysis()`` and the
loop-scaled HLO cost terms (FLOPs, collective bytes, HBM traffic — see
``hlo_analysis``) are written to a JSON artifact that EXPERIMENTS.md
§Dry-run / §Roofline and the perf loop read.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # full sweep
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --cell train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi_pod --skip-existing

Each cell runs in a subprocess so one failure cannot take down the sweep;
failures are recorded in the artifact with the exception text.  The
compiles target 512 placeholder CPU devices, so every cell's process runs
with ``JAX_PLATFORMS=cpu`` and never contends for an attached accelerator.
"""

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, cell_name: str, mesh_kind: str) -> dict:
    """Lower + compile one cell in-process; returns the artifact dict."""
    import jax

    import repro.configs as C
    from repro.launch.build import build_cell
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import active_params, total_params

    spec = C.get(arch)
    cell = C.CELLS[cell_name]
    ok, reason = C.cell_applicable(spec.model, cell)
    if not ok:
        return {"status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    chips = mesh.size

    t0 = time.time()
    built = build_cell(spec, cell, mesh)
    lowered = built.lower(mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    peak = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
        + ma.temp_size_in_bytes
    )
    ca = compiled.cost_analysis() or {}
    cost = analyze_hlo(compiled.as_text())

    art = {
        "status": "ok",
        "arch": arch,
        "cell": cell_name,
        "mesh": mesh_kind,
        "chips": chips,
        "kind": built.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes_per_device": int(peak),
            "fits_16g": bool(peak <= 16 * 2**30),
        },
        "xla_cost_analysis": {
            "flops_scan_body_once": float(ca.get("flops", -1.0)),
            "bytes_accessed_scan_body_once": float(ca.get("bytes accessed", -1.0)),
        },
        "hlo_cost": {
            "flops_per_device": cost.flops,
            "collective_bytes_per_device": cost.collective_bytes,
            "hbm_bytes_per_device": cost.hbm_bytes,
            "collective_breakdown": cost.collective_breakdown,
        },
        "model": {
            "total_params": total_params(spec.model),
            "active_params": active_params(spec.model),
            "tokens": cell.tokens if built.kind == "train" else cell.global_batch,
        },
    }
    return art


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "single_pod", "multi_pod"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--single", action="store_true",
                    help="run one cell in-process and print JSON (internal)")
    args = ap.parse_args()

    if args.single:
        try:
            art = run_cell(args.arch, args.cell, args.mesh)
        except Exception:
            art = {"status": "failed", "error": traceback.format_exc()[-2000:]}
        print("JSON_ARTIFACT:" + json.dumps(art))
        return

    import repro.configs as C

    archs = [args.arch] if args.arch else C.ARCHS
    cells = [args.cell] if args.cell else list(C.CELLS)
    meshes = [args.mesh] if args.mesh else ["single_pod", "multi_pod"]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        for cell in cells:
            for mesh in meshes:
                path = os.path.join(args.out, f"{arch}__{cell}__{mesh}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {path}")
                    continue
                t0 = time.time()
                proc = subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun", "--single",
                     "--arch", arch, "--cell", cell, "--mesh", mesh],
                    capture_output=True, text=True,
                    env={**os.environ, "PYTHONPATH": "src",
                         "JAX_PLATFORMS": "cpu"},
                )
                art = None
                for line in proc.stdout.splitlines():
                    if line.startswith("JSON_ARTIFACT:"):
                        art = json.loads(line[len("JSON_ARTIFACT:"):])
                if art is None:
                    art = {"status": "failed",
                           "error": (proc.stderr or proc.stdout)[-2000:]}
                art.setdefault("arch", arch)
                art.setdefault("cell", cell)
                art.setdefault("mesh", mesh)
                with open(path, "w") as f:
                    json.dump(art, f, indent=1)
                status = art["status"]
                extra = ""
                if status == "ok":
                    gib = art["memory"]["peak_bytes_per_device"] / 2**30
                    extra = f" peak={gib:.2f}GiB compile={art['compile_s']}s"
                elif status == "skipped":
                    extra = f" ({art['reason'][:50]})"
                else:
                    failures.append((arch, cell, mesh))
                print(f"[{status}] {arch} × {cell} × {mesh}"
                      f" ({time.time()-t0:.0f}s){extra}", flush=True)

    if failures:
        print(f"\nFAILED cells ({len(failures)}):")
        for f_ in failures:
            print("  ", *f_)
        sys.exit(1)
    print("\nAll dry-run cells passed.")


if __name__ == "__main__":
    from repro.hostdevices import force_host_device_count

    force_host_device_count(512)  # the production mesh's placeholder devices
    main()
