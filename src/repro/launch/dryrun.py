import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, and extract the roofline terms.

The two lines above MUST stay the first statements in this module: jax
locks the device count at first initialisation, so the 512 placeholder
host devices have to be requested before any jax import (including the
transitive ones below).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
        --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Per cell it records: compile wall-time, per-device memory analysis,
HLO FLOPs/bytes from ``compiled.cost_analysis()``, and collective
bytes parsed from the optimised HLO (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes) —
everything EXPERIMENTS.md sections Dry-run and Roofline consume.
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro.launch import cells as cells_mod
from repro.launch import hloanalysis
from repro.launch import mesh as mesh_mod

def run_cell(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    cell = cells_mod.build_cell(arch_id, shape_name)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step, in_shardings=cell.in_shardings(mesh))
        lowered = jitted.lower(*cell.abstract_args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        t2 = time.time()
        analysis = hloanalysis.analyze(compiled.as_text())
        t_analyze = time.time() - t2

    n_dev = int(np.prod(mesh.devices.shape))
    mem_fields = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "host_temp_size_in_bytes"):
        mem_fields[f] = int(getattr(mem, f, 0) or 0)
    record = {
        "arch": arch_id,
        "shape": shape_name,
        "kind": cell.kind,
        "mesh": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "analyze_s": round(t_analyze, 2),
        "memory": mem_fields,
        # global quantities: per-device analyzer numbers x devices
        "hlo_flops": analysis["flops"] * n_dev,
        "hlo_bytes": analysis["hbm_bytes"] * n_dev,
        "collective_bytes": analysis["collective_bytes"] * n_dev,
        "collectives_per_device": analysis["collectives"],
        # raw XLA aggregate (counts while bodies once; kept for reference)
        "xla_cost_flops_per_device": float(cost.get("flops", 0.0)),
        "model_flops": cell.model_flops,
        "comment": cell.comment,
    }
    return record


def roofline_terms(record: dict, chips: int | None = None) -> dict:
    """Three-term roofline (seconds) from a dry-run record."""
    chips = chips or record["devices"]
    compute_s = record["hlo_flops"] / (chips * mesh_mod.PEAK_FLOPS_BF16)
    memory_s = record["hlo_bytes"] / (chips * mesh_mod.HBM_BW)
    coll_s = record["collective_bytes"] / (chips * mesh_mod.ICI_BW)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    useful = record["model_flops"] / max(record["hlo_flops"], 1.0)
    bound = max(terms.values())
    return {**terms, "dominant": dominant, "useful_flops_ratio": useful,
            "roofline_fraction": (record["model_flops"] /
                                  (chips * mesh_mod.PEAK_FLOPS_BF16)) / bound
            if bound > 0 else 0.0}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        todo = list(cells_mod.iter_cells())
    else:
        todo = [(args.arch, args.shape, None)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for multi_pod in meshes:
        tag = "multipod" if multi_pod else "singlepod"
        for arch_id, shape_name, skip in todo:
            name = f"{arch_id}__{shape_name}__{tag}"
            path = out_dir / f"{name}.json"
            if skip is not None:
                path.write_text(json.dumps(
                    {"arch": arch_id, "shape": shape_name, "skipped": skip},
                    indent=2))
                print(f"[SKIP] {name}: {skip}")
                continue
            if path.exists():
                print(f"[CACHED] {name}")
                continue
            try:
                rec = run_cell(arch_id, shape_name, multi_pod)
                rec["roofline"] = roofline_terms(rec)
                path.write_text(json.dumps(rec, indent=2))
                r = rec["roofline"]
                print(f"[OK] {name}: compile={rec['compile_s']}s "
                      f"flops={rec['hlo_flops']:.3e} "
                      f"coll={rec['collective_bytes']:.3e}B "
                      f"dominant={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}", flush=True)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures += 1
                print(f"[FAIL] {name}: {e}")
                (out_dir / f"{name}.err").write_text(traceback.format_exc())
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
