"""Reproduces the EXPERIMENTS.md section-Perf hillclimb measurements.

Each entry re-lowers one hillclimb variant on the production mesh and
prints its roofline terms.  Run with:

    PYTHONPATH=src python -m benchmarks.perf_iterations [cell]

cells: granite_base granite_sp granite_sp_flashproj qwen3_base qwen3_sp
       qwen3_a2a convnext_base convnext_group
(default: all — takes a few minutes of compile time)
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import dataclasses  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import cells as cm  # noqa: E402
from repro.launch import mesh as mm  # noqa: E402
from repro.launch.dryrun import roofline_terms  # noqa: E402
from repro.launch.hloanalysis import analyze  # noqa: E402
from repro.training import optimizer as opt_mod  # noqa: E402
from repro.training import steps as steps_mod  # noqa: E402


def _measure(step, cell, mesh, in_specs=None, chips=None, ctx=None,
             subtract_pattern=None):
    import contextlib

    c2 = cm.Cell(cell.arch_id, cell.shape_name, cell.kind, step or cell.step,
                 cell.abstract_args, in_specs or cell.in_specs,
                 cell.model_flops)
    with jax.set_mesh(mesh), (ctx or contextlib.nullcontext()):
        compiled = jax.jit(c2.step, in_shardings=c2.in_shardings(mesh)) \
            .lower(*cell.abstract_args).compile()
        mem = compiled.memory_analysis()
    a = analyze(compiled.as_text(), detail=subtract_pattern is not None)
    hbm = a["hbm_bytes"]
    if subtract_pattern is not None:
        pat = re.compile(subtract_pattern)
        hbm -= sum(f for f, d in a["top_bytes"] if pat.search(d))
    n = chips or 256
    rec = {"hlo_flops": a["flops"] * n, "hlo_bytes": hbm * n,
           "collective_bytes": a["collective_bytes"] * n, "devices": n,
           "model_flops": cell.model_flops}
    r = roofline_terms(rec, chips=n)
    return r, mem.temp_size_in_bytes / 1e9


def _lm_variant(arch_mod, arch_id, shape, sp):
    cell = cm.build_cell(arch_id, shape)
    cfg = dataclasses.replace(arch_mod.full_config(), sequence_parallel=sp)
    step = steps_mod.lm_train_step(cfg, opt_mod.adamw(1e-4))
    return cell, step


def run(which="all", csv=print):
    mesh = mm.make_production_mesh()
    import repro.configs.granite_34b as g
    import repro.configs.qwen3_moe_235b_a22b as q
    import repro.configs.convnext_b as cb
    from repro.models import vision as V

    def report(tag, r, temp):
        csv(f"perf,{tag},compute_s,{r['compute_s']:.3f},")
        csv(f"perf,{tag},memory_s,{r['memory_s']:.3f},")
        csv(f"perf,{tag},collective_s,{r['collective_s']:.3f},")
        csv(f"perf,{tag},roofline_fraction,{r['roofline_fraction']:.4f},"
            f"temp={temp:.1f}GB")

    if which in ("all", "granite_base"):
        cell, step = _lm_variant(g, "granite_34b", "train_4k", sp=False)
        report("granite_base", *_measure(step, cell, mesh))
    if which in ("all", "granite_sp"):
        cell, step = _lm_variant(g, "granite_34b", "train_4k", sp=True)
        report("granite_sp", *_measure(step, cell, mesh))
    if which in ("all", "granite_sp_flashproj"):
        cell, step = _lm_variant(g, "granite_34b", "train_4k", sp=True)
        report("granite_sp_flashproj", *_measure(
            step, cell, mesh, subtract_pattern=r"\[16,3,4096,1024\]"))
    if which in ("all", "qwen3_base"):
        cell, step = _lm_variant(q, "qwen3_moe_235b_a22b", "train_4k", sp=False)
        report("qwen3_base", *_measure(step, cell, mesh))
    if which in ("all", "qwen3_sp"):
        cell, step = _lm_variant(q, "qwen3_moe_235b_a22b", "train_4k", sp=True)
        report("qwen3_sp_bf16combine", *_measure(step, cell, mesh))
    if which in ("all", "qwen3_a2a"):
        cell = cm.build_cell("qwen3_moe_235b_a22b", "train_4k")
        cfg = dataclasses.replace(q.full_config(), sequence_parallel=True,
                                  moe_a2a=True)
        step = steps_mod.lm_train_step(cfg, opt_mod.adamw(1e-4))
        report("qwen3_sp_a2a_moe", *_measure(step, cell, mesh))
    if which in ("all", "convnext_base"):
        cell = cm.build_cell("convnext_b", "serve_b128")
        report("convnext_base", *_measure(None, cell, mesh))
    if which in ("all", "convnext_group"):
        cell = cm.build_cell("convnext_b", "serve_b128")
        params_abs = cm._eval_params(
            lambda: V.convnext_init(jax.random.PRNGKey(0), cb.full_config()))
        param_specs = jax.tree.map(lambda _: P(), params_abs)
        group = jax.make_mesh((16, 1), ("data", "model"),
                              devices=jax.devices()[:16])
        report("convnext_replica_group16", *_measure(
            None, cell, group, in_specs=(param_specs, cell.in_specs[1]),
            chips=16, ctx=shd.no_activation_constraints()))


def main():
    run(sys.argv[1] if len(sys.argv) > 1 else "all")


if __name__ == "__main__":
    main()
