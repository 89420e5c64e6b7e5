import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any jax import: the dry-run (and only the dry-run) needs
# 512 placeholder host devices for the production meshes, and runs on the
# CPU even on a TPU host, so it never takes the chip.
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh; record memory,
cost, collective and roofline analysis (EXPERIMENTS.md §Dry-run/§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out runs/dryrun \
      [--mappers blocked,hyperplane,portfolio[k=8]:hyperplane]

``--mappers`` accepts every ``parse_plan`` spelling (refinement prefixes,
bracket options, chained prefixes); each cell records per-mapper linksim
traffic plus DCI deltas against the blocked baseline.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.analysis.hlo import parse_hlo
from repro.analysis.linksim import simulate
from repro.analysis.roofline import roofline_from_module
from repro.configs import ARCHS, SHAPES, get_arch, shape_applicable
from repro.core import Stencil, device_layout
from repro.launch.input_specs import build_cell
from repro.launch.mesh import (machine_for, make_mapped_mesh,
                               make_production_mesh, stencil_for_plan)
from repro.optim.adamw import AdamWConfig
from repro.sharding.partition import use_partitioning


def _split_order(mname: str):
    """``"hyperplane+rm" -> ("hyperplane", "rm")``: only the trailing
    ``+rm`` suffix selects intra-pod order — a ``+`` anywhere else (e.g. a
    signed bracket-option value, ``annealed[t0=+1e-2]:``) is part of the
    mapper spelling."""
    if mname.endswith("+rm"):
        return mname[:-3], "rm"
    return mname, ""


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             mappers=("blocked", "stencil_strips"), out_dir=None,
             moe_dispatch: str = "einsum", overrides=None, part_rules=None,
             verbose=True):
    cfg = get_arch(arch_name)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    machine = machine_for(multi_pod)
    cell = build_cell(cfg, shape, mesh, moe_dispatch=moe_dispatch)
    if part_rules:
        cell.partitioning.rules.update(part_rules)
    with mesh, use_partitioning(cell.partitioning):
        jf = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)
        lowered = jf.lower(*cell.args)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}
    hlo_text = compiled.as_text()
    module = parse_hlo(hlo_text)
    chips = int(np.prod(mesh.devices.shape))
    rep = roofline_from_module(
        module, arch=arch_name, shape=shape_name,
        mesh="multi" if multi_pod else "single", chips=chips,
        machine=machine, model_flops_global=cell.model_flops,
        model_flops_full=cell.model_flops_full,
        memory_stats=mem, cost_analysis=ca)

    # topology decomposition: play the collectives on physical links for
    # each candidate device layout (paper metric: DCI bytes ~ J_sum/J_max).
    # Mapper names accept the full parse_plan grammar (chained prefixes,
    # bracket options, e.g. "portfolio[k=8]:hyperplane"); solved layouts
    # come from the plan cache, so sweeping many (arch, shape) cells
    # re-solves each distinct (stencil, mapper) pair only once.
    colls = module.collectives()
    link_reports = {}
    plan_stencil = stencil_for_plan(cfg, shape, multi_pod)
    for mname in mappers:
        base, order = _split_order(mname)
        layout = device_layout(base, mesh.devices.shape,
                               plan_stencil, machine.node_sizes(),
                               intra_order="rowmajor" if order == "rm"
                               else "mapper")
        r = simulate(colls, layout.reshape(-1), machine)
        link_reports[mname] = {**r.summary(), **r.times(machine)}
    # per-mapper DCI deltas against the blocked baseline (first mapper when
    # blocked isn't in the sweep): negative = the mapping saves DCI bytes.
    base_name = next((m for m in link_reports
                      if _split_order(m)[0] == "blocked"),
                     next(iter(link_reports), None))
    if base_name is not None:
        ref = link_reports[base_name]
        for rep in link_reports.values():
            rep["dci_total_delta"] = (rep["dci_total_bytes"]
                                      - ref["dci_total_bytes"])
            rep["dci_max_delta"] = (rep["max_dci_pod_bytes"]
                                    - ref["max_dci_pod_bytes"])

    n_coll = {}
    coll_by_op = {}
    for c in colls:
        n_coll[c.opcode] = n_coll.get(c.opcode, 0) + 1
        coll_by_op[c.opcode] = coll_by_op.get(c.opcode, 0.0) + \
            c.wire_bytes_per_device()
    result = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single", "status": "ok",
        "chips": chips, "compile_s": round(t_compile, 2),
        "kind": cell.kind,
        "memory": {
            "argument_gib": mem.argument_size_in_bytes / 2**30,
            "temp_gib": mem.temp_size_in_bytes / 2**30,
            "output_gib": mem.output_size_in_bytes / 2**30,
            "alias_gib": mem.alias_size_in_bytes / 2**30,
            "fits_16gib": rep.fits_hbm,
        },
        "roofline": rep.row(),
        "collectives": n_coll,
        "coll_wire_by_op": coll_by_op,
        "coll_payload_bytes_per_dev": rep.coll_payload_bytes,
        "coll_wire_bytes_per_dev": rep.coll_wire_bytes,
        "linksim": link_reports,
        "linksim_baseline": base_name,
        "fallbacks": [str(f) for f in cell.partitioning.fallbacks[:8]],
    }
    if out_dir:
        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        fname = f"{arch_name}_{shape_name}_{'multi' if multi_pod else 'single'}.json"
        (out / fname).write_text(json.dumps(result, indent=1, default=float))
    if verbose:
        r = result["roofline"]
        print(f"[{result['mesh']:6s}] {arch_name:22s} {shape_name:12s} "
              f"compile={t_compile:6.1f}s dom={r['dominant']:10s} "
              f"tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e} "
              f"tx={r['t_collective_s']:.3e} useful={r['useful_ratio']:.2f} "
              f"arg/dev={result['memory']['argument_gib']:.2f}GiB "
              f"temp/dev={result['memory']['temp_gib']:.2f}GiB", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--mappers",
                    default="blocked,stencil_strips,hyperplane,kdtree,"
                            "portfolio:hyperplane",
                    help="comma list; any parse_plan spelling works "
                         "(portfolio[k=8]:hyperplane, "
                         "sharded[shards=4,k=64,restarts=auto]:hyperplane, "
                         "chained prefixes, +rm for rowmajor intra-pod "
                         "order)")
    ap.add_argument("--moe-dispatch", default="einsum",
                    choices=["einsum", "scatter"])
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    from repro.core import parse_plan
    from repro.core.mapping import split_mapper_list
    mappers = split_mapper_list(args.mappers)
    for m in mappers:                     # fail fast on typos, full spelling
        parse_plan(_split_order(m)[0])

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_cell(arch, shape, mp, mappers=mappers,
                                            out_dir=args.out,
                                            moe_dispatch=args.moe_dispatch))
                except Exception as e:
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "multi" if mp else "single",
                                    "status": "error", "error": repr(e)})
                    print(f"ERROR {arch} {shape} multi={mp}: {e!r}", flush=True)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    Path(args.out).mkdir(parents=True, exist_ok=True)
    (Path(args.out) / "summary.json").write_text(
        json.dumps(results, indent=1, default=float))
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
