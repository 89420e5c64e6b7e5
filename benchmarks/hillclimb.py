import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ before any jax import (same contract as launch/dryrun.py)
"""§Perf hillclimbing driver: run a cell's baseline + named variants, print
the three roofline terms and memory for each, and save the iteration log.

  PYTHONPATH=src python -m benchmarks.hillclimb --cell mixtral-prefill
  PYTHONPATH=src python -m benchmarks.hillclimb --list
"""
import argparse
import json
from pathlib import Path

from repro.launch.dryrun import run_cell

# (name, run_cell kwargs) — each list is one hillclimb with its hypothesis
# log kept in EXPERIMENTS.md §Perf.
CELLS = {
    # worst useful_ratio: GShard einsum dispatch is quadratic in S at 32k
    "mixtral-prefill": dict(
        arch="mixtral-8x7b", shape="prefill_32k", multi=False,
        variants=[
            ("baseline-einsum", {}),
            ("scatter-dispatch", {"moe_dispatch": "scatter"}),
            ("scatter+cap1.0", {"moe_dispatch": "scatter",
                                "overrides": {"capacity_factor": 1.0}}),
            ("scatter+cap+kvshard", {"moe_dispatch": "scatter",
                                     "overrides": {"capacity_factor": 1.0},
                                     "part_rules": {"prefill_kv_constrain": True}}),
        ]),
    # most collective-bound: FSDP gathers x microbatches + EP all-to-all
    "deepseek-train": dict(
        arch="deepseek-v3-671b", shape="train_4k", multi=True,
        variants=[
            ("baseline", {}),
            ("scatter-dispatch", {"moe_dispatch": "scatter"}),
            ("mb4", {"overrides": {"microbatches": 4}}),
            ("mb4+scatter", {"moe_dispatch": "scatter",
                             "overrides": {"microbatches": 4}}),
            ("mb2+scatter", {"moe_dispatch": "scatter",
                             "overrides": {"microbatches": 2}}),
            ("mb2", {"overrides": {"microbatches": 2}}),
            ("mb1", {"overrides": {"microbatches": 1}}),
        ]),
    # collective-bound dense prefill: 56 heads don't divide the model axis
    "yi-prefill": dict(
        arch="yi-34b", shape="prefill_32k", multi=False,
        variants=[
            ("baseline-56h", {}),
            ("pad-heads-64", {"overrides": {"n_heads": 64}}),
            ("pad-heads+mb-na", {"overrides": {"n_heads": 64,
                                               "remat": "dots"}}),
            ("pad-heads+kvshard", {"overrides": {"n_heads": 64},
                                   "part_rules": {"prefill_kv_constrain": True}}),
        ]),
    # long-context decode: ring cache for SWA (memory term)
    "mixtral-long": dict(
        arch="mixtral-8x7b", shape="long_500k", multi=False,
        variants=[
            ("baseline-full-cache", {}),
            ("ring-cache", {"overrides": {"swa_ring_cache": True}}),
        ]),
    "zamba-long": dict(
        arch="zamba2-2.7b", shape="long_500k", multi=False,
        variants=[
            ("baseline-full-cache", {}),
            ("ring-cache", {"overrides": {"swa_ring_cache": True}}),
        ]),
    # SSD chunk-size compute/memory trade (small-d_model ssm)
    "mamba-train": dict(
        arch="mamba2-130m", shape="train_4k", multi=False,
        variants=[
            ("baseline-Q256", {}),
            ("Q128", {"overrides": {"ssm_chunk": 128}}),
            ("Q64", {"overrides": {"ssm_chunk": 64}}),
        ]),
}


def fmt_row(name, r):
    ro = r["roofline"]
    m = r["memory"]
    ops = r.get("coll_wire_by_op", {})
    opstr = " ".join(f"{k.split('-')[-1][:3]}:{v:.2e}"
                     for k, v in sorted(ops.items()))
    return (f"{name:22s} tc={ro['t_compute_s']:9.3e} tm={ro['t_memory_s']:9.3e} "
            f"tx={ro['t_collective_s']:9.3e} dom={ro['dominant']:10s} "
            f"useful={ro['useful_ratio']:5.2f} arg={m['argument_gib']:6.2f}G "
            f"temp={m['temp_gib']:6.2f}G | {opstr}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="runs/perf")
    ap.add_argument("--mappers", default="blocked,stencil_strips",
                    help="comma list; any name get_mapper resolves")
    ap.add_argument("--refine", action="store_true",
                    help="also route collectives over swap-refined layouts "
                         "(core.refine local search on top of each mapper)")
    args = ap.parse_args()
    if args.list or not args.cell:
        print("cells:", ", ".join(CELLS))
        return
    spec = CELLS[args.cell]
    mappers = tuple(args.mappers.split(","))
    if args.refine:
        mappers += tuple(f"refined:{m}" for m in mappers
                         if not m.startswith("refined:"))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    results = []
    for name, kw in spec["variants"]:
        r = run_cell(spec["arch"], spec["shape"], spec["multi"],
                     mappers=mappers, verbose=False,
                     **kw)
        results.append({"variant": name, **r})
        print(fmt_row(name, r), flush=True)
    (out / f"{args.cell}.json").write_text(
        json.dumps(results, indent=1, default=float))


if __name__ == "__main__":
    main()
