"""Graph-layer suite: the CommGraph generalization's pinned claims.

Two machine-checked claims back the arbitrary-sparse-graph PR
(``results/BENCH_10.json``):

(a) **spelling parity** — for *every* ``available_mappers()`` spelling,
    the ``graph:`` flavor of the plan (cost core driven by
    ``CommGraph.from_stencil`` slot decomposition) returns bit-identical
    labels and exactly equal J_max/J_sum to the native grid path on a
    4x4 nearest-neighbor instance, under a distinct plan key with
    independent cache entries (two cold misses, then two hits);
(b) **arch DCI** — on every architecture in the config registry, mapping
    the real communication graph (TP/DP rings + MoE all-to-all from
    :func:`~repro.core.graph.arch_comm_graph`) with the default graph
    plan lex-dominates the blocked identity layout, with a strict J_sum
    reduction on >= 3 archs, and the link-level replay
    (:func:`~repro.analysis.replay_graph`) agrees with the graph
    objective *exactly* (``dci_total == J_sum``,
    ``max_dci_pod == J_max``) on both layouts.

  PYTHONPATH=src python -m benchmarks.graph_suite
  PYTHONPATH=src python -m benchmarks.graph_suite --tiny
  PYTHONPATH=src python -m benchmarks.graph_suite --json results/BENCH_10.json
"""
import argparse
import json
import time

import numpy as np

from repro.analysis import replay_graph
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS
from repro.core import (MappingProblem, PlanCache, Stencil, arch_comm_graph,
                        graph_create, parse_plan)
from repro.core.mapping import available_mappers

#: claim (a) instance — small enough that all 56 spellings finish, rich
#: enough (two axes, four nodes) that broken slot wiring can't hide.
PARITY_DIMS = (4, 4)
PARITY_SIZES = (4, 4, 4, 4)

GRAPH_PLAN = "annealed:graphgreedy"   # claim (b) mapping plan
MIN_STRICT_WINS = 3                   # claim (b): strict J_sum win floor


def _parity_spellings(tiny: bool):
    names = available_mappers()
    if tiny:
        # device: compiles jax kernels, sharded: forks worker processes —
        # both covered by the full run; the smoke tier keeps the pure
        # in-process engines.
        names = [n for n in names
                 if not n.startswith(("device:", "sharded"))]
    return names


def run_parity(tiny: bool = False):
    """Claim (a): one row per spelling, grid path vs graph: path."""
    problem = MappingProblem(PARITY_DIMS,
                             Stencil.nearest_neighbor(len(PARITY_DIMS)),
                             PARITY_SIZES)
    rows = []
    for spelling in _parity_spellings(tiny):
        p_grid = parse_plan(spelling)
        p_graph = parse_plan("graph:" + spelling)
        t0 = time.perf_counter()
        s_grid = p_grid.solve(problem)
        t_grid = time.perf_counter() - t0
        t0 = time.perf_counter()
        s_graph = p_graph.solve(problem)
        t_graph = time.perf_counter() - t0
        cache = PlanCache(maxsize=64)
        cache.solve(problem, p_grid)
        cache.solve(problem, p_graph)
        cold = (cache.hits, cache.misses) == (0, 2)
        cache.solve(problem, p_grid)
        cache.solve(problem, p_graph)
        warm = (cache.hits, cache.misses) == (2, 2)
        rows.append({
            "spelling": spelling,
            "labels_equal": bool(np.array_equal(s_grid.assignment,
                                                s_graph.assignment)),
            "j_max_equal": s_grid.j_max == s_graph.j_max,
            "j_sum_equal": s_grid.j_sum == s_graph.j_sum,
            "keys_distinct": p_graph.key == "graph:" + p_grid.key,
            "cache_independent": cold and warm,
            "j_max": s_grid.j_max, "j_sum": s_grid.j_sum,
            "t_grid_s": t_grid, "t_graph_s": t_graph,
        })
    return rows


def run_arch_dci(tiny: bool = False):
    """Claim (b): one row per registry arch, mapped vs blocked DCI."""
    archs = list(ARCHS)
    num_devices, node_size, n_nodes = 64, 8, 8
    if tiny:
        archs, num_devices, node_size = archs[:3], 32, 4
    sizes = (node_size,) * n_nodes
    rows = []
    for name in archs:
        g = arch_comm_graph(name, num_devices)
        t0 = time.perf_counter()
        mapped = graph_create(g, node_sizes=sizes, plan=GRAPH_PLAN,
                              cache=False)
        t_map = time.perf_counter() - t0
        blocked = graph_create(g, node_sizes=sizes, reorder=False,
                               cache=False)
        rep_m = replay_graph(g, mapped.solution.assignment, sizes)
        rep_b = replay_graph(g, blocked.solution.assignment, sizes)
        rows.append({
            "arch": name, "num_devices": num_devices,
            "edges": int(len(g.indices)), "slots": len(g.slots()),
            "plan": mapped.plan_key,
            "j_sum_mapped": mapped.j_sum, "j_sum_blocked": blocked.j_sum,
            "j_max_mapped": mapped.j_max, "j_max_blocked": blocked.j_max,
            "j_sum_ratio": blocked.j_sum / max(1e-9, mapped.j_sum),
            "j_max_ratio": blocked.j_max / max(1e-9, mapped.j_max),
            "lex_no_worse": (mapped.j_max, mapped.j_sum)
                <= (blocked.j_max, blocked.j_sum),
            "strict_j_sum_win": mapped.j_sum < blocked.j_sum,
            "replay_exact": (rep_m.dci_total == mapped.j_sum
                             and rep_m.max_dci_pod() == mapped.j_max
                             and rep_b.dci_total == blocked.j_sum
                             and rep_b.max_dci_pod() == blocked.j_max),
            "t_map_s": t_map,
        })
    return rows


def validate_graph_claims(out):
    """The PR's acceptance bar, machine-checked (PASS/FAIL verdicts)."""
    claims = []
    par = out["parity"]
    bad = [r["spelling"] for r in par
           if not (r["labels_equal"] and r["j_max_equal"]
                   and r["j_sum_equal"] and r["keys_distinct"]
                   and r["cache_independent"])]
    claims.append(("PASS" if not bad else "FAIL")
                  + f": graph: flavor bit-identical to the grid path on "
                  f"all {len(par)} registered spellings, with distinct "
                  "plan keys and independent cache entries"
                  + (f" (violations: {bad})" if bad else ""))
    arch = out["arch_dci"]
    bad = [r["arch"] for r in arch if not r["replay_exact"]]
    claims.append(("PASS" if not bad else "FAIL")
                  + ": linksim replay agrees with the graph objective "
                  f"exactly on all {len(arch)} archs, both layouts "
                  "(dci_total == J_sum, max_dci_pod == J_max)"
                  + (f" (violations: {bad})" if bad else ""))
    bad = [r["arch"] for r in arch if not r["lex_no_worse"]]
    wins = sum(r["strict_j_sum_win"] for r in arch)
    ok = not bad and wins >= MIN_STRICT_WINS
    best = max(r["j_sum_ratio"] for r in arch)
    claims.append(("PASS" if ok else "FAIL")
                  + f": mapped comm graph lex-dominates blocked on all "
                  f"{len(arch)} archs with a strict J_sum win on "
                  f"{wins} >= {MIN_STRICT_WINS} (best {best:.2f}x)"
                  + (f" (lex violations: {bad})" if bad else ""))
    return claims


def print_graph_table(out):
    par = out["parity"]
    n_ok = sum(r["labels_equal"] and r["j_max_equal"] and r["j_sum_equal"]
               for r in par)
    print(f"parity: {n_ok}/{len(par)} spellings bit-identical "
          f"(grid {sum(r['t_grid_s'] for r in par):.1f}s, "
          f"graph {sum(r['t_graph_s'] for r in par):.1f}s)")
    for r in par:
        if not (r["labels_equal"] and r["cache_independent"]):
            print(f"  MISMATCH {r['spelling']}")
    print()
    print(f"{'arch':22s} {'edges':>6s} {'slots':>5s} {'Jsum_blk':>10s} "
          f"{'Jsum_map':>10s} {'redux':>7s} {'Jmax_rx':>7s} {'exact':>5s} "
          f"{'t_map':>7s}")
    for r in out["arch_dci"]:
        print(f"{r['arch']:22s} {r['edges']:6d} {r['slots']:5d} "
              f"{r['j_sum_blocked']:10.3g} {r['j_sum_mapped']:10.3g} "
              f"{r['j_sum_ratio']:6.2f}x {r['j_max_ratio']:6.2f}x "
              f"{'yes' if r['replay_exact'] else 'NO':>5s} "
              f"{r['t_map_s']:6.2f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="in-process spellings + 3 archs at 32 devices "
                         "(CI smoke)")
    ap.add_argument("--json", default=None, help="dump rows + claims")
    args = ap.parse_args()
    enable_compile_cache()
    out = {"parity": run_parity(args.tiny),
           "arch_dci": run_arch_dci(args.tiny)}
    print_graph_table(out)
    print()
    claims = validate_graph_claims(out)
    for c in claims:
        print("# " + c)
    out["claims"] = claims
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=float)
    if any(c.startswith("FAIL") for c in claims):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
