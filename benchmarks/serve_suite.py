"""Serving-layer suite: the resident plan server's pinned claims.

Four machine-checked claims back the mapping-as-a-service PR
(``results/BENCH_9.json``):

(a) **bit-identity** — a ``sharded[...]`` plan served through the
    :class:`~repro.serving.PlanServer`'s persistent-worker engine returns
    the exact layout, J_max, and J_sum of the stateless cold-process
    ``cart_create`` at equal config, on every instance;
(b) **IPC reduction** — per temperature boundary, the resident protocol
    (leader keys + kill/restart masks) moves >= 10x fewer bytes than the
    stateless ``_block_step``'s payload re-ship.  Both sides are
    *measured*: the stateless engine under
    :func:`~repro.core.refine.sharded.measure_ipc` (pickled payload +
    result sizes), the resident pool via its byte-exact framed-pickle
    counters;
(c) **warm-serve latency** — a warm ``cart_create`` through the server
    (cache hit) lands at p50 <= 0.1x the cold-process solve wall-time;
(d) **anytime** — a deadlined request always returns a *valid* plan
    (scheduler cardinalities realized) within its deadline, with
    J_max <= 1.2x the undeadlined solve's.

  PYTHONPATH=src python -m benchmarks.serve_suite
  PYTHONPATH=src python -m benchmarks.serve_suite --quick
  PYTHONPATH=src python -m benchmarks.serve_suite --json results/BENCH_9.json
"""
import argparse
import json
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import CartGrid, Stencil, evaluate, get_mapper
from repro.core.plan import (MappingProblem, PlanCache, cart_create,
                             parse_plan)
from repro.core.refine.sharded import measure_ipc
from repro.serving import PlanClient, PlanServer, ResidentShardedRefiner

#: (label, dims, node_sizes, plan) — ragged instances (the regime the
#: refiners exist for), sized so boundary wall-times dominate overheads.
INSTANCES = [
    ("2d-6x8-ragged", (6, 8), [16, 16, 10, 6],
     "sharded[shards=2,k=8,restarts=auto]:hyperplane"),
    ("2d-16x28-ragged", (16, 28), [32] * 10 + [16] * 4 + [32] * 2,
     "sharded[shards=2,k=16,restarts=auto]:hyperplane"),
    ("3d-4x4x4-hom", (4, 4, 4), [16] * 4,
     "sharded[shards=2,k=8,restarts=auto]:hyperplane"),
]
QUICK_INSTANCES = INSTANCES[:1]

WARM_REPEATS = 20          # warm-serve p50 sample size
IPC_FLOOR = 10.0           # claim (b): >= 10x per-boundary reduction
WARM_FRAC = 0.1            # claim (c): warm p50 <= 0.1x cold
ANYTIME_JMAX = 1.2         # claim (d): J_max <= 1.2x undeadlined
ANYTIME_FRAC = 0.5         # deadline as a fraction of the undeadlined wall


def _problem(dims, sizes):
    return MappingProblem(tuple(dims), Stencil.nearest_neighbor(len(dims)),
                          tuple(sizes))


def run_serve(instances=INSTANCES):
    """One row per instance and claim family; the server is started once
    (2 threads, persistent shard workers) and shared across claims the
    way production traffic would."""
    identity, ipc_rows, warm_rows, anytime_rows = [], [], [], []
    with PlanServer(threads=2, shard_workers=2, max_queue=64) as srv:
        cli = PlanClient(srv)
        for label, dims, sizes, plan in instances:
            problem = _problem(dims, sizes)

            # -- (a) + (c): cold stateless reference vs served ------------
            t0 = time.perf_counter()
            ref = cart_create(dims, node_sizes=sizes, plan=plan,
                              cache=PlanCache())
            t_cold = time.perf_counter() - t0
            t = cli.cart_create_async(dims, node_sizes=sizes, plan=plan)
            served = t.result(timeout=600)
            identity.append({
                "instance": label, "plan": plan,
                "layout_equal": bool(np.array_equal(served.layout,
                                                    ref.layout)),
                "j_max_equal": served.j_max == ref.j_max,
                "j_sum_equal": served.j_sum == ref.j_sum,
                "j_max": served.j_max, "j_sum": served.j_sum,
                "t_cold_s": t_cold, "t_served_cold_s": t.latency_s,
            })

            warm_lat = []
            for _ in range(WARM_REPEATS):
                w = cli.cart_create_async(dims, node_sizes=sizes, plan=plan)
                r = w.result(timeout=60)
                assert r.from_cache, "warm repeat must be a cache hit"
                warm_lat.append(w.latency_s)
            warm_lat.sort()
            warm_rows.append({
                "instance": label, "plan": plan, "t_cold_s": t_cold,
                "warm_p50_s": warm_lat[len(warm_lat) // 2],
                "warm_p95_s": warm_lat[min(len(warm_lat) - 1,
                                           int(0.95 * len(warm_lat)))],
                "repeats": WARM_REPEATS,
                "frac": warm_lat[len(warm_lat) // 2] / t_cold,
            })

            # -- (b): measured per-boundary IPC, stateless vs resident ----
            grid = CartGrid(dims)
            stencil = problem.stencil
            start = get_mapper("hyperplane").assignment(grid, stencil,
                                                        list(sizes))
            stage = parse_plan(plan).stages[-1]
            cfg = dict(stage.refiner.config())
            cfg["backend"] = "serial"       # meter sees identical payloads
            with measure_ipc() as meter:
                stateless = stage.refiner.refine(grid, stencil,
                                                 start.copy(),
                                                 num_nodes=len(sizes))
            with ResidentShardedRefiner(**cfg) as resident_ref:
                resident = resident_ref.refine(grid, stencil, start.copy(),
                                               num_nodes=len(sizes))
            ipc = resident.stats["ipc"]
            stateless_pb = meter.bytes_total / max(1, meter.dispatches)
            ipc_rows.append({
                "instance": label, "plan": plan,
                "identical": bool(np.array_equal(stateless.assignment,
                                                 resident.assignment)),
                "stateless_bytes_total": meter.bytes_total,
                "stateless_dispatches": meter.dispatches,
                "stateless_bytes_per_boundary": stateless_pb,
                "resident_step_bytes": ipc["step_bytes"],
                "resident_boundaries": ipc["boundaries"],
                "resident_bytes_per_boundary":
                    ipc["step_bytes_per_boundary"],
                "resident_init_bytes": ipc["init_bytes"],
                "resident_collect_bytes": ipc["collect_bytes"],
                "reduction": stateless_pb
                    / max(1e-9, ipc["step_bytes_per_boundary"]),
            })

            # -- (d): anytime under a deadline.  Invalidate first: a warm
            # cache would serve the full-quality entry instantly, which is
            # correct serving behavior but wouldn't exercise the cut path
            # this claim is about.
            srv.invalidate(problem)
            deadline_s = max(0.05, ANYTIME_FRAC * t_cold)
            a = cli.cart_create_async(dims, node_sizes=sizes, plan=plan,
                                      deadline_ms=1e3 * deadline_s)
            ar = a.result(timeout=600)
            counts = np.bincount(ar.solution.assignment,
                                 minlength=len(sizes))
            stats = ar.solution.stage_stats[-1]
            anytime_rows.append({
                "instance": label, "plan": plan,
                "deadline_s": deadline_s, "latency_s": a.latency_s,
                "within_deadline": a.latency_s <= deadline_s,
                "cut": a.anytime_cut,
                "cut_stage": stats.get("cut_stage"),
                "cut_at": stats.get("cut_at"),
                "n_temps": stats.get("n_temps"),
                "valid": bool(np.array_equal(np.sort(counts),
                                             np.sort(np.array(sizes)))),
                "j_max": ar.j_max, "j_max_full": ref.j_max,
                "j_max_ratio": ar.j_max / ref.j_max,
            })
        server_stats = srv.stats()
    return {"identity": identity, "ipc": ipc_rows, "warm": warm_rows,
            "anytime": anytime_rows, "server_stats": server_stats}


def validate_serve_claims(out):
    """The PR's acceptance bar, machine-checked (PASS/FAIL verdicts)."""
    claims = []
    bad = [r for r in out["identity"]
           if not (r["layout_equal"] and r["j_max_equal"]
                   and r["j_sum_equal"])]
    claims.append(("PASS" if not bad else "FAIL")
                  + ": persistent-worker serving bit-identical to the "
                  f"stateless sharded engine on all {len(out['identity'])} "
                  "instances (layout, J_max, J_sum)"
                  + (f" (violations: {[r['instance'] for r in bad]})"
                     if bad else ""))
    bad = [r for r in out["ipc"]
           if not r["identical"] or r["reduction"] < IPC_FLOOR]
    claims.append(("PASS" if not bad else "FAIL")
                  + f": measured per-boundary IPC bytes drop >= "
                  f"{IPC_FLOOR:.0f}x vs stateless _block_step on all "
                  f"{len(out['ipc'])} instances (min "
                  f"{min(r['reduction'] for r in out['ipc']):.1f}x)"
                  + (f" (violations: {[(r['instance'], round(r['reduction'], 1)) for r in bad]})"
                     if bad else ""))
    bad = [r for r in out["warm"] if r["frac"] > WARM_FRAC]
    claims.append(("PASS" if not bad else "FAIL")
                  + f": warm served cart_create p50 <= {WARM_FRAC:.1f}x the "
                  f"cold-process solve on all {len(out['warm'])} instances "
                  f"(worst {max(r['frac'] for r in out['warm']):.4f}x)"
                  + (f" (violations: {[(r['instance'], round(r['frac'], 3)) for r in bad]})"
                     if bad else ""))
    bad = [r for r in out["anytime"]
           if not (r["valid"] and r["within_deadline"]
                   and r["j_max_ratio"] <= ANYTIME_JMAX)]
    claims.append(("PASS" if not bad else "FAIL")
                  + ": anytime returns a valid plan within its deadline "
                  f"with J_max <= {ANYTIME_JMAX:.1f}x the undeadlined "
                  f"solve on all {len(out['anytime'])} instances"
                  + (f" (violations: {[(r['instance'], r['valid'], round(r['latency_s'], 3), round(r['deadline_s'], 3), round(r['j_max_ratio'], 3)) for r in bad]})"
                     if bad else ""))
    return claims


def print_serve_table(out):
    print(f"{'instance':18s} {'ident':>5s} {'t_cold':>8s} {'warm_p50':>9s} "
          f"{'frac':>7s} {'ipc_less':>9s} {'ipc_res':>8s} {'redux':>6s} "
          f"{'deadline':>8s} {'latency':>8s} {'cut':>4s} {'Jmax_r':>6s}")
    for ident, w, i, a in zip(out["identity"], out["warm"], out["ipc"],
                              out["anytime"]):
        ok = (ident["layout_equal"] and ident["j_max_equal"]
              and ident["j_sum_equal"])
        print(f"{ident['instance']:18s} {'yes' if ok else 'NO':>5s} "
              f"{w['t_cold_s'] * 1e3:6.0f}ms "
              f"{w['warm_p50_s'] * 1e3:7.1f}ms {w['frac']:7.4f} "
              f"{i['stateless_bytes_per_boundary']:9.0f} "
              f"{i['resident_bytes_per_boundary']:8.0f} "
              f"{i['reduction']:5.1f}x "
              f"{a['deadline_s'] * 1e3:6.0f}ms {a['latency_s'] * 1e3:6.0f}ms "
              f"{'yes' if a['cut'] else 'no':>4s} {a['j_max_ratio']:6.3f}")
    st = out["server_stats"]
    print(f"\nserver: completed={st['completed']} errors={st['errors']} "
          f"rejected={st['rejected']} deadline_misses={st['deadline_misses']} "
          f"anytime_cuts={st['anytime_cuts']} "
          f"cache_hit_rate={st['cache_hit_rate']:.2f} "
          f"p50={st.get('latency_p50_ms', 0):.1f}ms "
          f"p95={st.get('latency_p95_ms', 0):.1f}ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="first instance only (smoke)")
    ap.add_argument("--json", default=None, help="dump rows + claims")
    args = ap.parse_args()
    enable_compile_cache()
    out = run_serve(QUICK_INSTANCES if args.quick else INSTANCES)
    print_serve_table(out)
    print()
    claims = validate_serve_claims(out)
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
