#!/usr/bin/env bash
# Tier-1 verify: fast test suite + a smoke run of the refinement benchmark.
# No PYTHONPATH needed — pytest.ini sets pythonpath=src, and the benchmark
# is invoked with an explicit PYTHONPATH below.
#
#   scripts/verify.sh          # tier-1 (default, < ~2 min)
#   scripts/verify.sh --slow   # additionally run the -m slow tests
set -euo pipefail
cd "$(dirname "$0")/.."
# a CPU check: tests and benchmarks here never take a chip (the on-chip
# smoke is chip_smoke.py, run on a TPU host)
export JAX_PLATFORMS=cpu

python -m pytest -x -q

if [[ "${1:-}" == "--slow" ]]; then
    python -m pytest -q -m slow
fi

# batched-engine parity + scheduled-refiner/portfolio invariants, the
# sharded-portfolio engine (shard invariance, adaptive control, cache
# hardening), the elastic re-mesh + linksim replay integration modules,
# and the plan-layer contract (grammar<->plan parity, PlanCache,
# cart_create), run explicitly so a collection failure elsewhere can't
# mask a refinement regression
python -m pytest -q tests/test_refine_batch.py tests/test_portfolio.py \
    tests/test_sharded_portfolio.py \
    tests/test_run_temperature_props.py tests/test_device_portfolio.py \
    tests/test_elastic_remesh.py tests/test_linksim_replay.py \
    tests/test_plan.py tests/test_repair.py \
    tests/test_hier.py tests/test_topology_tree.py tests/test_serving.py \
    tests/test_graph.py tests/test_graph_plan.py \
    tests/test_cost_weight_parity.py tests/test_single_flight.py

# smoke the whole refinement registry (refined: / refined2: / annealed: /
# portfolio: / sharded:) incl. the linksim replay columns (ragged rows
# replay on per-pod torus sizes) and the matching-K sharded claim
# (bit-identity / adaptive superset); the full K=8 sweep is the `-m slow`
# acceptance test (test_portfolio_k8_acceptance_on_suite_ragged_rows)
PYTHONPATH=src python -m benchmarks.refine_suite --tiny --linksim \
    --variants "refined,refined2,annealed,portfolio[k=4],sharded[shards=2,k=4,restarts=auto]"

# the K-scaling claim, focused so it stays offline-sized: 4x the starts
# (K=32 sharded across 2 worker processes vs K=8 single-process) must cost
# < 4x the wall-time while never worsening (J_max, J_sum) vs annealed —
# run on the 16x28 ragged suite instance, where per-temperature work is
# chunky enough for the mp backend to amortize IPC
PYTHONPATH=src python -m benchmarks.refine_suite --instances 16x28 \
    --stencils hops --mappers hyperplane,random \
    --variants "annealed,portfolio[k=8],sharded[shards=2,k=32,restarts=auto,backend=mp]"

# sharded smoke: shard-count invariance of the grammar spelling — the
# sharded engine must be bit-identical to the single-process portfolio
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.core import CartGrid, Stencil, get_mapper

grid, stencil, sizes = CartGrid((6, 8)), Stencil.nearest_neighbor(2), \
    [16, 16, 10, 6]
ref = get_mapper("portfolio[k=4]:hyperplane").assignment(grid, stencil,
                                                         sizes)
sh = get_mapper("sharded[shards=2,k=4]:hyperplane").assignment(grid,
                                                               stencil,
                                                               sizes)
np.testing.assert_array_equal(sh, ref)
print("sharded smoke OK: sharded[shards=2,k=4] == portfolio[k=4] bit-exact")
EOF

# device-portfolio suite: dominance vs the serial portfolio at equal
# proposal budget over the base-mapper matrix, plus the K-scaling sweep
# (K=1024 under 4x the K=8 wall-time at fixed budget) — exit 1 on any
# FAIL — and the machine-readable BENCH_7.json perf snapshot.
mkdir -p results
PYTHONPATH=src python -m benchmarks.refine_suite \
    --device --json results/BENCH_7.json

# device smoke: the device: grammar spelling end to end — integer-exact
# count state, deterministic, sizes preserved, no host fallback
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.core import CartGrid, Stencil, evaluate, get_mapper

grid, stencil, sizes = CartGrid((6, 8)), Stencil.nearest_neighbor(2), \
    [16, 16, 10, 6]
vm = get_mapper("device[k=4,sa_moves=40]:hyperplane")
a1 = vm.assignment(grid, stencil, sizes)
stats = vm.last_result.stats
assert stats["backend"].startswith("device["), stats["backend"]
assert np.bincount(a1, minlength=4).tolist() == sizes
a2 = get_mapper("device[k=4,sa_moves=40]:hyperplane").assignment(
    grid, stencil, sizes)
np.testing.assert_array_equal(a1, a2)
c = evaluate(grid, stencil, a1, num_nodes=4)
print(f"device smoke OK: backend={stats['backend']} "
      f"J=(max {c.j_max:.0f}, sum {c.j_sum:.0f}) "
      f"proposals={stats['proposals']}")
EOF

# hierarchical mapping suite: hier-vs-flat-portfolio on the 4096-chip
# 2-level machine (J_max within 5% at <= 25% of the wall-time) + the
# depth sweep vs blocked (strict J_sum win at every depth) — exit 1 on
# any FAIL — and the machine-readable BENCH_8.json perf snapshot
mkdir -p results
PYTHONPATH=src python -m benchmarks.refine_suite --hier \
    --json results/BENCH_8.json

# hier smoke: the hier: grammar spelling end to end — recursive restricted
# solves, subtree-cache hits on an identical re-mesh, sizes preserved
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.core import CartGrid, Stencil, evaluate, get_mapper
from repro.core.refine import hier_subtree_cache

grid, stencil, sizes = CartGrid((8, 8)), Stencil.nearest_neighbor(2), \
    [16] * 4
hier_subtree_cache().clear()
vm = get_mapper("hier:hyperplane")
a1 = vm.assignment(grid, stencil, sizes)
stats = vm.last_result.stats
assert stats["backend"].startswith("hier["), stats["backend"]
assert stats["solves"] >= 1 and stats["cache_hits"] == 0
assert np.bincount(a1, minlength=4).tolist() == sizes
a2 = get_mapper("hier:hyperplane").assignment(grid, stencil, sizes)
np.testing.assert_array_equal(a1, a2)      # warm re-mesh: pure cache hits
c = evaluate(grid, stencil, a1, num_nodes=4)
print(f"hier smoke OK: backend={stats['backend']} "
      f"J=(max {c.j_max:.0f}, sum {c.j_sum:.0f}) "
      f"solves={stats['solves']} cache={hier_subtree_cache().stats()}")
EOF

# warm-start repair suite: repair-vs-cold on the loss/add/slow churn
# scenarios — quality within 5% on (J_max, J_sum), wall-time <= 50% of the
# cold elastic solve, warm path only (exit 1 on any FAIL) — and the
# machine-readable BENCH_6.json perf snapshot
mkdir -p results
PYTHONPATH=src python -m benchmarks.refine_suite --repair \
    --json results/BENCH_6.json

# repair smoke: monitor-driven slow-pod flow — down-weighted warm repair
# from a served solution, cached under the survivor signature
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.core import (MappingProblem, PlanCache, Stencil,
                        elastic_portfolio_plan, repair_layout)
from repro.core.repair import downweighted_node_sizes

cache = PlanCache()
stencil = Stencil.nearest_neighbor(2)
prev = elastic_portfolio_plan().solve(
    MappingProblem((6, 8), stencil, (8,) * 6), cache)
dw = downweighted_node_sizes((8,) * 6, 4, 2.0)
rep = repair_layout(prev, dw, cache=cache)
assert not rep.from_cache
assert np.bincount(rep.assignment, minlength=6).tolist() == dw
st = rep.stage_stats[0]
assert st["kind"] == "repair" and not st["used_fallback"]
again = repair_layout(prev, dw, cache=cache)
assert again.from_cache and again.key() == rep.key()
print(f"repair smoke OK: J=(max {rep.j_max:.0f}, sum {rep.j_sum:.0f}) "
      f"pinned={st['pinned']} swaps={st['swaps']} cache={cache.stats()}")
EOF

# serving suite: resident persistent-worker engine bit-identical to the
# stateless sharded engine, measured per-boundary IPC >= 10x smaller,
# warm served cart_create p50 <= 0.1x cold, anytime valid within deadline
# at J_max <= 1.2x (exit 1 on any FAIL) — and the machine-readable
# BENCH_9.json perf snapshot
mkdir -p results
PYTHONPATH=src python -m benchmarks.serve_suite --json results/BENCH_9.json

# serve smoke: start server -> warm-up sweep over the topology registry ->
# concurrent submits (mixed warm/cold) -> anytime deadline hit on a fresh
# problem -> clean shutdown with no orphaned worker processes
PYTHONPATH=src python - <<'EOF'
import multiprocessing as mp
import numpy as np
from repro.core.plan import MappingProblem
from repro.core.stencil import Stencil
from repro.serving import PlanClient, PlanServer

plan = "sharded[shards=2,k=4,restarts=auto]:hyperplane"
with PlanServer(threads=2, shard_workers=2, default_plan=plan) as srv:
    warm = srv.warm_up()
    assert warm["swept"] >= 2, warm
    # the resident shard workers ran, not the inline fallback
    assert set(warm["backends"].values()) == {"resident"}, warm
    cli = PlanClient(srv)
    tickets = [cli.cart_create_async((6, 8), node_sizes=(16, 16, 10, 6))
               for _ in range(6)]
    results = [t.result(timeout=300) for t in tickets]
    for r in results[1:]:
        np.testing.assert_array_equal(r.layout, results[0].layout)
    fresh = MappingProblem((10, 12), Stencil.nearest_neighbor(2),
                           (32, 32, 32, 24))
    a = srv.submit(fresh, deadline_ms=200)
    sol = a.result(timeout=300)
    counts = np.bincount(sol.assignment, minlength=4)
    assert sorted(counts) == sorted((32, 32, 32, 24))
    st = srv.stats()
    assert st["errors"] == 0 and st["completed"] == 7, st
    assert st["warmed"] == warm["swept"], st
assert mp.active_children() == [], mp.active_children()
print(f"serve smoke OK: warm={warm} anytime_cut={a.anytime_cut} "
      f"latency={a.latency_s * 1e3:.0f}ms p50={st['latency_p50_ms']:.1f}ms "
      f"hit_rate={st['cache_hit_rate']:.2f}")
EOF

# graph-layer suite: every available_mappers() spelling bit-identical
# between the grid and graph: paths with independent cache keys, plus
# mapped-vs-blocked DCI on every registry arch with exact linksim replay
# agreement (exit 1 on any FAIL) — the --tiny smoke first (in-process
# spellings, 3 archs), then the full run emitting the machine-readable
# BENCH_10.json perf snapshot
mkdir -p results
PYTHONPATH=src python -m benchmarks.graph_suite --tiny
PYTHONPATH=src python -m benchmarks.graph_suite \
    --json results/BENCH_10.json

# graph smoke: extract a real arch comm graph -> map it through the graph:
# plan flavor -> replay the mapped traffic exactly, warm hit on re-solve
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.analysis import replay_graph
from repro.core import PlanCache, arch_comm_graph, graph_create

cache = PlanCache()
g = arch_comm_graph("mixtral-8x7b", 64)
sizes = (8,) * 8
cold = graph_create(g, node_sizes=sizes, cache=cache)
assert cold.plan_key.startswith("graph:") and not cold.from_cache
rep = replay_graph(g, cold.solution.assignment, sizes)
assert rep.dci_total == cold.j_sum and rep.max_dci_pod() == cold.j_max
warm = graph_create(g, node_sizes=sizes, cache=cache)
assert warm.from_cache
np.testing.assert_array_equal(cold.layout, warm.layout)
blocked = graph_create(g, node_sizes=sizes, reorder=False, cache=False)
print(f"graph smoke OK: plan={cold.plan_key} edges={len(g.indices)} "
      f"Jsum {blocked.j_sum / cold.j_sum:.2f}x better than blocked "
      f"cache={cache.stats()}")
EOF

# cart_create smoke: cold solve -> warm cache hit, asserted via counters
PYTHONPATH=src python - <<'EOF'
import numpy as np
from repro.core import PlanCache, cart_create

cache = PlanCache()
cold = cart_create((8, 8), chips_per_pod=16, cache=cache)
assert (cache.hits, cache.misses) == (0, 1) and not cold.from_cache
warm = cart_create((8, 8), chips_per_pod=16, cache=cache)
assert (cache.hits, cache.misses) == (1, 1) and warm.from_cache
np.testing.assert_array_equal(cold.layout, warm.layout)
assert (warm.j_max, warm.j_sum) == (cold.j_max, cold.j_sum)
print(f"cart_create smoke OK: plan={cold.plan_key} "
      f"J=(max {cold.j_max:.0f}, sum {cold.j_sum:.0f}) "
      f"cache={cache.stats()}")
EOF
echo "verify OK"
