"""Device-resident portfolio conformance suite.

Draw-for-draw parity between the device engine and the numpy kernel is
impossible (different rng generators), so correctness is pinned as a
contract instead:

* **integer-exact count state** — the device-resident stacked crossing
  counts must equal a from-scratch numpy recount of the device
  assignments after *every* temperature boundary, and the reported
  (J_max, J_sum) keys must match ``evaluate`` on the fetched states
  (dyadic weights, so float32 on-device accumulation is exact);
* **alive-mask monotonicity** — a killed ladder freezes: no accepted
  proposals, state bit-stable across subsequent temperatures;
* **seed determinism** — the device rng stream is a pure function of the
  per-ladder seed: equal seeds reproduce runs exactly, and a ladder's
  trajectory is independent of which other seeds share the batch;
* **pinned dominance** — at equal proposal budget (same K, same
  schedule), the device portfolio's final (J_max, J_sum) is
  lexicographically never worse than ``portfolio[k=K]`` across the
  refine_suite tiny instances (the device's structural edge: per-ladder
  best-seen candidates plus polish over all unique survivors, vs the
  host's top-3);
* **K-scaling** — at equal total proposal budget, K=256 stacked ladders
  run under 4x the wall-time of K=8 (the bench pins the same claim at
  K=1024 in ``results/BENCH_7.json``);
* **delegation** — ``max_swaps``/``pinned`` runs fall back to the
  single-process host portfolio;
* **layering** — the device path takes the count state from the cost
  core, never from the ``multiprocessing`` engine.
"""
import copy
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, DevicePortfolioRefiner, PlanCache,
                        PortfolioRefiner, Stencil, available_mappers,
                        evaluate, get_mapper, parse_plan)
from repro.core.cost_delta import neighbor_table, stacked_count_arrays
from repro.core.plan import MappingProblem
from repro.core.refine.device import DeviceLadderEngine, _threefry_keys

# the refine_suite --tiny instances (same rows as benchmarks.refine_suite)
TINY = [
    ("2d-8x8-hom", (8, 8), [16] * 4),
    ("2d-6x8-ragged", (6, 8), [16, 16, 10, 6]),
    ("3d-4x4x4-hom", (4, 4, 4), [16] * 4),
]

#: dyadic edge weights: float32 dot products of integer counts are exact,
#: so device keys can be compared to the float64 reference with ==
W_STENCIL = Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)),
                    (2.0, 2.0, 0.5, 0.5), name="ring-dyadic")


def _instance(seed, dims=(6, 7), n_nodes=5):
    grid = CartGrid(dims)
    rng = np.random.default_rng(seed)
    sizes = np.full(n_nodes, grid.size // n_nodes)
    sizes[: grid.size - sizes.sum()] += 1
    return grid, rng.permutation(np.repeat(np.arange(n_nodes), sizes))


def _exact_keys(grid, stencil, nodes, n_nodes):
    """Reference (J_max, J_sum) per row from a numpy recount."""
    co, cn = stacked_count_arrays(neighbor_table(grid, stencil), nodes,
                                  n_nodes)
    w = stencil.weight_array()
    per = (cn.astype(np.float64) * w[None, None, :]).sum(axis=2)
    return per.max(axis=1), (co.astype(np.float64) * w[None, :]).sum(axis=1)


# ---------------------------------------------------------------------------
# invariant: the resident integer count state is exact at every boundary


def test_count_state_integer_exact_after_every_boundary():
    """After each temperature (including one with a spawned restart row),
    the device count state equals a from-scratch numpy recount of the
    fetched assignments — integer ==, no tolerance — and the reported
    keys match ``evaluate`` exactly."""
    grid, start = _instance(3)
    eng = DeviceLadderEngine(grid, W_STENCIL, start, seeds=(0, 1, 2),
                             num_nodes=5, weighted=True, restart_slots=1)
    alive = np.ones(3, dtype=bool)
    rows = eng.rows
    for ti, T in enumerate((2.0, 1.0, 0.5, 0.25)):
        rep = eng.run_temperature(np.full(rows, T), 30, alive,
                                  np.full(rows, 1e-2))
        snap = eng.snapshot()
        co, cn = stacked_count_arrays(neighbor_table(grid, W_STENCIL),
                                      snap["nodes"], 5)
        np.testing.assert_array_equal(cn, eng.counts())
        jm, js = _exact_keys(grid, W_STENCIL, snap["nodes"], 5)
        np.testing.assert_array_equal(rep.j_max, jm)
        np.testing.assert_array_equal(rep.j_sum, js)
        for i in range(3):          # the reference metric agrees row-wise
            c = evaluate(grid, W_STENCIL, snap["nodes"][i], num_nodes=5,
                         weighted=True)
            assert (c.j_max, c.j_sum) == (rep.j_max[i], rep.j_sum[i])
        if ti == 1:                 # mid-run restart spawn, then keep going
            assert eng.spawn_restart(snap["nodes"][0], seed=77) == 0
    assert eng.spawn_restart(start, seed=78) is None    # slots exhausted


@given(seed=st.integers(0, 10**6), k=st.integers(2, 4),
       sa_moves=st.integers(1, 30))
@settings(max_examples=5, deadline=None)    # each new shape jit-compiles
def test_boundary_report_bounds(seed, k, sa_moves):
    """Device boundary reports satisfy the shared engine contract:
    accepted within [0, sa_moves], zero for dead rows, done sticky."""
    grid, start = _instance(seed % 97)
    eng = DeviceLadderEngine(grid, Stencil.nearest_neighbor(2), start,
                             seeds=tuple(range(k)), num_nodes=5)
    alive = np.ones(k, dtype=bool)
    alive[k - 1] = False
    rep = eng.run_temperature(np.full(k, 1.0), sa_moves, alive,
                              np.full(k, 1e-2))
    assert np.all(rep.accepted >= 0) and np.all(rep.accepted <= sa_moves)
    assert rep.accepted[k - 1] == 0
    done1 = rep.done.copy()
    rep2 = eng.run_temperature(np.full(k, 0.5), sa_moves, alive,
                               np.full(k, 1e-2))
    assert np.all(rep2.done >= done1)           # sticky


# ---------------------------------------------------------------------------
# invariant: alive-mask monotonicity (kill == freeze)


def test_killed_ladder_freezes_bit_stable():
    grid, start = _instance(11)
    eng = DeviceLadderEngine(grid, Stencil.nearest_neighbor(2), start,
                             seeds=(4, 5, 6), num_nodes=5)
    alive = np.ones(3, dtype=bool)
    eng.run_temperature(np.full(3, 2.0), 40, alive, np.full(3, 1e-2))
    alive[1] = False                            # kill at the boundary
    frozen = eng.states()[1].copy()
    frozen_cn = eng.counts()[1].copy()
    for T in (1.0, 0.5, 0.25):
        rep = eng.run_temperature(np.full(3, T), 40, alive,
                                  np.full(3, 1e-2))
        assert rep.accepted[1] == 0
        np.testing.assert_array_equal(eng.states()[1], frozen)
        np.testing.assert_array_equal(eng.counts()[1], frozen_cn)


# ---------------------------------------------------------------------------
# invariant: seed-determinism of the device rng stream


def test_seed_determinism_and_batch_independence():
    """Same seeds => identical trajectories; and a ladder's stream depends
    only on its own seed, not on which seeds ride in the batch."""
    grid, start = _instance(21)
    st_ = Stencil.nearest_neighbor(2)
    kw = dict(num_nodes=5)
    e1 = DeviceLadderEngine(grid, st_, start, seeds=(5, 6), **kw)
    e2 = DeviceLadderEngine(grid, st_, start, seeds=(5, 6), **kw)
    e3 = DeviceLadderEngine(grid, st_, start, seeds=(5, 9), **kw)
    alive = np.ones(2, dtype=bool)
    for T in (2.0, 1.0):
        r1 = e1.run_temperature(np.full(2, T), 50, alive, np.full(2, 1e-2))
        r2 = e2.run_temperature(np.full(2, T), 50, alive, np.full(2, 1e-2))
        r3 = e3.run_temperature(np.full(2, T), 50, alive, np.full(2, 1e-2))
        np.testing.assert_array_equal(r1.accepted, r2.accepted)
        np.testing.assert_array_equal(e1.states(), e2.states())
        # row 0 (seed 5) is identical even though row 1's seed changed
        np.testing.assert_array_equal(e1.states()[0], e3.states()[0])
        assert r1.accepted[0] == r3.accepted[0]


def test_refiner_is_deterministic_end_to_end():
    grid, start = _instance(31)
    st_ = Stencil.nearest_neighbor(2)
    r1 = DevicePortfolioRefiner(k=4, sa_moves=40).refine(
        grid, st_, start, num_nodes=5)
    r2 = DevicePortfolioRefiner(k=4, sa_moves=40).refine(
        grid, st_, start, num_nodes=5)
    np.testing.assert_array_equal(r1.assignment, r2.assignment)
    assert (r1.final.j_max, r1.final.j_sum) \
        == (r2.final.j_max, r2.final.j_sum)


#: seeds around the int32 and uint32 edges, negative, and past 32 bits
KEY_SEEDS = (0, 2**31 - 2, 2**31 - 1, 2**31, 2**31 + 1, 2**32 + 3, -1,
             -12345, 2**40 + 9, -2**40)


def _prngkey_loop(seeds):
    return np.stack([np.asarray(jax.random.PRNGKey(int(s))) for s in seeds])


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_threefry_keys_match_prngkey(x64):
    """The host-built keys are ``PRNGKey``'s, bit for bit, in either x64
    mode: so every ladder's stream is still that of its own seed."""
    with jax.enable_x64(x64):
        got, want = _threefry_keys(KEY_SEEDS), _prngkey_loop(KEY_SEEDS)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_engine_and_spawned_keys_match_prngkey():
    """The engine's rows (seeds, then restart slots keyed by 0) and the
    key a spawned restart writes are ``PRNGKey`` of their seeds."""
    grid, start = _instance(21)
    eng = DeviceLadderEngine(grid, Stencil.nearest_neighbor(2), start,
                             seeds=(5, 6, 9), num_nodes=5, restart_slots=3)
    np.testing.assert_array_equal(np.asarray(eng._keys),
                                  _prngkey_loop((5, 6, 9, 0, 0, 0)))
    assert eng.spawn_restart(start, seed=2**31 + 7) == 0
    np.testing.assert_array_equal(np.asarray(eng._keys[3]),
                                  _prngkey_loop((2**31 + 7,))[0])


def test_engine_setup_makes_no_per_row_key_call(monkeypatch):
    """Set-up builds all 128 rows' keys without one ``PRNGKey`` call, and
    every row starts as the broadcast start and its counts."""
    calls = []
    real = jax.random.PRNGKey
    monkeypatch.setattr(jax.random, "PRNGKey",
                        lambda *a, **kw: calls.append(a) or real(*a, **kw))
    grid, start = _instance(41)
    st_ = Stencil.nearest_neighbor(2)
    eng = DeviceLadderEngine(grid, st_, start, seeds=tuple(range(64)),
                             num_nodes=5, restart_slots=64)
    assert calls == []
    _, cn0 = stacked_count_arrays(neighbor_table(grid, st_), start[None], 5)
    for arr, row in ((eng._node, start), (eng._best_node, start),
                     (eng._cn, cn0[0])):
        arr = np.asarray(arr)
        assert arr.dtype == np.int32 and arr.shape == (128,) + row.shape
        np.testing.assert_array_equal(arr, np.broadcast_to(row, arr.shape))


# ---------------------------------------------------------------------------
# pinned dominance: never worse than portfolio[k=K] at equal budget


@pytest.mark.parametrize("base", ["hyperplane", "random"])
@pytest.mark.parametrize("label,dims,sizes", TINY)
def test_device_dominates_portfolio_at_equal_budget(label, dims, sizes,
                                                    base):
    """The acceptance claim, on the refine_suite tiny instances: at equal
    proposal budget (same K, same schedule) the device portfolio is
    lexicographically (J_max, J_sum) never worse than ``portfolio[k=K]``.
    The device's edge is structural, not stochastic: 2K candidates
    (end states plus device-tracked per-ladder walk minima) and polish
    over every unique survivor instead of the host's top-3.
    ``benchmarks.refine_suite --device`` machine-checks the same claim
    over the full base-mapper matrix into results/BENCH_7.json."""
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(len(dims))
    dev = get_mapper(f"device[k=32,sa_moves=40,polish_top=none]:{base}")
    host = get_mapper(f"portfolio[k=32,sa_moves=40]:{base}")
    cd = evaluate(grid, stencil, dev.assignment(grid, stencil, sizes),
                  num_nodes=len(sizes))
    ch = evaluate(grid, stencil, host.assignment(grid, stencil, sizes),
                  num_nodes=len(sizes))
    assert (cd.j_max, cd.j_sum) <= (ch.j_max, ch.j_sum), \
        f"device worse than portfolio on {label}/{base}"


def test_refiner_preserves_sizes_and_never_worsens():
    for label, dims, sizes in TINY:
        grid = CartGrid(dims)
        st_ = Stencil.nearest_neighbor(len(dims))
        rng = np.random.default_rng(7)
        start = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
        res = DevicePortfolioRefiner(k=4, sa_moves=40).refine(
            grid, st_, start, num_nodes=len(sizes))
        np.testing.assert_array_equal(
            np.bincount(res.assignment, minlength=len(sizes)), sizes)
        assert (res.final.j_max, res.final.j_sum) \
            <= (res.initial.j_max, res.initial.j_sum)
        assert res.stats["backend"].startswith("device[")
        assert res.stats["proposals"] == 4 * 4 * 40     # rows*temps*moves


# ---------------------------------------------------------------------------
# K-scaling: batching amortizes — the accelerator claim at test scale


def test_k_scaling_equal_budget_wall_time():
    """At equal total proposal budget, K=256 stacked ladders cost < 4x the
    wall-time of K=8 (jit warm, min-of-3).  The lock-step vmapped kernel
    makes per-proposal cost roughly K-independent; BENCH_7 pins the same
    measurement at K=1024."""
    grid, start = _instance(5, dims=(8, 8), n_nodes=4)
    st_ = Stencil.nearest_neighbor(2)
    budget = 2560                               # proposals per temperature
    walls = {}
    for K in (8, 256):
        moves = budget // K
        eng = DeviceLadderEngine(grid, st_, start,
                                 seeds=tuple(range(K)), num_nodes=4)
        alive = np.ones(K, dtype=bool)
        temps, eps = np.full(K, 1.0), np.full(K, 1e-2)
        eng.run_temperature(temps, moves, alive, eps)       # compile
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            eng.run_temperature(temps, moves, alive, eps)
            best = min(best, time.perf_counter() - t0)
        walls[K] = best
    assert walls[256] < 4.0 * walls[8], walls


# ---------------------------------------------------------------------------
# grammar, plan cache, and delegation


def test_device_grammar_plan_key_and_cache():
    assert any(n.startswith("device:") for n in available_mappers())
    plan = parse_plan("device[sa_moves=40,k=4]:hyperplane")
    assert plan.key == "device[k=4,sa_moves=40]:hyperplane"
    assert plan.cacheable
    cache = PlanCache()
    problem = MappingProblem((8, 8), Stencil.nearest_neighbor(2), (16,) * 4)
    s1 = cache.solve(problem, plan)
    s2 = cache.solve(problem, plan)
    assert not s1.from_cache and s2.from_cache
    np.testing.assert_array_equal(s1.assignment, s2.assignment)


def test_budgeted_and_pinned_runs_delegate_to_host():
    """max_swaps and pinned masks are host-kernel semantics (move-level
    coupling); the device refiner must hand them to the single-process
    portfolio rather than approximate them."""
    grid, start = _instance(41)
    st_ = Stencil.nearest_neighbor(2)
    res = DevicePortfolioRefiner(k=3, sa_moves=30, max_swaps=10).refine(
        grid, st_, start, num_nodes=5)
    assert res.stats["delegated"] == "max_swaps"
    assert res.stats["backend"] == "host-fallback"
    assert res.swaps <= 10
    ref = copy.copy(PortfolioRefiner(k=3, sa_moves=30))
    ref.max_swaps = 10
    host = ref.refine(grid, st_, start, num_nodes=5)
    np.testing.assert_array_equal(res.assignment, host.assignment)

    pinned = np.zeros(grid.size, dtype=bool)
    pinned[:10] = True
    res = DevicePortfolioRefiner(k=3, sa_moves=30).refine(
        grid, st_, start, num_nodes=5, pinned=pinned)
    assert res.stats["delegated"] == "pinned"
    np.testing.assert_array_equal(res.assignment[pinned], start[pinned])


@pytest.mark.parametrize("module", ["device", "device_swap"])
def test_device_path_imports_nothing_from_sharded(module):
    """The device refiner and its swap scorer take the count state and the
    table memo from the cost core, not from the ``multiprocessing``
    engine."""
    import ast
    import pathlib

    import repro.core.refine as refine
    path = pathlib.Path(refine.__file__).with_name(module + ".py")
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        assert not any(n == "sharded" or n.endswith(".sharded")
                       for n in names), ast.dump(node)


def test_device_restarts_spawn_from_pool():
    """Kill-heavy instance with adaptive control on: killed ladders fund
    restart rows (static preallocated slots), restart seeds are fresh,
    and the count-state invariant holds at the end."""
    grid = CartGrid((10, 12))
    st_ = Stencil.nn_with_hops(2)
    rng = np.random.default_rng(51)
    start = rng.permutation(np.repeat(np.arange(4), (32, 32, 32, 24)))
    res = DevicePortfolioRefiner(
        k=6, sa_moves=60, kill_factor=1.0, restarts="auto", retune=True,
        rounds=1, max_passes=2,
        temperatures=(4.0, 2.0, 1.0, 0.5, 0.25)).refine(
        grid, st_, start, num_nodes=4)
    assert res.stats["killed"] > 0, "instance no longer kill-heavy"
    assert res.stats["restarted"] > 0
    assert res.stats["restart_slots"] == 6
    assert not set(res.stats["restart_seeds"]) & set(res.stats["seeds"])
    assert (res.final.j_max, res.final.j_sum) \
        <= (res.initial.j_max, res.initial.j_sum)


# ---------------------------------------------------------------------------
# the device swap scorer: same swaps and layouts as numpy scoring


@pytest.mark.parametrize("dims,n_nodes", [((9, 8), 6), ((4, 5, 4), 5)],
                         ids=["2d", "3d"])
def test_device_scorer_serves_the_numpy_layout(monkeypatch, dims, n_nodes):
    """With unit weights the rounds' and the polish's pairs are scored on
    the device (told here that the CPU is an accelerator); forcing numpy
    scoring (the module's factory returns no scorer) gives the same
    layout, swaps, passes and pass counters."""
    from repro.core.refine import device_swap
    monkeypatch.setattr(device_swap, "_accelerator", lambda: True)
    grid = CartGrid(dims)
    st_ = Stencil.nearest_neighbor(len(dims))
    start = np.random.default_rng(17).permutation(
        np.arange(grid.size) % n_nodes)

    def solve():
        return DevicePortfolioRefiner(k=4, sa_moves=30, restarts="auto",
                                      seed=3).refine(grid, st_, start,
                                                     num_nodes=n_nodes)
    dev = solve()
    monkeypatch.setattr(device_swap, "device_swap_scorer",
                        lambda *a, **k: None)
    host = solve()
    assert dev.assignment.tobytes() == host.assignment.tobytes()
    assert (dev.swaps, dev.passes) == (host.swaps, host.passes)
    dc, hc = dev.stats["counters"], host.stats["counters"]
    for phase in ("rounds", "survivors/polish"):
        for name in ("swap.passes", "swap.pairs", "swap.applied"):
            assert dc[f"{phase}/{name}"] == hc[f"{phase}/{name}"]
        assert dc[f"{phase}/swap.device_pairs"] == dc[f"{phase}/swap.pairs"]
        assert f"{phase}/swap.device_pairs" not in hc


def test_scorer_builds_one_program_for_frontiers_of_every_size(
        monkeypatch):
    """Passes over frontiers of different sizes, some over one chunk,
    run one compiled program for the problem."""
    from repro.core.refine import device_swap
    from repro.core.refine.device_swap import device_swap_scorer
    from repro.core.refine.swap import SwapRefiner
    monkeypatch.setattr(device_swap, "_accelerator", lambda: True)
    grid = CartGrid((11, 7))
    st_ = Stencil.nearest_neighbor(2)
    start = np.random.default_rng(2).permutation(np.arange(77) % 5)
    scorer = device_swap_scorer(grid, st_, np.ones(st_.k), chunk=72)
    sizes = []
    score = scorer.score

    def counted(ic, P, Q):
        sizes.append(P.size)
        return score(ic, P, Q)
    scorer.score = counted
    built = []

    def on_compile(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for objective in ("j_sum", "j_max"):
            SwapRefiner(objective=objective, scorer=scorer).refine(
                grid, st_, start, num_nodes=5)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert len(set(sizes)) > 2 and max(sizes) > 72
    assert len(built) == 1


def test_other_refiners_never_import_the_scorer():
    """``refined:``, ``refined2:``, ``annealed:``, ``portfolio:``,
    ``sharded:`` and ``hier:`` score with numpy: a fresh process that runs
    them all never loads the device scorer's module."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    env = dict(os.environ,
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    code = (
        "import sys\n"
        "from repro.core import CartGrid, Stencil, get_mapper\n"
        "g, st = CartGrid((6, 8)), Stencil.nearest_neighbor(2)\n"
        "for s in ['refined:hyperplane', 'refined2:hyperplane',\n"
        "          'annealed:hyperplane', 'portfolio[k=2]:hyperplane',\n"
        "          'sharded[k=2,shards=2]:hyperplane', 'hier:hyperplane']:\n"
        "    get_mapper(s).assignment(g, st, [16, 16, 16])\n"
        "print('repro.core.refine.device_swap' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "False"
