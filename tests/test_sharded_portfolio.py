"""Sharded adaptive portfolio engine: shard-count invariance (bit-identical
to the single-process portfolio for any shard count), multiprocessing-
backend parity, restart-from-leader dominance, accept-rate retune bounds,
the killed-budget pool accounting, and the `sharded[...]:`
grammar/plan/cache wiring.

Invariance assertions use array_equal / ==, not isclose: the sharded
coordinator replays the single-process engine's floats exactly (same
kernel, same merge order), so any drift is a bug.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, PlanCache, PortfolioRefiner, RefinedMapper,
                        ShardedPortfolioRefiner, Stencil, available_mappers,
                        device_layout, ensure_refined, evaluate, get_mapper,
                        parse_plan)

#: a schedule small enough for tests but long enough that kills, restarts,
#: and several retune boundaries actually happen.
KW = dict(rounds=1, max_passes=2, sa_moves=60,
          temperatures=(4.0, 2.0, 1.0, 0.5, 0.25))

#: an instance where aggressive early-kill (kill_factor=1.0) reliably
#: kills ladders, so the adaptive pool has budget to redistribute.
KILL_DIMS, KILL_SIZES = (10, 12), (32, 32, 32, 24)


def _kill_instance(seed):
    grid = CartGrid(KILL_DIMS)
    stencil = Stencil.nn_with_hops(2)
    rng = np.random.default_rng(seed)
    a = rng.permutation(np.repeat(np.arange(len(KILL_SIZES)), KILL_SIZES))
    return grid, stencil, a


# ---------------------------------------------------------------------------
# shard-count invariance: bit-identical to the single-process portfolio


@pytest.mark.parametrize("dims,sizes", [((8, 8), (16,) * 4),
                                        ((6, 8), (16, 16, 10, 6))])
def test_shard_count_invariance_bit_identical(dims, sizes):
    """Acceptance: sharded[shards=S,k=K] == portfolio[k=K] bit for bit, for
    any S, when adaptive control is off — same assignment, same final
    (J_max, J_sum), same swap/pass counts."""
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(2)
    rng = np.random.default_rng(5)
    a = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
    kw = dict(rounds=2, max_passes=3, sa_moves=40)
    ref = PortfolioRefiner(k=6, seed=3, **kw).refine(
        grid, stencil, a, num_nodes=len(sizes))
    for S in (1, 2, 3, 4, 6):
        sh = ShardedPortfolioRefiner(shards=S, k=6, seed=3,
                                     backend="serial", **kw).refine(
            grid, stencil, a, num_nodes=len(sizes))
        np.testing.assert_array_equal(sh.assignment, ref.assignment,
                                      err_msg=f"shards={S}")
        assert (sh.final.j_max, sh.final.j_sum) \
            == (ref.final.j_max, ref.final.j_sum)
        assert (sh.swaps, sh.passes) == (ref.swaps, ref.passes)
        assert sh.stats["ladder_keys"] == ref.stats["ladder_keys"]
        assert sh.stats["killed"] == ref.stats["killed"]
        assert sh.stats["shards"] == min(S, 6)


def test_shard_invariance_on_kill_heavy_weighted_instance():
    """The kill rule sees the *global* leader at every boundary, so shard
    invariance must survive an instance with real kills — and byte-weighted
    scoring (weighted='auto') rides through the sharded payloads."""
    grid, stencil, a = _kill_instance(1)
    heavy = Stencil(stencil.offsets,
                    tuple(8.0 if i < 2 else 1.0
                          for i in range(stencil.k)))
    for st_ in (stencil, heavy):
        ref = PortfolioRefiner(k=6, seed=1, kill_factor=1.0, **KW).refine(
            grid, st_, a, num_nodes=len(KILL_SIZES))
        assert ref.stats["killed"] > 0      # the scenario is exercised
        for S in (2, 4):
            sh = ShardedPortfolioRefiner(
                shards=S, k=6, seed=1, kill_factor=1.0, backend="serial",
                **KW).refine(grid, st_, a, num_nodes=len(KILL_SIZES))
            np.testing.assert_array_equal(sh.assignment, ref.assignment)
            assert sh.stats["killed"] == ref.stats["killed"]


def test_mp_backend_matches_serial():
    """The multiprocessing backend ships picklable per-block tasks and must
    return exactly what the in-process blocks return."""
    grid, stencil, a = _kill_instance(2)
    kw = dict(shards=2, k=4, seed=2, rounds=1, max_passes=2, sa_moves=40)
    serial = ShardedPortfolioRefiner(backend="serial", **kw).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    mp_res = ShardedPortfolioRefiner(backend="mp", **kw).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    np.testing.assert_array_equal(serial.assignment, mp_res.assignment)
    assert serial.stats["ladder_keys"] == mp_res.stats["ladder_keys"]
    assert mp_res.stats["backend"] == "mp"


# ---------------------------------------------------------------------------
# adaptive control: restart-from-leader dominance + pool accounting


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_adaptive_restarts_never_worse_than_portfolio(seed):
    """Restart ladders are pure extra candidates (originals replay the
    single-process engine exactly; restarts never feed the kill rule), so
    adaptive-on is lexicographically never worse than portfolio[k=K]."""
    grid, stencil, a = _kill_instance(seed)
    base = PortfolioRefiner(k=5, seed=seed, kill_factor=1.0, **KW).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    ad = ShardedPortfolioRefiner(
        shards=3, k=5, seed=seed, kill_factor=1.0, restarts="auto",
        retune=True, backend="serial", **KW).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert (ad.final.j_max, ad.final.j_sum) \
        <= (base.final.j_max, base.final.j_sum)
    # exact reported costs + preserved scheduler allocation
    check = evaluate(grid, stencil, ad.assignment,
                     num_nodes=len(KILL_SIZES))
    assert (check.j_max, check.j_sum) == (ad.final.j_max, ad.final.j_sum)
    np.testing.assert_array_equal(
        np.bincount(ad.assignment, minlength=len(KILL_SIZES)),
        np.bincount(a, minlength=len(KILL_SIZES)))


def test_restart_pool_accounting_and_cap():
    """Killed ladders fund the restart pool; restarts only spend what the
    pool holds, an int `restarts` caps the total, and restarts=None spawns
    none."""
    grid, stencil, a = _kill_instance(1)
    common = dict(shards=2, k=6, seed=1, kill_factor=1.0,
                  backend="serial", **KW)
    auto = ShardedPortfolioRefiner(restarts="auto", **common).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert auto.stats["killed"] > 0
    assert auto.stats["restarted"] > 0
    # every restart was funded by a killed ladder's unspent temperatures
    assert auto.stats["restarted"] <= auto.stats["killed"]
    assert auto.stats["pool_moves_left"] >= 0
    capped = ShardedPortfolioRefiner(restarts=1, **common).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert capped.stats["restarted"] <= 1
    off = ShardedPortfolioRefiner(restarts=None, **common).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert off.stats["restarted"] == 0 and off.stats["restart_t_mults"] == []


def test_accept_rate_retune_bounds():
    """Retune moves a restart ladder's temperature multiplier in the
    documented direction — up when the accept rate is below the band, down
    when above — and always stays inside retune_bounds (clamped, never
    runaway)."""
    grid, stencil, a = _kill_instance(1)
    common = dict(shards=2, k=6, seed=1, kill_factor=1.0, restarts="auto",
                  retune=True, backend="serial", **KW)
    # a band no walk can satisfy from below: every boundary doubles, so the
    # multiplier must hit (and never exceed) the upper clamp
    bounds = (0.5, 2.0)
    hot = ShardedPortfolioRefiner(accept_band=(0.95, 0.99),
                                  retune_bounds=bounds, **common).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    mults = hot.stats["restart_t_mults"]
    assert mults and all(bounds[0] <= m <= bounds[1] for m in mults)
    assert max(mults) == bounds[1]
    # the mirror: any acceptance is "too hot", so multipliers only shrink
    cold = ShardedPortfolioRefiner(accept_band=(0.0, 0.0),
                                   retune_bounds=bounds, **common).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    mults = cold.stats["restart_t_mults"]
    assert mults and all(bounds[0] <= m <= bounds[1] for m in mults)
    assert min(mults) < 1.0
    # retune is restart-only, so dominance survives it (structural)
    base = PortfolioRefiner(k=6, seed=1, kill_factor=1.0, **KW).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    for res in (hot, cold):
        assert (res.final.j_max, res.final.j_sum) \
            <= (base.final.j_max, base.final.j_sum)


def test_restarts_auto_with_zero_sa_moves_terminates():
    """Regression: a zero-proposal schedule (sa_moves=0) makes a restart
    cost nothing — the spawn loop must not spin forever handing out free
    restarts (every other engine accepts sa_moves=0 and completes)."""
    grid, stencil, a = _kill_instance(1)
    res = ShardedPortfolioRefiner(
        shards=2, k=4, seed=1, kill_factor=1.0, restarts="auto",
        backend="serial", rounds=1, max_passes=2, sa_moves=0).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert res.stats["restarted"] == 0
    base = PortfolioRefiner(k=4, seed=1, kill_factor=1.0, rounds=1,
                            max_passes=2, sa_moves=0).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    np.testing.assert_array_equal(res.assignment, base.assignment)


def test_sharded_validates_config():
    with pytest.raises(ValueError):
        ShardedPortfolioRefiner(shards=0)
    with pytest.raises(ValueError):
        ShardedPortfolioRefiner(restarts=-1)
    with pytest.raises(ValueError):
        ShardedPortfolioRefiner(backend="cluster")
    with pytest.raises(ValueError):
        ShardedPortfolioRefiner(accept_band=(0.9, 0.1))
    with pytest.raises(ValueError):
        ShardedPortfolioRefiner(retune_bounds=(2.0, 4.0))  # must bracket 1
    with pytest.warns(UserWarning, match="duplicate portfolio seeds"):
        r = ShardedPortfolioRefiner(seeds=[4, 4, 9])
    assert r.seeds == (4, 9) and r.k == 2
    assert r.config()["seeds"] == (4, 9)          # honest cache identity


# ---------------------------------------------------------------------------
# grammar / plan / cache wiring


def test_sharded_grammar_stage_and_registry():
    m = get_mapper("sharded[shards=2,k=3,sa_moves=40]:hyperplane")
    assert isinstance(m, RefinedMapper)
    assert isinstance(m.refiner, ShardedPortfolioRefiner)
    assert m.refiner.shards == 2 and m.refiner.k == 3
    assert m.name == "sharded:hyperplane"
    assert "sharded:blocked" in available_mappers()
    # canonical plan key: bracket options sorted, stable across spellings
    assert parse_plan("sharded[k=3,shards=2]:hyperplane").key \
        == parse_plan("sharded[shards=2,k=3]:hyperplane").key
    # restarts=auto / retune=true coerce through the option grammar
    r = get_mapper("sharded[restarts=auto,retune=true,k=2]:blocked").refiner
    assert r.restarts == "auto" and r.retune is True
    r = get_mapper("sharded[restarts=3]:blocked").refiner
    assert r.restarts == 3
    # already-refined spellings pass through ensure_refined unchanged
    assert ensure_refined("sharded[k=2]:hyperplane") == "sharded[k=2]:hyperplane"
    # plans carry the stage; cacheable (all-plain config)
    plan = parse_plan("sharded[k=2,sa_moves=30]:kdtree")
    assert plan.cacheable
    assert ShardedPortfolioRefiner(k=2).as_stage().cacheable


def test_bare_sharded_equals_bare_portfolio():
    """`sharded:<base>` and `portfolio:<base>` share every schedule default,
    so the bare spellings are bit-identical."""
    grid = CartGrid((6, 8))
    stencil = Stencil.nearest_neighbor(2)
    sizes = [16, 16, 10, 6]
    a_sh = get_mapper("sharded:kdtree").assignment(grid, stencil, sizes)
    a_pf = get_mapper("portfolio:kdtree").assignment(grid, stencil, sizes)
    np.testing.assert_array_equal(a_sh, a_pf)


def test_sharded_layouts_cache_and_thread_through_device_layout():
    dims, sizes = (8, 8), [16] * 4
    stencil = Stencil.nearest_neighbor(2)
    cache = PlanCache()
    name = "sharded[shards=2,k=2,sa_moves=30]:hyperplane"
    L1 = device_layout(name, dims, stencil, sizes, cache=cache)
    L2 = device_layout(name, dims, stencil, sizes, cache=cache)
    assert (cache.hits, cache.misses) == (1, 1)
    np.testing.assert_array_equal(L1, L2)
    assert sorted(L1.reshape(-1).tolist()) == list(range(64))


def test_budgeted_sharded_delegates_to_single_process():
    """A max_swaps budget couples every ladder through one shared counter —
    the single-process engine IS that semantics, so the budgeted sharded
    stage must equal the budgeted portfolio bit for bit (and respect the
    per-stage cap)."""
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    sizes = (16,) * 4
    base = get_mapper("random").assignment(grid, stencil, list(sizes))
    kw = dict(k=3, seed=2, rounds=2, max_passes=3, sa_moves=40)
    for budget in (0, 3, 7):
        sh = ShardedPortfolioRefiner(shards=2, **kw).as_stage(
            budget=budget).run(grid, stencil, sizes, base)
        pf = PortfolioRefiner(**kw).as_stage(budget=budget).run(
            grid, stencil, sizes, base)
        np.testing.assert_array_equal(sh.assignment, pf.assignment)
        assert sh.stats["swaps"] <= budget
        assert sh.result.stats["backend"] == "single-process"


# ---------------------------------------------------------------------------
# restart-ladder seeding: never collide with explicit user seeds


def test_restart_seeder_warns_and_shifts_on_collision():
    """A restart seed landing on an explicit portfolio seed must shift
    past every colliding value with a warning — a restart ladder may never
    replay an original trajectory."""
    from repro.core.refine.engine import RestartSeeder
    seeder = RestartSeeder((0, 5, 6), start=5)
    with pytest.warns(UserWarning, match="collides with an explicit"):
        assert seeder() == 7            # 5 and 6 are both taken
    assert seeder() == 8                # stream continues past the shift
    # the default stream (max+1) never collides: no warning expected
    import warnings as _warnings
    clean = RestartSeeder((3, 9, 4))
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        assert [clean() for _ in range(3)] == [10, 11, 12]
    with pytest.raises(ValueError, match="at least one"):
        RestartSeeder(())


def test_restart_seeds_are_fresh_and_reported():
    """End to end on a kill-heavy run with explicit seeds: the restart
    seeds reported in stats are unique and disjoint from the originals."""
    grid, stencil, a = _kill_instance(2)
    res = ShardedPortfolioRefiner(
        shards=2, seeds=(11, 3, 7, 5), kill_factor=1.0, restarts="auto",
        backend="serial", rounds=1, max_passes=2, sa_moves=60,
        temperatures=(4.0, 2.0, 1.0, 0.5, 0.25)).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert res.stats["restarted"] > 0, "instance no longer kill-heavy"
    restart_seeds = res.stats["restart_seeds"]
    assert len(restart_seeds) == res.stats["restarted"]
    assert len(set(restart_seeds)) == len(restart_seeds)
    assert not set(restart_seeds) & {11, 3, 7, 5}
    assert min(restart_seeds) > 11      # max(seeds)+1 counting upward


# ---------------------------------------------------------------------------
# crash injection: a worker raising mid-run must not orphan the pool


def test_sharded_crash_leaves_no_orphans(monkeypatch):
    """Regression for the ProcessPoolExecutor leak: when a worker task
    raises mid-run, the engine must fall back to the serial path (same
    result — the coordinator state is untouched) AND still shut the
    executor down (the try/finally), leaving no orphaned children."""
    import multiprocessing as mp
    import os
    import repro.core.refine.sharded as sh
    grid, stencil, a = _kill_instance(3)
    kw = dict(shards=2, k=4, seed=3, rounds=1, max_passes=2, sa_moves=40)
    want = ShardedPortfolioRefiner(backend="serial", **kw).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))

    before = set(p.pid for p in mp.active_children())
    parent = os.getpid()
    real = sh._block_step

    def boom(payload):
        if os.getpid() != parent:     # fork children inherit the patch
            raise RuntimeError("injected worker crash")
        return real(payload)

    monkeypatch.setattr(sh, "_block_step", boom)
    res = ShardedPortfolioRefiner(backend="mp", **kw).refine(
        grid, stencil, a, num_nodes=len(KILL_SIZES))
    assert res.stats["backend"] == "serial-fallback"
    np.testing.assert_array_equal(res.assignment, want.assignment)
    assert res.stats["ladder_keys"] == want.stats["ladder_keys"]
    # the finally-shutdown joined every pool process: nothing new survives
    after = set(p.pid for p in mp.active_children())
    assert after <= before
