"""Plan-layer contract: grammar<->plan parity, the serving cache, budgets,
and the `cart_create` facade.

Pinned invariants:
  * parity — for EVERY spelling in ``available_mappers()`` (and chained
    prefixes), ``parse_plan(name).solve(problem)`` returns the same
    assignment bit-exactly as ``get_mapper(name)`` on the refine_suite
    ``--tiny`` instances;
  * cache — hit/miss/eviction counters, content-keyed identity (changing
    stencil *weights* must miss), disk spill round-trip, and the
    acceptance claim: a warm cache makes a repeated mesh build >= 10x
    faster than the cold portfolio solve;
  * chained prefixes — appending a lexicographic refine stage never
    worsens ``(J_max, J_sum)`` (property test);
  * option grammar — negative numbers / scientific notation parse, and
    errors name the full spelling.
"""
import json
import math
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, MapperInapplicable, MappingPlan,
                        MappingProblem, PlanCache, Stencil, available_mappers,
                        cart_create, evaluate, get_mapper, mapped_device_array,
                        parse_plan)
from repro.core.mapping import parse_mapper_options, split_mapper_name
from repro.core.plan import default_plan_cache
from repro.core.refine import (BaseStage, RefineStage, ScheduledRefiner,
                               SwapRefiner)

# the refine_suite --tiny instances
TINY = [
    ("2d-8x8-hom", (8, 8), (16,) * 4),
    ("2d-6x8-ragged", (6, 8), (16, 16, 10, 6)),
    ("3d-4x4x4-hom", (4, 4, 4), (16,) * 4),
]

CHAINED = ("refined2:refined:hyperplane",
           "portfolio[k=2,sa_moves=40]:refined:kdtree",
           "annealed[sa_moves=50]:refined[policy=steepest]:blocked")


def _problem(dims, sizes, stencil=None):
    return MappingProblem(dims, stencil or Stencil.nearest_neighbor(len(dims)),
                          sizes)


# ---------------------------------------------------------------------------
# parity: the string grammar is a thin front-end onto plans


@pytest.mark.parametrize("label,dims,sizes", TINY)
def test_parse_plan_parity_with_get_mapper_all_spellings(label, dims, sizes):
    """Acceptance: every available_mappers() spelling solves bit-exactly
    equal through the plan API and the Mapper API."""
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(len(dims))
    problem = _problem(dims, sizes, stencil)
    for name in available_mappers():
        plan = parse_plan(name)
        try:
            via_mapper = get_mapper(name).assignment(grid, stencil,
                                                     list(sizes))
        except MapperInapplicable:
            with pytest.raises(MapperInapplicable):
                plan.solve(problem)
            continue
        sol = plan.solve(problem)
        np.testing.assert_array_equal(sol.assignment, via_mapper,
                                      err_msg=f"{name} on {label}")
        cost = evaluate(grid, stencil, via_mapper, num_nodes=len(sizes))
        assert (sol.j_max, sol.j_sum) == (cost.j_max, cost.j_sum)


def test_parse_plan_parity_chained_prefixes():
    """Chained prefixes work identically through both front-ends, one
    refine stage per prefix, applied inner-first."""
    dims, sizes = (8, 8), (16,) * 4
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(2)
    problem = _problem(dims, sizes, stencil)
    for name in CHAINED:
        plan = parse_plan(name)
        assert len(plan.stages) == 3
        assert isinstance(plan.stages[0], BaseStage)
        assert all(isinstance(s, RefineStage) for s in plan.stages[1:])
        sol = plan.solve(problem)
        via_mapper = get_mapper(name).assignment(grid, stencil, list(sizes))
        np.testing.assert_array_equal(sol.assignment, via_mapper, err_msg=name)


def test_plan_key_canonical_and_kwargs_merge():
    assert parse_plan("portfolio[seed=3,k=8]:hyperplane").key \
        == "portfolio[k=8,seed=3]:hyperplane"
    # kwargs configure the outermost refiner and land in the key; bracket
    # options win on conflict (same rule as get_mapper)
    assert parse_plan("refined:kdtree", policy="steepest").key \
        == "refined[policy=steepest]:kdtree"
    assert parse_plan("portfolio[k=4]:hyperplane", k=16).key \
        == "portfolio[k=4]:hyperplane"
    assert parse_plan("refined2:refined:hyperplane").key \
        == "refined2:refined:hyperplane"
    # base kwargs (no prefix) are part of the spelling too
    assert parse_plan("random", seed=7).key == "random{seed=7}"
    m = get_mapper("annealed[sa_moves=50]:kdtree")
    assert m.plan_key == "annealed[sa_moves=50]:kdtree"


def test_get_mapper_fallback_and_budget_kwargs_still_work():
    """Wrapper-level knobs survive the parse_plan rewrite: `fallback`
    starts refinement from another base when the primary is inapplicable
    (nodecart on ragged sizes), `budget` caps stage swaps — via kwargs or
    bracket options, through both front-ends."""
    dims, sizes = (6, 8), (16, 16, 10, 6)          # ragged: nodecart raises
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(2)
    with pytest.raises(MapperInapplicable):
        get_mapper("refined:nodecart").assignment(grid, stencil, list(sizes))
    a = get_mapper("refined:nodecart",
                   fallback="blocked").assignment(grid, stencil, list(sizes))
    np.testing.assert_array_equal(np.bincount(a, minlength=4), sizes)
    plan = parse_plan("annealed[fallback=blocked,budget=5]:nodecart")
    assert plan.stages[0].fallback is not None
    assert plan.stages[1].budget == 5
    assert plan.key == "annealed@budget=5:nodecart@fallback=blocked"
    sol = plan.solve(_problem(dims, sizes, stencil))
    assert sum(s.get("swaps", 0) for s in sol.stage_stats) <= 5
    via_mapper = get_mapper(
        "annealed[fallback=blocked,budget=5]:nodecart").assignment(
        grid, stencil, list(sizes))
    np.testing.assert_array_equal(sol.assignment, via_mapper)


def test_hand_built_stages_never_share_keys_across_configs():
    """Cache-identity soundness: two differently-configured hand-built
    plans (no spelled options) must have different keys — and neither may
    collide with the bare parsed spelling."""
    from repro.core.mapping import RandomMapper
    p1 = MappingPlan([BaseStage("hyperplane"),
                      ScheduledRefiner(anneal=True, seed=1,
                                       sa_moves=300).as_stage()])
    p2 = MappingPlan([BaseStage("hyperplane"),
                      ScheduledRefiner(anneal=True, seed=2,
                                       sa_moves=50).as_stage()])
    parsed = parse_plan("annealed:hyperplane")
    assert p1.key != p2.key
    assert p1.key != parsed.key and p2.key != parsed.key
    # equal configs do share (deduplication, not just safety)
    p1b = MappingPlan([BaseStage("hyperplane"),
                       ScheduledRefiner(anneal=True, seed=1,
                                        sa_moves=300).as_stage()])
    assert p1.key == p1b.key
    # instance-built base mappers carry their configuration too
    assert MappingPlan([BaseStage(RandomMapper(seed=9))]).key \
        != MappingPlan([BaseStage(RandomMapper(seed=1))]).key
    # and the cache really separates them
    cache = PlanCache()
    problem = _problem((8, 8), (16,) * 4)
    s1 = cache.solve(problem, p1)
    s2 = cache.solve(problem, p2)
    assert not s2.from_cache and cache.misses == 2


def test_unkeyable_plans_bypass_the_cache():
    """A stage whose configuration has no stable spelling (nested objects
    would render as memory-address reprs) must never enter the cache."""
    from repro.core import RefinedMapper
    inner = RefinedMapper("hyperplane")            # nested objects in vars()
    plan = MappingPlan([BaseStage(inner)])
    assert not plan.cacheable
    cache = PlanCache()
    s1 = cache.solve(_problem((8, 8), (16,) * 4), plan)
    s2 = cache.solve(_problem((8, 8), (16,) * 4), plan)
    assert not s1.from_cache and not s2.from_cache
    assert cache.stats()["puts"] == 0
    # and to_mapper propagates "no stable key" instead of a bogus one
    assert plan.to_mapper().plan_key is None
    # a foreign refiner without config() is likewise unkeyed
    class Alien:
        def __init__(self):
            self.helper = object()
        def refine(self, *a, **k):                 # pragma: no cover
            raise NotImplementedError
    assert not RefineStage(Alien()).cacheable
    # cacheable plans still advertise it
    assert parse_plan("annealed:hyperplane").cacheable
    assert MappingPlan([BaseStage("hyperplane"),
                        SwapRefiner().as_stage()]).cacheable


def test_refine_stage_rejects_assignment_violating_node_sizes():
    """The blocked-allocation guard: a base whose assignment doesn't
    realize node_sizes must raise, not silently corrupt the bijection."""
    grid, stencil = CartGrid((4, 4)), Stencil.nearest_neighbor(2)
    bad = np.repeat([0, 1], [10, 6])               # node_sizes say [8, 8]
    with pytest.raises(AssertionError, match="node_sizes"):
        SwapRefiner().as_stage().run(grid, stencil, (8, 8), bad)


def test_device_layout_cache_key_is_canonical():
    """Equivalent spellings (reordered bracket options, get_mapper
    instances) share one cache entry."""
    from repro.core import device_layout
    dims, sizes = (8, 8), [16] * 4
    stencil = Stencil.nearest_neighbor(2)
    cache = PlanCache()
    spelled = "annealed[sa_moves=50,seed=1]:hyperplane"
    reordered = "annealed[seed=1,sa_moves=50]:hyperplane"
    L1 = device_layout(spelled, dims, stencil, sizes, cache=cache)
    L2 = device_layout(reordered, dims, stencil, sizes, cache=cache)
    L3 = device_layout(get_mapper(spelled), dims, stencil, sizes, cache=cache)
    assert (cache.hits, cache.misses) == (2, 1)
    np.testing.assert_array_equal(L1, L2)
    np.testing.assert_array_equal(L1, L3)


def test_parse_plan_unknown_names_raise():
    with pytest.raises(KeyError, match="unknown mapper"):
        parse_plan("nope")
    with pytest.raises(KeyError, match=r"base of 'refined:nope'"):
        parse_plan("refined:nope")
    with pytest.raises(ValueError, match="first stage"):
        MappingPlan([RefineStage(SwapRefiner())])


def test_solution_layout_matches_device_layout_rowmajor():
    from repro.core import device_layout
    dims, sizes = (6, 8), (16, 16, 10, 6)
    problem = _problem(dims, sizes)
    sol = parse_plan("refined:hyperplane").solve(problem)
    L = device_layout("refined:hyperplane", dims, problem.stencil,
                      list(sizes), intra_order="rowmajor", cache=False)
    np.testing.assert_array_equal(sol.layout(), L)


# ---------------------------------------------------------------------------
# bracket-option grammar: negative numbers, scientific notation, errors


def test_parse_mapper_options_negative_and_scientific():
    out = parse_mapper_options("t0=1e-2,seed=-3,x=+4,y=-2.5E3,z=1e3,w=.5")
    assert out == {"t0": 0.01, "seed": -3, "x": 4, "y": -2500.0,
                   "z": 1000.0, "w": 0.5}
    assert isinstance(out["seed"], int) and isinstance(out["z"], float)
    # through the full spelling (the ISSUE's example)
    prefix, opts, base = split_mapper_name("annealed[t0=1e-2]:hyperplane")
    assert (prefix, opts, base) == ("annealed", {"t0": 0.01}, "hyperplane")
    sched = parse_plan("annealed[sa_moves=50,tol=1e-9]:blocked").stages[1]
    assert sched.refiner.tol == 1e-9


def test_parse_mapper_options_errors_name_full_spelling():
    with pytest.raises(ValueError, match=r"'annealed\[k\]:hyperplane'"):
        split_mapper_name("annealed[k]:hyperplane")
    with pytest.raises(ValueError, match=r"'portfolio\[k=1,k=2\]:kdtree'"):
        parse_plan("portfolio[k=1,k=2]:kdtree")
    # chained: the error quotes the ORIGINAL spelling, not the inner rest
    with pytest.raises(ValueError,
                       match=r"'portfolio:annealed\[=3\]:kdtree'"):
        parse_plan("portfolio:annealed[=3]:kdtree")


# ---------------------------------------------------------------------------
# the serving cache


def test_plan_cache_hit_miss_and_weights_invalidate():
    dims, sizes = (8, 8), (16,) * 4
    cache = PlanCache()
    plan = parse_plan("refined:hyperplane")
    p1 = _problem(dims, sizes)
    s1 = cache.solve(p1, plan)
    assert (cache.hits, cache.misses) == (0, 1) and not s1.from_cache
    s2 = cache.solve(_problem(dims, sizes), plan)     # equal content, new obj
    assert (cache.hits, cache.misses) == (1, 1) and s2.from_cache
    np.testing.assert_array_equal(s1.assignment, s2.assignment)
    assert s2.key() == s1.key() and s2.stage_stats

    # changing stencil WEIGHTS (same offsets) must miss
    heavy = Stencil(p1.stencil.offsets, (8.0,) + (1.0,) * (p1.stencil.k - 1))
    assert _problem(dims, sizes, heavy).content_hash() != p1.content_hash()
    cache.solve(_problem(dims, sizes, heavy), plan)
    assert cache.misses == 2
    # different plan, different node sizes, different objective: all miss
    cache.solve(p1, parse_plan("refined2:hyperplane"))
    cache.solve(_problem(dims, (20, 16, 14, 14)), plan)
    cache.solve(MappingProblem(dims, p1.stencil, sizes, objective="j_max"),
                plan)
    assert cache.misses == 5 and cache.hits == 1


def test_plan_cache_hits_are_isolated_from_caller_mutation():
    """Warm hits hand back fresh copies: mutating a returned solution must
    not corrupt the live cache entry (serving-grade contract)."""
    cache = PlanCache()
    plan = parse_plan("refined:hyperplane")
    problem = _problem((8, 8), (16,) * 4)
    cache.solve(problem, plan)
    warm = cache.solve(problem, plan)
    warm.stage_stats[1]["swaps"] = "CORRUPTED"
    warm.assignment[:] = -1
    clean = cache.solve(problem, plan)
    assert clean.stage_stats[1]["swaps"] != "CORRUPTED"
    assert clean.assignment.min() >= 0
    # layout hits too
    L1 = cache.layout(problem, plan.key, "rowmajor",
                      lambda: np.arange(64).reshape(8, 8))
    L1[:] = -1
    L2 = cache.layout(problem, plan.key, "rowmajor", lambda: 1 / 0)
    assert L2.min() >= 0


def test_split_mapper_list_and_dryrun_order_suffix():
    """CLI list splitting respects bracket commas, and the dry-run's +rm
    order suffix never bites a signed bracket-option value."""
    import os
    from repro.core.mapping import split_mapper_list
    # the dryrun import sets 512 fake devices and the CPU platform; don't
    # leak either
    saved = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
    try:
        from repro.launch.dryrun import _split_order
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert split_mapper_list(
        "blocked,portfolio[k=8,seed=3]:kdtree,hyperplane+rm") \
        == ["blocked", "portfolio[k=8,seed=3]:kdtree", "hyperplane+rm"]
    assert _split_order("hyperplane+rm") == ("hyperplane", "rm")
    assert _split_order("annealed[tol=+1e-9]:hyperplane") \
        == ("annealed[tol=+1e-9]:hyperplane", "")
    base, order = _split_order("annealed[tol=+1e-9]:hyperplane+rm")
    assert order == "rm"
    assert parse_plan(base).stages[1].refiner.tol == 1e-9


def test_plan_cache_lru_eviction_and_clear():
    cache = PlanCache(maxsize=2)
    for i in range(3):
        cache.put(f"k{i}", {"v": i})
    assert cache.evictions == 1 and cache.get("k0") is None
    assert cache.get("k2")["v"] == 2
    cache.clear()
    assert cache.stats() == {"size": 0, "hits": 0, "misses": 0,
                             "disk_hits": 0, "puts": 0, "evictions": 0,
                             "corrupt_drops": 0, "expired": 0,
                             "invalidations": 0, "disk_evictions": 0}


def test_plan_cache_disk_spill_roundtrip(tmp_path):
    dims, sizes = (6, 8), (16, 16, 10, 6)
    plan = parse_plan("refined:hyperplane")
    c1 = PlanCache(disk_dir=tmp_path)
    sol = c1.solve(_problem(dims, sizes), plan)
    assert list(tmp_path.glob("*.json"))
    # a fresh cache (fresh process, conceptually) reads the spill back
    c2 = PlanCache(disk_dir=tmp_path)
    warm = c2.solve(_problem(dims, sizes), plan)
    assert warm.from_cache and c2.disk_hits == 1 and c2.misses == 0
    np.testing.assert_array_equal(warm.assignment, sol.assignment)
    assert warm.key() == sol.key()


def test_plan_cache_env_dir_read_at_construction(tmp_path, monkeypatch):
    """Regression: ``$REPRO_MAPS_CACHE_DIR`` set *after* import must still
    direct ``PlanCache(disk_dir=True)`` spills — the pre-fix code froze
    the path into ``DEFAULT_CACHE_DIR`` at import time, so late env
    changes (pytest monkeypatching, embedders configuring before first
    use) were silently ignored."""
    from repro.core.plan import default_cache_dir
    target = tmp_path / "late-env"
    monkeypatch.setenv("REPRO_MAPS_CACHE_DIR", str(target))
    assert default_cache_dir() == target
    cache = PlanCache(disk_dir=True)
    assert cache.disk_dir == target
    cache.put("k", {"v": 1})
    assert list(target.glob("*.json"))
    # a second late change moves the NEXT construction, not existing ones
    other = tmp_path / "other"
    monkeypatch.setenv("REPRO_MAPS_CACHE_DIR", str(other))
    assert cache.disk_dir == target
    assert PlanCache(disk_dir=True).disk_dir == other
    # unset: falls back to the documented default
    monkeypatch.delenv("REPRO_MAPS_CACHE_DIR")
    assert default_cache_dir().name == "repro-maps"


def test_plan_cache_corrupt_spill_is_miss_and_dropped(tmp_path):
    """A truncated/corrupt spill file is a *miss*, never an exception, and
    the bad file is deleted so it cannot poison every future read."""
    plan = parse_plan("refined:hyperplane")
    problem = _problem((8, 8), (16,) * 4)
    c1 = PlanCache(disk_dir=tmp_path)
    c1.solve(problem, plan)
    path = next(tmp_path.glob("*.json"))
    key = f"sol:{problem.content_hash()}:{plan.key}"
    for garbage in ('{"key": tru',                  # truncated JSON
                    "[1, 2, 3]",                    # valid JSON, not a dict
                    '"just a string"',
                    json.dumps({"key": key}),       # right key, no value
                    json.dumps({"key": key, "value": 7})):  # non-dict value
        path.write_text(garbage)
        fresh = PlanCache(disk_dir=tmp_path)
        assert fresh.get(key) is None, garbage
        assert (fresh.misses, fresh.disk_hits) == (1, 0), garbage
        assert fresh.corrupt_drops == 1, garbage
        assert not path.exists(), garbage           # dropped, not left to rot
        assert "corrupt_drops" in fresh.stats()
    # a valid spill for a *different* key (hash-prefix collision) is a
    # plain miss: the file is someone else's entry and must survive
    path.write_text(json.dumps({"key": "other", "value": {"x": 1}}))
    fresh = PlanCache(disk_dir=tmp_path)
    assert fresh.get(key) is None and fresh.corrupt_drops == 0
    assert path.exists()
    # and after the drop, a re-solve repopulates the spill cleanly
    path.unlink()
    c2 = PlanCache(disk_dir=tmp_path)
    sol = c2.solve(problem, plan)
    assert not sol.from_cache
    assert PlanCache(disk_dir=tmp_path).solve(problem, plan).from_cache


def test_plan_cache_stale_tmp_cleanup(tmp_path):
    """A crashed writer's abandoned .tmp (per-writer unique name — nobody
    will ever finish it) is swept on the next put; fresh in-flight ones
    are left alone."""
    import os as _os
    stale = tmp_path / "deadbeef.12345.aaaaaaaa.tmp"
    stale.write_text('{"key": "never finis')
    _os.utime(stale, (time.time() - 3600, time.time() - 3600))
    fresh = tmp_path / "cafebabe.12346.bbbbbbbb.tmp"
    fresh.write_text("in flight")
    cache = PlanCache(disk_dir=tmp_path)
    cache.put("k", {"v": 1})
    assert not stale.exists()
    assert fresh.exists()
    assert cache.get("k") == {"v": 1}


def _hammer_put(args):
    """Worker for the concurrent-put stress: every process spills the same
    key (plus one private key) many times into one shared dir."""
    disk_dir, wid, n = args
    cache = PlanCache(disk_dir=disk_dir)
    for i in range(n):
        cache.put("shared", {"writer": wid, "i": i})
        cache.put(f"private-{wid}", {"writer": wid, "i": i})
    return cache.get("shared") is not None


def test_plan_cache_concurrent_put_stress(tmp_path):
    """Many processes spilling the same key concurrently: unique tmp names
    + flock'd atomic publish mean the spill file is always one writer's
    complete JSON — never interleaved, never truncated — and no .tmp
    litter survives."""
    import multiprocessing as mp
    if "fork" not in mp.get_all_start_methods():
        pytest.skip("needs fork start method")
    ctx = mp.get_context("fork")
    with ctx.Pool(4) as pool:
        ok = pool.map(_hammer_put, [(str(tmp_path), w, 25) for w in range(4)])
    assert all(ok)
    assert not list(tmp_path.glob("*.tmp"))
    reader = PlanCache(disk_dir=tmp_path)
    got = reader.get("shared")
    assert got is not None and got["i"] == 24      # some writer's last put
    assert reader.corrupt_drops == 0
    for w in range(4):
        assert reader.get(f"private-{w}") == {"writer": w, "i": 24}


def test_warm_cache_mesh_build_10x_faster_than_cold_portfolio():
    """Acceptance: a warm PlanCache makes a repeated mesh build >= 10x
    faster than the cold solve on a portfolio row, proven by hit counters
    (mapped_device_array is make_mapped_mesh minus the jax Mesh wrapper)."""
    dims, sizes = (8, 8), [22, 16, 16, 10]          # ragged portfolio row
    stencil = Stencil.nearest_neighbor(2)
    devices = list(range(math.prod(dims)))
    cache = PlanCache()
    name = "portfolio[k=4]:hyperplane"
    t0 = time.perf_counter()
    cold = mapped_device_array(devices, name, dims, stencil, 16,
                               node_sizes=sizes, cache=cache)
    t_cold = time.perf_counter() - t0
    assert (cache.hits, cache.misses) == (0, 1)
    t0 = time.perf_counter()
    warm = mapped_device_array(devices, name, dims, stencil, 16,
                               node_sizes=sizes, cache=cache)
    t_warm = time.perf_counter() - t0
    assert (cache.hits, cache.misses) == (1, 1)
    np.testing.assert_array_equal(np.vectorize(int)(cold),
                                  np.vectorize(int)(warm))
    assert t_warm < t_cold / 10.0, (t_cold, t_warm)


def test_elastic_auto_upgrade_is_cacheable():
    """The ragged-pod ensure_refined upgrade carries a stable plan_key, so
    even a *plain* mapper name reuses its elastic portfolio solve."""
    dims, sizes = (6, 4), [8, 8, 5, 3]
    stencil = Stencil.nearest_neighbor(2)
    devices = list(range(24))
    cache = PlanCache()
    for _ in range(2):
        arr = mapped_device_array(devices, "hyperplane", dims, stencil, 8,
                                  node_sizes=sizes, cache=cache)
    assert (cache.hits, cache.misses) == (1, 1)
    # ad-hoc instances (no plan_key) never pollute the cache
    from repro.core.mapping import HyperplaneMapper
    mapped_device_array(devices, HyperplaneMapper(), dims, stencil, 8,
                        node_sizes=sizes, auto_refine=False, cache=cache)
    assert (cache.hits, cache.misses) == (1, 1)


# ---------------------------------------------------------------------------
# per-stage budgets


def test_refine_stage_budget_caps_swaps():
    dims, sizes = (8, 8), (16,) * 4
    grid, stencil = CartGrid(dims), Stencil.nearest_neighbor(2)
    base = get_mapper("random").assignment(grid, stencil, list(sizes))
    free = SwapRefiner().as_stage().run(grid, stencil, sizes, base)
    assert free.stats["swaps"] > 2
    for budget in (0, 1, 2):
        capped = SwapRefiner().as_stage(budget=budget).run(
            grid, stencil, sizes, base)
        assert capped.stats["swaps"] <= budget
    sched = ScheduledRefiner(anneal=True, sa_moves=30).as_stage(budget=3).run(
        grid, stencil, sizes, base)
    assert sched.stats["swaps"] <= 3
    # a budgeted stage still never loses the lexicographic guarantee
    k_in = evaluate(grid, stencil, base, num_nodes=4)
    k_out = evaluate(grid, stencil, sched.assignment, num_nodes=4)
    assert (k_out.j_max, k_out.j_sum) <= (k_in.j_max, k_in.j_sum)


# ---------------------------------------------------------------------------
# chained-prefix lexicographic improvement (property)


@given(st.integers(0, 10_000), st.sampled_from(["hyperplane", "random",
                                                "kdtree"]))
@settings(max_examples=12, deadline=None)
def test_chained_prefix_lexicographic_improvement(seed, base):
    """Appending a lexicographic refine stage to any plan never worsens
    (J_max, J_sum): `refined2:refined:<base>` <= `refined:<base>`."""
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(2, 5))
    per = int(rng.integers(3, 7))
    dims = (n_nodes * per,) if rng.integers(2) else (n_nodes, per)
    sizes = (per,) * n_nodes if len(dims) == 1 \
        else (dims[1],) * n_nodes
    problem = _problem(dims, sizes)
    inner = parse_plan(f"refined:{base}").solve(problem)
    chained = parse_plan(f"refined2:refined:{base}").solve(problem)
    assert chained.key() <= inner.key(), (dims, sizes, base)


# ---------------------------------------------------------------------------
# cart_create facade


def test_cart_create_cold_then_warm():
    cache = PlanCache()
    r1 = cart_create((8, 8), node_sizes=[16] * 4, cache=cache)
    assert not r1.from_cache and (cache.hits, cache.misses) == (0, 1)
    r2 = cart_create((8, 8), node_sizes=[16] * 4, cache=cache)
    assert r2.from_cache and (cache.hits, cache.misses) == (1, 1)
    np.testing.assert_array_equal(r1.layout, r2.layout)
    assert r1.layout.shape == (8, 8)
    assert sorted(r1.layout.reshape(-1).tolist()) == list(range(64))
    assert r1.plan_key == "annealed:hyperplane"       # the documented default
    # the default-cache path works too (no explicit cache object)
    r3 = cart_create((8, 8), node_sizes=[16] * 4)
    np.testing.assert_array_equal(r3.layout, r1.layout)
    assert default_plan_cache().puts >= 1


def test_cart_create_chips_per_pod_and_ragged_tail():
    r = cart_create((6, 4), chips_per_pod=9, plan="refined:hyperplane",
                    cache=False)
    assert r.problem.node_sizes == (9, 9, 6) and r.problem.is_ragged
    counts = np.bincount(r.solution.assignment, minlength=3)
    np.testing.assert_array_equal(counts, [9, 9, 6])
    with pytest.raises(ValueError, match="node_sizes or chips_per_pod"):
        cart_create((4, 4))


def test_cart_create_reorder_false_is_blocked():
    r = cart_create((4, 4), chips_per_pod=4, reorder=False, cache=False)
    np.testing.assert_array_equal(r.layout.reshape(-1), np.arange(16))
    assert r.plan_key == "blocked"


def test_cart_create_beats_blocked_on_stencil():
    blocked = cart_create((8, 8), chips_per_pod=16, reorder=False,
                          cache=False)
    mapped = cart_create((8, 8), chips_per_pod=16, cache=False)
    assert (mapped.j_max, mapped.j_sum) <= (blocked.j_max, blocked.j_sum)


# ---------------------------------------------------------------------------
# cross-engine parity matrix: serial / mp / device portfolio spellings

#: one spelling per execution engine, same portfolio configuration.  The
#: execution backend is part of the cache identity (PR-5 faithfulness
#: rule), so the keys must be pairwise DISTINCT while every family shows
#: identical cache *behavior*: canonical key, cacheable, miss-then-hit.
ENGINE_FAMILIES = {
    "serial": "portfolio[k=3,sa_moves=30]:hyperplane",
    "mp": "sharded[k=3,sa_moves=30,shards=2]:hyperplane",
    "device": "device[k=3,sa_moves=30]:hyperplane",
}


def test_cross_engine_parity_matrix_keys_and_cache_behavior():
    """Every engine spelling that accepts a backend/engine option behaves
    identically through the plan layer: the spelled name IS the canonical
    key (round-trips through parse_plan), the plan is cacheable, and a
    repeat solve is a cache hit — while the keys stay pairwise distinct so
    one engine's cached assignment is never served for another's."""
    problem = _problem((8, 8), (16,) * 4)
    keys = {}
    for family, name in ENGINE_FAMILIES.items():
        plan = parse_plan(name)
        assert plan.key == name, f"{family}: non-canonical key"
        assert parse_plan(plan.key).key == plan.key     # round-trip
        assert plan.cacheable, f"{family}: must be cacheable"
        assert get_mapper(name).plan_key == name
        cache = PlanCache()
        s1 = cache.solve(problem, plan)
        s2 = cache.solve(problem, plan)
        assert not s1.from_cache and s2.from_cache, \
            f"{family}: miss-then-hit broken"
        np.testing.assert_array_equal(s1.assignment, s2.assignment)
        keys[family] = plan.key
    assert len(set(keys.values())) == len(keys), \
        f"engine keys must be pairwise distinct: {keys}"
    # one shared cache never crosses engines: three solves, three misses
    cache = PlanCache()
    for name in ENGINE_FAMILIES.values():
        cache.solve(problem, parse_plan(name))
    assert cache.misses == len(ENGINE_FAMILIES) and cache.hits == 0


def test_ad_hoc_device_instances_bypass_the_cache():
    """A hand-built device refiner carrying an engine_factory has no
    stable spelling (the factory is an opaque object), so its stage and
    any plan containing it must be uncacheable — same contract as nested
    foreign objects in test_unkeyable_plans_bypass_the_cache."""
    from repro.core import DevicePortfolioRefiner
    from repro.core.refine.device import DeviceLadderEngine
    ad_hoc = DevicePortfolioRefiner(k=2, sa_moves=30,
                                    engine_factory=DeviceLadderEngine)
    stage = ad_hoc.as_stage()
    assert not stage.cacheable
    plan = MappingPlan([BaseStage("hyperplane"), stage])
    assert not plan.cacheable
    assert plan.to_mapper().plan_key is None
    cache = PlanCache()
    problem = _problem((8, 8), (16,) * 4)
    s1 = cache.solve(problem, plan)
    s2 = cache.solve(problem, plan)
    assert not s1.from_cache and not s2.from_cache
    assert cache.stats()["puts"] == 0
    # the factory really is used: identical configuration, same result
    np.testing.assert_array_equal(s1.assignment, s2.assignment)
    # the same configuration without the factory is cacheable
    assert DevicePortfolioRefiner(k=2, sa_moves=30).as_stage().cacheable


# ---------------------------------------------------------------------------
# serving-cache extensions: TTL, invalidation, disk budget, concurrency


def test_plan_cache_ttl_expiry_mem_and_disk(tmp_path):
    """A TTL'd entry serves until its deadline then reads as a miss — in
    memory AND through the disk spill (the expiry rides inside the blob,
    so a fresh cache over the same directory honors it too)."""
    cache = PlanCache(disk_dir=tmp_path, ttl_s=0.05)
    cache.put("sol:h1:planA", {"v": 1})
    assert cache.get("sol:h1:planA")["v"] == 1
    time.sleep(0.08)
    assert cache.get("sol:h1:planA") is None
    assert cache.expired >= 1
    # the expired spill file was dropped on read, not left to rot
    c2 = PlanCache(disk_dir=tmp_path)
    assert c2.get("sol:h1:planA") is None
    # per-put override: ttl_s=None pins the entry forever
    cache.put("sol:h1:planB", {"v": 2}, ttl_s=None)
    time.sleep(0.08)
    assert cache.get("sol:h1:planB")["v"] == 2


def test_plan_cache_invalidate_by_problem_hash(tmp_path):
    """invalidate(problem_hash) drops every entry of that problem —
    solutions and layouts, memory and disk — and leaves other problems'
    entries untouched."""
    cache = PlanCache(disk_dir=tmp_path)
    cache.put("sol:aaa:planA", {"v": 1})
    cache.put("lay:aaa:planA:rowmajor", {"v": 2})
    cache.put("sol:bbb:planA", {"v": 3})
    assert cache.invalidate("aaa") == 2
    assert cache.invalidations == 2
    assert cache.get("sol:aaa:planA") is None
    assert cache.get("lay:aaa:planA:rowmajor") is None
    assert cache.get("sol:bbb:planA")["v"] == 3
    # disk spills of the invalidated problem are gone for fresh readers
    c2 = PlanCache(disk_dir=tmp_path)
    assert c2.get("sol:aaa:planA") is None
    assert c2.get("sol:bbb:planA")["v"] == 3
    assert cache.invalidate("zzz") == 0


def test_plan_cache_disk_budget_evicts_lru_order(tmp_path):
    """Regression for the disk-budget sweep's eviction ORDER: the sweep
    must drop oldest-mtime spills first, and a disk *read* refreshes the
    entry's mtime — so a recently-read entry survives a newer-but-unread
    one."""
    pad = "x" * 200
    cache = PlanCache(maxsize=1, disk_dir=tmp_path, max_disk_bytes=600)
    cache.put("sol:h1:k0", {"v": 0, "pad": pad})
    time.sleep(0.05)
    cache.put("sol:h2:k1", {"v": 1, "pad": pad})      # k0 falls out of mem
    time.sleep(0.05)
    assert cache.get("sol:h1:k0")["v"] == 0           # disk hit -> mtime now
    assert cache.disk_hits == 1
    cache.put("sol:h3:k2", {"v": 2, "pad": pad})      # budget forces a sweep
    assert cache.disk_evictions >= 1
    # k1 (oldest mtime) was evicted; the freshly-read k0 survived
    c2 = PlanCache(disk_dir=tmp_path)
    assert c2.get("sol:h2:k1") is None
    assert c2.get("sol:h1:k0")["v"] == 0
    assert c2.get("sol:h3:k2")["v"] == 2
    st = cache.stats()
    assert st["disk_bytes"] <= 600 and st["disk_files"] == 2


def test_plan_cache_concurrent_ttl_and_invalidate(tmp_path):
    """Satellite: multi-threaded get/put with TTL expiry racing
    invalidation — no exceptions, and the counters stay consistent (every
    lookup is exactly one hit, one disk hit, or one miss)."""
    import threading
    cache = PlanCache(maxsize=16, disk_dir=tmp_path, ttl_s=0.02)
    stop = threading.Event()
    errors = []
    lookups = [0] * 4

    def worker(i):
        k = 0
        try:
            while not stop.is_set():
                key = f"sol:h{i}:k{k % 8}"
                cache.put(key, {"v": k}, ttl_s=0.01 if k % 3 else None)
                got = cache.get(key)
                assert got is None or isinstance(got["v"], int)
                lookups[i] += 1
                k += 1
        except BaseException as e:          # surfaced to the main thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    t_end = time.perf_counter() + 0.6
    while time.perf_counter() < t_end:
        for i in range(4):
            cache.invalidate(f"h{i}")
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors
    st = cache.stats()
    # every lookup is exactly one hit or one miss (disk hits count as
    # hits — the entry was served — plus the disk_hits sub-counter)
    assert st["hits"] + st["misses"] == sum(lookups)
    assert st["disk_hits"] <= st["hits"]
    assert st["size"] <= 16
    assert all(isinstance(v, int) and v >= 0 for v in st.values())
