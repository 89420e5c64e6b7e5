"""Multi-start annealing portfolio engine: bit-exact parity of the batched
K-state deltas with K scalar IncrementalCost tracks, the portfolio-vs-
annealed dominance guarantee (ladder 0 reproduces the scalar annealed
trajectory), early-kill behaviour, the `portfolio[k=8]:` option-parsing
grammar, weighted="auto" resolution through the refine stack, and the jax
kernel that builds the integer count state against the numpy loop.

Parity assertions use == / array_equal, not isclose: the portfolio path
keeps the same integer crossing counts and the same ascending-offset float
accumulation as the scalar path, so any drift is a bug.
"""
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, CommGraph, IncrementalCost, MaskedGrid,
                        NeighborTable, PortfolioCost, PortfolioRefiner,
                        RefinedMapper, ScheduledRefiner, Stencil, SwapRefiner,
                        available_mappers, evaluate, get_mapper,
                        parse_mapper_options, split_mapper_name,
                        stacked_crossing_counts)
from repro.core.cost_delta import neighbor_table, stacked_count_arrays

STENCILS = {
    "nn": Stencil.nearest_neighbor,
    "comp": Stencil.component,
    "hops": Stencil.nn_with_hops,
}


def random_instance(rng, d=None, max_nodes=6):
    d = d or int(rng.integers(1, 4))
    dims = tuple(int(rng.integers(2, 6)) for _ in range(d))
    periodic = tuple(bool(rng.integers(2)) for _ in range(d))
    grid = CartGrid(dims, periodic=periodic)
    n_nodes = int(rng.integers(2, max_nodes + 1))
    node_of_pos = rng.integers(0, n_nodes, size=grid.size)
    return grid, n_nodes, node_of_pos


# ---------------------------------------------------------------------------
# PortfolioCost: batched K-state deltas bit-exact vs K scalar tracks
@given(st.integers(0, 10_000), st.sampled_from(sorted(STENCILS)),
       st.booleans())
@settings(max_examples=40, deadline=None)
def test_portfolio_deltas_bit_exact_vs_scalar(seed, sname, weighted):
    """Each row of swap_deltas equals the scalar delta_swap/peek_per_node
    of an IncrementalCost tracking the same assignment, bit for bit; after
    commits the full state (counts, j_sum, per_node, boundary) stays in
    lock-step with the K scalar tracks."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, _ = random_instance(rng)
    stencil = STENCILS[sname](grid.ndim)
    K = int(rng.integers(1, 5))
    assigns = rng.integers(0, n_nodes, size=(K, grid.size))
    pc = PortfolioCost(grid, stencil, assigns, num_nodes=n_nodes,
                       weighted=weighted)
    ics = [IncrementalCost(grid, stencil, a, num_nodes=n_nodes,
                           weighted=weighted) for a in assigns]
    for _ in range(3):
        rows = np.unique(rng.integers(0, K, size=K))
        P = rng.integers(0, grid.size, size=rows.size)
        Q = rng.integers(0, grid.size, size=rows.size)
        d = pc.swap_deltas(rows, P, Q, with_loads=True, with_counts=True)
        assert d.size == rows.size
        for i, r in enumerate(rows):
            sd = ics[r].delta_swap(int(P[i]), int(Q[i]))
            assert np.array_equal(d.d_count_off[i], sd.d_count_off)
            assert d.d_j_sum[i] == sd.d_j_sum
            peek = ics[r].peek_per_node(sd)
            assert np.array_equal(d.new_per_node[i], peek)
            assert d.new_j_max[i] == peek.max(initial=0.0)
        keep = np.nonzero(rng.random(rows.size) < 0.5)[0]
        pc.commit(d, keep)
        for i in keep:
            ics[rows[i]].apply_swap(int(P[i]), int(Q[i]))
        masks = pc.boundary_masks()
        for r in range(K):
            assert np.array_equal(pc.node[r], ics[r].node_of_pos)
            assert pc.j_sum()[r] == ics[r].j_sum
            assert pc.j_max()[r] == ics[r].j_max
            assert np.array_equal(pc.per_node()[r], ics[r].per_node)
            assert np.array_equal(np.nonzero(masks[r])[0],
                                  ics[r].boundary_positions())
            check = ics[r].cost()
            assert pc.cost(r).j_sum == check.j_sum
            assert pc.cost(r).j_max == check.j_max


def test_portfolio_cost_validates_input():
    grid = CartGrid((4, 4))
    st2 = Stencil.nearest_neighbor(2)
    with pytest.raises(ValueError):
        PortfolioCost(grid, st2, np.zeros(16, dtype=np.int64), num_nodes=2)
    pc = PortfolioCost(grid, st2, np.zeros((3, 16), dtype=np.int64),
                       num_nodes=2)
    with pytest.raises(ValueError):
        pc.swap_deltas([0, 1], [2, 3], [4])          # length mismatch
    with pytest.raises(ValueError):
        pc.swap_deltas([5], [0], [1])                # row out of range
    with pytest.raises(ValueError):
        pc.swap_deltas([0], [0], [99])               # position out of range
    with pytest.raises(ValueError):
        pc.apply_swaps([1, 1], [0, 2], [3, 4])       # duplicate row
    d = pc.swap_deltas([0], [0], [1], with_loads=True, with_counts=False)
    with pytest.raises(ValueError):
        pc.commit(d)                                 # needs with_counts
    empty = pc.swap_deltas(np.empty(0, np.int64), np.empty(0, np.int64),
                           np.empty(0, np.int64), with_loads=True,
                           with_counts=True)
    assert empty.size == 0
    pc.commit(empty)                                 # no-op commit is fine


# ---------------------------------------------------------------------------
# the integer count state: the jax kernel against the numpy loop


def _perms(rng, K, sizes):
    """K random layouts holding ``sizes[n]`` positions on node n."""
    base = np.repeat(np.arange(len(sizes)), sizes)
    return np.stack([rng.permutation(base) for _ in range(K)])


def _graph_case(rng):
    src = rng.integers(0, 24, size=60)
    dst = rng.integers(0, 24, size=60)
    g = CommGraph.from_edges(24, src, dst, weights=rng.integers(1, 4, 60))
    return g.grid(), g.slot_stencil(), rng.integers(0, 3, size=(3, 24)), 3


#: (grid, stencil, (K, p) assignments, num_nodes) per case
COUNT_CASES = {
    "mixed-periodic-hops": lambda rng: (
        CartGrid((5, 6), periodic=(True, False)), Stencil.nn_with_hops(2),
        rng.integers(0, 4, size=(3, 30)), 4),
    # the paper's largest Fig. 8 instances: 992 processes on 31 nodes
    "fig8-2d": lambda rng: (CartGrid((32, 31)), Stencil.nearest_neighbor(2),
                            _perms(rng, 4, [32] * 31), 31),
    "fig8-3d": lambda rng: (CartGrid((31, 8, 4)), Stencil.nearest_neighbor(3),
                            _perms(rng, 4, [32] * 31), 31),
    "component": lambda rng: (CartGrid((6, 7)), Stencil.component(2),
                              rng.integers(0, 5, size=(3, 42)), 5),
    "masked": lambda rng: (
        MaskedGrid(CartGrid((6, 6), periodic=(True, True)),
                   np.arange(36) % 3 != 0),
        Stencil.nearest_neighbor(2), rng.integers(0, 4, size=(3, 36)), 4),
    "comm-graph": _graph_case,
    # one row: the device engine's start and a restart's spawn
    "one-row": lambda rng: (CartGrid((8, 8)), Stencil.nearest_neighbor(2),
                            _perms(rng, 1, [16] * 4), 4),
    # nodes that hold no position
    "spare-nodes": lambda rng: (CartGrid((6, 5)), Stencil.nearest_neighbor(2),
                                rng.integers(0, 3, size=(3, 30)), 7),
}


@pytest.mark.parametrize("case", sorted(COUNT_CASES))
def test_stacked_crossing_counts_matches_portfolio_cost(case):
    """The jax kernel's integer counts equal the numpy loop's and
    PortfolioCost's own init, bit for bit, and feeding them back as
    ``counts=`` reproduces the full state.  The table memo serves a
    masked or graph-backed grid its own table, never the one of a plain
    grid with the same dims."""
    rng = np.random.default_rng(11)
    grid, stencil, A, n_nodes = COUNT_CASES[case](rng)
    neighbor_table(CartGrid(tuple(grid.dims), periodic=tuple(grid.periodic)),
                   stencil)
    ref = NeighborTable.build(grid, stencil)
    table = neighbor_table(grid, stencil)
    for name in ("out_valid", "out_tgt", "in_valid", "in_src"):
        np.testing.assert_array_equal(getattr(table, name),
                                      getattr(ref, name))
    co, cn = stacked_crossing_counts(grid, stencil, A, n_nodes)
    assert co.dtype == cn.dtype == np.int64
    assert cn.shape == (A.shape[0], n_nodes, stencil.k)
    co_np, cn_np = stacked_count_arrays(ref, A, n_nodes)
    np.testing.assert_array_equal(co, co_np)
    np.testing.assert_array_equal(cn, cn_np)
    pc = PortfolioCost(grid, stencil, A, num_nodes=n_nodes)
    np.testing.assert_array_equal(co, pc._count_off)
    np.testing.assert_array_equal(cn, pc._count_node)
    pre = PortfolioCost(grid, stencil, A, num_nodes=n_nodes, counts=(co, cn))
    np.testing.assert_array_equal(pre.per_node(), pc.per_node())
    assert pre.j_sum().tolist() == pc.j_sum().tolist()
    assert pre.j_max().tolist() == pc.j_max().tolist()
    with pytest.raises(ValueError, match="wrong shapes"):
        PortfolioCost(grid, stencil, A, num_nodes=n_nodes,
                      counts=(co, cn[:, :-1]))


# ---------------------------------------------------------------------------
# PortfolioRefiner: dominance, determinism, invariants
@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_portfolio_never_worse_than_annealed_same_seed(seed):
    """portfolio: ladder 0 replays the annealed ladder of the same seed
    (same rng draw order, bit-equal energies on unit weights), so the
    portfolio's lexicographic best can never lose to annealed."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng, max_nodes=4)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    kwargs = dict(rounds=2, max_passes=3, sa_moves=40)
    ann = ScheduledRefiner(anneal=True, seed=seed, **kwargs).refine(
        grid, stencil, node_of_pos, num_nodes=n_nodes)
    port = PortfolioRefiner(k=3, seed=seed, **kwargs).refine(
        grid, stencil, node_of_pos, num_nodes=n_nodes)
    assert (port.final.j_max, port.final.j_sum) \
        <= (ann.final.j_max, ann.final.j_sum)
    # portfolio is itself a refiner: never worse than its input, preserves
    # the scheduler allocation, and reports exact costs
    assert (port.final.j_max, port.final.j_sum) \
        <= (port.initial.j_max, port.initial.j_sum)
    np.testing.assert_array_equal(
        np.bincount(port.assignment, minlength=n_nodes),
        np.bincount(node_of_pos, minlength=n_nodes))
    check = evaluate(grid, stencil, port.assignment, num_nodes=n_nodes)
    assert check.j_sum == port.final.j_sum
    assert check.j_max == port.final.j_max


def test_portfolio_k1_is_exactly_annealed():
    """With one start the portfolio IS the annealed schedule: same
    assignment, same final cost, bit for bit."""
    rng = np.random.default_rng(7)
    grid = CartGrid((8, 8))
    stencil = Stencil.nn_with_hops(2)
    a = rng.permutation(np.repeat(np.arange(4), 16))
    ann = ScheduledRefiner(anneal=True, seed=3).refine(grid, stencil, a,
                                                       num_nodes=4)
    port = PortfolioRefiner(k=1, seed=3).refine(grid, stencil, a,
                                                num_nodes=4)
    np.testing.assert_array_equal(ann.assignment, port.assignment)
    assert (ann.final.j_sum, ann.final.j_max) \
        == (port.final.j_sum, port.final.j_max)


def test_portfolio_deterministic():
    rng = np.random.default_rng(5)
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    a = rng.permutation(np.repeat(np.arange(4), 16))
    r1 = PortfolioRefiner(k=4, seed=11).refine(grid, stencil, a, num_nodes=4)
    r2 = PortfolioRefiner(k=4, seed=11).refine(grid, stencil, a, num_nodes=4)
    np.testing.assert_array_equal(r1.assignment, r2.assignment)
    assert r1.stats["ladder_keys"] == r2.stats["ladder_keys"]


def test_portfolio_early_kill_never_kills_ladder_zero():
    """kill_factor=1.0 is maximally aggressive (any start whose best-seen
    J_max trails the leader dies at the next temperature boundary) — the
    dominance guarantee must survive because ladder 0 is exempt."""
    rng = np.random.default_rng(19)
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    a = rng.permutation(np.repeat(np.arange(8), 8))
    kwargs = dict(rounds=2, max_passes=3, sa_moves=60)
    ann = ScheduledRefiner(anneal=True, seed=2, **kwargs).refine(
        grid, stencil, a, num_nodes=8)
    port = PortfolioRefiner(k=6, seed=2, kill_factor=1.0, **kwargs).refine(
        grid, stencil, a, num_nodes=8)
    assert (port.final.j_max, port.final.j_sum) \
        <= (ann.final.j_max, ann.final.j_sum)
    none = PortfolioRefiner(k=6, seed=2, kill_factor=None, **kwargs).refine(
        grid, stencil, a, num_nodes=8)
    assert none.stats["killed"] == 0
    assert (none.final.j_max, none.final.j_sum) \
        <= (port.final.j_max, port.final.j_sum)  # killing only loses cands
    assert port.stats["polished"] >= 1
    assert port.stats["k"] == 6 and len(port.stats["ladder_keys"]) == 6


def test_portfolio_validates_config():
    with pytest.raises(ValueError):
        PortfolioRefiner(k=0)
    with pytest.raises(ValueError):
        PortfolioRefiner(kill_factor=0.5)
    assert PortfolioRefiner(seeds=[9, 4]).k == 2
    assert PortfolioRefiner(kill_factor=None).kill_factor is None


def test_portfolio_duplicate_seeds_dedupe_warn_and_honest_config():
    """Duplicate explicit seeds replay identical trajectories — they are
    deduped order-preserved with a warning, and config() (the stage layer's
    cache identity) reflects the deduped tuple so two spellings of the same
    effective portfolio share one cache key."""
    with pytest.warns(UserWarning, match="duplicate portfolio seeds"):
        r = PortfolioRefiner(seeds=[3, 3, 5, 3])
    assert r.seeds == (3, 5) and r.k == 2
    assert r.config()["seeds"] == (3, 5)
    assert r.config() == PortfolioRefiner(seeds=[3, 5]).config()
    # the deduped portfolio IS the clean one, bit for bit
    rng = np.random.default_rng(0)
    grid = CartGrid((6, 6))
    stencil = Stencil.nearest_neighbor(2)
    a = rng.permutation(np.repeat(np.arange(3), 12))
    with pytest.warns(UserWarning):
        dup = PortfolioRefiner(seeds=[3, 3, 5], sa_moves=40)
    clean = PortfolioRefiner(seeds=[3, 5], sa_moves=40)
    np.testing.assert_array_equal(
        dup.refine(grid, stencil, a, num_nodes=3).assignment,
        clean.refine(grid, stencil, a, num_nodes=3).assignment)
    # an all-duplicate list still leaves one ladder (never zero starts)
    with pytest.warns(UserWarning):
        assert PortfolioRefiner(seeds=[7, 7]).k == 1


# ---------------------------------------------------------------------------
# registry: portfolio: prefix + bracket-option grammar
def test_portfolio_prefix_resolves_for_every_mapper():
    from repro.core.mapping import MAPPERS
    for name in sorted(MAPPERS):
        m = get_mapper(f"portfolio:{name}")
        assert isinstance(m, RefinedMapper)
        assert isinstance(m.refiner, PortfolioRefiner)
        assert m.name == f"portfolio:{name}"
    assert "portfolio:blocked" in available_mappers()
    with pytest.raises(KeyError):
        get_mapper("portfolio:doesnotexist")


def test_bracket_options_configure_the_refiner():
    m = get_mapper("portfolio[k=3,seed=5]:kdtree")
    assert m.refiner.seeds == (5, 6, 7)
    m = get_mapper("portfolio[k=2,kill_factor=1.25]:blocked")
    assert m.refiner.k == 2 and m.refiner.kill_factor == 1.25
    m = get_mapper("portfolio[kill_factor=none]:blocked")
    assert m.refiner.kill_factor is None
    # bracket options win over call kwargs (the name is the spec)
    m = get_mapper("portfolio[k=3]:blocked", k=6, sa_moves=10)
    assert m.refiner.k == 3 and m.refiner.schedule.sa_moves == 10
    # the grammar covers every refine prefix
    m = get_mapper("annealed[seed=9]:hyperplane")
    assert isinstance(m.refiner, ScheduledRefiner) and m.refiner.seed == 9
    m = get_mapper("refined[policy=steepest]:blocked")
    assert m.refiner.policy == "steepest"
    m = get_mapper("refined2[rounds=2]:blocked")
    assert m.refiner.rounds == 2


def test_mapper_name_parsing_contract():
    assert split_mapper_name("hyperplane") is None
    assert split_mapper_name("portfolio:kdtree") == ("portfolio", {}, "kdtree")
    prefix, opts, base = split_mapper_name("portfolio[k=8,seed=3]:kdtree")
    assert (prefix, base) == ("portfolio", "kdtree")
    assert opts == {"k": 8, "seed": 3}
    assert parse_mapper_options("a=1,b=2.5,c=true,d=x") == {
        "a": 1, "b": 2.5, "c": True, "d": "x"}
    with pytest.raises(ValueError):
        parse_mapper_options("k")            # no '='
    with pytest.raises(ValueError):
        parse_mapper_options("k=1,k=2")      # duplicate key
    with pytest.raises(ValueError):
        get_mapper("portfolio[k]:blocked")


def test_portfolio_mapper_not_worse_than_annealed_on_ragged():
    """The registry-level guarantee on the suite's tiny ragged instance."""
    grid = CartGrid((6, 8))
    stencil = Stencil.nearest_neighbor(2)
    sizes = [16, 16, 10, 6]
    for base in ("random", "kdtree"):
        ann = get_mapper(f"annealed:{base}").cost(grid, stencil, sizes)
        port = get_mapper(f"portfolio[k=3]:{base}").cost(grid, stencil, sizes)
        assert (port.j_max, port.j_sum) <= (ann.j_max, ann.j_sum), base


# ---------------------------------------------------------------------------
# weighted="auto": byte-weighted and unit-weight objectives, one code path
def test_weighted_auto_resolution():
    unit = Stencil.nearest_neighbor(2)
    heavy = Stencil(unit.offsets, (4.0, 4.0, 1.0, 1.0))   # dyadic => exact
    assert not unit.is_weighted and heavy.is_weighted
    grid = CartGrid((6, 6))
    a = np.repeat(np.arange(3), 12)
    assert not IncrementalCost(grid, unit, a, num_nodes=3,
                               weighted="auto").weighted
    assert IncrementalCost(grid, heavy, a, num_nodes=3,
                           weighted="auto").weighted
    assert not IncrementalCost(grid, heavy, a, num_nodes=3,
                               weighted=False).weighted
    w = evaluate(grid, heavy, a, num_nodes=3, weighted="auto")
    assert w.j_sum == evaluate(grid, heavy, a, num_nodes=3,
                               weighted=True).j_sum
    assert w.j_sum != evaluate(grid, heavy, a, num_nodes=3,
                               weighted=False).j_sum


def test_refiners_score_weighted_stencils_in_bytes():
    """With default weighted="auto" every refiner optimizes the byte
    objective on a weighted stencil; the weighted result is never worse in
    bytes than the input and matches a weighted re-evaluation exactly
    (dyadic weights)."""
    rng = np.random.default_rng(3)
    grid = CartGrid((8, 8))
    heavy = Stencil(Stencil.nearest_neighbor(2).offsets,
                    (8.0, 8.0, 1.0, 1.0))
    a = rng.permutation(np.repeat(np.arange(4), 16))
    base = evaluate(grid, heavy, a, num_nodes=4, weighted=True)
    for refiner in (SwapRefiner(max_passes=4),
                    ScheduledRefiner(rounds=2, max_passes=3),
                    PortfolioRefiner(k=2, rounds=2, max_passes=3,
                                     sa_moves=30)):
        res = refiner.refine(grid, heavy, a, num_nodes=4)
        check = evaluate(grid, heavy, res.assignment, num_nodes=4,
                         weighted=True)
        assert res.final.j_sum == check.j_sum
        assert res.final.j_sum < base.j_sum     # bytes actually optimized
        np.testing.assert_array_equal(
            np.bincount(res.assignment, minlength=4),
            np.bincount(a, minlength=4))


# ---------------------------------------------------------------------------
# acceptance: K=8 on the full suite's ragged instances (slow)
@pytest.mark.slow
def test_portfolio_k8_acceptance_on_suite_ragged_rows():
    """portfolio[k=8] is lexicographically <= annealed on every full-suite
    ragged (instance, stencil, mapper) row, at < 8x the annealed wall-time
    wherever the annealed run takes long enough to time (>= 0.2s)."""
    cases = [((16, 28), [256, 192]), ((12, 8, 8), [128] * 5 + [96, 32])]
    for dims, sizes in cases:
        grid = CartGrid(dims)
        for sfn in (Stencil.nearest_neighbor, Stencil.nn_with_hops):
            stencil = sfn(grid.ndim)
            for base in ("random", "kdtree", "hyperplane"):
                a = get_mapper(base).assignment(grid, stencil, sizes)
                t0 = time.perf_counter()
                ann = ScheduledRefiner(anneal=True).refine(
                    grid, stencil, a, num_nodes=len(sizes))
                t_ann = time.perf_counter() - t0
                t0 = time.perf_counter()
                port = PortfolioRefiner(k=8).refine(
                    grid, stencil, a, num_nodes=len(sizes))
                t_port = time.perf_counter() - t0
                assert (port.final.j_max, port.final.j_sum) \
                    <= (ann.final.j_max, ann.final.j_sum), (dims, base)
                if t_ann >= 0.2:
                    assert t_port < 8 * t_ann, (dims, base, t_port, t_ann)
