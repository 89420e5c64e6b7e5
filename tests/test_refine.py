"""Refinement subsystem: incremental-cost parity, refiner invariants, and
the refined:<base> quality regression on the paper's stencils.

Parity is exact — IncrementalCost keeps integer crossing counts and
reconstructs floats in evaluate()'s accumulation order, so == (not isclose)
is the right assertion for unit weights.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, IncrementalCost, MapperInapplicable,
                        RefinedMapper, Stencil, SwapRefiner, dims_create,
                        device_layout, evaluate, get_mapper, layout_cost,
                        node_of_rank_blocked, refine_assignment)
from repro.core.mapping import MAPPERS, available_mappers, check_bijection

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
# IncrementalCost parity with full evaluate()
@given(st.integers(0, 10_000), st.sampled_from(sorted(STENCILS)))
@settings(max_examples=100, deadline=None)
def test_incremental_matches_evaluate_after_random_edits(seed, sname):
    """100+ randomized (grid, stencil, mapping) cases: state after arbitrary
    moves+swaps equals a fresh evaluate() bit-for-bit."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng)
    stencil = STENCILS[sname](grid.ndim)
    ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=n_nodes)

    c0 = evaluate(grid, stencil, node_of_pos, num_nodes=n_nodes)
    assert ic.j_sum == c0.j_sum
    assert ic.j_max == c0.j_max
    assert np.array_equal(ic.per_node, c0.per_node)

    for _ in range(15):
        if rng.integers(2):
            p, q = rng.integers(0, grid.size, size=2)
            ic.apply_swap(int(p), int(q))
        else:
            ic.apply_move(int(rng.integers(grid.size)),
                          int(rng.integers(n_nodes)))
    c1 = evaluate(grid, stencil, ic.node_of_pos, num_nodes=n_nodes)
    assert ic.j_sum == c1.j_sum
    assert ic.j_max == c1.j_max
    assert np.array_equal(ic.per_node, c1.per_node)


@given(st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_delta_predicts_applied_change(seed):
    """delta_swap/delta_move preview exactly the committed change."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=n_nodes)

    p, q = (int(x) for x in rng.integers(0, grid.size, size=2))
    before = ic.j_sum
    predicted = ic.delta_swap(p, q)
    peek = ic.peek_per_node(predicted)
    ic.apply_swap(p, q)
    assert ic.j_sum == before + predicted.d_j_sum
    assert np.array_equal(ic.per_node, peek)

    pos, node = int(rng.integers(grid.size)), int(rng.integers(n_nodes))
    before = ic.j_sum
    predicted = ic.delta_move(pos, node)
    ic.apply_move(pos, node)
    assert ic.j_sum == before + predicted.d_j_sum


def test_incremental_weighted_matches_evaluate():
    grid = CartGrid((6, 5))
    stencil = Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)),
                      weights=(4.0, 4.0, 1.0, 1.0))
    rng = np.random.default_rng(7)
    node_of_pos = rng.integers(0, 3, size=grid.size)
    ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=3,
                         weighted=True)
    for _ in range(25):
        ic.apply_swap(int(rng.integers(grid.size)),
                      int(rng.integers(grid.size)))
    c = evaluate(grid, stencil, ic.node_of_pos, num_nodes=3, weighted=True)
    assert ic.j_sum == c.j_sum
    np.testing.assert_allclose(ic.per_node, c.per_node, rtol=0, atol=1e-9)


def test_incremental_rejects_bad_shapes():
    grid = CartGrid((4, 4))
    stencil = Stencil.nearest_neighbor(2)
    with pytest.raises(ValueError):
        IncrementalCost(grid, stencil, np.zeros(7, dtype=np.int64))
    ic = IncrementalCost(grid, stencil, np.zeros(16, dtype=np.int64),
                         num_nodes=2)
    with pytest.raises(ValueError):
        ic.delta_move(0, 5)


# ---------------------------------------------------------------------------
# SwapRefiner invariants
@given(st.integers(0, 10_000), st.sampled_from(["j_sum", "j_max"]),
       st.sampled_from(["first", "steepest"]))
@settings(max_examples=25, deadline=None)
def test_refiner_monotonic_and_cardinality_preserving(seed, objective, policy):
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng, max_nodes=4)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    refiner = SwapRefiner(objective=objective, policy=policy, max_passes=3)
    res = refiner.refine(grid, stencil, node_of_pos, num_nodes=n_nodes)
    # objective never increases
    assert res.final.j_sum <= res.initial.j_sum or objective == "j_max"
    if objective == "j_max":
        assert (res.final.j_max, res.final.j_sum) \
            <= (res.initial.j_max, res.initial.j_sum)
    # swaps preserve per-node cardinalities exactly
    np.testing.assert_array_equal(
        np.bincount(res.assignment, minlength=n_nodes),
        np.bincount(node_of_pos, minlength=n_nodes))
    # reported final cost is truthful
    check = evaluate(grid, stencil, res.assignment, num_nodes=n_nodes)
    assert check.j_sum == res.final.j_sum
    assert check.j_max == res.final.j_max


def test_refiner_fixpoint_on_optimal_blocked_strips():
    """An already-optimal strip partition admits no improving swap."""
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    node_of_pos = get_mapper("stencil_strips").assignment(grid, stencil,
                                                          [16] * 4)
    res = refine_assignment(grid, stencil, node_of_pos, num_nodes=4)
    assert res.swaps == 0
    np.testing.assert_array_equal(res.assignment, node_of_pos)


def test_refiner_max_swaps_cap():
    rng = np.random.default_rng(3)
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    node_of_pos = rng.permutation(np.repeat(np.arange(4), 16))
    res = SwapRefiner(max_swaps=2).refine(grid, stencil, node_of_pos,
                                          num_nodes=4)
    assert res.swaps <= 2


def test_refiner_validates_config():
    with pytest.raises(ValueError):
        SwapRefiner(objective="nope")
    with pytest.raises(ValueError):
        SwapRefiner(policy="nope")
    with pytest.raises(ValueError):
        SwapRefiner(max_passes=0)


# ---------------------------------------------------------------------------
# RefinedMapper integration
def test_refined_prefix_resolves_for_every_mapper():
    for name in sorted(MAPPERS):
        m = get_mapper(f"refined:{name}")
        assert isinstance(m, RefinedMapper)
        assert m.name == f"refined:{name}"
    assert f"refined:{sorted(MAPPERS)[0]}" in available_mappers()
    with pytest.raises(KeyError):
        get_mapper("refined:doesnotexist")


@pytest.mark.parametrize("d,dims,sizes", [
    (2, (10, 8), [16] * 5),           # 2D 5-point
    (3, (6, 4, 4), [16] * 6),         # 3D 7-point
])
def test_refined_no_worse_than_base_on_paper_stencils(d, dims, sizes):
    """refined:<base> J_sum <= base for every registered mapper on the 2D
    5-point and 3D 7-point stencils (acceptance criterion)."""
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(d)
    for name in sorted(MAPPERS):
        try:
            base_cost = get_mapper(name).cost(grid, stencil, sizes)
        except MapperInapplicable:
            continue
        refined = get_mapper(f"refined:{name}")
        ref_cost = refined.cost(grid, stencil, sizes)
        assert ref_cost.j_sum <= base_cost.j_sum, (name, d)
        coords = refined.coords(grid, stencil, sizes)
        check_bijection(coords, grid.dims)


def test_refined_nodecart_regression():
    """refined:nodecart <= nodecart on the paper's stencil fixtures."""
    for d, dims, sizes in [(2, (8, 8), [16] * 4), (3, (8, 8, 8), [64] * 8)]:
        grid = CartGrid(dims)
        stencil = Stencil.nearest_neighbor(d)
        jb = get_mapper("nodecart").cost(grid, stencil, sizes).j_sum
        jr = get_mapper("refined:nodecart").cost(grid, stencil, sizes).j_sum
        assert jr <= jb


def test_refined_improves_random_substantially():
    grid = CartGrid((12, 12))
    stencil = Stencil.nearest_neighbor(2)
    sizes = [16] * 9
    jb = get_mapper("random").cost(grid, stencil, sizes).j_sum
    jr = get_mapper("refined:random").cost(grid, stencil, sizes).j_sum
    assert jr < jb  # local search must find at least one improving swap


def test_refined_respects_blocked_allocation():
    grid = CartGrid((6, 8))
    stencil = Stencil.nn_with_hops(2)
    sizes = [10, 14, 12, 12]  # heterogeneous
    m = get_mapper("refined:hyperplane")
    a = m.assignment(grid, stencil, sizes)
    np.testing.assert_array_equal(np.bincount(a, minlength=4), sizes)
    # the bijection places node i's ranks exactly on node i's positions
    coords = m.coords(grid, stencil, sizes)
    flat = np.ravel_multi_index(tuple(coords.T), grid.dims)
    owner = node_of_rank_blocked(sizes)
    np.testing.assert_array_equal(a[flat], owner)


def test_refined_through_device_layout_string_name():
    """remap accepts mapper names, including refined:<base>."""
    stencil = Stencil.nearest_neighbor(2)
    sizes = [16, 16, 16, 16]
    L_base = device_layout("random", (8, 8), stencil, sizes)
    L_ref = device_layout("refined:random", (8, 8), stencil, sizes)
    cb = layout_cost(L_base, stencil, sizes)
    cr = layout_cost(L_ref, stencil, sizes)
    assert sorted(L_ref.reshape(-1)) == list(range(64))
    assert cr.j_sum <= cb.j_sum
