"""Mapping algorithm invariants + paper-claim regressions.

Invariants (hypothesis, every algorithm):
  * rank->coordinate is a bijection onto the grid;
  * the scheduler allocation is respected (node i owns exactly n_i cells);
  * per-rank distributed forms agree with the batch form.

Paper claims (§VI.C / §VI.D, machine-independent):
  * Hyperplane and Stencil Strips beat Nodecart on J_sum for all three
    stencils on the headline instances;
  * k-d tree and Stencil Strips find the optimal component-stencil mapping
    (J_max == 2 per interior node);
  * every algorithm improves on blocked; random is worst;
  * Thm V.1/V.2: a suitable hyperplane split always exists with balance
    >= 1/2 when p = C*n.
"""
import math

import numpy as np
import pytest
from hypothesis import assume, given, settings, strategies as st

from repro.core import (CartGrid, MapperInapplicable, Stencil, dims_create,
                        evaluate, get_mapper)
from repro.core.mapping import MAPPERS, check_bijection
from repro.core.mapping.hyperplane import HyperplaneMapper, _find_split
from repro.core.mapping.kdtree import KDTreeMapper
from repro.core.mapping.stencil_strips import StencilStripsMapper

STENCILS = {
    "nn": Stencil.nearest_neighbor,
    "comp": Stencil.component,
    "hops": Stencil.nn_with_hops,
}


def make_instance(n_nodes, ppn, d):
    dims = dims_create(n_nodes * ppn, d)
    return CartGrid(dims), [ppn] * n_nodes


@given(st.sampled_from(sorted(MAPPERS)), st.integers(2, 6), st.integers(2, 9),
       st.integers(2, 3), st.sampled_from(sorted(STENCILS)))
@settings(max_examples=40, deadline=None)
def test_mapper_invariants(mname, n_nodes, ppn, d, sname):
    grid, sizes = make_instance(n_nodes, ppn, d)
    stencil = STENCILS[sname](d)
    mapper = get_mapper(mname, max_passes=2) if mname == "graphgreedy" \
        else get_mapper(mname)
    try:
        coords = mapper.coords(grid, stencil, sizes)
    except MapperInapplicable:
        assume(False)
    check_bijection(coords, grid.dims)
    assignment = mapper.assignment(grid, stencil, sizes)
    counts = np.bincount(assignment, minlength=n_nodes)
    np.testing.assert_array_equal(counts, sizes)


@given(st.integers(2, 5), st.integers(2, 8), st.integers(2, 3))
@settings(max_examples=25, deadline=None)
def test_heterogeneous_node_sizes(n_nodes, base, d):
    """The paper's contribution over Nodecart: heterogeneous n_i works."""
    sizes = [base + (i % 3) for i in range(n_nodes)]
    dims = dims_create(sum(sizes), d)
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(d)
    for mname in ("hyperplane", "kdtree", "stencil_strips"):
        a = get_mapper(mname).assignment(grid, stencil, sizes)
        np.testing.assert_array_equal(np.bincount(a, minlength=n_nodes), sizes)


@given(st.integers(2, 6), st.integers(2, 9), st.integers(2, 3))
@settings(max_examples=30, deadline=None)
def test_per_rank_forms_agree(n_nodes, ppn, d):
    grid, sizes = make_instance(n_nodes, ppn, d)
    stencil = Stencil.nearest_neighbor(d)
    hp = HyperplaneMapper()
    batch = hp.coords(grid, stencil, sizes)
    for r in [0, grid.size // 2, grid.size - 1]:
        assert tuple(batch[r]) == hp.coord_of_rank(grid.dims, stencil, ppn, r)
    kd = KDTreeMapper()
    batch = kd.coords(grid, stencil, sizes)
    for r in [0, grid.size // 3, grid.size - 1]:
        assert tuple(batch[r]) == kd.coord_of_rank(grid.dims, stencil, 0, r)


def test_strips_closed_form_matches_enumeration():
    # divisible case: 8x8 grid, n=16, nearest neighbor -> strips of 4
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    m = StencilStripsMapper()
    batch = m.coords(grid, stencil, [16] * 4)
    for r in range(grid.size):
        assert tuple(batch[r]) == m.coord_of_rank(grid.dims, stencil, 16, r)


@given(st.integers(2, 12), st.integers(2, 16), st.integers(2, 3))
@settings(max_examples=40, deadline=None)
def test_hyperplane_split_exists_and_balanced(C, n, d):
    """Thm V.1 (existence) + Thm V.2 (|g'|/|g''| >= 1/2)."""
    dims = list(dims_create(C * n, d))
    cos2 = Stencil.nearest_neighbor(d).cos2_sums()
    split = _find_split(dims, cos2, n)
    assert split is not None, f"no split for dims={dims}, n={n}"
    i, d_left = split
    left = d_left * math.prod(dims) // dims[i]
    right = math.prod(dims) - left
    assert left % n == 0 and right % n == 0
    assert min(left, right) / max(left, right) >= 0.5 - 1e-9


# ---------------------------------------------------------------------------
# paper §VI quality claims on the headline instances
@pytest.mark.parametrize("N,n,dims", [(50, 48, (50, 48)), (100, 48, (75, 64))])
def test_paper_quality_ordering(N, n, dims):
    grid = CartGrid(dims)
    sizes = [n] * N
    for sname, stencil in [("nn", Stencil.nearest_neighbor(2)),
                           ("hops", Stencil.nn_with_hops(2)),
                           ("comp", Stencil.component(2))]:
        j = {}
        for mname in ("blocked", "nodecart", "hyperplane", "kdtree",
                      "stencil_strips", "random"):
            j[mname] = get_mapper(mname).cost(grid, stencil, sizes).j_sum
        # the paper's headline ordering
        assert j["hyperplane"] < j["nodecart"] < j["blocked"], (sname, j)
        assert j["stencil_strips"] < j["nodecart"], (sname, j)
        assert j["kdtree"] < j["blocked"], (sname, j)
        assert j["random"] > j["blocked"] * 0.9, (sname, j)


def test_component_optimal_kdtree_and_strips():
    """§VI.D: 'only k-d tree and Stencil Strips managed to find an optimal
    mapping, where each compute node has two outgoing communication edges'."""
    grid = CartGrid((50, 48))
    stencil = Stencil.component(2)
    for mname in ("kdtree", "stencil_strips"):
        c = get_mapper(mname).cost(grid, stencil, [48] * 50)
        assert c.j_max == 2, mname


def test_nodecart_inapplicable_cases():
    """Nodecart needs homogeneous n with n | p — exactly the cases the
    paper's algorithms are 'also applicable to' (contribution 2)."""
    stencil = Stencil.nearest_neighbor(2)
    # n does not divide p
    with pytest.raises(MapperInapplicable):
        get_mapper("nodecart").coords(CartGrid((5, 7)), stencil, [4] * 9)
    # heterogeneous node sizes
    with pytest.raises(MapperInapplicable):
        get_mapper("nodecart").coords(CartGrid((4, 3)), stencil, [5, 4, 3])


def test_nodecart_applicable_beats_blocked():
    grid = CartGrid((8, 8))
    stencil = Stencil.nearest_neighbor(2)
    jb = get_mapper("blocked").cost(grid, stencil, [16] * 4).j_sum
    jn = get_mapper("nodecart").cost(grid, stencil, [16] * 4).j_sum
    assert jn < jb
