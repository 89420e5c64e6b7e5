"""The plain reference against the program's own counts, at small sizes
and at both cells' shapes."""
import json

import numpy as np
import pytest

from conftest import BENCH
from benchlib import reference
from benchlib.traffic import capacities

CONFIGS = sorted((BENCH / "configs").glob("*.json"))


def _program_costs(dims, offsets, caps, a):
    from repro.core import CartGrid, Stencil, evaluate
    st = Stencil(tuple(tuple(o) for o in offsets))
    c = evaluate(CartGrid(tuple(dims)), st, a, num_nodes=len(caps))
    return c.j_max, c.j_sum


def _random_layout(rng, caps):
    return rng.permutation(reference.blocked(caps))


SHAPES = [((5, 7), [[1, 0], [-1, 0], [0, 1], [0, -1]], (4, 11, 9, 11)),
          ((4, 3, 5), [[1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0],
                       [0, 0, 1], [0, 0, -1]], (20, 20, 20)),
          ((6, 6), [[1, 0], [-1, 0], [0, 2], [0, -2], [1, 1]], (9,) * 4)]


@pytest.mark.parametrize("dims,offsets,caps", SHAPES)
def test_keys_match_evaluate_small(dims, offsets, caps):
    rng = np.random.default_rng(0)
    table = reference.neighbours(dims, offsets, [False] * len(dims))
    A = np.stack([_random_layout(rng, caps) for _ in range(5)])
    jmax, jsum = reference.keys(reference.count_state(table, A, len(caps)))
    for r in range(len(A)):
        assert (jmax[r], jsum[r]) == _program_costs(dims, offsets, caps, A[r])


def test_periodic_wraps():
    table = reference.neighbours((4,), [[1], [-1]], [True])
    a = np.array([0, 0, 1, 1])
    jmax, jsum = reference.keys(reference.count_state(table, a, 2))
    assert (jmax[0], jsum[0]) == (2, 4)      # 1->2, 3->0 and back


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_cell_shapes(path):
    """At each cell's shape: the count state equals the program's numpy
    count arrays, the keys equal ``evaluate``, the blocked layout equals
    ``parse_plan("blocked")``, and the grid is ``dims_create``'s."""
    from repro.core import (CartGrid, NeighborTable, Stencil, dims_create,
                            parse_plan)
    from repro.core.cost_delta import stacked_count_arrays
    from repro.core.plan import MappingProblem
    cfg = json.loads(path.read_text())
    dims, offsets = cfg["dims"], cfg["stencil"]["offsets"]
    assert tuple(dims) == dims_create(cfg["processes"], len(dims))
    caps = capacities(cfg)
    st = Stencil(tuple(tuple(o) for o in offsets))
    table = reference.neighbours(dims, offsets, cfg["periodic"])
    rng = np.random.default_rng(1)
    A = np.stack([_random_layout(rng, caps) for _ in range(3)]
                 + [reference.blocked(caps)])
    ours = reference.count_state(table, A, len(caps))
    nt = NeighborTable.build(CartGrid(tuple(dims)), st)
    _, theirs = stacked_count_arrays(nt, A, len(caps))
    assert np.array_equal(ours, theirs)
    sol = parse_plan("blocked").solve(
        MappingProblem(tuple(dims), st, caps))
    assert np.array_equal(sol.assignment, reference.blocked(caps))
    jmax, jsum = reference.keys(ours)
    assert (jmax[-1], jsum[-1]) == (sol.j_max, sol.j_sum)


def test_capacities_hold():
    caps = (3, 2, 1)
    assert reference.capacities_hold(np.array([0, 1, 0, 2, 1, 0]), caps)
    assert not reference.capacities_hold(np.array([0, 1, 1, 2, 1, 0]), caps)
    assert not reference.capacities_hold(np.array([0, 1, 0, 2, 1]), caps)
    assert not reference.capacities_hold(np.array([0, 1, 0, 3, 1, 0]), caps)
