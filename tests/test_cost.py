"""Cost function vs a brute-force oracle."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import CartGrid, Stencil, evaluate
from repro.core.cost import node_of_rank_blocked


def brute_cost(grid, stencil, node_of_pos, weighted=False):
    j = 0.0
    per_node = {}
    for r in range(grid.size):
        c = np.array(grid.coord_of(r))
        for off, w in zip(stencil.offsets, stencil.weights):
            t = c + np.array(off)
            if ((t < 0) | (t >= np.array(grid.dims))).any():
                continue
            tr = grid.rank_of(tuple(t))
            if node_of_pos[r] != node_of_pos[tr]:
                ww = w if weighted else 1.0
                j += ww
                per_node[node_of_pos[r]] = per_node.get(node_of_pos[r], 0) + ww
    return j, max(per_node.values(), default=0.0)


@given(st.integers(2, 5), st.integers(2, 5), st.integers(2, 4),
       st.sampled_from(["nn", "comp", "hops"]), st.booleans())
@settings(max_examples=30, deadline=None)
def test_evaluate_matches_bruteforce(h, w, n_nodes, sname, weighted):
    grid = CartGrid((h, w))
    st_map = {"nn": Stencil.nearest_neighbor(2),
              "comp": Stencil.component(2),
              "hops": Stencil.nn_with_hops(2)}
    stencil = st_map[sname]
    if weighted:
        stencil = Stencil(stencil.offsets,
                          tuple(1.0 + i for i in range(stencil.k)))
    rng = np.random.default_rng(h * 100 + w * 10 + n_nodes)
    node_of_pos = rng.integers(0, n_nodes, size=grid.size)
    cost = evaluate(grid, stencil, node_of_pos, num_nodes=n_nodes,
                    weighted=weighted)
    bj, bm = brute_cost(grid, stencil, node_of_pos, weighted)
    assert cost.j_sum == bj
    assert cost.j_max == bm


def test_blocked_rows_cost_known_value():
    # 4x4 grid, 4 nodes of 4 (one row each), nearest neighbor: every
    # vertical edge crosses: 2 directed x 4 cols x 3 row-gaps = 24
    grid = CartGrid((4, 4))
    node_of_pos = node_of_rank_blocked([4] * 4)
    c = evaluate(grid, Stencil.nearest_neighbor(2), node_of_pos, 4)
    assert c.j_sum == 24
    assert c.j_max == 8  # middle rows talk up and down
