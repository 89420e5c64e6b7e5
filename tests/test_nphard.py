"""Executable NP-hardness reduction (paper §IV, Thm IV.3)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cost import evaluate
from repro.core.nphard import (assignment_from_3way, grid_partition_brute,
                               reduce_3way_to_grid, three_way_partition_brute)


def test_paper_example_instance():
    # Fig. 3: I' = {6,3,3,2,2,2}, D = [6,2]... (paper draws the transpose);
    # our construction: D = [3, 6], Q = 2*6-6 = 6
    inst = reduce_3way_to_grid([6, 3, 3, 2, 2, 2])
    assert inst.grid.dims == (3, 6)
    assert inst.budget == 6
    colors = three_way_partition_brute(inst.node_sizes)
    assert colors is not None
    a = assignment_from_3way(inst, colors)
    c = evaluate(inst.grid, inst.stencil, a, num_nodes=6)
    assert c.j_sum <= inst.budget


@given(st.lists(st.integers(1, 6), min_size=3, max_size=7))
@settings(max_examples=40, deadline=None)
def test_reduction_forward_and_backward(items):
    if sum(items) % 3 != 0:
        with pytest.raises(ValueError):
            reduce_3way_to_grid(items)
        return
    inst = reduce_3way_to_grid(items)
    colors = three_way_partition_brute(items)
    mapping = grid_partition_brute(inst)
    # yes-instance of 3WAY  <=>  GRID-PARTITION achieves Q
    if colors is not None:
        a = assignment_from_3way(inst, colors)
        c = evaluate(inst.grid, inst.stencil, a, num_nodes=len(items))
        assert c.j_sum <= inst.budget
        assert mapping is not None
    else:
        assert mapping is None
