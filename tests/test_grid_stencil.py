"""Unit + property tests for CartGrid / Stencil / dims_create."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CartGrid, Stencil, dims_create


def test_grid_roundtrip():
    g = CartGrid((3, 4, 5))
    assert g.size == 60
    for r in [0, 1, 17, 59]:
        assert g.rank_of(g.coord_of(r)) == r


def test_grid_coords_row_major():
    g = CartGrid((2, 3))
    np.testing.assert_array_equal(
        g.coords(), [[0, 0], [0, 1], [0, 2], [1, 0], [1, 1], [1, 2]])


def test_shift_ranks_truncates_at_border():
    g = CartGrid((2, 2))
    valid, tgt = g.shift_ranks((0, 1))
    np.testing.assert_array_equal(valid, [True, False, True, False])
    assert tgt[0] == 1 and tgt[2] == 3


def test_shift_ranks_periodic():
    g = CartGrid((2, 2), periodic=(False, True))
    valid, tgt = g.shift_ranks((0, 1))
    assert valid.all()
    np.testing.assert_array_equal(tgt, [1, 0, 3, 2])


@given(st.integers(1, 512), st.integers(1, 4))
@settings(max_examples=60, deadline=None)
def test_dims_create_properties(p, nd):
    dims = dims_create(p, nd)
    assert len(dims) == nd
    assert math.prod(dims) == p
    assert list(dims) == sorted(dims, reverse=True)  # MPI spec: decreasing


def test_paper_stencils_2d():
    nn = Stencil.nearest_neighbor(2)
    assert set(nn.offsets) == {(1, 0), (-1, 0), (0, 1), (0, -1)}
    comp = Stencil.component(2)
    assert set(comp.offsets) == {(1, 0), (-1, 0)}
    hops = Stencil.nn_with_hops(2)
    assert set(hops.offsets) == {(1, 0), (-1, 0), (0, 1), (0, -1),
                                 (2, 0), (-2, 0), (3, 0), (-3, 0)}


def test_stencil_axis_stats():
    hops = Stencil.nn_with_hops(2)
    np.testing.assert_array_equal(hops.axis_comm_counts(), [6, 2])
    np.testing.assert_array_equal(hops.extents(), [6, 2])
    cos2 = hops.cos2_sums()
    assert cos2[0] > cos2[1]  # dim 0 carries more traffic


def test_component_distortion_zero_on_silent_dim():
    comp = Stencil.component(2)  # communicates along dim 0 only
    alpha = comp.distortion_factors()
    assert alpha[1] == 0.0 and alpha[0] > 0


def test_flat_interface_roundtrip():
    # the paper's MPIX_Cart_stencil_comm flattened stencil[] array
    s = Stencil.from_flat([1, 0, -1, 0, 0, 1, 0, -1], ndims=2, k=4)
    assert set(s.offsets) == set(Stencil.nearest_neighbor(2).offsets)


def test_stencil_rejects_bad_input():
    with pytest.raises(ValueError):
        Stencil(((0, 0),))  # self-loop
    with pytest.raises(ValueError):
        Stencil(((1, 0), (1, 0)))  # duplicate
    with pytest.raises(ValueError):
        Stencil(((1, 0),), weights=(0.0,))  # non-positive weight
