"""The device swap scorer (``repro.core.refine.device_swap``) against the
host's :meth:`IncrementalCost.batch_swap_deltas`: the same ``d_j_sum``
and ``new_j_max`` for every pair, on random assignments, with and without
periodic axes, on a grid of more positions than a byte holds (the scorer
looks positions up byte by byte), for frontiers that fill no chunk, part
of one, exactly one, and spill into a second (a small chunk here, so the
multi-chunk path runs on the CPU); and the weights and the backend it
refuses.  The scorer serves only accelerators, so these tests tell it
that the CPU is one."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import CartGrid, DevicePortfolioRefiner, Stencil
from repro.core.cost_delta import IncrementalCost
from repro.core.refine import device_swap
from repro.core.refine.device_swap import device_swap_scorer
from repro.core.refine.swap import SwapRefiner

#: pairs per program in these tests
CHUNK = 16

GRIDS = {
    "2d": ((7, 6), (False, False)),
    "2d-periodic": ((7, 6), (True, False)),
    "2d-periodic-size2": ((2, 9), (True, False)),
    "2d-over-256": ((20, 15), (False, True)),
    "3d": ((4, 3, 5), (False, False, False)),
    "3d-periodic-size2": ((4, 2, 3), (False, True, True)),
}


@pytest.fixture(autouse=True)
def accelerator(monkeypatch):
    monkeypatch.setattr(device_swap, "_accelerator", lambda: True)


def _state(name, weights=None, n_nodes=4, seed=0):
    dims, periodic = GRIDS[name]
    grid = CartGrid(dims, periodic=periodic)
    stencil = Stencil.nearest_neighbor(len(dims))
    if weights is not None:
        stencil = Stencil(stencil.offsets, tuple(weights[:stencil.k]))
    rng = np.random.default_rng(seed)
    node = rng.permutation(np.arange(grid.size) % n_nodes)
    ic = IncrementalCost(grid, stencil, node, num_nodes=n_nodes,
                         weighted="auto")
    return grid, stencil, ic, rng


def _pairs(ic, rng, m):
    """``m`` pairs: the swap frontier's own (stencil-adjacent pairs among
    them, which exercise the pair-internal dedup), then random ones."""
    P, Q = SwapRefiner()._frontier_pairs(ic)
    size = ic.grid.size
    P2 = rng.integers(size, size=m)
    Q2 = (P2 + rng.integers(1, size, size=m)) % size
    return (np.concatenate([P, P2])[:m], np.concatenate([Q, Q2])[:m])


@pytest.mark.parametrize("m", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                               4 * CHUNK + 3])
@pytest.mark.parametrize("name", sorted(GRIDS))
def test_scores_equal_the_host(name, m):
    grid, stencil, ic, rng = _state(name)
    P, Q = _pairs(ic, rng, m)
    scorer = device_swap_scorer(grid, stencil, ic.weights, chunk=CHUNK)
    d_j_sum, new_j_max = scorer.score(ic, P, Q)
    bd = ic.batch_swap_deltas(P, Q, with_loads=True)
    assert d_j_sum.shape == new_j_max.shape == (m,)
    np.testing.assert_array_equal(d_j_sum, bd.d_j_sum)
    np.testing.assert_array_equal(new_j_max, bd.new_j_max)


@pytest.mark.parametrize("name", ["2d-periodic", "3d"])
def test_integer_byte_weights_are_scored_exactly(name):
    grid, stencil, ic, rng = _state(name, weights=(3, 1, 4, 1, 5, 9))
    assert stencil.is_weighted
    P, Q = _pairs(ic, rng, 3 * CHUNK)
    scorer = device_swap_scorer(grid, stencil, ic.weights, chunk=CHUNK)
    d_j_sum, new_j_max = scorer.score(ic, P, Q)
    bd = ic.batch_swap_deltas(P, Q, with_loads=True)
    np.testing.assert_array_equal(d_j_sum, bd.d_j_sum)
    np.testing.assert_array_equal(new_j_max, bd.new_j_max)


@pytest.mark.parametrize("weights,accelerated", [
    ((1.5, 1.5, 0.25, 0.25), True), ((2.0 ** 28,) * 4, True),
    ((1.0,) * 4, False)], ids=["fractional", "int32-overflow", "cpu"])
def test_numpy_scores_elsewhere(monkeypatch, weights, accelerated):
    """No scorer where int32 could differ from float64, nor on the CPU
    backend, so the device refiner's passes score with numpy and count no
    device pairs."""
    monkeypatch.setattr(device_swap, "_accelerator", lambda: accelerated)
    grid = CartGrid((6, 5))
    stencil = Stencil(Stencil.nearest_neighbor(2).offsets, weights)
    assert device_swap_scorer(grid, stencil,
                              stencil.weight_array()) is None
    start = np.random.default_rng(1).permutation(np.arange(30) % 3)
    res = DevicePortfolioRefiner(k=2, sa_moves=10).refine(
        grid, stencil, start, num_nodes=3)
    counters = res.stats["counters"]
    assert counters["survivors/polish/swap.pairs"] > 0
    assert not any(k.endswith("swap.device_pairs") for k in counters)
