"""Batched refinement engine: bit-exact parity of batch_swap_deltas with the
scalar delta path, SwapRefiner's local optimum, ScheduledRefiner schedule
invariants, the refined2:/annealed: registry spellings, and elastic
auto-refinement in mapped_device_array.

Parity assertions use == / array_equal, not isclose: the batch path
accumulates the same integer crossing counts in the same offset order as
the scalar path, so any drift is a bug.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CartGrid, IncrementalCost, MapperInapplicable,
                        RefinedMapper, ScheduledRefiner, Stencil, SwapRefiner,
                        available_mappers, evaluate, get_mapper, layout_cost,
                        mapped_device_array)
from repro.core.mapping import MAPPERS
from repro.core.remap import ensure_refined

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
# batch_swap_deltas parity with the scalar path
@given(st.integers(0, 10_000), st.sampled_from(sorted(STENCILS)),
       st.booleans())
@settings(max_examples=60, deadline=None)
def test_batch_deltas_bit_exact_vs_scalar(seed, sname, weighted):
    """Random grids/stencils/assignments: every batched row equals the
    scalar delta_swap / peek_per_node result bit-for-bit."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng)
    stencil = STENCILS[sname](grid.ndim)
    ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=n_nodes,
                         weighted=weighted)
    m = int(rng.integers(1, 32))
    P = rng.integers(0, grid.size, size=m)
    Q = rng.integers(0, grid.size, size=m)
    bd = ic.batch_swap_deltas(P, Q, with_loads=True)
    assert bd.size == m
    for i in range(m):
        d = ic.delta_swap(int(P[i]), int(Q[i]))
        assert np.array_equal(bd.d_count_off[i], d.d_count_off)
        assert bd.d_j_sum[i] == d.d_j_sum
        peek = ic.peek_per_node(d)
        assert np.array_equal(bd.new_per_node[i], peek)
        assert bd.new_j_max[i] == peek.max(initial=0.0)


def test_batch_deltas_validates_input():
    grid = CartGrid((4, 4))
    ic = IncrementalCost(grid, Stencil.nearest_neighbor(2),
                         np.zeros(16, dtype=np.int64), num_nodes=2)
    with pytest.raises(ValueError):
        ic.batch_swap_deltas([0, 1], [2])
    with pytest.raises(ValueError):
        ic.batch_swap_deltas([0], [99])
    bd = ic.batch_swap_deltas(np.empty(0, dtype=np.int64),
                              np.empty(0, dtype=np.int64), with_loads=True)
    assert bd.size == 0 and bd.new_j_max.shape == (0,)


# ---------------------------------------------------------------------------
# batch SwapRefiner engine invariants
@given(st.integers(0, 10_000), st.sampled_from(["j_sum", "j_max"]),
       st.sampled_from(["first", "steepest"]))
@settings(max_examples=25, deadline=None)
def test_batch_refiner_monotonic_and_cardinality_preserving(seed, objective,
                                                            policy):
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng, max_nodes=4)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    refiner = SwapRefiner(objective=objective, policy=policy, max_passes=3)
    res = refiner.refine(grid, stencil, node_of_pos, num_nodes=n_nodes)
    if objective == "j_max":
        assert (res.final.j_max, res.final.j_sum) \
            <= (res.initial.j_max, res.initial.j_sum)
    else:
        assert res.final.j_sum <= res.initial.j_sum
    np.testing.assert_array_equal(
        np.bincount(res.assignment, minlength=n_nodes),
        np.bincount(node_of_pos, minlength=n_nodes))
    check = evaluate(grid, stencil, res.assignment, num_nodes=n_nodes)
    assert check.j_sum == res.final.j_sum
    assert check.j_max == res.final.j_max


@pytest.mark.parametrize("objective", ["j_sum", "j_max"])
@pytest.mark.parametrize("instance", ["2d-nn", "3d-hops"])
def test_converged_refiner_is_a_local_optimum(instance, objective):
    """A run that stops before its pass limit has reached a swap local
    optimum: one more pass scores no frontier pair above ``tol``, so it
    accepts nothing."""
    rng = np.random.default_rng(11)
    grid, stencil, n_nodes = {
        "2d-nn": (CartGrid((10, 10)), Stencil.nearest_neighbor(2), 5),
        "3d-hops": (CartGrid((4, 4, 4)), Stencil.nn_with_hops(3), 4),
    }[instance]
    a = rng.permutation(np.repeat(np.arange(n_nodes), grid.size // n_nodes))
    refiner = SwapRefiner(objective=objective, max_passes=50)
    res = refiner.refine(grid, stencil, a, num_nodes=n_nodes)
    assert res.swaps > 0 and res.passes < 50
    ic = IncrementalCost(grid, stencil, res.assignment, num_nodes=n_nodes,
                         weighted=refiner.weighted)
    P, Q = refiner._frontier_pairs(ic)
    gains, _ = refiner._batch_gains(ic, P, Q)
    assert P.size and gains.max() <= refiner._tol(ic)
    again = refiner.refine(grid, stencil, res.assignment, num_nodes=n_nodes)
    assert again.swaps == 0
    np.testing.assert_array_equal(again.assignment, res.assignment)


# ---------------------------------------------------------------------------
# ScheduledRefiner invariants
@given(st.integers(0, 10_000), st.booleans())
@settings(max_examples=15, deadline=None)
def test_scheduled_never_worsens_lexicographically(seed, anneal):
    """(J_max, J_sum) of the returned assignment is lexicographically no
    worse than the input's — the schedule considers the input a candidate."""
    rng = np.random.default_rng(seed)
    grid, n_nodes, node_of_pos = random_instance(rng, max_nodes=4)
    stencil = Stencil.nearest_neighbor(grid.ndim)
    ref = ScheduledRefiner(rounds=2, max_passes=3, anneal=anneal,
                           sa_moves=40, seed=seed)
    res = ref.refine(grid, stencil, node_of_pos, num_nodes=n_nodes)
    assert (res.final.j_max, res.final.j_sum) \
        <= (res.initial.j_max, res.initial.j_sum)
    np.testing.assert_array_equal(
        np.bincount(res.assignment, minlength=n_nodes),
        np.bincount(node_of_pos, minlength=n_nodes))
    check = evaluate(grid, stencil, res.assignment, num_nodes=n_nodes)
    assert check.j_sum == res.final.j_sum
    assert check.j_max == res.final.j_max


def test_scheduled_jmax_no_worse_than_plain_refined():
    """The schedule's first phase IS the default refined: pass, so its
    selected result can never exceed refined:'s J_max (acceptance
    criterion, checked here on the ragged-pod suite instances)."""
    cases = [((16, 28), [256, 192]), ((6, 8), [16, 16, 10, 6]),
             ((12, 8, 8), [128] * 5 + [96, 32])]
    for dims, sizes in cases:
        grid = CartGrid(dims)
        stencil = Stencil.nearest_neighbor(grid.ndim)
        for base in ("hyperplane", "kdtree", "random"):
            plain = get_mapper(f"refined:{base}").cost(grid, stencil, sizes)
            sched = get_mapper(f"refined2:{base}").cost(grid, stencil, sizes)
            ann = get_mapper(f"annealed:{base}").cost(grid, stencil, sizes)
            assert sched.j_max <= plain.j_max, (dims, base)
            assert ann.j_max <= plain.j_max, (dims, base)


def test_scheduled_deterministic():
    rng = np.random.default_rng(5)
    grid = CartGrid((8, 8))
    stencil = Stencil.nn_with_hops(2)
    a = rng.permutation(np.repeat(np.arange(4), 16))
    r1 = ScheduledRefiner(anneal=True, seed=3).refine(grid, stencil, a,
                                                      num_nodes=4)
    r2 = ScheduledRefiner(anneal=True, seed=3).refine(grid, stencil, a,
                                                      num_nodes=4)
    np.testing.assert_array_equal(r1.assignment, r2.assignment)
    assert (r1.final.j_sum, r1.final.j_max) == (r2.final.j_sum, r2.final.j_max)


def test_scheduled_validates_config():
    with pytest.raises(ValueError):
        ScheduledRefiner(objectives=())
    with pytest.raises(ValueError):
        ScheduledRefiner(rounds=-1)
    # rounds=0 is valid: skip the deterministic rounds, ladder/polish only
    # (the repair warm path's pinned portfolio uses it)
    assert ScheduledRefiner(rounds=0).rounds == 0
    with pytest.raises(ValueError):
        ScheduledRefiner(objectives=("nope",))


# ---------------------------------------------------------------------------
# registry spellings
def test_new_prefixes_resolve_for_every_mapper():
    for name in sorted(MAPPERS):
        for prefix in ("refined2", "annealed"):
            m = get_mapper(f"{prefix}:{name}")
            assert isinstance(m, RefinedMapper)
            assert isinstance(m.refiner, ScheduledRefiner)
            assert m.name == f"{prefix}:{name}"
        assert get_mapper(f"annealed:{name}").refiner.anneal
        assert not get_mapper(f"refined2:{name}").refiner.anneal
    listed = available_mappers()
    for prefix in ("refined:", "refined2:", "annealed:"):
        assert prefix + "blocked" in listed
    with pytest.raises(KeyError):
        get_mapper("refined2:doesnotexist")


def test_prefix_kwargs_configure_the_refiner():
    m = get_mapper("refined2:hyperplane", rounds=2, sa_moves=10)
    assert m.refiner.rounds == 2 and m.refiner.sa_moves == 10
    m = get_mapper("annealed:blocked", seed=9)
    assert m.refiner.seed == 9


# ---------------------------------------------------------------------------
# elastic ragged pods: refinement at mesh construction time
def test_mapped_device_array_auto_refines_ragged():
    """A pod that lost chips gets the scheduled-refinement upgrade without
    the caller naming it: (J_max, J_sum) is lexicographically no worse than
    the unrefined layout, on both the ragged-tail path and explicit
    surviving node_sizes."""
    stencil = Stencil.nearest_neighbor(2)
    devices = list(range(48))
    for kwargs in ({"chips_per_pod": 20},                       # ragged tail
                   {"chips_per_pod": 16,
                    "node_sizes": [16, 16, 10, 6]}):            # elastic pods
        arrs = {}
        for auto in (False, True):
            arrs[auto] = mapped_device_array(devices, "hyperplane", (6, 8),
                                             stencil, auto_refine=auto,
                                             **kwargs)
        sizes = kwargs.get("node_sizes")
        if sizes is None:
            full, rem = divmod(48, kwargs["chips_per_pod"])
            sizes = [kwargs["chips_per_pod"]] * full + [rem]
        base = layout_cost(np.vectorize(int)(arrs[False]), stencil, sizes)
        ref = layout_cost(np.vectorize(int)(arrs[True]), stencil, sizes)
        assert (ref.j_max, ref.j_sum) <= (base.j_max, base.j_sum)
        assert sorted(arrs[True].reshape(-1)) == devices


def test_mapped_device_array_homogeneous_unchanged():
    """Uniform pods never trigger the auto-upgrade (bit-identical layout)."""
    stencil = Stencil.nearest_neighbor(2)
    devices = list(range(48))
    a = mapped_device_array(devices, "hyperplane", (6, 8), stencil, 12)
    b = mapped_device_array(devices, "hyperplane", (6, 8), stencil, 12,
                            auto_refine=False)
    np.testing.assert_array_equal(np.vectorize(int)(a), np.vectorize(int)(b))


def test_mapped_device_array_validates_node_sizes():
    stencil = Stencil.nearest_neighbor(2)
    with pytest.raises(ValueError):
        mapped_device_array(list(range(48)), "blocked", (6, 8), stencil, 16,
                            node_sizes=[16, 16, 10])


def test_ensure_refined_idempotent():
    from repro.core import PortfolioRefiner
    assert ensure_refined("refined:kdtree") == "refined:kdtree"
    assert ensure_refined("annealed:kdtree") == "annealed:kdtree"
    assert ensure_refined("portfolio[k=2]:kdtree") == "portfolio[k=2]:kdtree"
    m = get_mapper("refined:blocked")
    assert ensure_refined(m) is m
    for wrapped in (ensure_refined("kdtree"),
                    ensure_refined(get_mapper("kdtree"))):
        assert isinstance(wrapped, RefinedMapper)
        assert isinstance(wrapped.refiner, PortfolioRefiner)
        assert wrapped.name == "portfolio:kdtree"
        assert wrapped.fallback is not None  # ragged-inapplicable bases too


def test_auto_refine_covers_inapplicable_base():
    """Nodecart cannot map ragged node sizes at all; the elastic upgrade
    must still refine (from the blocked fallback) instead of silently
    falling back to the unrefined identity layout."""
    stencil = Stencil.nearest_neighbor(2)
    devices = list(range(48))
    sizes = [16, 16, 10, 6]
    with pytest.raises(MapperInapplicable):
        get_mapper("nodecart").assignment(CartGrid((6, 8)), stencil, sizes)
    arr = mapped_device_array(devices, "nodecart", (6, 8), stencil, 16,
                              node_sizes=sizes)
    ident = mapped_device_array(devices, "blocked", (6, 8), stencil, 16,
                                node_sizes=sizes, auto_refine=False)
    cost = layout_cost(np.vectorize(int)(arr), stencil, sizes)
    base = layout_cost(np.vectorize(int)(ident), stencil, sizes)
    assert sorted(arr.reshape(-1)) == devices
    assert (cost.j_max, cost.j_sum) < (base.j_max, base.j_sum)
