"""Property suite for the machine hierarchy model
(:class:`repro.topology.machine.TopologyTree`) and the chip-addressing
contract of both machine spec classes.

Pinned properties:

* structural invariants — leaf count equals the chip count, per-level
  node counts are prefix products of the fan-outs (so the last level has
  exactly ``num_pods`` nodes), and sibling chip ranges tile the parent's
  range exactly;
* ragged round-trip — ``TopologyTree(sizes).node_sizes() == sizes`` and
  per-subtree chip counts are sums of ``pod_sizes`` slices;
* hier composition bijection — a :class:`~repro.core.refine.hier.HierRefiner`
  pass over any balanced instance returns an assignment with *exactly*
  the input's node cardinalities (the property its internal composition
  assert enforces, checked here from the outside on random instances);
* chip addressing — ``pod_of``/``torus_coord`` raise :class:`ValueError`
  on out-of-range chip ids (-1 and ``num_chips``) in **both**
  :class:`~repro.topology.machine.MachineSpec` and
  :class:`~repro.topology.machine.RaggedMachineSpec`; the pre-fix code
  silently returned a phantom pod id for both.
"""
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import CartGrid, Stencil, evaluate
from repro.core.refine import HierRefiner, hier_subtree_cache
from repro.topology.machine import (LevelSpec, MachineSpec,
                                    RaggedMachineSpec, TopologyTree,
                                    V5E_4RACK, V5E_POD)


def _random_levels(rng, max_levels=3, max_fanout=4):
    n_levels = int(rng.integers(1, max_levels + 1))
    return tuple(LevelSpec(f"l{i}", int(rng.integers(1, max_fanout + 1)))
                 for i in range(n_levels))


# ---------------------------------------------------------------------------
# structural invariants


@given(st.integers(0, 10_000))
@settings(max_examples=50, deadline=None)
def test_tree_leaf_and_level_counts(seed):
    rng = np.random.default_rng(seed)
    levels = _random_levels(rng)
    num_pods = math.prod(l.fanout for l in levels)
    sizes = [int(rng.integers(1, 9)) for _ in range(num_pods)]
    tree = TopologyTree(sizes, levels)
    assert tree.depth == len(levels)
    assert tree.num_pods == num_pods
    assert tree.leaf_count() == tree.num_chips == sum(sizes)
    # node counts are prefix products of the fan-outs
    for lvl in range(tree.depth + 1):
        assert tree.num_nodes_at(lvl) == math.prod(
            l.fanout for l in levels[:lvl])
    assert tree.num_nodes_at(0) == 1
    assert tree.num_nodes_at(tree.depth) == num_pods


@given(st.integers(0, 10_000))
@settings(max_examples=50, deadline=None)
def test_tree_sibling_ranges_tile_parent(seed):
    """Children's pod/chip ranges partition the parent's range, and
    ``child_sizes`` sums to ``chip_count`` at every internal node."""
    rng = np.random.default_rng(seed)
    levels = _random_levels(rng)
    num_pods = math.prod(l.fanout for l in levels)
    sizes = [int(rng.integers(1, 9)) for _ in range(num_pods)]
    tree = TopologyTree(sizes, levels)
    for lvl in range(tree.depth):
        f = tree.fanout_at(lvl)
        for j in range(tree.num_nodes_at(lvl)):
            plo, phi = tree.pod_range(lvl, j)
            clo, chi = tree.chip_range(lvl, j)
            kids_p, kids_c = [], []
            for c in range(f):
                k = j * f + c
                kids_p.append(tree.pod_range(lvl + 1, k))
                kids_c.append(tree.chip_range(lvl + 1, k))
            assert kids_p[0][0] == plo and kids_p[-1][1] == phi
            assert kids_c[0][0] == clo and kids_c[-1][1] == chi
            for (a, b), (c_, d) in zip(kids_p, kids_p[1:]):
                assert b == c_        # contiguous, no gaps or overlap
            assert sum(tree.child_sizes(lvl, j)) == tree.chip_count(lvl, j)
    # pods' children are the chips themselves
    for p in range(tree.num_pods):
        assert tree.child_sizes(tree.depth, p) == [1] * sizes[p]
        assert tree.chip_count(tree.depth, p) == sizes[p]


@given(st.integers(0, 10_000))
@settings(max_examples=50, deadline=None)
def test_tree_ragged_node_sizes_round_trip(seed):
    rng = np.random.default_rng(seed)
    levels = _random_levels(rng)
    num_pods = math.prod(l.fanout for l in levels)
    sizes = [int(rng.integers(1, 9)) for _ in range(num_pods)]
    tree = TopologyTree(sizes, levels)
    assert tree.node_sizes() == sizes
    # level ancestors are consistent with pod ranges
    for pod in range(num_pods):
        for lvl in range(tree.depth + 1):
            j = tree.level_node_of_pod(pod, lvl)
            lo, hi = tree.pod_range(lvl, j)
            assert lo <= pod < hi


def test_tree_default_single_level_and_validation():
    t = TopologyTree([4, 2, 3])                     # default: one pod level
    assert t.depth == 1 and t.num_pods == 3 and t.num_chips == 9
    assert t.node_sizes() == [4, 2, 3]
    with pytest.raises(ValueError):
        TopologyTree([])
    with pytest.raises(ValueError):
        TopologyTree([4, 0])
    with pytest.raises(ValueError):                  # fan-outs don't multiply
        TopologyTree([4] * 6, (LevelSpec("a", 2), LevelSpec("b", 2)))
    with pytest.raises(ValueError):
        LevelSpec("bad", 0)
    with pytest.raises(ValueError):
        t.num_nodes_at(5)
    with pytest.raises(ValueError):
        t.pod_range(1, 3)
    with pytest.raises(ValueError):                  # pods have no one fanout
        t.fanout_at(1)


def test_machine_levels_validation_and_tree():
    tree = V5E_4RACK.topology_tree()
    assert tree.depth == 2 and tree.num_pods == 16
    assert tree.leaf_count() == V5E_4RACK.num_chips == 16 * 256
    assert [l.name for l in tree.levels] == ["rack", "pod"]
    assert tree.chip_range(1, 0) == (0, 4 * 256)     # rack 0 = pods 0..3
    with pytest.raises(ValueError):                  # 2*3 != 4 pods
        MachineSpec(num_pods=4, torus=(2,),
                    levels=(LevelSpec("a", 2), LevelSpec("b", 3)))
    flat = V5E_POD.topology_tree()                   # levels=() default
    assert flat.depth == 1 and flat.num_pods == 1
    assert flat.node_sizes() == [256]


# ---------------------------------------------------------------------------
# chip addressing: out-of-range ids raise (regression — the pre-fix
# ``pod_of`` happily returned ``chip // chips_per_pod`` for any int)


@pytest.mark.parametrize("machine", [
    MachineSpec(num_pods=3, torus=(2, 2)),           # 12 chips
    RaggedMachineSpec(pod_sizes=(5, 3, 4)),          # 12 chips, ragged
    V5E_4RACK,
])
def test_pod_of_boundary_ids(machine):
    n = machine.num_chips
    assert machine.pod_of(0) == 0
    assert machine.pod_of(n - 1) == machine.num_pods - 1
    for bad in (-1, n, n + 7):
        with pytest.raises(ValueError):
            machine.pod_of(bad)
        with pytest.raises(ValueError):
            machine.torus_coord(bad)


def test_ragged_pod_of_interior_boundaries():
    r = RaggedMachineSpec(pod_sizes=(5, 3, 4))
    assert [r.pod_of(c) for c in (4, 5, 7, 8, 11)] == [0, 1, 1, 2, 2]


# ---------------------------------------------------------------------------
# hier composition bijection


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_hier_assignment_bijection(seed):
    """On random balanced instances, the composed hierarchical assignment
    realizes exactly the input's node cardinalities — node i keeps its
    size, every position keeps exactly one node."""
    rng = np.random.default_rng(seed)
    f1, f2 = int(rng.integers(2, 4)), int(rng.integers(2, 4))
    n = f1 * f2
    per = int(rng.integers(2, 5))
    grid = CartGrid((n, per))
    stencil = Stencil.nearest_neighbor(2)
    a = rng.permutation(np.repeat(np.arange(n), per))
    hier_subtree_cache().clear()
    res = HierRefiner(fanouts=f"{f1}x{f2}", solver="refined").refine(
        grid, stencil, a, num_nodes=n)
    out = np.asarray(res.assignment)
    assert out.shape == a.shape
    np.testing.assert_array_equal(np.bincount(out, minlength=n),
                                  np.bincount(a, minlength=n))
    # and never lexicographically worse than its input
    assert (res.final.j_max, res.final.j_sum) \
        <= (res.initial.j_max, res.initial.j_sum)


# ---------------------------------------------------------------------------
# ragged-aware fan-out derivation (derive_fanouts / TopologyTree.derive /
# MachineSpec.topology_tree(depth=...))


def test_derive_fanouts_ragged_round_trip():
    """A ragged allocation derives fan-outs from the actual chip counts:
    the tree round-trips node_sizes exactly, and its level-1 subtree chip
    totals are no more skewed than the pod-count-only dims_create split
    (here: perfectly balanced 16/16 vs dims_create's 8..12 spread)."""
    from repro.core.grid import dims_create
    from repro.topology.machine import derive_fanouts
    sizes = (4, 4, 4, 4, 2, 2, 6, 6)

    def spread(fanouts):
        starts = np.concatenate(([0], np.cumsum(sizes)))
        groups = np.diff(starts[::math.prod(fanouts[1:])])
        return int(groups.max() - groups.min())

    fo = derive_fanouts(sizes, depth=2)
    assert math.prod(fo) == len(sizes)
    assert spread(fo) <= spread(tuple(dims_create(len(sizes), 2)))
    assert spread(fo) == 0                      # this instance balances

    tree = TopologyTree.derive(sizes, depth=2)
    assert tree.depth == 2
    assert tree.node_sizes() == list(sizes)     # exact round-trip
    assert tree.num_chips == sum(sizes)
    # sibling subtrees at level 1 carry equal chip counts
    totals = [tree.chip_range(1, i)[1] - tree.chip_range(1, i)[0]
              for i in range(tree.num_nodes_at(1))]
    assert len(set(totals)) == 1


def test_derive_fanouts_uniform_keeps_dims_create():
    """Uniform pods score 0 imbalance for every factorization, so the
    derivation must return exactly the dims_create fan-outs (bit-compat
    with the pre-derivation contiguous-equal-groups assumption)."""
    from repro.core.grid import dims_create
    from repro.topology.machine import derive_fanouts
    for n, depth in ((8, 2), (12, 2), (16, 3), (7, 2)):
        assert derive_fanouts([16] * n, depth) == tuple(dims_create(n, depth))


def test_machine_topology_tree_depth_derivation():
    """MachineSpec.topology_tree(depth=) derives for level-less machines,
    ragged specs use their true sizes, and machines with declared levels
    reject a conflicting re-derivation."""
    ragged = RaggedMachineSpec(pod_sizes=(4, 4, 4, 4, 2, 2, 6, 6))
    tree = ragged.topology_tree(depth=2)
    assert tree.node_sizes() == list(ragged.pod_sizes)
    assert tree.depth == 2
    flat = MachineSpec(num_pods=6, torus=(2, 2)).topology_tree(depth=2)
    assert flat.depth == 2 and flat.num_pods == 6
    with pytest.raises(ValueError):
        V5E_4RACK.topology_tree(depth=3)     # declares 2 levels
    # depth matching the declaration is a no-op passthrough
    assert V5E_4RACK.topology_tree(depth=len(V5E_4RACK.levels)).depth \
        == len(V5E_4RACK.levels)
