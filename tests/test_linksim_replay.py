"""Closing the loop between the mapping metrics and simulated link
traffic: replaying a mapping's stencil communication through
analysis.linksim must reproduce J_sum / J_max exactly on the DCI counters
(dci_total == J_sum, max_dci_pod == J_max for unit weights — same
directed, source-counted accounting), and therefore rank base vs refined
vs annealed vs portfolio layouts monotonically with their J_max.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.analysis.linksim import (machine_for_nodes, replay_assignment,
                                    simulate, stencil_collectives)
from repro.core import CartGrid, Stencil, evaluate, get_mapper
from repro.topology.machine import LevelSpec, V5E_POD

STENCILS = {
    "nn": Stencil.nearest_neighbor,
    "comp": Stencil.component,
    "hops": Stencil.nn_with_hops,
}

# the EXPERIMENTS.md homogeneous grids (tiny + one full-suite instance)
GRIDS = [
    ((8, 8), [16] * 4),
    ((4, 4, 4), [16] * 4),
    ((8, 8, 8), [64] * 8),
]

VARIANTS = ("base", "refined", "annealed", "portfolio[k=3]")


def _mapper_name(variant, base):
    return base if variant == "base" else f"{variant}:{base}"


# ---------------------------------------------------------------------------
# exactness: the simulator's DCI counters ARE the paper metrics
@given(st.integers(0, 10_000), st.sampled_from(sorted(STENCILS)))
@settings(max_examples=25, deadline=None)
def test_replay_dci_equals_cost_metrics(seed, sname):
    """Random homogeneous instances: replaying an arbitrary assignment
    gives dci_total == J_sum and max_dci_pod == J_max exactly."""
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(2, 6))
    per = int(rng.integers(2, 7))
    dims = (n_nodes, per) if rng.integers(2) else (per, n_nodes)
    grid = CartGrid(dims, periodic=(bool(rng.integers(2)),) * 2)
    stencil = STENCILS[sname](2)
    sizes = [grid.size // n_nodes] * n_nodes
    a = rng.permutation(np.repeat(np.arange(n_nodes), sizes[0]))
    cost = evaluate(grid, stencil, a, num_nodes=n_nodes)
    rep = replay_assignment(grid, stencil, a, sizes)
    assert rep.dci_total == cost.j_sum
    assert rep.max_dci_pod() == cost.j_max
    np.testing.assert_array_equal(rep.dci_pod_egress, cost.per_node)


def test_replay_weighted_stencil_counts_bytes():
    grid = CartGrid((6, 6))
    heavy = Stencil(Stencil.nearest_neighbor(2).offsets,
                    (8.0, 8.0, 1.0, 1.0))
    a = np.repeat(np.arange(3), 12)
    cost_w = evaluate(grid, heavy, a, num_nodes=3, weighted=True)
    rep = replay_assignment(grid, heavy, a, [12] * 3)       # weighted=True
    assert rep.dci_total == cost_w.j_sum
    assert rep.max_dci_pod() == cost_w.j_max
    rep_unit = replay_assignment(grid, heavy, a, [12] * 3, weighted=False)
    cost_u = evaluate(grid, heavy, a, num_nodes=3, weighted=False)
    assert rep_unit.dci_total == cost_u.j_sum


def test_stencil_collectives_shape():
    grid = CartGrid((4, 4), periodic=(True, False))
    stencil = Stencil.nearest_neighbor(2)
    colls = stencil_collectives(grid, stencil)
    assert len(colls) == stencil.k
    for c, off in zip(colls, stencil.offsets):
        assert c.opcode == "collective-permute"
        valid, tgt = grid.shift_ranks(off)
        assert len(c.pairs) == int(valid.sum())
        for s, t in c.pairs:
            assert tgt[s] == t
    # replay respects the machine's pod structure: one pod => no DCI
    rep = simulate(colls, np.arange(16), machine_for_nodes([16]))
    assert rep.dci_total == 0.0 and rep.ici_total > 0.0


def test_machine_for_nodes_homogeneous_and_ragged():
    m = machine_for_nodes([8] * 6)
    assert m.num_pods == 6 and m.chips_per_pod == 8
    # ragged allocations get a per-pod-torus machine (elastic pods)
    r = machine_for_nodes([16, 12])
    assert r.num_pods == 2 and r.num_chips == 28
    assert r.node_sizes() == [16, 12]
    assert [r.pod_of(c) for c in (0, 15, 16, 27)] == [0, 0, 1, 1]
    assert r.torus_coord(16) == (0,) and r.torus_coord(27) == (11,)
    # hop path stays inside the pod's own ring (size 12, not 16)
    path = r.torus_hop_path(27, 16)
    assert len(path) == 1 and path[0][2] == +1        # wraps 11 -> 0
    with pytest.raises(ValueError):
        machine_for_nodes([8, 0])


def test_machine_for_nodes_near_square_torus_matches_v5e():
    """Regression: a 256-chip pod must model as V5E_POD's real (16, 16)
    ICI torus, not the pre-fix 1-d 256-ring, and the replay must be
    ICI-identical to the hand-built V5E_POD spec.  An explicit ``torus=``
    still overrides."""
    m = machine_for_nodes([256])
    assert m.torus == (16, 16) == V5E_POD.torus
    grid, stencil = CartGrid((16, 16)), Stencil.nearest_neighbor(2)
    colls = stencil_collectives(grid, stencil)
    layout = np.arange(256)
    auto = simulate(colls, layout, m)
    ref = simulate(colls, layout, V5E_POD)
    assert auto.ici_total == ref.ici_total
    assert auto.max_ici_link() == ref.max_ici_link()
    # the old 1-d model inflated hop counts: the ring walks up to 128
    # hops where the square torus needs at most 16
    ring = simulate(colls, layout, machine_for_nodes([256], torus=(256,)))
    assert ring.ici_total > auto.ici_total
    # factorization corner cases
    assert machine_for_nodes([12] * 2).torus == (4, 3)
    assert machine_for_nodes([7] * 3).torus == (7,)        # prime: 1-d ring
    assert machine_for_nodes([1]).torus == (1,)
    # explicit override must hold the pod exactly
    assert machine_for_nodes([16] * 4, torus=(4, 4)).torus == (4, 4)
    with pytest.raises(ValueError, match="does not hold"):
        machine_for_nodes([16] * 4, torus=(4, 2))
    with pytest.raises(ValueError, match="ragged"):
        machine_for_nodes([16, 12], torus=(4, 4))


def test_replay_per_level_egress_parity():
    """Deep-machine replay: per-level DCI egress at the finest (pod)
    level equals the flat dci_pod_egress exactly (the parity invariant),
    and coarser levels only aggregate — total rack-crossing bytes can
    never exceed total pod-crossing bytes."""
    grid, stencil = CartGrid((8, 8)), Stencil.nearest_neighbor(2)
    sizes = [4] * 16
    levels = (LevelSpec("rack", 4), LevelSpec("pod", 4))
    a = get_mapper("hyperplane").assignment(grid, stencil, sizes)
    rep = replay_assignment(grid, stencil, a, sizes, levels=levels)
    cost = evaluate(grid, stencil, a, num_nodes=16)
    assert rep.dci_total == cost.j_sum
    assert rep.max_dci_pod() == cost.j_max
    np.testing.assert_array_equal(rep.level_egress["pod"],
                                  rep.dci_pod_egress)
    assert rep.max_level_egress("pod") == rep.max_dci_pod()
    assert rep.level_egress["rack"].shape == (4,)
    assert rep.level_egress["rack"].sum() <= rep.dci_total
    # rack egress is exactly the cross-rack slice of the pair traffic
    rack_of = {p: p // 4 for p in range(16)}
    cross_rack = sum(b for (pa, pb), b in rep.dci_pair_bytes.items()
                     if rack_of[pa] != rack_of[pb])
    assert rep.level_egress["rack"].sum() == cross_rack
    # a flat machine reports no level counters
    flat = replay_assignment(grid, stencil, a, sizes)
    assert flat.level_egress == {}


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_replay_dci_equals_cost_metrics_ragged(seed):
    """Ragged (elastic) allocations close the same loop: per-pod torus
    sizes, dci_total == J_sum and max_dci_pod == J_max exactly."""
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(2, 6))
    sizes = [int(rng.integers(2, 9)) for _ in range(n_nodes)]
    total = sum(sizes)
    dims = (total,) if rng.integers(2) else (2, -(-total // 2))
    if int(np.prod(dims)) != total:       # odd total: keep it 1-d
        dims = (total,)
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(len(dims))
    a = rng.permutation(np.repeat(np.arange(n_nodes), sizes))
    cost = evaluate(grid, stencil, a, num_nodes=n_nodes)
    rep = replay_assignment(grid, stencil, a, sizes)
    assert rep.dci_total == cost.j_sum
    assert rep.max_dci_pod() == cost.j_max
    np.testing.assert_array_equal(rep.dci_pod_egress, cost.per_node)


# ---------------------------------------------------------------------------
# the loop-closer: simulated DCI bottleneck is monotone in J_max across
# base -> refined -> annealed -> portfolio on the EXPERIMENTS grids
@pytest.mark.parametrize("dims,sizes", GRIDS[:2])
@pytest.mark.parametrize("sname", sorted(STENCILS))
def test_replay_monotone_with_jmax_rank(dims, sizes, sname):
    grid = CartGrid(dims)
    stencil = STENCILS[sname](grid.ndim)
    rows = []
    for base in ("random", "hyperplane"):
        per_variant = {}
        for variant in VARIANTS:
            a = get_mapper(_mapper_name(variant, base)).assignment(
                grid, stencil, sizes)
            cost = evaluate(grid, stencil, a, num_nodes=len(sizes))
            rep = replay_assignment(grid, stencil, a, sizes)
            assert rep.max_dci_pod() == cost.j_max     # exact, per variant
            per_variant[variant] = (cost.j_max, rep.max_dci_pod())
        rows.append((base, per_variant))
    for base, per_variant in rows:
        ranked = sorted(per_variant.values())
        dci = [d for _, d in ranked]
        assert dci == sorted(dci), (base, per_variant)  # monotone with rank
        # and the refinement chain never increases the simulated bottleneck
        assert per_variant["portfolio[k=3]"][1] <= per_variant["base"][1]
        assert per_variant["annealed"][1] <= per_variant["base"][1]


@pytest.mark.slow
def test_replay_monotone_full_grid():
    """The full-suite 8x8x8 instance (slower: portfolio on 512 cells)."""
    dims, sizes = GRIDS[2]
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(3)
    dci = {}
    for variant in VARIANTS:
        a = get_mapper(_mapper_name(variant, "random")).assignment(
            grid, stencil, sizes)
        cost = evaluate(grid, stencil, a, num_nodes=len(sizes))
        rep = replay_assignment(grid, stencil, a, sizes)
        assert rep.max_dci_pod() == cost.j_max
        dci[variant] = (cost.j_max, rep.max_dci_pod())
    assert dci["portfolio[k=3]"][1] <= dci["annealed"][1] <= dci["base"][1]
