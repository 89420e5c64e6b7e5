"""The device refiner on a small instance shaped like the paper's largest
3-D Fig. 8 instance, with the polish's swap scorer engaged.

At full size that instance is 992 processes on a (31, 8, 4) grid, the
7-point stencil, 31 full nodes of 32: one node holds one 8 x 4 slab.
Here it is a (7, 4, 2) grid on 7 nodes of 8, so one node holds one 4 x 2
slab.  The scorer serves only accelerators, so it is told here that the
CPU is one, and its chunk is small enough that the passes span several.

Pinned, on two anneal seeds:

* the served layout is a bijection onto the capacities, and its J_max
  and J_sum equal a numpy recount written here from the definitions;
* ``swap.device_slots`` counts whole chunks: a multiple of the chunk and
  at least ``swap.device_pairs``, both in the rounds and in the polish;
* one ``swap.frontier`` span per scored pass, under ``swap.score``;
* a ladder within one swap of its start at a boundary runs the next
  temperature at the schedule's first one, so none ends where it started,
  although at this size some accept nothing in their first temperature;
  at full size, a ladder that took one swap and, cooled, its reverse is
  held hot and leaves.
"""
import numpy as np
import pytest

from repro.core import Stencil, parse_plan
from repro.core.plan import MappingProblem

pytest.importorskip("jax")

DIMS = (7, 4, 2)
NODES, PER_NODE = 7, 8
CHUNK = 16
PLAN = "device[k=4,restarts=auto,sa_moves=30,seed={seed}]:hyperplane"
STENCIL = Stencil.nearest_neighbor(3)
PROBLEM = MappingProblem(DIMS, STENCIL, (PER_NODE,) * NODES)
PHASES = ("rounds", "survivors/polish")


def _recount(node_of_pos):
    """``(J_max, J_sum)``: directed stencil edges between positions on
    different nodes (row-major positions, no wrap), J_max the most that
    leave one node."""
    coords = np.stack(np.unravel_index(np.arange(len(node_of_pos)), DIMS),
                      axis=1)
    leaving = np.zeros(NODES, dtype=np.int64)
    for off in STENCIL.offsets:
        t = coords + np.asarray(off)
        inside = np.all((t >= 0) & (t < np.asarray(DIMS)), axis=1)
        src = np.nonzero(inside)[0]
        dst = np.ravel_multi_index(tuple(t[inside].T), DIMS)
        crossing = node_of_pos[src] != node_of_pos[dst]
        np.add.at(leaving, node_of_pos[src][crossing], 1)
    return int(leaving.max()), int(leaving.sum())


@pytest.fixture(scope="module", params=[1, 2], ids=["seed1", "seed2"])
def solved(request):
    """A solve through ``parse_plan`` with the scorer engaged at a chunk
    of ``CHUNK`` pairs; returns the solution and its engine stage."""
    from repro.core.refine import device_swap
    build = device_swap.device_swap_scorer
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device_swap, "_accelerator", lambda: True)
        mp.setattr(device_swap, "device_swap_scorer",
                   lambda grid, stencil, w: build(grid, stencil, w,
                                                  chunk=CHUNK))
        sol = parse_plan(PLAN.format(seed=request.param)).solve(PROBLEM)
    stage = next(s for s in reversed(sol.stage_stats) if "backend" in s)
    assert stage["backend"].startswith("device[")
    return sol, stage


def test_served_layout_is_a_bijection_onto_the_capacities(solved):
    sol, _ = solved
    a = np.asarray(sol.assignment)
    assert a.shape == (int(np.prod(DIMS)),)
    assert np.array_equal(np.bincount(a, minlength=NODES),
                          [PER_NODE] * NODES)


def test_served_costs_equal_a_numpy_recount(solved):
    sol, _ = solved
    assert (sol.j_max, sol.j_sum) == _recount(np.asarray(sol.assignment))


def test_device_slots_are_whole_chunks_over_the_pairs(solved):
    _, stage = solved
    counters = stage["counters"]
    spans_more_than_a_chunk = False
    for phase in PHASES:
        pairs = counters[f"{phase}/swap.device_pairs"]
        slots = counters[f"{phase}/swap.device_slots"]
        assert pairs == counters[f"{phase}/swap.pairs"] > 0
        assert slots % CHUNK == 0 and slots >= pairs
        # a pass of m pairs dispatches ceil(m / CHUNK) chunks
        passes = counters[f"{phase}/swap.passes"]
        assert slots < pairs + passes * CHUNK
        spans_more_than_a_chunk |= slots > passes * CHUNK
    assert spans_more_than_a_chunk, "no pass needed a second chunk"


def test_one_frontier_span_per_scored_pass(solved):
    _, stage = solved
    spans, counters = stage["spans"], stage["counters"]
    for phase in PHASES:
        passes = counters[f"{phase}/swap.passes"]
        assert spans[f"{phase}/swap.score"][0] == passes
        calls, seconds = spans[f"{phase}/swap.score/swap.frontier"]
        assert calls == passes
        assert 0.0 < seconds <= spans[f"{phase}/swap.score"][1]
    assert not any(path.endswith("swap.frontier")
                   and not path.endswith("swap.score/swap.frontier")
                   for path in spans)


class _Recorded:
    """A :class:`DeviceLadderEngine` that keeps, per temperature, the
    original ladders' temperatures, accepted counts and distance from the
    start: the engine's own count and a numpy recount of its rows."""

    @staticmethod
    def factory(runs):
        from repro.core.refine.device import DeviceLadderEngine

        class Recorded(DeviceLadderEngine):
            def __init__(self, grid, stencil, start, *args, **kwargs):
                super().__init__(grid, stencil, start, *args, **kwargs)
                self.start = np.array(start)
                self.temps, self.accepted, self.away = [], [], []
                runs.append(self)

            def run_temperature(self, temps, *args, **kwargs):
                rep = super().run_temperature(temps, *args, **kwargs)
                self.temps.append(np.array(temps[:self.k]))
                self.accepted.append(rep.accepted[:self.k])
                rows = self.snapshot()["nodes"][:self.k]
                away = (rows != self.start[None, :]).sum(axis=1)
                assert np.array_equal(self.moved_positions(), away)
                self.away.append(away)
                return rep

        return Recorded


def _refine_recorded(problem, base, **kwargs):
    from repro.core import DevicePortfolioRefiner
    runs = []
    DevicePortfolioRefiner(restarts="auto",
                           engine_factory=_Recorded.factory(runs),
                           **kwargs).refine(problem.grid(), problem.stencil,
                                            base,
                                            num_nodes=len(problem.node_sizes))
    eng, = runs
    return eng


@pytest.mark.parametrize("seed", [1, 2])
def test_a_ladder_stays_hot_until_it_leaves_its_start(seed):
    schedule = (2.0, 1.0, 0.5, 0.25)
    base = parse_plan("hyperplane").solve(PROBLEM).assignment
    eng = _refine_recorded(PROBLEM, base, k=16, seed=seed, sa_moves=20,
                           temperatures=schedule)
    accepted = np.stack(eng.accepted, axis=1)          # (K, temperatures)
    assert (accepted[:, 0] == 0).any(), "no ladder stalled: nothing pinned"
    near = np.ones(eng.k, dtype=bool)
    for t, T in enumerate(schedule):
        # within one swap of the start (at most two positions differ) at
        # the boundary before: the first temperature again
        assert np.array_equal(eng.temps[t], np.where(near, 2.0, T))
        near = eng.away[t] <= 2
    assert (accepted.sum(axis=1) > 0).all()
    assert (eng.away[-1] > 0).all()


def test_a_ladder_one_swap_from_its_start_is_held_hot():
    """At full size, 992 processes on (31, 8, 4) and 31 nodes of 32, the
    ladder seeded 941575385 (ladder 220 of the plan seeded 941575165)
    accepts one swap at the first temperature.  Cooled, it rejected every
    uphill proposal and, in the last temperature, accepted the one
    downhill move, that swap's reverse: it ended at its start.  Held at
    the first temperature while one swap away, it leaves."""
    problem = MappingProblem((31, 8, 4), STENCIL, (32,) * 31)
    base = parse_plan("hyperplane").solve(problem).assignment
    eng = _refine_recorded(problem, base, seeds=[941575385])
    assert eng.accepted[0][0] == 1 and eng.away[0][0] == 2
    assert eng.temps[1][0] == 2.0
    assert eng.away[-1][0] > 2
