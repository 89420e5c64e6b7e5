"""Property-based contract of the numpy ladder kernel
(:func:`repro.core.refine.portfolio.run_temperature`) — the host side of
the engine interface every portfolio backend (serial / sharded / device)
speaks.  The properties pinned here are exactly the ones the device
engine's conformance suite (``tests/test_device_portfolio.py``) re-checks
on accelerator state, so a drift in either implementation shows up as a
broken shared contract, not a silent divergence:

* accepted-count bounds — ``0 <= accepted[i] <= sa_moves``, and exactly 0
  for dead or done ladders;
* done/alive interaction — dead and done ladders are excluded from the
  boundary snapshot, never consume their rng stream, and their state
  freezes; ``done`` only ever flips False -> True (sticky);
* rng-replay determinism — re-running from a deep-copied (state, rng)
  pair reproduces accepted counts, assignments, and done flags exactly;
* batch independence — a ladder's trajectory depends only on its own rng
  and start state, never on which batch it ran in (the property the
  sharded engine's bit-identity rests on);
* budget cap — the kernel checks the budget before each batched move, so
  the overshoot is bounded by one batch: ``sum(accepted) < budget + K``.
"""
import copy

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import CartGrid, PortfolioCost, Stencil
from repro.core.refine.portfolio import run_temperature

DIMS = [(6, 6), (8, 8), (6, 8), (4, 4, 4)]


def _ladders(seed, k, dims=(6, 6), n_nodes=4):
    """A (pc, rngs, done) triple on a random balanced-ish assignment."""
    grid = CartGrid(dims)
    stencil = Stencil.nearest_neighbor(len(dims))
    rng = np.random.default_rng(seed)
    sizes = np.full(n_nodes, grid.size // n_nodes)
    sizes[: grid.size - sizes.sum()] += 1
    start = rng.permutation(np.repeat(np.arange(n_nodes), sizes))
    pc = PortfolioCost(grid, stencil,
                       np.broadcast_to(start, (k, grid.size)),
                       num_nodes=n_nodes)
    rngs = [np.random.default_rng(seed + 100 + i) for i in range(k)]
    return pc, rngs, np.zeros(k, dtype=bool)


@given(seed=st.integers(0, 10**6), k=st.integers(1, 5),
       sa_moves=st.integers(1, 50), dead=st.integers(0, 4),
       dims=st.sampled_from(DIMS))
@settings(max_examples=15)
def test_accepted_bounds_and_dead_rows_frozen(seed, k, sa_moves, dead, dims):
    """0 <= accepted <= sa_moves everywhere; a dead ladder accepts
    nothing, keeps its assignment, and its rng stream is never touched."""
    pc, rngs, done = _ladders(seed, k, dims)
    alive = np.ones(k, dtype=bool)
    alive[min(dead, k - 1)] = dead < k  # sometimes all alive
    dead_rows = np.nonzero(~alive)[0]
    frozen_states = pc.node[dead_rows].copy()
    frozen_rng = [copy.deepcopy(rngs[i].bit_generator.state)
                  for i in dead_rows]
    accepted = run_temperature(pc, rngs, alive, done, np.full(k, 1.0),
                               sa_moves, np.full(k, 1e-2))
    assert accepted.shape == (k,)
    assert np.all(accepted >= 0) and np.all(accepted <= sa_moves)
    assert np.all(accepted[dead_rows] == 0)
    np.testing.assert_array_equal(pc.node[dead_rows], frozen_states)
    for j, i in enumerate(dead_rows):
        assert rngs[i].bit_generator.state == frozen_rng[j]


@given(seed=st.integers(0, 10**6), k=st.integers(2, 5),
       sa_moves=st.integers(1, 40))
@settings(max_examples=15)
def test_done_ladders_freeze_and_skip_rng(seed, k, sa_moves):
    """A ladder already marked done behaves exactly like a dead one (no
    proposals, no rng draws) and done flags are sticky — the kernel never
    clears one."""
    pc, rngs, done = _ladders(seed, k)
    done[0] = True
    state0 = pc.node[0].copy()
    rng0 = copy.deepcopy(rngs[0].bit_generator.state)
    accepted = run_temperature(pc, rngs, np.ones(k, dtype=bool), done,
                               np.full(k, 0.5), sa_moves, np.full(k, 1e-2))
    assert accepted[0] == 0
    np.testing.assert_array_equal(pc.node[0], state0)
    assert rngs[0].bit_generator.state == rng0
    assert done[0]                       # sticky


@given(seed=st.integers(0, 10**6), k=st.integers(1, 4),
       sa_moves=st.integers(1, 40), temp=st.floats(1e-3, 4.0))
@settings(max_examples=15)
def test_rng_replay_determinism(seed, k, sa_moves, temp):
    """Deep-copying (pc, rngs, done) and replaying the call reproduces the
    run bit for bit — accepted counts, assignments, loads, done flags."""
    pc, rngs, done = _ladders(seed, k)
    pc2 = copy.deepcopy(pc)
    rngs2 = copy.deepcopy(rngs)
    done2 = done.copy()
    alive = np.ones(k, dtype=bool)
    temps, eps = np.full(k, temp), np.full(k, 1e-2)
    a1 = run_temperature(pc, rngs, alive, done, temps, sa_moves, eps)
    a2 = run_temperature(pc2, rngs2, alive, done2, temps, sa_moves, eps)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(pc.node, pc2.node)
    np.testing.assert_array_equal(done, done2)
    np.testing.assert_array_equal(pc.j_max(), pc2.j_max())
    np.testing.assert_array_equal(pc.j_sum(), pc2.j_sum())


@given(seed=st.integers(0, 10**6), k=st.integers(2, 5),
       sa_moves=st.integers(5, 40))
@settings(max_examples=10)
def test_batch_composition_independence(seed, k, sa_moves):
    """Ladder i advanced inside a K-batch equals ladder i advanced alone
    with the same seed — the kernel's per-ladder rng/state isolation (what
    the sharded engine's shard-count invariance is built on)."""
    pc, rngs, done = _ladders(seed, k)
    solo_states = []
    for i in range(k):
        pc1, _, done1 = _ladders(seed, 1)
        rngs1 = [np.random.default_rng(seed + 100 + i)]
        run_temperature(pc1, rngs1, np.ones(1, dtype=bool), done1,
                        np.full(1, 1.0), sa_moves, np.full(1, 1e-2))
        solo_states.append(pc1.node[0].copy())
    run_temperature(pc, rngs, np.ones(k, dtype=bool), done,
                    np.full(k, 1.0), sa_moves, np.full(k, 1e-2))
    for i in range(k):
        np.testing.assert_array_equal(pc.node[i], solo_states[i],
                                      err_msg=f"ladder {i} diverged")


@given(seed=st.integers(0, 10**6), k=st.integers(1, 5),
       sa_moves=st.integers(1, 40), budget=st.integers(0, 30))
@settings(max_examples=15)
def test_budget_cap_overshoot_bounded_by_one_batch(seed, k, sa_moves,
                                                   budget):
    """The budget is checked before each batched move (one accept per
    participating ladder), so the total overshoots by strictly less than
    one batch: ``sum(accepted) < budget + K``; budget=0 accepts nothing."""
    pc, rngs, done = _ladders(seed, k)
    accepted = run_temperature(pc, rngs, np.ones(k, dtype=bool), done,
                               np.full(k, 2.0), sa_moves, np.full(k, 1e-2),
                               budget=budget)
    assert accepted.sum() < budget + k
    if budget == 0:
        assert accepted.sum() == 0
