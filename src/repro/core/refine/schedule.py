"""Alternating-objective refinement schedules (J_max-aware local search).

A single-objective :class:`~repro.core.refine.SwapRefiner` run stalls at the
first plateau of its own metric: a J_sum pass leaves bottleneck imbalance on
the table, and a J_max pass stops as soon as no single swap lowers the
bottleneck — exactly the weakness Schulz & Träff (Better Process Mapping and
Sparse Quadratic Assignment, 2017) identify for bottleneck metrics.
:class:`ScheduledRefiner` runs the two objectives in alternating phases so
each unlocks moves for the other, and (``anneal=True``) follows with a
simulated-annealing temperature ladder that accepts controlled uphill swaps
to hop J_max plateaus, re-polishing after every temperature.

The result is selected lexicographically by ``(J_max, J_sum)`` over every
phase boundary *including the input*, so a schedule can never return a
mapping that is lexicographically worse than what it was given — and since
its first phase is exactly the default ``refined:<base>`` pass, the
``refined2:``/``annealed:`` variants are J_max-no-worse than ``refined:``
by construction (for matching phase parameters).

Usage::

    from repro.core import ScheduledRefiner, get_mapper
    res = ScheduledRefiner(anneal=True).refine(grid, stencil, a, num_nodes=N)
    m = get_mapper("annealed:hyperplane")      # same engine, mapper-shaped
"""
from __future__ import annotations

import math
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from ..cost_delta import IncrementalCost
from ..grid import CartGrid
from ..stencil import Stencil
from .swap import RefineResult, SwapRefiner

__all__ = ["ScheduledRefiner"]


class ScheduledRefiner:
    """Alternate j_sum/j_max :class:`SwapRefiner` phases, optionally followed
    by a simulated-annealing ladder; returns the lexicographically best
    ``(J_max, J_sum)`` assignment seen.

    Args:
      objectives: phase order within one round (each entry is a SwapRefiner
        objective).  The default runs J_sum first — matching the default
        ``refined:<base>`` pass exactly — then relieves the bottleneck.
      rounds: maximum schedule rounds; a round with zero accepted swaps
        stops early.
      policy / max_passes / weighted / tol / max_partners:
        forwarded to each phase's :class:`SwapRefiner`.
      anneal: append the SA ladder after the deterministic schedule.
      temperatures: SA ladder (descending), in units of one unit-weight
        J_max step; scaled by the stencil's mean weight when ``weighted``.
      sa_moves: proposed swaps per temperature.
      seed: SA rng seed (the whole refiner stays deterministic).
      max_swaps: total accepted-swap budget across every phase and the SA
        ladder (None = unlimited — the default, bit-identical to the
        budget-free engine).  This is what per-stage plan budgets
        (:class:`~repro.core.refine.stage.RefineStage`) thread into.
    """

    def __init__(self, objectives: Sequence[str] = ("j_sum", "j_max"),
                 rounds: int = 4, policy: str = "first", max_passes: int = 8,
                 weighted="auto", tol: float = 1e-12,
                 max_partners: int = 32, anneal: bool = False,
                 temperatures: Sequence[float] = (2.0, 1.0, 0.5, 0.25),
                 sa_moves: int = 200, seed: int = 0,
                 max_swaps: Optional[int] = None):
        if not objectives:
            raise ValueError("objectives must be non-empty")
        if rounds < 0:
            raise ValueError("rounds must be >= 0 (0 = skip the "
                             "deterministic rounds, ladder/polish only)")
        # validate eagerly (same errors as SwapRefiner would raise later)
        for obj in objectives:
            SwapRefiner(objective=obj, policy=policy, max_passes=max_passes)
        self.objectives = tuple(objectives)
        self.rounds = int(rounds)
        self.policy = policy
        self.max_passes = int(max_passes)
        self.weighted = weighted
        self.tol = float(tol)
        self.max_partners = int(max_partners)
        self.anneal = bool(anneal)
        self.temperatures = tuple(float(t) for t in temperatures)
        self.sa_moves = int(sa_moves)
        self.seed = int(seed)
        if max_swaps is not None and int(max_swaps) < 0:
            raise ValueError("max_swaps must be >= 0 (or None)")
        self.max_swaps = None if max_swaps is None else int(max_swaps)

    def as_stage(self, budget: Optional[int] = None):
        """Uniform :class:`~repro.core.refine.stage.RefineStage` adapter
        (``budget`` caps this stage's accepted swaps)."""
        from .stage import RefineStage
        return RefineStage(self, budget=budget,
                           prefix="annealed" if self.anneal else "refined2")

    def config(self) -> dict:
        """Full constructor configuration — the stage layer's canonical
        cache identity for hand-built refiners."""
        return {"objectives": self.objectives, "rounds": self.rounds,
                "policy": self.policy, "max_passes": self.max_passes,
                "weighted": self.weighted, "tol": self.tol,
                "max_partners": self.max_partners, "anneal": self.anneal,
                "temperatures": self.temperatures,
                "sa_moves": self.sa_moves, "seed": self.seed,
                "max_swaps": self.max_swaps}

    # -- phases -------------------------------------------------------------
    def _phase(self, objective: str, max_swaps: Optional[int] = None,
               scorer=None) -> SwapRefiner:
        return SwapRefiner(objective=objective, policy=self.policy,
                           max_passes=self.max_passes, weighted=self.weighted,
                           tol=self.tol, max_partners=self.max_partners,
                           max_swaps=max_swaps, scorer=scorer)

    def _sa_ladder(self, grid: CartGrid, stencil: Stencil,
                   assignment: np.ndarray, num_nodes: Optional[int],
                   rng: np.random.Generator,
                   budget: Optional[int] = None) -> Tuple[np.ndarray, int]:
        """One descending temperature ladder of Metropolis swap moves.
        Energy is J_max plus a J_sum tie-break term scaled below one
        bottleneck unit, so uphill acceptance is governed by the bottleneck.
        Proposals are sampled from a boundary snapshot refreshed once per
        temperature — a swap only perturbs the boundary locally, and any
        staleness merely shifts the proposal distribution, which the
        post-ladder polish phases absorb."""
        ic = IncrementalCost(grid, stencil, assignment, num_nodes=num_nodes,
                             weighted=self.weighted)
        t_scale = float(np.mean(ic.weights))
        eps = 1.0 / (1.0 + abs(ic.j_sum))
        accepted = 0
        for T in self.temperatures:
            T = max(T * t_scale, 1e-12)
            boundary = ic.boundary_positions()
            for _ in range(self.sa_moves):
                if budget is not None and accepted >= budget:
                    return ic.node_of_pos.copy(), accepted
                if boundary.size < 2:
                    return ic.node_of_pos.copy(), accepted
                p = int(boundary[rng.integers(boundary.size)])
                partners = boundary[ic.node_of_pos[boundary]
                                    != ic.node_of_pos[p]]
                if partners.size == 0:
                    break
                q = int(partners[rng.integers(partners.size)])
                delta = ic.delta_swap(p, q)
                d_e = (ic.peek_j_max(delta) - ic.j_max
                       + delta.d_j_sum * eps)
                if d_e <= 0.0 or rng.random() < math.exp(-d_e / T):
                    ic.apply_swap(p, q)
                    accepted += 1
        return ic.node_of_pos.copy(), accepted

    # -- schedule building blocks (shared with PortfolioRefiner) ------------
    def run_rounds(self, grid: CartGrid, stencil: Stencil, cur: np.ndarray,
                   num_nodes: Optional[int], consider,
                   max_swaps: Optional[int] = None, scorer=None) \
            -> Tuple[np.ndarray, int, int]:
        """The deterministic alternating-objective rounds: returns the final
        phase-chain state (the SA ladder's start point — *not* the
        lexicographic best) plus accepted-swap/pass counts.  ``consider`` is
        called with every phase result's ``(assignment, (j_max, j_sum))``;
        ``max_swaps`` caps total accepted swaps across all phases;
        ``scorer`` goes to every phase's :class:`SwapRefiner`."""
        swaps = passes = 0
        for _ in range(self.rounds):
            round_swaps = 0
            for obj in self.objectives:
                cap = None if max_swaps is None else max_swaps - swaps
                res = self._phase(obj, cap, scorer).refine(
                    grid, stencil, cur, num_nodes=num_nodes)
                cur = res.assignment
                swaps += res.swaps
                passes += res.passes
                round_swaps += res.swaps
                consider(cur, (res.final.j_max, res.final.j_sum))
                if max_swaps is not None and swaps >= max_swaps:
                    return cur, swaps, passes
            if round_swaps == 0:
                break
        return cur, swaps, passes

    def polish(self, grid: CartGrid, stencil: Stencil, cur: np.ndarray,
               num_nodes: Optional[int], consider,
               max_swaps: Optional[int] = None, scorer=None) \
            -> Tuple[np.ndarray, int, int]:
        """One pass of the phase objectives over a (perturbed) state — what
        the annealed schedule runs after its SA ladder."""
        swaps = passes = 0
        for obj in self.objectives:
            cap = None if max_swaps is None else max_swaps - swaps
            res = self._phase(obj, cap, scorer).refine(grid, stencil, cur,
                                                       num_nodes=num_nodes)
            cur = res.assignment
            swaps += res.swaps
            passes += res.passes
            consider(cur, (res.final.j_max, res.final.j_sum))
            if max_swaps is not None and swaps >= max_swaps:
                break
        return cur, swaps, passes

    # -- driver -------------------------------------------------------------
    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None) -> RefineResult:
        t0 = time.perf_counter()
        cur = np.asarray(node_of_pos, dtype=np.int64).copy()
        initial = IncrementalCost(grid, stencil, cur, num_nodes=num_nodes,
                                  weighted=self.weighted).cost()
        best, best_key = cur.copy(), (initial.j_max, initial.j_sum)

        def consider(candidate: np.ndarray, key: Tuple[float, float]):
            nonlocal best, best_key
            if key < best_key:
                best, best_key = candidate.copy(), key

        cur, swaps, passes = self.run_rounds(grid, stencil, cur, num_nodes,
                                             consider,
                                             max_swaps=self.max_swaps)

        if self.anneal and (self.max_swaps is None
                            or swaps < self.max_swaps):
            rng = np.random.default_rng(self.seed)
            budget = None if self.max_swaps is None \
                else self.max_swaps - swaps
            perturbed, accepted = self._sa_ladder(grid, stencil, cur,
                                                  num_nodes, rng,
                                                  budget=budget)
            swaps += accepted
            budget = None if self.max_swaps is None \
                else self.max_swaps - swaps
            cur, s, p = self.polish(grid, stencil, perturbed, num_nodes,
                                    consider, max_swaps=budget)
            swaps += s
            passes += p

        final = IncrementalCost(grid, stencil, best, num_nodes=num_nodes,
                                weighted=self.weighted).cost()
        return RefineResult(assignment=best, initial=initial, final=final,
                            swaps=swaps, passes=passes,
                            wall_time_s=time.perf_counter() - t0)
