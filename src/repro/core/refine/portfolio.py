"""Multi-start annealing portfolio (seed-parallel plateau escape).

A single :class:`~repro.core.refine.ScheduledRefiner` ladder stalls on
J_max plateaus its one random walk cannot hop; general mapping tools
(Schulz & Träff 2017, "Better Process Mapping and Sparse Quadratic
Assignment"; Faraj et al. 2020, "High-Quality Hierarchical Process
Mapping") escape those with a *portfolio* of independent starts.
:class:`PortfolioRefiner` runs K such ladders as **one batched
computation**:

* the deterministic alternating j_sum/j_max rounds are seed-independent,
  so they run **once** and every ladder starts from their output;
* the K simulated-annealing ladders advance in lock-step — each ladder
  draws its proposal from its own :class:`numpy.random.Generator`, and all
  K (state, swap) deltas are scored by a single
  :meth:`~repro.core.cost_delta.PortfolioCost.swap_deltas` call per move
  (stacked ``(K, p)`` assignments, shared neighbour table, chunked load
  matrices) instead of K interpreted ladder loops;
* ladders whose best-seen bottleneck drifts beyond ``kill_factor`` times
  the portfolio leader's are killed at temperature boundaries
  (early-kill of dominated starts) — ladder 0 is never killed;
* surviving ladder states are deduplicated and polished with the
  schedule's phase objectives, and the lexicographically best
  ``(J_max, J_sum)`` over *everything seen* (input included) is returned.

Because ladder 0 uses ``default_rng(seeds[0])`` and the batched engine
reproduces the scalar ladder's draw order and float arithmetic (exactly,
for unit/dyadic weights), the portfolio's candidate set is a superset of
``ScheduledRefiner(anneal=True, seed=seeds[0])``'s — so ``portfolio:`` is
lexicographically never worse than ``annealed:`` on the same seed
(pinned by ``tests/test_portfolio.py``).

Usage::

    from repro.core import PortfolioRefiner, get_mapper
    res = PortfolioRefiner(k=8).refine(grid, stencil, a, num_nodes=N)
    m = get_mapper("portfolio:hyperplane")        # default K=8
    m = get_mapper("portfolio[k=4,seed=7]:kdtree")  # bracket options
"""
from __future__ import annotations

import math
import time
import warnings
from typing import Optional, Sequence, Tuple

import numpy as np

from ... import obs
from ..cost_delta import IncrementalCost, PortfolioCost
from ..grid import CartGrid
from ..stencil import Stencil
from .engine import phase_stats
from .schedule import ScheduledRefiner
from .swap import RefineResult

__all__ = ["PortfolioRefiner", "run_temperature"]


def run_temperature(pc: PortfolioCost, rngs, alive: np.ndarray,
                    done: np.ndarray, temps: np.ndarray, sa_moves: int,
                    eps: np.ndarray,
                    budget: Optional[int] = None,
                    allowed: Optional[np.ndarray] = None) -> np.ndarray:
    """Advance every alive, not-yet-done ladder of ``pc`` through one
    temperature of ``sa_moves`` Metropolis proposals, batched per move.

    This is THE ladder kernel: :class:`PortfolioRefiner` runs it once per
    temperature over all K ladders, and the sharded engine
    (:class:`~repro.core.refine.sharded.ShardedPortfolioRefiner`) runs it
    per seed block inside worker processes — both replicate
    :meth:`ScheduledRefiner._sa_ladder` move for move per ladder (same rng
    draw order: position, partner, then acceptance only for uphill moves;
    same boundary snapshot per temperature; same early-out rules), so a
    ladder's trajectory depends only on its own rng and start state, never
    on which batch it ran in.

    ``temps`` is the per-ladder *absolute* temperature (schedule scale and
    any adaptive retune multiplier already folded in); ``eps`` the
    per-ladder J_sum tie-break scale.  ``pc``, ``rngs`` and ``done`` are
    mutated in place; ``budget`` caps the call's accepted swaps (checked
    before each batched move, exactly as the single-process engine does).
    ``allowed`` (a (p,) bool mask, default all-True) restricts proposals to
    a position subset — both endpoints of every swap are drawn from
    ``boundary & allowed``, so positions outside it are *pinned* and can
    never move (the repair path's churn-untouched nodes).  ``None``
    preserves the historical draw sequence bit for bit.
    Returns the per-ladder accepted-swap counts.
    """
    K = pc.n_starts
    masks = pc.boundary_masks()
    if allowed is not None:
        masks = masks & np.asarray(allowed, dtype=bool)[None, :]
    boundaries = {i: np.nonzero(masks[i])[0]
                  for i in range(K) if alive[i] and not done[i]}
    stopped = set()         # no cross-node partner this temperature
    accepted = np.zeros(K, dtype=np.int64)
    total = 0
    for _ in range(sa_moves):
        if budget is not None and total >= budget:
            break
        rows, Ps, Qs = [], [], []
        for i, b in boundaries.items():
            if done[i] or i in stopped:
                continue
            if b.size < 2:
                done[i] = True
                continue
            p = int(b[rngs[i].integers(b.size)])
            partners = b[pc.node[i, b] != pc.node[i, p]]
            if partners.size == 0:
                stopped.add(i)
                continue
            q = int(partners[rngs[i].integers(partners.size)])
            rows.append(i)
            Ps.append(p)
            Qs.append(q)
        if not rows:
            break           # every ladder done/stopped this temperature
        rows_a = np.asarray(rows, dtype=np.int64)
        d = pc.swap_deltas(rows_a, Ps, Qs, with_loads=True,
                           with_counts=True)
        d_e = (d.new_j_max - pc.j_max()[rows_a]
               + d.d_j_sum * eps[rows_a])
        acc = [idx for idx, i in enumerate(rows)
               if (d_e[idx] <= 0.0
                   or rngs[i].random() < math.exp(-float(d_e[idx])
                                                  / float(temps[i])))]
        if acc:
            pc.commit(d, acc)
            total += len(acc)
            for idx in acc:
                accepted[rows[idx]] += 1
    return accepted


class PortfolioRefiner:
    """K-start batched annealing on top of the deterministic schedule.

    Args:
      k: number of independent annealing starts (ignored when ``seeds`` is
        given explicitly).
      seed: base rng seed; start i uses ``default_rng(seed + i)``, so
        ``seed`` alone pins the whole portfolio and start 0 matches
        ``ScheduledRefiner(anneal=True, seed=seed)``.
      seeds: explicit per-start seeds (overrides ``k``/``seed``).
      kill_factor: a start (other than start 0) is killed at a temperature
        boundary when its best-seen J_max exceeds ``kill_factor`` times the
        portfolio-wide best-seen J_max; ``None`` disables early-kill.
      polish_top: how many surviving ladders get the full post-ladder
        polish phases (start 0 always does; the rest are ranked by their
        exact ladder-end ``(J_max, J_sum)``).  Unpolished survivors still
        contribute their raw states as candidates.  ``None`` polishes every
        survivor — thorough but the polish stage then scales with K, which
        is what the default bounds.
      max_swaps: total accepted-swap budget across the shared prefix, all
        ladders, and the polish phases (None = unlimited, the default and
        bit-identical path; the ``portfolio <= annealed`` dominance
        guarantee is only stated for the unbudgeted engine).  Per-stage
        plan budgets (:class:`~repro.core.refine.stage.RefineStage`)
        thread into this.
      Remaining keyword arguments configure the underlying schedule —
      identical names and defaults as :class:`ScheduledRefiner`
      (``objectives``, ``rounds``, ``policy``, ``max_passes``, ``weighted``
      — ``"auto"`` keys byte-weighted scoring off the stencil — ``tol``,
      ``max_partners``, ``temperatures``, ``sa_moves``).
    """

    def __init__(self, k: int = 8, seed: int = 0,
                 seeds: Optional[Sequence[int]] = None,
                 kill_factor: Optional[float] = 1.5,
                 polish_top: Optional[int] = 3,
                 objectives: Sequence[str] = ("j_sum", "j_max"),
                 rounds: int = 4, policy: str = "first", max_passes: int = 8,
                 weighted="auto", tol: float = 1e-12, max_partners: int = 32,
                 temperatures: Sequence[float] = (2.0, 1.0, 0.5, 0.25),
                 sa_moves: int = 200, max_swaps: Optional[int] = None):
        if seeds is not None:
            raw = tuple(int(s) for s in seeds)
            # duplicate seeds replay identical trajectories — ladders burnt
            # for zero extra candidates.  Dedupe order-preserved (ladder 0
            # keeps its dominance role) and keep cache keys honest: config()
            # reflects the deduped tuple, never the raw spelling.
            seeds = tuple(dict.fromkeys(raw))
            if len(seeds) != len(raw):
                warnings.warn(
                    f"duplicate portfolio seeds {raw} collapsed to {seeds}: "
                    "identical seeds replay identical annealing trajectories",
                    UserWarning, stacklevel=2)
        else:
            seeds = tuple(int(seed) + i for i in range(int(k)))
        if not seeds:
            raise ValueError("portfolio needs at least one start")
        if kill_factor is not None and kill_factor < 1.0:
            raise ValueError("kill_factor must be >= 1.0 (or None)")
        if polish_top is not None and polish_top < 1:
            raise ValueError("polish_top must be >= 1 (or None)")
        self.seeds = seeds
        self.k = len(seeds)
        self.kill_factor = None if kill_factor is None else float(kill_factor)
        self.polish_top = None if polish_top is None else int(polish_top)
        if max_swaps is not None and int(max_swaps) < 0:
            raise ValueError("max_swaps must be >= 0 (or None)")
        self.max_swaps = None if max_swaps is None else int(max_swaps)
        # the shared schedule: its deterministic rounds are the common
        # prefix, its polish phases close each ladder, and its SA
        # parameters define the ladders themselves.
        self.schedule = ScheduledRefiner(
            objectives=objectives, rounds=rounds, policy=policy,
            max_passes=max_passes, weighted=weighted, tol=tol,
            max_partners=max_partners, anneal=True,
            temperatures=temperatures, sa_moves=sa_moves, seed=seeds[0])

    def as_stage(self, budget: Optional[int] = None):
        """Uniform :class:`~repro.core.refine.stage.RefineStage` adapter
        (``budget`` caps this stage's accepted swaps)."""
        from .stage import RefineStage
        return RefineStage(self, budget=budget, prefix="portfolio")

    def config(self) -> dict:
        """Full constructor configuration — the stage layer's canonical
        cache identity for hand-built refiners.  ``seeds`` subsumes
        ``k``/``seed``; the shared schedule's ``anneal``/``seed`` are
        implied."""
        cfg = {k: v for k, v in self.schedule.config().items()
               if k not in ("anneal", "seed", "max_swaps")}
        cfg.update({"seeds": self.seeds, "kill_factor": self.kill_factor,
                    "polish_top": self.polish_top,
                    "max_swaps": self.max_swaps})
        return cfg

    # -- batched SA ladders -------------------------------------------------
    def _batched_ladders(self, grid: CartGrid, stencil: Stencil,
                         start: np.ndarray, num_nodes: Optional[int],
                         budget: Optional[int] = None,
                         allowed: Optional[np.ndarray] = None) \
            -> Tuple[PortfolioCost, np.ndarray, int, int]:
        """Advance K ladders from ``start`` in lock-step.  Returns the
        portfolio state, the per-ladder alive mask (False = early-killed),
        total accepted swaps, and the count of killed ladders.

        Per-ladder control flow replicates
        :meth:`ScheduledRefiner._sa_ladder` move for move (same rng draw
        order: position, partner, then acceptance only for uphill moves;
        same per-temperature boundary snapshot; same early-out rules), so
        ladder i's trajectory equals a scalar ladder seeded ``seeds[i]``.
        Only the delta/energy arithmetic is batched across ladders.
        """
        from .engine import BoundaryController, SerialLadderEngine
        sched = self.schedule
        K = self.k
        eng = SerialLadderEngine(grid, stencil, start, self.seeds,
                                 num_nodes=num_nodes, weighted=sched.weighted,
                                 allowed=allowed)
        pc = eng.pc
        t_scale = float(np.mean(pc.weights))
        j_sum0 = pc.j_sum()
        eps = 1.0 / (1.0 + np.abs(j_sum0))          # (K,) per-ladder
        ctrl = BoundaryController(
            k=K, kill_factor=self.kill_factor,
            start_keys=np.stack([pc.j_max(), j_sum0], axis=1))
        accepted = 0
        for T0 in sched.temperatures:
            if budget is not None and accepted >= budget:
                break               # skip leftover temperatures' setup too
            T = max(T0 * t_scale, 1e-12)
            rep = eng.run_temperature(
                np.full(K, T), sched.sa_moves, ctrl.alive, eps,
                budget=None if budget is None else budget - accepted)
            accepted += int(rep.accepted.sum())
            # temperature boundary: exact keys, early-kill of dominated runs
            ctrl.update_best(np.stack([rep.j_max, rep.j_sum], axis=1))
            ctrl.kill()
        return pc, ctrl.alive, accepted, ctrl.killed

    # -- survivor selection + polish (shared with the sharded engine) -------
    def _polish_survivors(self, grid: CartGrid, stencil: Stencil,
                          num_nodes: Optional[int], consider,
                          nodes: np.ndarray, lad_j_max: np.ndarray,
                          lad_j_sum: np.ndarray, alive: np.ndarray,
                          swaps: int, passes: int, scorer=None):
        """Feed every surviving raw ladder state to ``consider`` (its exact
        key is already on hand, so it is a candidate for free), then run the
        full polish phases on the most promising survivors: start 0 always
        (the dominance guarantee vs the single annealed run), then the best
        survivors by ladder-end key, deduplicating identical end states.
        ``nodes`` is the (K, p) ladder-end assignment stack; ``scorer``
        goes to the polish phases (only the device engine passes one).
        Returns the updated ``(swaps, passes, polish_order)``."""
        sched = self.schedule
        K = nodes.shape[0]
        for i in range(K):
            if alive[i]:
                consider(nodes[i].copy(),
                         (float(lad_j_max[i]), float(lad_j_sum[i])))
        ranked = sorted((i for i in range(K) if alive[i]),
                        key=lambda i: (lad_j_max[i], lad_j_sum[i], i))
        budget = len(ranked) if self.polish_top is None else self.polish_top
        seen = set()
        polish_order = []
        for i in [0] + ranked:
            if not alive[i] or len(polish_order) >= budget:
                continue
            key = nodes[i].tobytes()
            if key not in seen:
                seen.add(key)
                polish_order.append(i)
        for i in polish_order:
            cap = None if self.max_swaps is None \
                else max(0, self.max_swaps - swaps)
            _, s, p = sched.polish(grid, stencil, nodes[i].copy(), num_nodes,
                                   consider, max_swaps=cap, scorer=scorer)
            swaps += s
            passes += p
        return swaps, passes, polish_order

    # -- driver -------------------------------------------------------------
    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None,
               pinned: Optional[np.ndarray] = None) -> RefineResult:
        """Refine ``node_of_pos``.  ``pinned`` (a (p,) bool mask) freezes a
        position subset: the deterministic rounds and polish phases — which
        have no notion of pinning — are skipped, and the SA ladders draw
        both swap endpoints from unpinned positions only, so the result is
        guaranteed to agree with the input everywhere ``pinned`` is True
        (the repair path's churn-untouched nodes).  ``pinned=None`` is the
        historical engine, bit for bit."""
        t0 = time.perf_counter()
        sched = self.schedule
        if pinned is not None:
            pinned = np.asarray(pinned, dtype=bool).reshape(-1)
            if pinned.shape[0] != grid.size:
                raise ValueError(f"pinned mask has {pinned.shape[0]} "
                                 f"entries for a {grid.size}-position grid")
        with obs.recording() as rec:
            # 1. start key and the shared deterministic prefix
            # (seed-independent, run once; pin-oblivious, so the pinned
            # path skips it)
            with obs.span("rounds"):
                cur = np.asarray(node_of_pos, dtype=np.int64).copy()
                initial = IncrementalCost(grid, stencil, cur,
                                          num_nodes=num_nodes,
                                          weighted=sched.weighted).cost()
                best, best_key = cur.copy(), (initial.j_max, initial.j_sum)

                def consider(candidate: np.ndarray,
                             key: Tuple[float, float]):
                    nonlocal best, best_key
                    if key < best_key:
                        best, best_key = candidate.copy(), key

                if pinned is None:
                    cur, swaps, passes = sched.run_rounds(
                        grid, stencil, cur, num_nodes, consider,
                        max_swaps=self.max_swaps)
                else:
                    swaps = passes = 0

            # 2. K annealing ladders, batched (budget caps accepted moves
            # at move granularity — up to K acceptances land per batched
            # move)
            with obs.span("ladders"):
                budget = None if self.max_swaps is None \
                    else self.max_swaps - swaps
                pc, alive, sa_accepted, killed = self._batched_ladders(
                    grid, stencil, cur, num_nodes, budget=budget,
                    allowed=None if pinned is None else ~pinned)
                swaps += sa_accepted

            # 3. raw survivors are free candidates; the best of them get
            # the full polish phases (shared with the sharded engine's
            # merge step) — pin-oblivious, so the pinned path takes raw
            # survivors only
            with obs.span("survivors"):
                lad_j_max, lad_j_sum = pc.j_max(), pc.j_sum()
                if pinned is None:
                    with obs.span("polish"):
                        swaps, passes, polish_order = \
                            self._polish_survivors(
                                grid, stencil, num_nodes, consider, pc.node,
                                lad_j_max, lad_j_sum, alive, swaps, passes)
                else:
                    K = pc.n_starts
                    for i in range(K):
                        if alive[i]:
                            consider(pc.node[i].copy(),
                                     (float(lad_j_max[i]),
                                      float(lad_j_sum[i])))
                    polish_order = []
                    assert np.array_equal(best[pinned],
                                          node_of_pos[pinned]), \
                        "pinned positions moved (ladder mask violated)"

                with obs.span("final"):
                    final = IncrementalCost(grid, stencil, best,
                                            num_nodes=num_nodes,
                                            weighted=sched.weighted).cost()
        wall = time.perf_counter() - t0
        stats = {
            "k": self.k,
            "seeds": self.seeds,
            "pinned": 0 if pinned is None else int(pinned.sum()),
            "sa_accepted": sa_accepted,
            "killed": killed,
            "polished": len(polish_order),
            "ladder_keys": [(float(j), float(s)) for j, s in
                            zip(pc.j_max(), pc.j_sum())],
            **phase_stats(rec, wall),
        }
        return RefineResult(assignment=best, initial=initial, final=final,
                            swaps=swaps, passes=passes, wall_time_s=wall,
                            stats=stats)
