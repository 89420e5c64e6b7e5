"""Pairwise-swap local search over a node-of-position assignment.

Swaps exchange the owning nodes of two grid positions, so the per-node
cardinalities — the scheduler's allocation — are preserved by construction;
only improving swaps are accepted, so the objective is monotonically
non-increasing.  Candidate generation is boundary-driven: only positions
with a crossing incident edge can gain from a swap with one of their
stencil neighbours on a different node, which keeps a pass at
O(|boundary| * k^2) delta evaluations instead of O(p^2).

Each pass builds the whole candidate frontier as ``(P, Q)`` index arrays
and scores every pair in one
:meth:`~repro.core.cost_delta.IncrementalCost.batch_swap_deltas` call
(whose rows equal the scalar
:meth:`~repro.core.cost_delta.IncrementalCost.delta_swap` bit for bit).
A steepest pass is then a single ``argmax`` over the gain array; a
first-improvement pass applies a maximal set of spatially-disjoint
improving swaps per batch (positions whose neighbourhood an accepted swap
touched are masked out, so every applied delta is still exact).

Each pass records the spans ``swap.score`` (frontier and gains), with
``swap.frontier`` (building the frontier) inside it, and ``swap.apply``,
and the counters ``swap.passes``, ``swap.pairs`` (pairs scored) and
``swap.applied`` (see :mod:`repro.obs`).  Given a device scorer
(:mod:`repro.core.refine.device_swap`, which only the device portfolio
passes), the pairs are scored on the accelerator instead, with the same
integer values, and each pass adds its pairs to ``swap.device_pairs`` and
the pair slots it dispatched (whole chunks) to ``swap.device_slots`` as
well.

Usage::

    refiner = SwapRefiner(objective="j_max", policy="steepest")
    res = refiner.refine(grid, stencil, node_of_pos, num_nodes=N)
    res.assignment, res.final.j_sum, res.final.j_max, res.wall_time_s
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from ... import obs
from ..cost import MappingCost
from ..cost_delta import LOAD_CHUNK_ELEMS, IncrementalCost
from ..grid import CartGrid
from ..stencil import Stencil

__all__ = ["SwapRefiner", "RefineResult", "refine_assignment"]

_OBJECTIVES = ("j_sum", "j_max")
_POLICIES = ("first", "steepest")

#: j_max batch scoring materializes (chunk, N) load matrices; this bounds
#: chunk * N so peak extra memory stays ~tens of MB regardless of frontier.
_LOAD_CHUNK_ELEMS = LOAD_CHUNK_ELEMS
#: soft cap on far (non-adjacent) candidate pairs per sweep: when the
#: frontier is huge (early refinement of a random-quality mapping) the
#: per-vertex partner cap is scaled down so one sweep stays bounded.
_MAX_FAR_PAIRS = 200_000


@dataclass
class RefineResult:
    """Outcome of one refinement run.  ``stats`` carries engine-specific
    extras (the portfolio engine reports per-ladder keys, kills, and stage
    wall-times there)."""

    assignment: np.ndarray       # (p,) refined node-of-position
    initial: MappingCost
    final: MappingCost
    swaps: int
    passes: int
    wall_time_s: float
    stats: Optional[dict] = None

    @property
    def improvement(self) -> float:
        return self.initial.j_sum - self.final.j_sum


class SwapRefiner:
    """Greedy boundary-vertex swap refinement.

    Args:
      objective: "j_sum" (total inter-node edges) or "j_max" (bottleneck
        node's outgoing edges, J_sum as tie-break).
      policy: "first" applies a maximal spatially-disjoint set of improving
        swaps per sweep; "steepest" scores the whole frontier each round
        and applies the single best swap.
      max_passes: full boundary sweeps before giving up.
      max_swaps: hard cap on accepted swaps (None = unlimited).
      weighted: score with the stencil's per-offset byte weights; the
        default ``"auto"`` uses them iff the stencil carries non-unit
        weights, so byte-weighted and unit-weight objectives share this one
        code path.
      tol: minimum improvement for a swap to count, in units of the mean
        offset weight (scaled at refine time, so the default guards float
        noise on byte-weighted stencils and stays exact-zero-equivalent for
        unit weights).
      max_partners: cap on non-adjacent swap partners considered per
        (boundary vertex, communicating node) pair (evenly subsampled,
        deterministic).  Partners are boundary vertices of the nodes p
        communicates with (KL/FM-style), which catches improving exchanges
        between cells that are not stencil neighbours of each other.
      scorer: internal; a :class:`~repro.core.refine.device_swap.DeviceSwapScorer`
        that scores the pairs on the accelerator (None: numpy).  It
        changes no result, so it is not part of ``config()``.
    """

    def __init__(self, objective: str = "j_sum", policy: str = "first",
                 max_passes: int = 8, max_swaps: Optional[int] = None,
                 weighted="auto", tol: float = 1e-12,
                 max_partners: int = 32, scorer=None):
        if objective not in _OBJECTIVES:
            raise ValueError(f"objective must be one of {_OBJECTIVES}")
        if policy not in _POLICIES:
            raise ValueError(f"policy must be one of {_POLICIES}")
        if max_passes <= 0:
            raise ValueError("max_passes must be positive")
        self.objective = objective
        self.policy = policy
        self.max_passes = int(max_passes)
        self.max_swaps = max_swaps
        self.weighted = weighted
        self.tol = float(tol)
        self.max_partners = int(max_partners)
        self.scorer = scorer

    def as_stage(self, budget: Optional[int] = None):
        """Uniform :class:`~repro.core.refine.stage.RefineStage` adapter
        (``budget`` caps this stage's accepted swaps)."""
        from .stage import RefineStage
        return RefineStage(self, budget=budget, prefix="refined")

    def config(self) -> dict:
        """Full constructor configuration — the stage layer's canonical
        cache identity for hand-built refiners."""
        return {"objective": self.objective, "policy": self.policy,
                "max_passes": self.max_passes, "max_swaps": self.max_swaps,
                "weighted": self.weighted, "tol": self.tol,
                "max_partners": self.max_partners}

    def _tol(self, ic: IncrementalCost) -> float:
        """Acceptance threshold in the objective's own units: byte-weighted
        deltas are ~mean-weight sized, so the raw tol would drown in float
        noise there; unit weights leave it bitwise unchanged."""
        return self.tol * float(np.mean(ic.weights))

    # -- driver -------------------------------------------------------------
    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None) -> RefineResult:
        t0 = time.perf_counter()
        ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=num_nodes,
                             weighted=self.weighted)
        initial = ic.cost()
        swaps = passes = 0
        budget = self.max_swaps if self.max_swaps is not None else np.inf
        while passes < self.max_passes and swaps < budget:
            passes += 1
            if self.policy == "steepest":
                improved, swaps = self._steepest_pass(ic, swaps, budget)
            else:
                improved, swaps = self._first_pass(ic, swaps, budget)
            if not improved:
                break
        return RefineResult(assignment=ic.node_of_pos.copy(), initial=initial,
                            final=ic.cost(), swaps=swaps, passes=passes,
                            wall_time_s=time.perf_counter() - t0)

    # -- one pass -----------------------------------------------------------
    def _frontier_pairs(self, ic: IncrementalCost) \
            -> Tuple[np.ndarray, np.ndarray]:
        """All candidate swap pairs as (P, Q) arrays, deduplicated with
        P < Q: every crossing stencil edge, plus for each boundary vertex
        up to ``max_partners`` boundary vertices of each node its crossing
        edges touch (evenly subsampled in boundary order)."""
        node, t, size = ic.node_of_pos, ic.table, ic.grid.size
        n_nodes = ic.n_nodes
        us, vs = [], []
        for j in range(ic.stencil.k):
            u = np.nonzero(t.out_valid[j] & (node != node[t.out_tgt[j]]))[0]
            us.append(u)
            vs.append(t.out_tgt[j][u])
        U = np.concatenate(us) if us else np.empty(0, dtype=np.int64)
        V = np.concatenate(vs) if vs else np.empty(0, dtype=np.int64)
        if U.size == 0:
            return (np.empty(0, dtype=np.int64),) * 2
        adj_codes = np.minimum(U, V) * size + np.maximum(U, V)
        # (boundary vertex, communicating node) pairs from both edge ends
        pt = np.unique(np.concatenate([U * n_nodes + node[V],
                                       V * n_nodes + node[U]]))
        p_of, tn_of = pt // n_nodes, pt % n_nodes
        boundary = np.nonzero(np.bincount(
            np.concatenate([U, V]), minlength=size))[0]
        order = np.argsort(node[boundary], kind="stable")
        members = boundary[order]                       # boundary, node-major
        cnt_node = np.bincount(node[boundary], minlength=n_nodes)
        starts = np.concatenate([[0], np.cumsum(cnt_node)[:-1]])
        cap = self.max_partners
        if p_of.size * cap > _MAX_FAR_PAIRS:
            cap = max(1, _MAX_FAR_PAIRS // p_of.size)
        cnt = cnt_node[tn_of]
        take = np.minimum(cnt, cap)
        rows = np.repeat(np.arange(p_of.size), take)
        seg_start = np.cumsum(take) - take
        within = np.arange(int(take.sum())) - np.repeat(seg_start, take)
        stride = cnt / np.maximum(take, 1)
        idx = starts[tn_of][rows] + (within * stride[rows]).astype(np.int64)
        Pf, Qf = p_of[rows], members[idx]
        keep = Pf != Qf
        far_codes = (np.minimum(Pf, Qf) * size + np.maximum(Pf, Qf))[keep]
        codes = np.unique(np.concatenate([adj_codes, far_codes]))
        return codes // size, codes % size

    def _pair_deltas(self, ic: IncrementalCost, P: np.ndarray,
                     Q: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Per-pair ``d_j_sum`` and, for j_max, ``new_j_max`` (float64),
        from the device scorer when there is one, else from numpy."""
        if self.scorer is not None:
            d_j_sum, new_j_max = self.scorer.score(ic, P, Q)
            return d_j_sum.astype(np.float64), new_j_max.astype(np.float64)
        if self.objective == "j_sum":
            return ic.batch_swap_deltas(P, Q).d_j_sum, None
        # j_max scoring needs (m, N) load matrices; chunk so peak memory is
        # bounded no matter how large the frontier is.
        m = P.size
        chunk = max(1, _LOAD_CHUNK_ELEMS // max(1, ic.n_nodes))
        d_j_sum = np.empty(m, dtype=np.float64)
        new_j_max = np.empty(m, dtype=np.float64)
        for s in range(0, m, chunk):
            e = min(s + chunk, m)
            bd = ic.batch_swap_deltas(P[s:e], Q[s:e], with_loads=True)
            d_j_sum[s:e], new_j_max[s:e] = bd.d_j_sum, bd.new_j_max
        return d_j_sum, new_j_max

    def _batch_gains(self, ic: IncrementalCost, P: np.ndarray,
                     Q: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Per-pair gain of the configured objective (positive = improving).
        For j_max also returns the strict-improvement mask (gains driven by
        a real bottleneck drop rather than the J_sum tie-break)."""
        d_j_sum, new_j_max = self._pair_deltas(ic, P, Q)
        if self.objective == "j_sum":
            return -d_j_sum, None
        primary = ic.j_max - new_j_max
        tie = np.where(d_j_sum < 0, -d_j_sum * 1e-9, 0.0)
        return np.where(primary != 0.0, primary, tie), primary > 0.0

    @staticmethod
    def _affected(ic: IncrementalCost, P: np.ndarray,
                  Q: np.ndarray) -> np.ndarray:
        """(m, N) bool: the nodes whose load each swap would change
        (first-improvement's disjointness guard under j_max), chunked like
        the scoring."""
        per_node = ic.per_node
        chunk = max(1, _LOAD_CHUNK_ELEMS // max(1, ic.n_nodes))
        return np.concatenate([
            ic.batch_swap_deltas(P[s:s + chunk], Q[s:s + chunk],
                                 with_loads=True).new_per_node
            != per_node[None, :] for s in range(0, P.size, chunk)])

    def _count_pairs(self, m: int) -> None:
        obs.count("swap.pairs", m)
        if self.scorer is not None:
            obs.count("swap.device_pairs", m)
            c = self.scorer.chunk
            obs.count("swap.device_slots", -(-m // c) * c)

    def _steepest_pass(self, ic: IncrementalCost, swaps: int,
                       budget: float) -> Tuple[bool, int]:
        """One whole-frontier batch, then apply the single best swap."""
        if swaps >= budget:
            return False, swaps
        obs.count("swap.passes", 1)
        with obs.span("swap.score"):
            with obs.span("swap.frontier"):
                P, Q = self._frontier_pairs(ic)
            if P.size:
                gains, _ = self._batch_gains(ic, P, Q)
                best = int(np.argmax(gains))
        self._count_pairs(P.size)
        if P.size == 0 or gains[best] <= self._tol(ic):
            return False, swaps
        with obs.span("swap.apply"):
            ic.apply_swap(int(P[best]), int(Q[best]))
        obs.count("swap.applied", 1)
        return True, swaps + 1

    def _first_pass(self, ic: IncrementalCost, swaps: int,
                    budget: float) -> Tuple[bool, int]:
        """One whole-frontier batch, then greedily apply every improving
        swap whose endpoints are spatially disjoint from earlier accepted
        swaps (and their stencil neighbourhoods), so each applied delta is
        still exact against the committed state.

        Under j_max two extra guards keep the pass lexicographically
        monotone: only same-kind swaps are combined per sweep (all strict
        bottleneck drops, or all J_sum tie-breaks — mixing the two can
        re-raise the bottleneck a strict swap just lowered while a
        tie-break swap raises J_sum), and accepted swaps must touch
        disjoint *node* load sets (two distant swaps may each keep the max
        at M while jointly pushing a shared node past it).
        """
        obs.count("swap.passes", 1)
        with obs.span("swap.score"):
            with obs.span("swap.frontier"):
                P, Q = self._frontier_pairs(ic)
            if P.size:
                gains, strict = self._batch_gains(ic, P, Q)
                improving = gains > self._tol(ic)
                if strict is not None and bool(np.any(improving & strict)):
                    improving &= strict
                cand = np.nonzero(improving)[0]
                # row r of `affected` belongs to pair cand[r]
                affected = (self._affected(ic, P[cand], Q[cand])
                            if strict is not None and cand.size else None)
        self._count_pairs(P.size)
        if P.size == 0 or cand.size == 0:
            return False, swaps
        start = swaps
        with obs.span("swap.apply"):
            dirty = np.zeros(ic.grid.size, dtype=bool)
            dirty_nodes = np.zeros(ic.n_nodes, dtype=bool)
            for r, i in enumerate(cand):
                if swaps >= budget:
                    break
                p, q = int(P[i]), int(Q[i])
                if dirty[p] or dirty[q]:
                    continue
                if affected is not None and bool(np.any(dirty_nodes
                                                        & affected[r])):
                    continue
                ic.apply_swap(p, q)
                swaps += 1
                dirty[p] = dirty[q] = True
                dirty[ic.neighbors_of(p)] = True
                dirty[ic.neighbors_of(q)] = True
                if affected is not None:
                    dirty_nodes |= affected[r]
        obs.count("swap.applied", swaps - start)
        return swaps > start, swaps


def refine_assignment(grid: CartGrid, stencil: Stencil,
                      node_of_pos: np.ndarray,
                      num_nodes: Optional[int] = None,
                      **refiner_kwargs) -> RefineResult:
    """One-call convenience: refine an assignment with default settings."""
    return SwapRefiner(**refiner_kwargs).refine(grid, stencil, node_of_pos,
                                                num_nodes=num_nodes)
