"""Warm-start incremental plan repair for elastic re-meshes (churn path).

At fleet scale node churn is the steady state — stragglers, spot
preemption, elastic scale-up/down — and a full cold re-solve through the
mapping pipeline on every event is the latency floor the runtime pays to
recover quality.  *Better Process Mapping and Sparse Quadratic Assignment*
(Schulz & Träff 2017) shows local search from a good initial assignment
dominates solving from scratch; this module is that observation applied to
the plan layer: instead of re-running base mapper + deterministic rounds +
annealing portfolio + polish on the post-churn problem, **seed** the search
from the previous solution restricted to the survivors and only repair what
churn actually touched.

The repair pipeline (:func:`repair_seed` + :class:`RepairStage`):

1. **transfer** — every position of the (possibly re-shaped) post-churn
   grid inherits the node its geometric pre-image held in the previous
   assignment (identity when the mesh shape is unchanged), translated
   through ``node_map`` (new node index -> old node index; ``-1`` marks a
   node that did not exist before churn);
2. **restrict** — positions whose node died are *orphans*; surviving nodes
   over their new capacity orphan their boundary-most positions (fewest
   same-node stencil neighbours) first;
3. **re-home** — orphans are greedily adopted by adjacent surviving nodes
   with free capacity (majority vote over stencil neighbours, repeated to a
   fixed point), remaining capacity is filled row-major — the result is a
   valid assignment (``bincount == node_sizes``) by construction;
4. **pinned anneal** — nodes untouched by churn (capacity unchanged, no
   position moved) are *pinned*: the K-ladder annealing portfolio
   (:class:`~repro.core.refine.PortfolioRefiner` with ``pinned=``) proposes
   swaps only among the affected nodes' positions, skipping the
   deterministic rounds and polish a cold solve pays for.

:class:`RepairStage` packages 1–4 as a first-class plan stage whose
``spec()`` hashes the previous assignment, so repaired solutions are
cached by :class:`~repro.core.plan.PlanCache` under the post-churn problem
signature (survivor node sizes) without ever colliding with — or
invalidating — the pre-churn entries.  The entry points callers use are
:func:`~repro.core.remap.repair_layout` (solution-level) and
:func:`~repro.launch.mesh.repair_mapped_mesh` (jax Mesh-level);
``parse_plan("repair:hyperplane", previous=sol)`` spells the same stage in
the plan grammar (the base after the colon is the cold fallback when the
previous solution is unusable).

Claim this module pins (tests/test_repair.py, BENCH_6.json): repair reaches
within epsilon of the cold elastic solve's (J_max, J_sum) at a small
fraction of its wall-time across node-loss, node-add, and slow-pod
(down-weighted capacity) scenarios.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .cost import evaluate
from .cost_delta import IncrementalCost, NeighborTable
from .grid import CartGrid
from .stencil import Stencil
from .refine.stage import BaseStage, Stage, StageResult, canon_options

__all__ = ["RepairInapplicable", "RepairSeed", "repair_seed",
           "transfer_positions", "RepairStage", "repair_plan",
           "downweighted_node_sizes", "absorbed_node_sizes"]


class RepairInapplicable(ValueError):
    """The previous solution cannot seed this problem (dimensionality
    mismatch, unmappable node sets, ...) — callers fall back to a cold
    solve."""


# ---------------------------------------------------------------------------
# churn arithmetic helpers (who gets the lost/slow node's share)


def absorbed_node_sizes(node_sizes: Sequence[int], lost: int) -> List[int]:
    """Node ``lost``'s processes absorbed by the survivors (fixed process
    grid, the paper's heterogeneous-n_i setting): its capacity is spread
    round-robin over the remaining nodes, largest-capacity first so the
    relative imbalance stays minimal.  Returns the survivor sizes (length
    ``len(node_sizes) - 1``; pair with ``node_map`` = the surviving old
    indices in order)."""
    sizes = [int(s) for s in node_sizes]
    if not 0 <= lost < len(sizes):
        raise ValueError(f"lost node {lost} out of range for {len(sizes)} "
                         "nodes")
    if len(sizes) < 2:
        raise ValueError("cannot absorb the only node")
    share = sizes.pop(lost)
    order = sorted(range(len(sizes)), key=lambda i: (-sizes[i], i))
    for j in range(share):
        sizes[order[j % len(sizes)]] += 1
    return sizes


def downweighted_node_sizes(node_sizes: Sequence[int], slow: int,
                            factor: float) -> List[int]:
    """Slow-but-alive pod as a weighted-node re-solve: node ``slow`` keeps
    ``round(size / factor)`` of its processes (at least 1) and the freed
    share is absorbed round-robin by the healthy nodes — same total, same
    process grid, so the repaired plan can be compared like-for-like with a
    cold solve of the down-weighted problem."""
    sizes = [int(s) for s in node_sizes]
    if not 0 <= slow < len(sizes):
        raise ValueError(f"slow node {slow} out of range for {len(sizes)} "
                         "nodes")
    if factor < 1.0:
        raise ValueError("slowdown factor must be >= 1.0")
    if len(sizes) < 2:
        return sizes
    keep = max(1, int(round(sizes[slow] / float(factor))))
    freed = sizes[slow] - keep
    sizes[slow] = keep
    order = sorted((i for i in range(len(sizes)) if i != slow),
                   key=lambda i: (-sizes[i], i))
    for j in range(freed):
        sizes[order[j % len(order)]] += 1
    return sizes


# ---------------------------------------------------------------------------
# seed construction


def transfer_positions(grid: CartGrid,
                       prev_shape: Sequence[int]) -> np.ndarray:
    """For every position of ``grid`` (the post-churn mesh), the position of
    the pre-churn ``prev_shape`` grid whose normalized coordinate is its
    geometric pre-image (identity when the shapes match).  This is what
    lets repair survive a mesh-shape change (a pod loss shrinks the device
    count, so the re-mesh rarely keeps the exact shape)."""
    prev_shape = tuple(int(d) for d in prev_shape)
    if len(prev_shape) != grid.ndim:
        raise RepairInapplicable(
            f"previous mesh rank {len(prev_shape)} != new rank {grid.ndim}")
    if prev_shape == grid.dims:
        return np.arange(grid.size, dtype=np.int64)
    old = np.asarray(prev_shape, dtype=np.int64)
    new = np.asarray(grid.dims, dtype=np.int64)
    # cell-centred rescale, clipped: old_i = floor((c + .5) * old / new)
    oc = ((grid.coords() * 2 + 1) * old) // (2 * new)
    oc = np.clip(oc, 0, old - 1)
    return np.ravel_multi_index(tuple(oc.T), prev_shape).astype(np.int64)


@dataclass
class RepairSeed:
    """A repaired starting assignment plus everything the pinned anneal and
    the caller's invariants need: which positions moved, which nodes churn
    touched, and which positions are therefore pinned."""

    assignment: np.ndarray        # (p,) valid: bincount == new node_sizes
    desire: np.ndarray            # (p,) transferred pre-churn node (-1 dead)
    moved: np.ndarray             # (p,) bool: ended away from pre-churn home
    affected_nodes: np.ndarray    # new node ids churn touched, ascending
    pinned: np.ndarray            # (p,) bool: safe to exclude from search
    orphans: int                  # positions whose node died / was evicted
    rehomed_adjacent: int         # orphans adopted by a stencil neighbour


def _same_node_score(table: NeighborTable, desire: np.ndarray) -> np.ndarray:
    """Per position: how many stencil edges (either direction) connect it to
    a position desiring the same (live) node — the inverse of boundary-ness,
    used to pick which positions an over-capacity node orphans first."""
    score = np.zeros(desire.shape[0], dtype=np.int64)
    for j in range(table.out_valid.shape[0]):
        valid, tgt = table.out_valid[j], table.out_tgt[j]
        same = valid & (desire >= 0) & (desire == desire[tgt])
        score += same
        np.add.at(score, tgt[same], 1)
    return score


def _grow_region(table: NeighborTable, seed: np.ndarray, score: np.ndarray,
                 over: np.ndarray, locked: np.ndarray, node: int,
                 capacity: int) -> None:
    """Claim a connected region of ``capacity`` positions for a newly added
    ``node``.  Preference order: orphaned (dead-node) cells, then cells of
    *over-capacity* donors (``over``: per-node desired-minus-capacity —
    stealing those is free, the donor must shed them anyway; this also
    lands the region exactly where a mesh-growth transfer duplicated
    cells), then boundary-most cells.  Mutates ``seed`` (claimed positions
    -> ``node``), ``over`` (stolen cells shed the donor's excess) and
    ``locked`` (claimed positions are off-limits to later growth and
    eviction)."""
    p = seed.shape[0]
    avail = ~locked

    def pressure(cells: np.ndarray) -> np.ndarray:
        # 1 = free to steal: orphaned cell, or donor still over capacity
        s = seed[cells]
        return np.where(s < 0, 1, (over[np.clip(s, 0, None)] > 0)
                        .astype(np.int64))

    cand = np.nonzero(avail)[0]
    if cand.size == 0:
        return
    order = np.lexsort((cand, score[cand], -pressure(cand)))
    start = int(cand[order[0]])
    in_region = np.zeros(p, dtype=bool)
    adj = np.zeros(p, dtype=np.int64)    # stencil edges into the region

    def take(pos: int) -> None:
        in_region[pos] = True
        if seed[pos] >= 0:
            over[seed[pos]] -= 1
        out = table.out_tgt[table.out_valid[:, pos], pos]
        inc = table.in_src[table.in_valid[:, pos], pos]
        np.add.at(adj, np.concatenate([out, inc]), 1)

    take(start)
    while int(in_region.sum()) < capacity:
        cand = np.nonzero(avail & ~in_region & (adj > 0))[0]
        if cand.size == 0:               # disconnected leftovers
            cand = np.nonzero(avail & ~in_region)[0]
            if cand.size == 0:
                break
        # free-to-steal first, then most-attached, then boundary-most
        order = np.lexsort((cand, score[cand], -adj[cand], -pressure(cand)))
        take(int(cand[order[0]]))
    seed[in_region] = node
    locked[in_region] = True


def repair_seed(grid: CartGrid, stencil: Stencil,
                prev_assignment: np.ndarray, prev_shape: Sequence[int],
                prev_node_sizes: Sequence[int],
                node_sizes: Sequence[int],
                node_map: Optional[Sequence[Optional[int]]] = None) \
        -> RepairSeed:
    """Build the warm-start assignment for the post-churn problem.

    ``node_map[i]`` is the pre-churn index of post-churn node ``i`` (``-1``
    or ``None`` for a node that is new).  Default: identity when the node
    counts match; anything else must be spelled by the caller (the
    survivors' old indices in order, e.g.
    :meth:`~repro.runtime.fault.SimulatedFault.survivor_map`).
    """
    prev_assignment = np.asarray(prev_assignment, dtype=np.int64).reshape(-1)
    prev_sizes = [int(s) for s in prev_node_sizes]
    sizes = np.asarray([int(s) for s in node_sizes], dtype=np.int64)
    n_old, n_new = len(prev_sizes), len(sizes)
    if prev_assignment.shape[0] != int(np.prod(prev_shape)):
        raise RepairInapplicable(
            f"previous assignment has {prev_assignment.shape[0]} positions, "
            f"previous shape {tuple(prev_shape)} needs "
            f"{int(np.prod(prev_shape))}")
    if int(sizes.sum()) != grid.size:
        raise ValueError(f"sum(node_sizes)={int(sizes.sum())} != mesh size "
                         f"{grid.size}")
    if (sizes <= 0).any():
        raise ValueError("node_sizes must be positive")
    if node_map is None:
        if n_new != n_old:
            raise RepairInapplicable(
                f"{n_old} nodes before churn, {n_new} after: pass node_map "
                "(new index -> old index, -1 for added nodes)")
        node_map = list(range(n_new))
    node_map = [-1 if m is None else int(m) for m in node_map]
    if len(node_map) != n_new:
        raise ValueError(f"node_map has {len(node_map)} entries for "
                         f"{n_new} nodes")
    old_to_new = np.full(n_old, -1, dtype=np.int64)
    for i, o in enumerate(node_map):
        if o < 0:
            continue
        if o >= n_old:
            raise ValueError(f"node_map[{i}]={o} out of range for {n_old} "
                             "pre-churn nodes")
        if old_to_new[o] >= 0:
            raise ValueError(f"node_map maps old node {o} twice")
        old_to_new[o] = i

    # 1. transfer: post-churn position -> pre-churn node -> post-churn node
    src = transfer_positions(grid, prev_shape)
    desire = old_to_new[prev_assignment[src]]      # -1 where the node died
    seed = desire.copy()

    table = NeighborTable.build(grid, stencil)
    score = _same_node_score(table, desire)

    # 1b. newly added nodes claim a *connected* region up-front, routed
    # through over-capacity donors' cells — a scattered fill would hand the
    # anneal a hopeless seed and the new node a worst-case J
    locked = np.zeros(grid.size, dtype=bool)
    over = (np.bincount(seed[seed >= 0], minlength=n_new)
            - sizes).astype(np.int64)
    for node in (i for i, o in enumerate(node_map) if o < 0):
        _grow_region(table, seed, score, over, locked, int(node),
                     int(sizes[node]))

    # 2. restrict to capacities: over-full nodes orphan boundary-most first
    counts = np.bincount(seed[seed >= 0], minlength=n_new)
    for node in np.nonzero(counts > sizes)[0]:
        pos = np.nonzero(seed == node)[0]
        order = pos[np.lexsort((pos, score[pos]))]   # lowest score first
        seed[order[:counts[node] - sizes[node]]] = -1

    orphans = int((seed < 0).sum())
    free = sizes - np.bincount(seed[seed >= 0], minlength=n_new)

    # 3. re-home orphans: neighbour majority vote, repeated to a fixed point
    rehomed_adjacent = 0
    while True:
        orphan_pos = np.nonzero(seed < 0)[0]
        if orphan_pos.size == 0:
            break
        progress = False
        for pos in orphan_pos:
            out = table.out_tgt[table.out_valid[:, pos], pos]
            inc = table.in_src[table.in_valid[:, pos], pos]
            nbr = seed[np.concatenate([out, inc])]
            nbr = nbr[nbr >= 0]
            nbr = nbr[free[nbr] > 0]
            if nbr.size == 0:
                continue
            votes = np.bincount(nbr, minlength=n_new)
            node = int(votes.argmax())               # ties -> smaller id
            seed[pos] = node
            free[node] -= 1
            rehomed_adjacent += 1
            progress = True
        if not progress:
            break
    leftover = np.nonzero(seed < 0)[0]
    if leftover.size:                   # disconnected pockets / empty new
        fill = np.repeat(np.arange(n_new), free)     # nodes: row-major fill
        seed[leftover] = fill
        free[:] = 0

    # 4. what churn touched: capacity-changed nodes + both end-points of
    # every move (the donor a position left *and* the node it landed on —
    # the restricted search needs at least the donors to trade with)
    moved = seed != desire
    affected = set(int(n) for n in np.unique(seed[moved]))
    affected |= set(int(n) for n in np.unique(desire[moved]) if n >= 0)
    for i, o in enumerate(node_map):
        if o < 0 or prev_sizes[o] != int(sizes[i]):
            affected.add(i)
    affected_nodes = np.asarray(sorted(affected), dtype=np.int64)
    pinned = ~np.isin(seed, affected_nodes)
    return RepairSeed(assignment=seed, desire=desire, moved=moved,
                      affected_nodes=affected_nodes, pinned=pinned,
                      orphans=orphans, rehomed_adjacent=rehomed_adjacent)


def _restricted_polish(ic: IncrementalCost, allowed: np.ndarray,
                       objective: str = "lex",
                       max_passes: int = 4, max_partners: int = 32,
                       budget: Optional[int] = None,
                       max_positions: Optional[int] = None,
                       tol: float = 1e-12) -> int:
    """First-improvement descent over boundary pairs drawn entirely from
    ``allowed`` positions — the pin-respecting stand-in for the schedule's
    phases (which have no notion of pinning).  ``objective="j_sum"``
    accepts any J_sum-reducing swap that does not worsen J_max (the
    schedule's J_sum phase, guarded); ``"lex"`` accepts lexicographic
    (J_max, J_sum) improvements.  ``max_positions`` caps the outer sweep to
    the costliest boundary positions (partners still come from the full
    boundary) — the J_max binding set sits at the front of the cost-sorted
    order, so a small cap keeps the J_max-relieving swaps while shedding
    the long tail of no-op probes.  Mutates ``ic``; returns accepted
    swaps."""
    swaps = 0
    for _ in range(max_passes):
        improved = False
        boundary = ic.boundary_positions()
        boundary = boundary[allowed[boundary]]
        per_node = ic.per_node
        cost_of = per_node[ic.node_of_pos[boundary]]
        # costliest nodes' positions first (the J_max binding set), cheapest
        # partners first — the ordering that relieves the max node soonest
        boundary = boundary[np.argsort(-cost_of, kind="stable")]
        for p in boundary[:max_positions]:
            if budget is not None and swaps >= budget:
                return swaps
            partners = boundary[ic.node_of_pos[boundary]
                                != ic.node_of_pos[p]]
            partners = partners[np.argsort(
                ic.per_node[ic.node_of_pos[partners]], kind="stable")]
            for q in partners[:max_partners]:
                d = ic.delta_swap(int(p), int(q))
                d_max = ic.peek_j_max(d) - ic.j_max
                if objective == "j_sum":
                    ok = d.d_j_sum < -tol and d_max <= tol
                else:
                    ok = d_max < -tol or (abs(d_max) <= tol
                                          and d.d_j_sum < -tol)
                if ok:
                    ic.apply_swap(int(p), int(q))
                    swaps += 1
                    improved = True
                    break
        if not improved:
            break
    return swaps


def _resplit_pairs(grid: CartGrid, stencil: Stencil,
                   assignment: np.ndarray, num_nodes: int,
                   nodes: Sequence[int], max_passes: int = 3,
                   tol: float = 1e-12) -> Tuple[np.ndarray, int]:
    """Deterministic two-node re-tiling over the *affected* nodes: for every
    pair, re-partition the union of their cells along each grid axis
    (coordinate-sorted prefix split, both orders) and keep the best
    lexicographic (J_max, J_sum) improvement.  This crosses the
    block-rotation barriers swap-based annealing cannot (rotating two 2x4
    blocks into two 4x2 blocks takes ~8 coordinated swaps through strictly
    worse states).  Only the pair's own positions change, so pinned
    positions stay untouched.  Only pairs *adjacent* in the current
    assignment (sharing at least one stencil edge) are tried — a prefix
    re-split of two regions that never touch cannot beat the split they
    already have, and skipping them turns the O(n^2) pair sweep into the
    O(boundary) sweep that keeps the all-nodes-affected repair path under
    its latency budget.  Returns ``(assignment, accepted)``."""
    nodes = [int(n) for n in nodes]
    coords = grid.coords()
    nbr = NeighborTable.build(grid, stencil)
    cur = np.asarray(assignment, dtype=np.int64).copy()
    c = evaluate(grid, stencil, cur, num_nodes=num_nodes, weighted="auto")
    cur_key = (c.j_max, c.j_sum)
    accepted = 0

    def node_adjacency(assign: np.ndarray) -> np.ndarray:
        adj = np.zeros((num_nodes, num_nodes), dtype=bool)
        for j in range(nbr.out_valid.shape[0]):
            v = nbr.out_valid[j]
            adj[assign[v], assign[nbr.out_tgt[j][v]]] = True
        return adj | adj.T

    for _ in range(max_passes):
        improved = False
        adj = node_adjacency(cur)
        for ai in range(len(nodes)):
            for bi in range(ai + 1, len(nodes)):
                a, b = nodes[ai], nodes[bi]
                if not adj[a, b]:
                    continue
                cells_a = np.nonzero(cur == a)[0]
                cells_b = np.nonzero(cur == b)[0]
                if cells_a.size == 0 or cells_b.size == 0:
                    continue
                union = np.concatenate([cells_a, cells_b])
                best_key, best_trial = cur_key, None
                for axis in range(grid.ndim):
                    order = np.lexsort(tuple(
                        coords[union, ax]
                        for ax in range(grid.ndim) if ax != axis
                    ) + (coords[union, axis],))
                    for first, second in ((a, b), (b, a)):
                        split = cells_a.size if first == a else cells_b.size
                        trial = cur.copy()
                        trial[union[order[:split]]] = first
                        trial[union[order[split:]]] = second
                        if np.array_equal(trial, cur):
                            continue
                        tc = evaluate(grid, stencil, trial,
                                      num_nodes=num_nodes, weighted="auto")
                        key = (tc.j_max, tc.j_sum)
                        if key[0] < best_key[0] - tol or \
                                (abs(key[0] - best_key[0]) <= tol
                                 and key[1] < best_key[1] - tol):
                            best_key, best_trial = key, trial
                if best_trial is not None:
                    cur, cur_key = best_trial, best_key
                    accepted += 1
                    improved = True
        if not improved:
            break
    return cur, accepted


def _relabel_overlap(fresh: np.ndarray, desire: np.ndarray,
                     sizes: np.ndarray) -> np.ndarray:
    """Permutation of node labels (within equal-capacity groups — anything
    else would break ``bincount == node_sizes``) maximizing the number of
    positions whose fresh label matches the transferred previous node, so a
    fresh re-tile migrates as few shards as possible.  Greedy on the
    overlap matrix; J_max/J_sum are label-invariant, so this never costs
    quality.  Returns ``perm`` with ``perm[fresh_label] = node id``."""
    n = int(sizes.shape[0])
    overlap = np.zeros((n, n), dtype=np.int64)
    mask = desire >= 0
    np.add.at(overlap, (fresh[mask], desire[mask]), 1)
    perm = np.full(n, -1, dtype=np.int64)
    taken = np.zeros(n, dtype=bool)
    order = np.argsort(-overlap, axis=None, kind="stable")
    for flat in order:
        lab, node = divmod(int(flat), n)
        if perm[lab] >= 0 or taken[node] or sizes[lab] != sizes[node]:
            continue
        perm[lab], taken[node] = node, True
    for lab in np.nonzero(perm < 0)[0]:       # zero-overlap leftovers
        node = next(i for i in np.nonzero(~taken)[0]
                    if sizes[i] == sizes[lab])
        perm[lab], taken[node] = node, True
    return perm


# ---------------------------------------------------------------------------
# the plan stage


def _previous_parts(previous) -> Tuple[np.ndarray, Tuple[int, ...],
                                       Tuple[int, ...]]:
    """Normalize ``previous``: a MappingSolution / CartResult, or an
    ``(assignment, mesh_shape, node_sizes)`` triple."""
    if hasattr(previous, "solution"):             # CartResult
        previous = previous.solution
    if hasattr(previous, "assignment") and hasattr(previous, "problem"):
        return (np.asarray(previous.assignment, dtype=np.int64),
                tuple(previous.problem.mesh_shape),
                tuple(previous.problem.node_sizes))
    try:
        assignment, shape, sizes = previous
    except (TypeError, ValueError):
        raise TypeError(
            "previous must be a MappingSolution/CartResult or an "
            "(assignment, mesh_shape, node_sizes) triple, got "
            f"{type(previous).__name__}") from None
    return (np.asarray(assignment, dtype=np.int64).reshape(-1),
            tuple(int(d) for d in shape), tuple(int(s) for s in sizes))


class RepairStage(Stage):
    """The ``repair:`` plan stage: produce the post-churn assignment by
    warm-starting from a previous solution (seed + pinned anneal) instead
    of running a base mapper cold.

    Args:
      previous: the pre-churn :class:`~repro.core.plan.MappingSolution`
        (or ``CartResult``, or an ``(assignment, mesh_shape, node_sizes)``
        triple).
      node_map: post-churn node index -> pre-churn node index (``-1`` /
        ``None`` = newly added node).  Default identity when counts match.
      k / seed / sa_moves / temperatures: the repair portfolio's annealing
        shape (short ladders — the seed is already good; the final
        near-zero temperature acts as a sampled greedy descent).  ``k=0``
        returns the raw seed unrefined.
      pin: exclude positions of churn-untouched nodes from the search
        (``False`` anneals the whole mesh from the seed — slower, and the
        pinned-position invariant no longer holds).
      max_swaps: accepted-swap budget for the anneal (per-stage plan
        budgets thread into this).
      grow_base: mesh-*growth* strategy (scale-up / pod rejoin at a larger
        shape).  A grown grid admits tilings the previous solution never
        contained, so warm-seeding systematically lands in a worse basin;
        instead the deterministic ``grow_base`` mapper re-tiles the new
        grid from scratch (cheap — no portfolio) and the labels are then
        permuted to maximize overlap with the transferred previous
        assignment, minimizing migration volume.  Set to ``""`` to force
        the warm seed even on growth.
      fallback: a :class:`~repro.core.plan.MappingPlan` solved cold when
        the previous solution cannot seed this problem
        (:class:`RepairInapplicable`); without one the error propagates.

    The stage spec hashes the previous assignment (+ provenance + options),
    so plans containing it are cacheable: the repaired solution lands in
    the :class:`~repro.core.plan.PlanCache` keyed by the *post-churn*
    problem hash — pre-churn entries are untouched by construction.
    """

    is_initial = True       # produces the plan's first assignment

    def __init__(self, previous,
                 node_map: Optional[Sequence[Optional[int]]] = None,
                 k: int = 4, seed: int = 0, sa_moves: int = 40,
                 temperatures: Sequence[float] = (0.35, 1e-6),
                 pin: bool = True, max_swaps: Optional[int] = None,
                 grow_base: str = "hyperplane", fallback=None):
        self.prev_assignment, self.prev_shape, self.prev_sizes = \
            _previous_parts(previous)
        self.node_map = None if node_map is None else \
            tuple(-1 if m is None else int(m) for m in node_map)
        if int(k) < 0:
            raise ValueError("k must be >= 0 (0 = seed only)")
        self.k = int(k)
        self.seed = int(seed)
        self.sa_moves = int(sa_moves)
        self.temperatures = tuple(float(t) for t in temperatures)
        self.pin = bool(pin)
        if max_swaps is not None and int(max_swaps) < 0:
            raise ValueError("max_swaps must be >= 0 (or None)")
        self.max_swaps = None if max_swaps is None else int(max_swaps)
        self.grow_base = str(grow_base)
        self.fallback = fallback
        self.cacheable = True if fallback is None \
            else getattr(fallback, "cacheable", False)

    # -- identity ----------------------------------------------------------
    def _prev_hash(self) -> str:
        h = hashlib.sha256()
        h.update(self.prev_assignment.astype("<i8").tobytes())
        h.update(repr((self.prev_shape, self.prev_sizes,
                       self.node_map)).encode())
        return h.hexdigest()[:16]

    def options(self) -> Dict[str, object]:
        return {"k": self.k, "seed": self.seed, "sa_moves": self.sa_moves,
                "temperatures": self.temperatures, "pin": self.pin,
                "max_swaps": self.max_swaps, "grow_base": self.grow_base}

    def spec(self) -> str:
        s = f"repair[{canon_options(self.options())}]" \
            f"{{prev={self._prev_hash()}}}"
        if self.fallback is not None:
            s += f"@fallback={self.fallback.key}"
        return s

    # -- execution ---------------------------------------------------------
    def _run_fallback(self, grid: CartGrid, stencil: Stencil,
                      node_sizes: Sequence[int], reason: str) -> StageResult:
        assignment = None
        stats: List[dict] = []
        for st in self.fallback.stages:
            sr = st.run(grid, stencil, node_sizes, assignment)
            assignment = sr.assignment
            stats.append(sr.stats)
        return StageResult(assignment=assignment,
                           stats={"stage": self.spec(), "kind": "repair",
                                  "used_fallback": True,
                                  "fallback_reason": reason,
                                  "fallback_stats": stats})

    def _run_grow(self, grid: CartGrid, stencil: Stencil,
                  node_sizes: Sequence[int], rs: RepairSeed,
                  t0: float) -> StageResult:
        """Mesh-growth path: a grown grid admits tilings the previous
        solution never contained, so the warm seed is a systematically
        worse basin at any anneal effort.  Re-tile fresh with the
        deterministic ``grow_base`` mapper, then permute labels for maximum
        overlap with the transferred previous assignment (the migration
        volume is the only warm artifact worth keeping — J is
        label-invariant)."""
        n = len(node_sizes)
        sizes = np.asarray(node_sizes, dtype=np.int64)
        base = BaseStage(self.grow_base, fallback="blocked")
        fresh = base.run(grid, stencil, node_sizes, None).assignment
        perm = _relabel_overlap(fresh, rs.desire, sizes)
        cur = perm[fresh]
        resplits = 0
        swaps = 0
        if self.k > 0 and grid.size > 1 and (self.max_swaps is None
                                             or self.max_swaps > 0):
            cur, resplits = _resplit_pairs(grid, stencil, cur, n,
                                           list(range(n)), max_passes=1)
            ic = IncrementalCost(grid, stencil, cur, num_nodes=n,
                                 weighted="auto")
            allowed = np.ones(grid.size, dtype=bool)
            swaps = _restricted_polish(ic, allowed, objective="lex",
                                       max_passes=1, max_partners=8,
                                       max_positions=32,
                                       budget=self.max_swaps)
            cur = ic.node_of_pos.copy()
            final_key = (ic.j_max, ic.j_sum)
        else:
            c = evaluate(grid, stencil, cur, num_nodes=n, weighted="auto")
            final_key = (c.j_max, c.j_sum)
        migrated = int((cur != rs.desire).sum())
        stats = {
            "stage": self.spec(), "kind": "repair", "used_fallback": False,
            "strategy": "grow-fresh", "grow_base": self.grow_base,
            "backend": "host",
            "orphans": rs.orphans,
            "rehomed_adjacent": rs.rehomed_adjacent,
            "moved": migrated,
            "affected_nodes": list(range(n)),
            "pinned": 0,
            "pin": self.pin,
            "final": final_key,
            "swaps": swaps,
            "resplits": resplits,
            "wall_time_s": time.perf_counter() - t0,
        }
        return StageResult(assignment=cur, stats=stats)

    def run(self, grid: CartGrid, stencil: Stencil,
            node_sizes: Sequence[int],
            assignment: Optional[np.ndarray] = None) -> StageResult:
        if assignment is not None:
            raise ValueError("RepairStage must be the first stage of a plan")
        t0 = time.perf_counter()
        try:
            rs = repair_seed(grid, stencil, self.prev_assignment,
                             self.prev_shape, self.prev_sizes, node_sizes,
                             node_map=self.node_map)
        except RepairInapplicable as e:
            if self.fallback is None:
                raise
            return self._run_fallback(grid, stencil, node_sizes, str(e))
        # A *changed* mesh shape garbles the geometric transfer (the seed is
        # a rescale of the old tiling), and the new shape admits tilings the
        # previous solution never contained — on growth always, and on any
        # re-shape with uniform node sizes (where the deterministic base
        # mapper is at its strongest).  Re-tile fresh there; the warm seed
        # only survives as the relabeling target that minimizes migration.
        if self.grow_base and tuple(grid.dims) != self.prev_shape and \
                (grid.size > int(np.prod(self.prev_shape))
                 or len({int(s) for s in node_sizes}) == 1):
            return self._run_grow(grid, stencil, node_sizes, rs, t0)
        n = len(node_sizes)
        cur = rs.assignment
        allowed = ~rs.pinned if self.pin \
            else np.ones(grid.size, dtype=bool)
        ic = IncrementalCost(grid, stencil, cur, num_nodes=n,
                             weighted="auto")
        seed_key = (ic.j_max, ic.j_sum)
        swaps = 0
        resplits = 0
        final_key = seed_key
        if self.k > 0 and grid.size > 1 and (self.max_swaps is None
                                             or self.max_swaps > 0):
            from .refine import PortfolioRefiner

            def cap() -> Optional[int]:
                return None if self.max_swaps is None \
                    else max(0, self.max_swaps - swaps)

            # 1. pre-anneal re-tiling drops the seed into the right basin
            # before any stochastic moves are spent (a boundary-pair J_sum
            # descent here costs more than the anneal and finds less)
            cur, resplits = _resplit_pairs(grid, stencil, cur,
                                           n, rs.affected_nodes)
            # 2. short pinned annealing ladders (plateau escape)
            refiner = PortfolioRefiner(
                k=self.k, seed=self.seed, sa_moves=self.sa_moves,
                temperatures=self.temperatures, kill_factor=None,
                max_swaps=cap())
            res = refiner.refine(grid, stencil, cur, num_nodes=n,
                                 pinned=rs.pinned if self.pin else None)
            swaps += res.swaps
            # 3. deterministic pairwise re-tiling of the affected nodes —
            # the barrier-crossing move the local swap search lacks
            cur, post = _resplit_pairs(grid, stencil, res.assignment,
                                       n, rs.affected_nodes)
            resplits += post
            # 4. restricted lexicographic polish (short: the heavy lifting
            # already happened, this only irons out single-swap slack).
            # With nothing pinned the boundary set is the whole mesh and a
            # full polish would dominate the repair budget — one narrow
            # pass suffices after the unrestricted anneal.
            ic = IncrementalCost(grid, stencil, cur, num_nodes=n,
                                 weighted="auto")
            wide = bool(allowed.all())
            swaps += _restricted_polish(ic, allowed, objective="lex",
                                        max_passes=1 if wide else 2,
                                        max_partners=8 if wide else 16,
                                        max_positions=32 if wide else None,
                                        budget=cap())
            cur = ic.node_of_pos.copy()
            final_key = (ic.j_max, ic.j_sum)
        stats = {
            "stage": self.spec(), "kind": "repair", "used_fallback": False,
            "backend": "host",
            "orphans": rs.orphans,
            "rehomed_adjacent": rs.rehomed_adjacent,
            "moved": int(rs.moved.sum()),
            "affected_nodes": [int(x) for x in rs.affected_nodes],
            "pinned": int(rs.pinned.sum()),
            "pin": self.pin,
            "seed_key": seed_key,
            "final": final_key,
            "swaps": swaps,
            "resplits": resplits,
            "wall_time_s": time.perf_counter() - t0,
        }
        return StageResult(assignment=cur, stats=stats)


def repair_plan(previous,
                node_map: Optional[Sequence[Optional[int]]] = None,
                fallback=None, **options):
    """A one-stage :class:`~repro.core.plan.MappingPlan` that repairs
    ``previous`` onto whatever problem it is solved against.  ``options``
    are :class:`RepairStage` knobs (``k``, ``sa_moves``, ``temperatures``,
    ``pin``, ``max_swaps``, ``seed``); ``fallback`` may be a plan spelling
    or a :class:`~repro.core.plan.MappingPlan`."""
    from .plan import MappingPlan, parse_plan
    if isinstance(fallback, str):
        fallback = parse_plan(fallback)
    return MappingPlan([RepairStage(previous, node_map=node_map,
                                    fallback=fallback, **options)],
                       name="repair")
