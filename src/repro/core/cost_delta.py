"""Incremental delta-evaluation of mapping cost (local-search engine).

:func:`~repro.core.cost.evaluate` re-walks every (rank, offset) edge of the
grid, which makes a local-search step O(p * k).  :class:`IncrementalCost`
precomputes the stencil neighbour table once (one ``grid.shift_ranks`` call
per offset, plus its inverse) and afterwards answers "what happens to
J_sum / per-node load if position ``p`` moves from node ``a`` to node ``b``"
by touching only the O(k) edges incident to the affected positions.

Two query paths share the same integer-count core:

* scalar — :meth:`IncrementalCost.delta_move` / :meth:`~IncrementalCost.delta_swap`
  score one proposal at a time (O(k) per call, Python-level);
* batch — :meth:`IncrementalCost.batch_swap_deltas` scores an *array* of
  swap proposals in a handful of numpy passes (O(m * k) work with no
  Python-per-proposal overhead).  This is what lets
  :class:`~repro.core.refine.SwapRefiner` evaluate the entire boundary
  frontier of a 48x48 grid in one shot instead of ~50k interpreted calls.

State is kept as *integer* crossing counts per (node, offset), so the
reconstructed ``j_sum`` matches a full recomputation bit-for-bit (same
``total += w * count`` accumulation order as ``evaluate``), as does
``per_node`` for **arbitrary float weights**: both sides accumulate
``w * count`` per offset in ascending-offset order (``evaluate`` used to
add ``w`` count times instead, which differs in the last ulp for weights
like 0.1 — fixed, and pinned by ``tests/test_cost_weight_parity.py``).
The batch path
accumulates per-offset counts in the same ascending-``j`` order, so its
``d_j_sum`` / ``new_per_node`` are bit-exact with the scalar
:meth:`~IncrementalCost.delta_swap` / :meth:`~IncrementalCost.peek_per_node`
results.

Usage::

    ic = IncrementalCost(grid, stencil, node_of_pos, num_nodes=N)
    d = ic.delta_swap(p, q)            # scalar preview
    ic.apply_swap(p, q)                # commit (counts updated in O(k))

    P, Q = candidate_pairs             # (m,) position arrays
    bd = ic.batch_swap_deltas(P, Q, with_loads=True)
    best = int(np.argmin(bd.d_j_sum))  # most J_sum-improving swap
    ic.apply_swap(int(P[best]), int(Q[best]))
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cost import MappingCost
from .grid import CartGrid
from .stencil import Stencil, resolve_weighted

__all__ = ["IncrementalCost", "NeighborTable", "Delta", "BatchSwapDelta",
           "PortfolioCost", "PortfolioSwapDelta", "LOAD_CHUNK_ELEMS",
           "neighbor_table", "table_key", "stacked_count_arrays",
           "stacked_crossing_counts"]


def stacked_count_arrays(table: "NeighborTable", assignments: np.ndarray,
                         num_nodes: int) -> Tuple[np.ndarray, np.ndarray]:
    """Integer crossing counts for stacked (K, p) assignments:
    ``((K, k) count_off, (K, N, k) count_node)``.

    The numpy crossing-count loop: :class:`PortfolioCost` initializes
    from it (so do the ``multiprocessing`` workers' block states), and it
    is the reference the jax kernel :func:`stacked_crossing_counts` is
    tested against, bit for bit.
    """
    A = np.asarray(assignments, dtype=np.int64)
    K, k = A.shape[0], table.out_valid.shape[0]
    count_off = np.zeros((K, k), dtype=np.int64)
    count_node = np.zeros((K, int(num_nodes), k), dtype=np.int64)
    for j in range(k):
        valid, tgt = table.out_valid[j], table.out_tgt[j]
        crossing = valid[None, :] & (A != A[:, tgt])
        count_off[:, j] = crossing.sum(axis=1)
        rr, pp = np.nonzero(crossing)
        np.add.at(count_node[:, :, j], (rr, A[rr, pp]), 1)
    return count_off, count_node

#: Load-matrix scoring materializes (chunk, N) float matrices; callers chunk
#: proposals so chunk * N stays below this, bounding peak extra memory to
#: ~tens of MB no matter how large the frontier (or portfolio) is.
LOAD_CHUNK_ELEMS = 1 << 21


@dataclass(frozen=True)
class NeighborTable:
    """Per-offset forward and inverse neighbour lookups for one grid."""

    #: (k, p) bool — does position i have an out-neighbour under offset j?
    out_valid: np.ndarray
    #: (k, p) int — the out-neighbour's position (garbage where invalid).
    out_tgt: np.ndarray
    #: (k, p) bool — does position i have an in-neighbour under offset j?
    in_valid: np.ndarray
    #: (k, p) int — the in-neighbour's position (garbage where invalid).
    in_src: np.ndarray

    @staticmethod
    def build(grid: CartGrid, stencil: Stencil) -> "NeighborTable":
        p, k = grid.size, stencil.k
        out_valid = np.zeros((k, p), dtype=bool)
        out_tgt = np.zeros((k, p), dtype=np.int64)
        in_valid = np.zeros((k, p), dtype=bool)
        in_src = np.zeros((k, p), dtype=np.int64)
        for j, off in enumerate(stencil.offsets):
            valid, tgt = grid.shift_ranks(off)
            out_valid[j] = valid
            out_tgt[j] = tgt
            # a coordinate shift is injective on its valid domain, so the
            # inverse is single-valued: in_src[j][tgt[q]] = q.
            src = np.nonzero(valid)[0]
            in_valid[j][tgt[src]] = True
            in_src[j][tgt[src]] = src
        return NeighborTable(out_valid, out_tgt, in_valid, in_src)

    @staticmethod
    def from_graph(graph) -> "NeighborTable":
        """The table of a :class:`~repro.core.graph.CommGraph`: one row
        per slot of its partial-permutation decomposition (each slot is
        injective on its valid domain by construction, which is exactly
        what keeps the single-valued inverse above sound).  For
        stencil-extracted graphs this returns arrays bit-identical to
        ``build(grid, stencil)`` on the original grid."""
        return NeighborTable.build(graph.grid(), graph.slot_stencil())


#: :func:`neighbor_table`'s memo, keyed by :func:`table_key`: the table is
#: trajectory-independent and grid-sized, so each process builds it once
#: per problem (the pool workers rebuild block state every temperature).
_TABLE_MEMO: "OrderedDict[tuple, NeighborTable]" = OrderedDict()
_TABLE_MEMO_MAX = 8


def table_key(grid: CartGrid, stencil: Stencil) -> tuple:
    """The problem identity a neighbour table depends on.  ``cache_token``
    keeps graph-backed and masked grids from colliding with a plain
    :class:`CartGrid` of the same dims (they answer ``shift_ranks``
    differently, so sharing a table would be silently wrong)."""
    return (tuple(grid.dims), tuple(grid.periodic),
            getattr(grid, "cache_token", ""), stencil.offsets)


def neighbor_table(grid: CartGrid, stencil: Stencil) -> NeighborTable:
    """:meth:`NeighborTable.build`, memoized per process by
    :func:`table_key` (the last few problems are kept)."""
    key = table_key(grid, stencil)
    table = _TABLE_MEMO.get(key)
    if table is None:
        table = NeighborTable.build(grid, stencil)
        _TABLE_MEMO[key] = table
        while len(_TABLE_MEMO) > _TABLE_MEMO_MAX:
            _TABLE_MEMO.popitem(last=False)
    else:
        _TABLE_MEMO.move_to_end(key)
    return table


def stacked_crossing_counts(grid: CartGrid, stencil: Stencil,
                            assignments: np.ndarray, num_nodes: int) \
        -> Tuple[np.ndarray, np.ndarray]:
    """Integer crossing counts for a stacked (K, p) assignment array:
    ``((K, k) count_off, (K, N, k) count_node)``, bit-equal to
    :func:`stacked_count_arrays` and so to what :class:`PortfolioCost`
    builds in its own init (integers: exact).  One ``jax.vmap``-batched
    kernel over the rows (crossing masks and a ``segment_sum`` per
    offset), jitted once per shape.  This is the count state the device
    engine (:mod:`repro.core.refine.device`) seeds its ladders from and
    rekeys its survivors with, and that the sharded engine's serial
    backend hands each block.  jax is imported on the first call."""
    import jax.numpy as jnp
    table = neighbor_table(grid, stencil)
    co, cn = _jit_stacked_counts(int(num_nodes))(
        jnp.asarray(np.asarray(assignments, dtype=np.int64)),
        jnp.asarray(table.out_valid), jnp.asarray(table.out_tgt))
    return (np.asarray(co, dtype=np.int64),
            np.ascontiguousarray(np.asarray(cn, dtype=np.int64)
                                 .transpose(0, 2, 1)))


@functools.lru_cache(maxsize=8)
def _jit_stacked_counts(num_nodes: int):
    """Build (and cache) the jitted stacked-counts kernel for one node
    count.  ``num_segments`` must be static under jit; table arrays are
    traced arguments, so one cached callable serves every grid/stencil —
    jax's own jit cache keys the shapes.  The jitted function keeps the
    stable name ``stacked_crossing_counts_one`` (module
    ``jit_stacked_crossing_counts_one``) for trace reductions."""
    import jax
    import jax.numpy as jnp

    def stacked_crossing_counts_one(a, out_valid, out_tgt):     # a: (p,)
        crossing = out_valid & (a[None, :] != a[out_tgt])        # (k, p)
        count_off = crossing.sum(axis=1)
        # count_node[j, n] = #{i : crossing[j, i] and a[i] == n}
        count_node = jax.vmap(
            lambda c: jax.ops.segment_sum(c.astype(jnp.int32), a,
                                          num_segments=num_nodes))(crossing)
        return count_off, count_node                 # (k,), (k, N)

    return jax.jit(jax.vmap(stacked_crossing_counts_one,
                            in_axes=(0, None, None)))


@dataclass(frozen=True)
class Delta:
    """Effect of a proposed move/swap.  ``d_count_off[j]`` is the change in
    the number of crossing edges under offset j; ``d_count_node`` maps
    ``(node, offset) -> count change`` for the per-node outgoing loads."""

    d_j_sum: float
    d_count_off: np.ndarray                     # (k,) int64
    d_count_node: Dict[Tuple[int, int], int]    # (node, offset) -> int


@dataclass(frozen=True)
class BatchSwapDelta:
    """Vectorized effect of ``m`` proposed swaps (one row per pair).

    ``d_count_off[i, j]`` is the change in crossing edges under offset j if
    pair i is swapped; ``d_j_sum`` folds in the offset weights with the same
    ascending-offset accumulation as the scalar path, so
    ``d_j_sum[i] == delta_swap(p[i], q[i]).d_j_sum`` exactly.  When built
    ``with_loads``, ``new_per_node[i]`` equals
    ``peek_per_node(delta_swap(p[i], q[i]))`` bit-for-bit and ``new_j_max``
    is its row-max."""

    p: np.ndarray                         # (m,) int64
    q: np.ndarray                         # (m,) int64
    d_count_off: np.ndarray               # (m, k) int64
    d_j_sum: np.ndarray                   # (m,) float64
    new_per_node: Optional[np.ndarray]    # (m, N) float64 or None
    new_j_max: Optional[np.ndarray]       # (m,) float64 or None

    @property
    def size(self) -> int:
        return int(self.p.size)


class IncrementalCost:
    """Mutable mapping-cost state with O(k) move/swap deltas.

    Args:
      node_of_pos: (p,) node id owning each grid position (row-major); a
        private copy is taken.
      weighted: use the stencil's per-offset byte weights (as in
        ``evaluate(weighted=True)``); ``"auto"`` uses them iff the stencil
        carries non-unit weights.
    """

    def __init__(self, grid: CartGrid, stencil: Stencil,
                 node_of_pos: np.ndarray, num_nodes: Optional[int] = None,
                 weighted=False):
        node_of_pos = np.asarray(node_of_pos, dtype=np.int64)
        if node_of_pos.shape != (grid.size,):
            raise ValueError(f"node_of_pos must have shape ({grid.size},)")
        self.grid = grid
        self.stencil = stencil
        self.table = NeighborTable.build(grid, stencil)
        self.n_nodes = int(num_nodes if num_nodes is not None
                           else node_of_pos.max() + 1)
        self.weighted = resolve_weighted(weighted, stencil)
        self.weights = (stencil.weight_array() if self.weighted
                        else np.ones(stencil.k))
        self.node_of_pos = node_of_pos.copy()
        # integer crossing counts: (k,) total and (N, k) per source node
        k = stencil.k
        self._count_off = np.zeros(k, dtype=np.int64)
        self._count_node = np.zeros((self.n_nodes, k), dtype=np.int64)
        for j in range(k):
            valid, tgt = self.table.out_valid[j], self.table.out_tgt[j]
            crossing = valid & (self.node_of_pos != self.node_of_pos[tgt])
            self._count_off[j] = int(crossing.sum())
            np.add.at(self._count_node[:, j], self.node_of_pos[crossing], 1)
        self._per_node_cache: Optional[np.ndarray] = None

    @classmethod
    def from_graph(cls, graph, node_of_pos: np.ndarray,
                   num_nodes: Optional[int] = None,
                   weighted="auto") -> "IncrementalCost":
        """Cost state over a :class:`~repro.core.graph.CommGraph`: the
        graph's slot decomposition plays the stencil (offset ``(j+1,)`` =
        slot ``j``), so every delta query below works unchanged.  For
        stencil-extracted graphs the state — table, weights, counts — is
        bit-identical to the grid-path constructor."""
        return cls(graph.grid(), graph.slot_stencil(), node_of_pos,
                   num_nodes=num_nodes, weighted=weighted)

    # -- read-only views ----------------------------------------------------
    @property
    def j_sum(self) -> float:
        # identical accumulation order to evaluate(): total += w * count
        total = 0.0
        for j, w in enumerate(self.weights):
            total += float(self.weights[j]) * float(self._count_off[j])
        return total

    def _per_node(self) -> np.ndarray:
        # rebuilt from counts only after a commit (cache keeps repeated
        # j_max queries between swaps at O(N) instead of O(N*k))
        if self._per_node_cache is None:
            per_node = np.zeros(self.n_nodes, dtype=np.float64)
            for j, w in enumerate(self.weights):
                per_node += w * self._count_node[:, j]
            self._per_node_cache = per_node
        return self._per_node_cache

    @property
    def per_node(self) -> np.ndarray:
        return self._per_node().copy()

    @property
    def j_max(self) -> float:
        return float(self._per_node().max(initial=0.0))

    @property
    def count_node(self) -> np.ndarray:
        """(N, k) int64 crossing edges per source node and offset."""
        return self._count_node.copy()

    def cost(self) -> MappingCost:
        per_node = self.per_node
        bottleneck = int(per_node.argmax()) if self.n_nodes else 0
        return MappingCost(j_sum=self.j_sum,
                           j_max=float(per_node.max(initial=0.0)),
                           per_node=per_node, bottleneck=bottleneck)

    # -- edge enumeration ---------------------------------------------------
    def _edges_touching(self, positions: Sequence[int]) \
            -> List[Tuple[int, int, int]]:
        """Directed stencil edges (src, dst, offset) with an endpoint in
        ``positions``, each listed exactly once."""
        S = set(int(p) for p in positions)
        t = self.table
        edges: List[Tuple[int, int, int]] = []
        for s in S:
            for j in range(self.stencil.k):
                if t.out_valid[j, s]:
                    edges.append((s, int(t.out_tgt[j, s]), j))
                if t.in_valid[j, s]:
                    src = int(t.in_src[j, s])
                    if src not in S:   # else already listed as its out-edge
                        edges.append((src, s, j))
        return edges

    def _delta(self, overrides: Dict[int, int]) -> Delta:
        """Delta for reassigning ``overrides`` (position -> new node)."""
        node = self.node_of_pos
        d_count_off = np.zeros(self.stencil.k, dtype=np.int64)
        d_count_node: Dict[Tuple[int, int], int] = {}

        def bump(n: int, j: int, by: int):
            key = (n, j)
            d_count_node[key] = d_count_node.get(key, 0) + by

        for (u, v, j) in self._edges_touching(tuple(overrides)):
            old_u, old_v = int(node[u]), int(node[v])
            new_u = overrides.get(u, old_u)
            new_v = overrides.get(v, old_v)
            if old_u != old_v:
                d_count_off[j] -= 1
                bump(old_u, j, -1)
            if new_u != new_v:
                d_count_off[j] += 1
                bump(new_u, j, +1)
        d_j_sum = 0.0
        for j in range(self.stencil.k):
            d_j_sum += float(self.weights[j]) * float(d_count_off[j])
        return Delta(d_j_sum, d_count_off,
                     {k: v for k, v in d_count_node.items() if v != 0})

    # -- proposals ----------------------------------------------------------
    def delta_move(self, pos: int, new_node: int) -> Delta:
        """Delta if position ``pos`` is reassigned to ``new_node``.

        Note a bare move changes the per-node cardinalities — mapping
        pipelines that must respect the scheduler allocation should use
        :meth:`delta_swap` instead.
        """
        if not 0 <= new_node < self.n_nodes:
            raise ValueError(f"node {new_node} out of range")
        return self._delta({int(pos): int(new_node)})

    def delta_swap(self, p: int, q: int) -> Delta:
        """Delta if positions ``p`` and ``q`` exchange owning nodes."""
        p, q = int(p), int(q)
        return self._delta({p: int(self.node_of_pos[q]),
                            q: int(self.node_of_pos[p])})

    def delta_swap_j_sum(self, p: int, q: int) -> float:
        """J_sum-only fast path for swap proposals."""
        return self.delta_swap(p, q).d_j_sum

    def batch_swap_deltas(self, p_arr: Sequence[int], q_arr: Sequence[int],
                          with_loads: bool = False) -> BatchSwapDelta:
        """Score ``m`` swap proposals ``(p_arr[i], q_arr[i])`` in one shot.

        Enumerates, per offset, the same four directed-edge groups the
        scalar :meth:`delta_swap` walks — out-edges of p, out-edges of q,
        in-edges of p from outside the pair, in-edges of q from outside the
        pair — so every edge incident to a pair is counted exactly once and
        the integer ``d_count_off`` matches the scalar path bit-for-bit.

        ``with_loads=True`` additionally scatters the per-node count
        changes into an (m, N) matrix and returns the exact post-swap
        ``new_per_node`` / ``new_j_max`` (needed by J_max-objective
        refinement); it costs O(m * N) extra memory, so leave it off for
        pure J_sum scoring.
        """
        P = np.atleast_1d(np.asarray(p_arr, dtype=np.int64))
        Q = np.atleast_1d(np.asarray(q_arr, dtype=np.int64))
        if P.shape != Q.shape or P.ndim != 1:
            raise ValueError("p_arr and q_arr must be 1-d of equal length")
        if P.size and (P.min() < 0 or P.max() >= self.grid.size
                       or Q.min() < 0 or Q.max() >= self.grid.size):
            raise ValueError("positions out of range")
        node, t, k, m = self.node_of_pos, self.table, self.stencil.k, P.size
        A, B = node[P], node[Q]
        rows = np.arange(m)
        d_count_off = np.zeros((m, k), dtype=np.int64)
        new_per_node = (np.zeros((m, self.n_nodes), dtype=np.float64)
                        if with_loads else None)
        for j in range(k):
            dc = (np.zeros((m, self.n_nodes), dtype=np.int64)
                  if with_loads else None)
            # out-edges of p: source owner a -> b; target owner unchanged
            # unless the target is the partner (or, on degenerate periodic
            # axes, p itself).
            v1, t1 = t.out_valid[j, P], t.out_tgt[j, P]
            nv1 = np.where(t1 == Q, A, np.where(t1 == P, B, node[t1]))
            old1 = v1 & (node[t1] != A)
            new1 = v1 & (nv1 != B)
            # out-edges of q (mirror)
            v3, t3 = t.out_valid[j, Q], t.out_tgt[j, Q]
            nv3 = np.where(t3 == P, B, np.where(t3 == Q, A, node[t3]))
            old3 = v3 & (node[t3] != B)
            new3 = v3 & (nv3 != A)
            # in-edges from outside the pair (pair-internal edges are
            # already listed as out-edges above, same dedup as the scalar
            # ``src not in S`` rule)
            s2 = t.in_src[j, P]
            v2 = t.in_valid[j, P] & (s2 != Q) & (s2 != P)
            old2 = v2 & (node[s2] != A)
            new2 = v2 & (node[s2] != B)
            s4 = t.in_src[j, Q]
            v4 = t.in_valid[j, Q] & (s4 != P) & (s4 != Q)
            old4 = v4 & (node[s4] != B)
            new4 = v4 & (node[s4] != A)
            d_count_off[:, j] = (
                (new1.astype(np.int64) - old1) + (new2.astype(np.int64) - old2)
                + (new3.astype(np.int64) - old3) + (new4.astype(np.int64) - old4))
            if with_loads:
                # outgoing loads are counted at the *source* node
                np.subtract.at(dc, (rows[old1], A[old1]), 1)
                np.add.at(dc, (rows[new1], B[new1]), 1)
                np.subtract.at(dc, (rows[old3], B[old3]), 1)
                np.add.at(dc, (rows[new3], A[new3]), 1)
                n2 = node[s2]
                np.add.at(dc, (rows[new2 & ~old2], n2[new2 & ~old2]), 1)
                np.subtract.at(dc, (rows[old2 & ~new2], n2[old2 & ~new2]), 1)
                n4 = node[s4]
                np.add.at(dc, (rows[new4 & ~old4], n4[new4 & ~old4]), 1)
                np.subtract.at(dc, (rows[old4 & ~new4], n4[old4 & ~new4]), 1)
                # same order as peek_per_node: w_j * (count + d), j ascending
                new_per_node += self.weights[j] * (self._count_node[:, j][None, :] + dc)
        d_j_sum = np.zeros(m, dtype=np.float64)
        for j in range(k):
            d_j_sum += float(self.weights[j]) * d_count_off[:, j]
        new_j_max = (new_per_node.max(axis=1, initial=0.0)
                     if with_loads else None)
        return BatchSwapDelta(P, Q, d_count_off, d_j_sum,
                              new_per_node, new_j_max)

    def peek_per_node(self, delta: Delta) -> np.ndarray:
        """per_node as it would be after applying ``delta`` (no mutation),
        rebuilt from counts — exact w.r.t. the committed state."""
        counts = self._count_node.copy()
        for (n, j), by in delta.d_count_node.items():
            counts[n, j] += by
        per_node = np.zeros(self.n_nodes, dtype=np.float64)
        for j in range(self.stencil.k):
            per_node += self.weights[j] * counts[:, j]
        return per_node

    def peek_j_max(self, delta: Delta) -> float:
        """j_max after ``delta``, O(N + touched): adjusts only the touched
        nodes of the cached per_node (advisory — may differ from the exact
        count-rebuilt value by an ulp for non-dyadic float weights)."""
        per_node = self._per_node().copy()
        for (n, j), by in delta.d_count_node.items():
            per_node[n] += self.weights[j] * by
        return float(per_node.max(initial=0.0))

    # -- commits ------------------------------------------------------------
    def _apply(self, overrides: Dict[int, int], delta: Delta) -> Delta:
        self._count_off += delta.d_count_off
        for (n, j), by in delta.d_count_node.items():
            self._count_node[n, j] += by
        for pos, n in overrides.items():
            self.node_of_pos[pos] = n
        self._per_node_cache = None
        return delta

    def apply_move(self, pos: int, new_node: int) -> Delta:
        delta = self.delta_move(pos, new_node)
        return self._apply({int(pos): int(new_node)}, delta)

    def apply_swap(self, p: int, q: int) -> Delta:
        p, q = int(p), int(q)
        overrides = {p: int(self.node_of_pos[q]), q: int(self.node_of_pos[p])}
        delta = self._delta(overrides)
        return self._apply(overrides, delta)

    # -- boundary extraction (the refiner's candidate set) -------------------
    def boundary_positions(self) -> np.ndarray:
        """Positions with at least one crossing incident edge, ascending."""
        node, t = self.node_of_pos, self.table
        on_boundary = np.zeros(self.grid.size, dtype=bool)
        for j in range(self.stencil.k):
            valid, tgt = t.out_valid[j], t.out_tgt[j]
            crossing = valid & (node != node[tgt])
            on_boundary |= crossing
            # the target of a crossing out-edge is on the boundary too
            on_boundary[tgt[crossing]] = True
        return np.nonzero(on_boundary)[0]

    def neighbors_of(self, pos: int) -> np.ndarray:
        """Distinct stencil neighbours (out or in) of ``pos``, ascending."""
        t, pos = self.table, int(pos)
        out = t.out_tgt[t.out_valid[:, pos], pos]
        inc = t.in_src[t.in_valid[:, pos], pos]
        return np.unique(np.concatenate([out, inc]))

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"IncrementalCost(p={self.grid.size}, k={self.stencil.k}, "
                f"N={self.n_nodes}, j_sum={self.j_sum})")


@dataclass(frozen=True)
class PortfolioSwapDelta:
    """Vectorized effect of ``m`` swap proposals, each scored against its
    *own* portfolio state (row ``rows[i]`` of a :class:`PortfolioCost`).

    Integer fields are bit-exact with the scalar path: ``d_count_off[i]``
    equals ``IncrementalCost(..., assignments[rows[i]]).delta_swap(p[i],
    q[i]).d_count_off`` and ``new_per_node[i]`` equals the matching
    ``peek_per_node`` rebuild (same ascending-offset ``w * count``
    accumulation), so ``d_j_sum`` / ``new_j_max`` match bitwise too."""

    rows: np.ndarray                      # (m,) int64 portfolio state index
    p: np.ndarray                         # (m,) int64
    q: np.ndarray                         # (m,) int64
    d_count_off: np.ndarray               # (m, k) int64
    d_j_sum: np.ndarray                   # (m,) float64
    new_per_node: Optional[np.ndarray]    # (m, N) float64 or None
    new_j_max: Optional[np.ndarray]       # (m,) float64 or None
    d_count_node: Optional[np.ndarray]    # (m, N, k) int64 or None

    @property
    def size(self) -> int:
        return int(self.p.size)


class PortfolioCost:
    """K independent :class:`IncrementalCost` states advanced in lock-step.

    This is the portfolio-mode counterpart of
    :meth:`IncrementalCost.batch_swap_deltas`: instead of scoring ``m``
    proposals against one assignment, :meth:`swap_deltas` scores one
    proposal *per portfolio member* against that member's own assignment —
    the inner loop of :class:`~repro.core.refine.PortfolioRefiner`, where K
    simulated-annealing ladders each propose a swap per move and all K
    frontiers are scored in a handful of numpy passes.

    State layout mirrors the scalar class, stacked along a leading K axis:
    ``node`` is (K, p), the integer crossing counts are (K, k) and
    (K, N, k), and the cached per-node loads (K, N) are rebuilt from counts
    with the same ascending-offset accumulation — so every row of every
    quantity is bit-exact with a scalar ``IncrementalCost`` tracking the
    same assignment, for arbitrary float weights.  The neighbour table is
    built once and shared by all K states.

    Usage::

        pc = PortfolioCost(grid, stencil, assignments, num_nodes=N)  # (K, p)
        d = pc.swap_deltas(rows, P, Q)      # one proposal per listed row
        accept = d.new_j_max < pc.j_max()[rows]
        pc.apply_swaps(rows[accept], P[accept], Q[accept])
    """

    def __init__(self, grid: CartGrid, stencil: Stencil,
                 assignments: np.ndarray, num_nodes: Optional[int] = None,
                 weighted=False, table: Optional[NeighborTable] = None,
                 counts: Optional[Tuple[np.ndarray, np.ndarray]] = None):
        assignments = np.asarray(assignments, dtype=np.int64)
        if assignments.ndim != 2 or assignments.shape[1] != grid.size:
            raise ValueError(
                f"assignments must have shape (K, {grid.size})")
        self.grid = grid
        self.stencil = stencil
        self.table = table if table is not None \
            else NeighborTable.build(grid, stencil)
        self.n_starts = int(assignments.shape[0])
        self.n_nodes = int(num_nodes if num_nodes is not None
                           else assignments.max() + 1)
        self.weighted = resolve_weighted(weighted, stencil)
        self.weights = (stencil.weight_array() if self.weighted
                        else np.ones(stencil.k))
        self.node = assignments.copy()
        k = stencil.k
        if counts is not None:
            # precomputed integer crossing counts (the jax kernel,
            # :func:`stacked_crossing_counts`).  Counts are pure integers,
            # so any correct producer is bit-interchangeable with the loop
            # below; shapes are checked, values trusted.
            count_off, count_node = counts
            self._count_off = np.array(count_off, dtype=np.int64)
            self._count_node = np.array(count_node, dtype=np.int64)
            if self._count_off.shape != (self.n_starts, k) \
                    or self._count_node.shape != (self.n_starts,
                                                  self.n_nodes, k):
                raise ValueError("precomputed counts have wrong shapes")
        else:
            self._count_off, self._count_node = stacked_count_arrays(
                self.table, self.node, self.n_nodes)
        self._per_node = np.zeros((self.n_starts, self.n_nodes),
                                  dtype=np.float64)
        self._rebuild_rows(np.arange(self.n_starts))

    @classmethod
    def from_graph(cls, graph, assignments: np.ndarray,
                   num_nodes: Optional[int] = None, weighted="auto",
                   table: Optional[NeighborTable] = None,
                   counts=None) -> "PortfolioCost":
        """K stacked cost states over a
        :class:`~repro.core.graph.CommGraph` (slot decomposition as the
        stencil — see :meth:`IncrementalCost.from_graph`)."""
        return cls(graph.grid(), graph.slot_stencil(), assignments,
                   num_nodes=num_nodes, weighted=weighted, table=table,
                   counts=counts)

    def _rebuild_rows(self, rows: np.ndarray) -> None:
        # same ascending-offset `per_node += w * count` accumulation as the
        # scalar cache rebuild, so each row matches it bit-for-bit
        out = np.zeros((rows.size, self.n_nodes), dtype=np.float64)
        for j in range(self.stencil.k):
            out += self.weights[j] * self._count_node[rows, :, j]
        self._per_node[rows] = out

    # -- read-only views ----------------------------------------------------
    def j_sum(self) -> np.ndarray:
        """(K,) j_sum per state, same accumulation order as the scalar."""
        total = np.zeros(self.n_starts, dtype=np.float64)
        for j in range(self.stencil.k):
            total += float(self.weights[j]) * self._count_off[:, j]
        return total

    def per_node(self) -> np.ndarray:
        return self._per_node.copy()

    def j_max(self) -> np.ndarray:
        """(K,) bottleneck load per state (from the counts-rebuilt cache)."""
        return self._per_node.max(axis=1, initial=0.0)

    def assignment(self, row: int) -> np.ndarray:
        return self.node[int(row)].copy()

    def cost(self, row: int) -> MappingCost:
        per_node = self._per_node[int(row)].copy()
        bottleneck = int(per_node.argmax()) if self.n_nodes else 0
        j_sum = 0.0
        for j in range(self.stencil.k):
            j_sum += float(self.weights[j]) * float(self._count_off[row, j])
        return MappingCost(j_sum=j_sum,
                           j_max=float(per_node.max(initial=0.0)),
                           per_node=per_node, bottleneck=bottleneck)

    # -- boundary extraction ------------------------------------------------
    def boundary_masks(self) -> np.ndarray:
        """(K, p) bool: positions with a crossing incident edge, per state.
        ``np.nonzero(mask[i])[0]`` reproduces the scalar
        :meth:`IncrementalCost.boundary_positions` ordering exactly."""
        on_b = np.zeros((self.n_starts, self.grid.size), dtype=bool)
        for j in range(self.stencil.k):
            valid, tgt = self.table.out_valid[j], self.table.out_tgt[j]
            crossing = valid[None, :] & (self.node != self.node[:, tgt])
            on_b |= crossing
            rr, pp = np.nonzero(crossing)
            on_b[rr, tgt[pp]] = True
        return on_b

    # -- proposals ----------------------------------------------------------
    def swap_deltas(self, rows, p_arr, q_arr, with_loads: bool = True,
                    with_counts: bool = False) -> PortfolioSwapDelta:
        """Score ``m`` swap proposals, proposal i against state ``rows[i]``.

        Same four directed-edge groups per offset as
        :meth:`IncrementalCost.batch_swap_deltas`, with every node lookup
        routed through the proposal's own state row.  ``with_loads``
        materializes the exact post-swap (m, N) ``new_per_node`` /
        ``new_j_max`` (chunked over proposals so peak extra memory respects
        :data:`LOAD_CHUNK_ELEMS`); ``with_counts`` additionally returns the
        integer (m, N, k) per-node count changes (the commit payload
        :meth:`apply_swaps` uses).
        """
        rows = np.atleast_1d(np.asarray(rows, dtype=np.int64))
        P = np.atleast_1d(np.asarray(p_arr, dtype=np.int64))
        Q = np.atleast_1d(np.asarray(q_arr, dtype=np.int64))
        if not (rows.shape == P.shape == Q.shape) or rows.ndim != 1:
            raise ValueError("rows, p_arr, q_arr must be 1-d of equal length")
        if rows.size:
            if rows.min() < 0 or rows.max() >= self.n_starts:
                raise ValueError("portfolio rows out of range")
            if (P.min() < 0 or P.max() >= self.grid.size
                    or Q.min() < 0 or Q.max() >= self.grid.size):
                raise ValueError("positions out of range")
        m, k = P.size, self.stencil.k
        d_count_off = np.zeros((m, k), dtype=np.int64)
        new_per_node = (np.empty((m, self.n_nodes), dtype=np.float64)
                        if with_loads else None)
        d_count_node = (np.zeros((m, self.n_nodes, k), dtype=np.int64)
                        if with_counts else None)
        # the load/count paths materialize a (chunk, N, k) scratch, so the
        # chunk is sized against N * k to keep peak memory on budget
        chunk = m if not (with_loads or with_counts) else \
            max(1, LOAD_CHUNK_ELEMS // max(1, self.n_nodes * k))
        for s in range(0, m, max(chunk, 1)):
            e = min(s + chunk, m)
            self._swap_deltas_chunk(rows[s:e], P[s:e], Q[s:e],
                                    d_count_off[s:e],
                                    new_per_node[s:e] if with_loads else None,
                                    d_count_node[s:e] if with_counts else None)
        d_j_sum = np.zeros(m, dtype=np.float64)
        for j in range(k):
            d_j_sum += float(self.weights[j]) * d_count_off[:, j]
        new_j_max = (new_per_node.max(axis=1, initial=0.0)
                     if with_loads else None)
        return PortfolioSwapDelta(rows, P, Q, d_count_off, d_j_sum,
                                  new_per_node, new_j_max, d_count_node)

    def _swap_deltas_chunk(self, rows, P, Q, d_count_off, new_per_node,
                           d_count_node) -> None:
        """Whole-stencil vectorized scoring: every (offset, edge-group)
        quantity is computed as a (k, m) array in one pass, so the per-move
        cost of a portfolio ladder is a fixed handful of numpy ops instead
        of O(k) interpreted iterations."""
        node, t, m, k = self.node, self.table, P.size, self.stencil.k
        A, B = node[rows, P], node[rows, Q]                  # (m,)
        rows2, A2, B2 = rows[None, :], A[None, :], B[None, :]
        P2, Q2 = P[None, :], Q[None, :]
        # out-edges of p (target owner swaps if it is the partner or, on
        # degenerate periodic axes, p itself — same as the scalar path)
        T1 = t.out_tgt[:, P]                                 # (k, m)
        N1 = node[rows2, T1]
        NV1 = np.where(T1 == Q2, A2, np.where(T1 == P2, B2, N1))
        old1 = t.out_valid[:, P] & (N1 != A2)
        new1 = t.out_valid[:, P] & (NV1 != B2)
        # out-edges of q (mirror)
        T3 = t.out_tgt[:, Q]
        N3 = node[rows2, T3]
        NV3 = np.where(T3 == P2, B2, np.where(T3 == Q2, A2, N3))
        old3 = t.out_valid[:, Q] & (N3 != B2)
        new3 = t.out_valid[:, Q] & (NV3 != A2)
        # in-edges from outside the pair
        S2 = t.in_src[:, P]
        V2 = t.in_valid[:, P] & (S2 != Q2) & (S2 != P2)
        N2 = node[rows2, S2]
        old2 = V2 & (N2 != A2)
        new2 = V2 & (N2 != B2)
        S4 = t.in_src[:, Q]
        V4 = t.in_valid[:, Q] & (S4 != P2) & (S4 != Q2)
        N4 = node[rows2, S4]
        old4 = V4 & (N4 != B2)
        new4 = V4 & (N4 != A2)
        d_count_off[:] = (
            (new1.astype(np.int64) - old1) + (new2.astype(np.int64) - old2)
            + (new3.astype(np.int64) - old3)
            + (new4.astype(np.int64) - old4)).T
        if new_per_node is None and d_count_node is None:
            return
        own = d_count_node if d_count_node is not None else \
            np.zeros((m, self.n_nodes, k), dtype=np.int64)

        def scatter(mask, node_vals, by):
            jj, mm = np.nonzero(mask)
            np.add.at(own, (mm, node_vals[jj, mm], jj), by)

        scatter(old1, np.broadcast_to(A2, (k, m)), -1)
        scatter(new1, np.broadcast_to(B2, (k, m)), +1)
        scatter(old3, np.broadcast_to(B2, (k, m)), -1)
        scatter(new3, np.broadcast_to(A2, (k, m)), +1)
        scatter(new2 & ~old2, N2, +1)
        scatter(old2 & ~new2, N2, -1)
        scatter(new4 & ~old4, N4, +1)
        scatter(old4 & ~new4, N4, -1)
        if new_per_node is not None:
            # w_j * (count + d), j ascending — matches peek_per_node
            new_per_node[:] = 0.0
            for j in range(k):
                new_per_node += self.weights[j] * (
                    self._count_node[rows, :, j] + own[:, :, j])

    # -- commits ------------------------------------------------------------
    def commit(self, delta: PortfolioSwapDelta, idx=None) -> None:
        """Apply already-scored proposals (requires ``with_counts``); the
        optional ``idx`` selects a subset of the delta's proposals (the
        accepted ones).  Selected rows must be distinct.  The affected
        rows' per-node caches are rebuilt from counts, exactly as the
        scalar class does after a commit."""
        if delta.d_count_node is None:
            raise ValueError("commit needs a delta scored with_counts=True")
        sel = np.arange(delta.size) if idx is None \
            else np.atleast_1d(np.asarray(idx, dtype=np.int64))
        rows, P, Q = delta.rows[sel], delta.p[sel], delta.q[sel]
        if np.unique(rows).size != rows.size:
            raise ValueError("commit: one swap per row at most")
        if rows.size == 0:
            return
        self._count_off[rows] += delta.d_count_off[sel]
        self._count_node[rows] += delta.d_count_node[sel]
        pv, qv = self.node[rows, P].copy(), self.node[rows, Q].copy()
        self.node[rows, P] = qv
        self.node[rows, Q] = pv
        self._rebuild_rows(rows)

    def apply_swaps(self, rows, p_arr, q_arr) -> PortfolioSwapDelta:
        """Score-and-commit one swap per listed row (rows must be
        distinct)."""
        d = self.swap_deltas(rows, p_arr, q_arr, with_loads=False,
                             with_counts=True)
        self.commit(d)
        return d

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"PortfolioCost(K={self.n_starts}, p={self.grid.size}, "
                f"k={self.stencil.k}, N={self.n_nodes})")
