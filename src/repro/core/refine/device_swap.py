"""Score a swap pass's whole frontier on the accelerator.

:class:`~repro.core.refine.swap.SwapRefiner` scores every candidate pair
of a pass with :meth:`~repro.core.cost_delta.IncrementalCost.batch_swap_deltas`
on the host.  For the J_max objective that builds dense ``(m, N)`` load
matrices per offset, which is most of a polish's time, while the device
that ran the ladders sits idle.  :class:`DeviceSwapScorer` computes the
same two numbers per pair in one jitted program:

* ``d_j_sum`` -- the change in J_sum, from the same four directed-edge
  groups per offset and the same pair-internal dedup as the host;
* ``new_j_max`` -- the busiest node's load after the swap, from dense
  ``(chunk, N)`` per-node loads.

Each pair's neighbourhood (its positions' nodes, neighbours and their
nodes) is read from a per-position table by a one-hot matmul, not by
gathers: on a v5e, forty gathers of 65,536 indices took about 30 ms a
chunk, the matmul version about 2 ms a pass, transfers included.

Both come back as int32.  The scorer is built only for integer offset
weights, and only on an accelerator (:func:`device_swap_scorer` returns
``None`` otherwise): every quantity is then an integer below ``2**31``,
so the host's float64 values are reproduced exactly and a pass picks the
same swaps.

Pairs are scored in chunks of a fixed size (:data:`CHUNK`), padded with
pair ``(0, 0)`` whose scores the host drops, so one problem ``(p, N, k)``
compiles one program whatever the frontier's size.  The neighbour table
is uploaded once per problem; each pass uploads the assignment, the
``(N, k)`` crossing counts and the pairs, dispatches every chunk, then
fetches.  jax is imported only when a scorer is built.

Usage::

    scorer = device_swap_scorer(grid, stencil, weights)   # or None
    d_j_sum, new_j_max = scorer.score(ic, P, Q)           # (m,) int32 each
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from ..cost_delta import IncrementalCost, neighbor_table, table_key
from ..grid import CartGrid
from ..stencil import Stencil

__all__ = ["CHUNK", "DeviceSwapScorer", "device_swap_scorer"]

#: pairs per dispatched program.  On the paper's largest Fig. 8 instances
#: a polish pass scores up to 72,183 pairs in 2-D and 77,158 in 3-D (solves
#: on a v5e), so the largest passes run two chunks of the same program;
#: most run one.
CHUNK = 1 << 16

#: device copies of neighbour tables, keyed like the host table memo
_DEVICE_TABLES: "OrderedDict[tuple, tuple]" = OrderedDict()
_DEVICE_TABLES_MAX = 8


def _position_rows(node, out_valid, out_tgt, in_valid, in_src):
    """(p, 4k + 1) int32 per position: its node, then per offset its
    out-neighbour, that neighbour's node, its in-neighbour and that
    neighbour's node, each -1 where the stencil has no such edge."""
    import jax.numpy as jnp

    def masked(valid, x):
        return jnp.where(valid, x, -1)
    return jnp.concatenate([
        node[None], masked(out_valid, out_tgt),
        masked(out_valid, node[out_tgt]), masked(in_valid, in_src),
        masked(in_valid, node[in_src])]).T


def _lookup(rows, idx, bound: int):
    """``rows[idx]`` for int32 ``rows`` in ``[0, bound)``, as a one-hot
    matmul: the TPU runs it on its matrix unit, where a gather of tens of
    thousands of indices is slow.  Exact: the values are split into bytes,
    which bfloat16 holds exactly, and each output sums one product."""
    import jax.numpy as jnp
    p, f = rows.shape
    digits = 1
    while 256 ** digits < bound:
        digits += 1
    table = jnp.concatenate([(rows >> (8 * d)) & 255 for d in range(digits)],
                            axis=1).astype(jnp.bfloat16)
    one_hot = (idx[:, None] == jnp.arange(p, dtype=idx.dtype)[None, :])
    out = jnp.dot(one_hot.astype(jnp.bfloat16), table,
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    return sum(out[:, d * f:(d + 1) * f] << (8 * d) for d in range(digits))


@functools.lru_cache(maxsize=1)
def _scores_kernel():
    """The jitted scorer.  Its name ``swap_frontier_scores`` (module
    ``jit_swap_frontier_scores``) is stable for trace reductions; jax's
    jit cache keys the shapes, one program per ``(p, N, k, chunk)``."""
    import jax
    import jax.numpy as jnp

    def swap_frontier_scores(node, count_node, pq, weights, out_valid,
                             out_tgt, in_valid, in_src):
        P, Q = pq[0], pq[1]
        c = P.shape[0]
        n_nodes, k = count_node.shape
        rows = _position_rows(node, out_valid, out_tgt, in_valid, in_src)
        pair_rows = _lookup(rows + 1, jnp.concatenate([P, Q]),
                            max(node.shape[0], n_nodes) + 1) - 1
        RP, RQ = pair_rows[:c], pair_rows[c:]
        A, B = RP[:, 0], RQ[:, 0]
        nodes = jnp.arange(n_nodes, dtype=node.dtype)[None, :]
        d_sum = jnp.zeros(c, jnp.int32)
        d_a = jnp.zeros(c, jnp.int32)           # load change of node A
        d_b = jnp.zeros(c, jnp.int32)           # load change of node B
        d_load = jnp.zeros((c, n_nodes), jnp.int32)
        for j in range(k):
            w = weights[j]
            t1, n1, s2, n2 = (RP[:, 1 + i * k + j] for i in range(4))
            t3, n3, s4, n4 = (RQ[:, 1 + i * k + j] for i in range(4))
            # out-edges of p and of q: counted at the source, A or B; the
            # target's owner changes only if it is the partner (or, on
            # degenerate periodic axes, the position itself)
            nv1 = jnp.where(t1 == Q, A, jnp.where(t1 == P, B, n1))
            nv3 = jnp.where(t3 == P, B, jnp.where(t3 == Q, A, n3))
            v1, v3 = t1 >= 0, t3 >= 0
            new1 = (v1 & (nv1 != B)).astype(jnp.int32)
            old1 = (v1 & (n1 != A)).astype(jnp.int32)
            new3 = (v3 & (nv3 != A)).astype(jnp.int32)
            old3 = (v3 & (n3 != B)).astype(jnp.int32)
            # in-edges from outside the pair: counted at their source,
            # whose owner does not change (g = new crossing - old)
            v2 = (s2 >= 0) & (s2 != Q) & (s2 != P)
            v4 = (s4 >= 0) & (s4 != P) & (s4 != Q)
            g2 = (v2 & (n2 != B)).astype(jnp.int32) \
                - (v2 & (n2 != A)).astype(jnp.int32)
            g4 = (v4 & (n4 != A)).astype(jnp.int32) \
                - (v4 & (n4 != B)).astype(jnp.int32)
            d_sum = d_sum + w * (new1 - old1 + g2 + new3 - old3 + g4)
            d_a = d_a + w * (new3 - old1)
            d_b = d_b + w * (new1 - old3)
            d_load = d_load + jnp.where(n2[:, None] == nodes,
                                        (w * g2)[:, None], 0) \
                + jnp.where(n4[:, None] == nodes, (w * g4)[:, None], 0)
        d_load = d_load + jnp.where(A[:, None] == nodes, d_a[:, None], 0) \
            + jnp.where(B[:, None] == nodes, d_b[:, None], 0)
        load = (count_node * weights[None, :]).sum(axis=1)
        new_j_max = jnp.maximum((load[None, :] + d_load).max(axis=1), 0)
        return jnp.stack([d_sum, new_j_max])

    return jax.jit(swap_frontier_scores)


def _device_table(grid: CartGrid, stencil: Stencil) -> tuple:
    """The neighbour table on the device, uploaded once per problem."""
    import jax
    key = table_key(grid, stencil)
    dev = _DEVICE_TABLES.get(key)
    if dev is None:
        t = neighbor_table(grid, stencil)
        dev = tuple(jax.device_put(a) for a in (
            t.out_valid, t.out_tgt.astype(np.int32),
            t.in_valid, t.in_src.astype(np.int32)))
        _DEVICE_TABLES[key] = dev
        while len(_DEVICE_TABLES) > _DEVICE_TABLES_MAX:
            _DEVICE_TABLES.popitem(last=False)
    else:
        _DEVICE_TABLES.move_to_end(key)
    return dev


class DeviceSwapScorer:
    """Scores swap pairs of one problem on the device; built by
    :func:`device_swap_scorer`.  ``chunk`` is the fixed number of pairs
    per program (tests pass a small one)."""

    def __init__(self, grid: CartGrid, stencil: Stencil,
                 weights: np.ndarray, chunk: int = CHUNK):
        import jax
        self._put = jax.device_put
        self._table = _device_table(grid, stencil)
        self._weights = jax.device_put(np.asarray(weights, dtype=np.int32))
        self.chunk = int(chunk)

    def score(self, ic: IncrementalCost, P: np.ndarray,
              Q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``(d_j_sum, new_j_max)`` of swapping ``P[i]`` with ``Q[i]``
        against ``ic``'s committed state, as ``(m,)`` int32 arrays equal
        to ``ic.batch_swap_deltas(P, Q, with_loads=True)``'s values."""
        m, c = int(P.size), self.chunk
        if m == 0:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        put, kernel = self._put, _scores_kernel()
        node = put(ic.node_of_pos.astype(np.int32))
        count_node = put(ic.count_node.astype(np.int32))
        pq = np.zeros((2, -(-m // c) * c), dtype=np.int32)
        pq[0, :m], pq[1, :m] = P, Q
        outs = [kernel(node, count_node, put(pq[:, s:s + c]), self._weights,
                       *self._table) for s in range(0, m, c)]
        res = np.concatenate([np.asarray(o) for o in outs], axis=1)[:, :m]
        return res[0], res[1]


def _accelerator() -> bool:
    """True when jax's default backend is an accelerator.  On the CPU
    backend the device is the host itself, and numpy scores a pass faster
    than the padded chunk does (a 10 x 9 device solve: 0.14 s with numpy
    scoring, 1.76 s with the scorer)."""
    import jax
    return jax.default_backend() != "cpu"


def device_swap_scorer(grid: CartGrid, stencil: Stencil,
                       weights: np.ndarray,
                       chunk: int = CHUNK) -> Optional[DeviceSwapScorer]:
    """A scorer for the problem, or ``None`` where numpy should score: on
    the CPU backend, or where the device's int32 could differ from the
    host's float64 (offset weights that are not integers, or loads that
    could reach ``2**31``: every offset adds at most one crossing edge per
    position)."""
    w = np.asarray(weights, dtype=np.float64)
    if not _accelerator() or not np.array_equal(w, np.round(w)) \
            or grid.size * float(np.abs(w).sum()) >= 2.0 ** 31:
        return None
    return DeviceSwapScorer(grid, stencil, w, chunk=chunk)
