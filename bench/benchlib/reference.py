"""The plain reference: crossing counts, J_max and J_sum, the blocked layout.

Written from the paper's definitions (arXiv:2005.09521, section II) and
nothing of the program under test:

* a Cartesian grid of ``p`` positions in row-major order; the stencil's
  offset ``o`` gives position ``x`` the out-neighbour ``x + o`` when that
  lies inside the grid (no wrap on a non-periodic axis);
* a mapping gives each position a node; a directed edge crosses when its
  two endpoints sit on different nodes;
* ``J_sum`` counts crossing edges, ``J_max`` is the largest number of
  crossing edges that leave one node;
* the ``blocked`` layout is the default rank order: the first ``c_0``
  positions on node 0, the next ``c_1`` on node 1, and so on.

Every count is an integer, so every comparison with it is exact.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

__all__ = ["neighbours", "count_state", "keys", "blocked", "capacities_hold"]


def neighbours(dims: Sequence[int], offsets: Sequence[Sequence[int]],
               periodic: Sequence[bool]) -> Tuple[np.ndarray, np.ndarray]:
    """``(valid, target)``, each ``(k, p)``: whether position ``x`` has an
    out-neighbour under offset ``j``, and which position it is."""
    dims = np.asarray(dims, dtype=np.int64)
    p = int(np.prod(dims))
    coords = np.stack(np.unravel_index(np.arange(p), tuple(dims)), axis=1)
    k = len(offsets)
    valid = np.ones((k, p), dtype=bool)
    target = np.zeros((k, p), dtype=np.int64)
    for j, off in enumerate(offsets):
        t = coords + np.asarray(off, dtype=np.int64)[None, :]
        for ax in range(len(dims)):
            if periodic[ax]:
                t[:, ax] %= dims[ax]
            else:
                valid[j] &= (t[:, ax] >= 0) & (t[:, ax] < dims[ax])
        t = np.clip(t, 0, dims - 1)
        target[j] = np.ravel_multi_index(tuple(t.T), tuple(dims))
    return valid, target


def count_state(table: Tuple[np.ndarray, np.ndarray], nodes: np.ndarray,
                num_nodes: int) -> np.ndarray:
    """``(R, N, k)`` crossing counts of stacked ``(R, p)`` assignments:
    entry ``[r, n, j]`` counts the positions on node ``n`` whose offset-``j``
    edge crosses to another node in row ``r``."""
    valid, target = table
    A = np.asarray(nodes, dtype=np.int64)
    if A.ndim == 1:
        A = A[None, :]
    R = A.shape[0]
    k = valid.shape[0]
    N = int(num_nodes)
    out = np.zeros((R, N, k), dtype=np.int64)
    row_base = (np.arange(R, dtype=np.int64) * N)[:, None]
    for j in range(k):
        crossing = valid[j][None, :] & (A != A[:, target[j]])
        idx = (row_base + A)[crossing]
        out[:, :, j] = np.bincount(idx, minlength=R * N).reshape(R, N)
    return out


def keys(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(J_max, J_sum)`` per row of an ``(R, N, k)`` count state."""
    per_node = counts.sum(axis=2)
    return per_node.max(axis=1), per_node.sum(axis=1)


def blocked(capacities: Sequence[int]) -> np.ndarray:
    """Node of each position under the default rank order."""
    caps = np.asarray(capacities, dtype=np.int64)
    return np.repeat(np.arange(caps.size), caps)


def capacities_hold(nodes: np.ndarray, capacities: Sequence[int]) -> bool:
    """True when ``nodes`` places exactly ``capacities[n]`` positions on
    each node ``n`` (so it is a bijection onto the allocation's slots)."""
    caps = np.asarray(capacities, dtype=np.int64)
    a = np.asarray(nodes)
    if a.ndim != 1 or a.size != caps.sum():
        return False
    if a.size and (a.min() < 0 or a.max() >= caps.size):
        return False
    return bool(np.array_equal(np.bincount(a, minlength=caps.size), caps))
