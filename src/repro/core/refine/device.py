"""Device-resident annealing portfolio: vmapped Metropolis ladders on the
accelerator.

The numpy portfolio (:mod:`repro.core.refine.portfolio`) advances K ladders
per move but runs the proposal loop in Python, so K tops out around 8-64.
This engine moves the *whole temperature* onto the accelerator:

* the integer crossing-count state for K stacked assignments — the
  ``(K, N, k)`` ``count_node`` arrays of
  :func:`~repro.core.cost_delta.stacked_crossing_counts` — lives on the
  device for the entire run;
* proposals are drawn with ``jax.random`` (one key per ladder, split per
  move, so a ladder's stream depends only on its own seed — deterministic
  and batch-composition-independent);
* a vmapped Metropolis accept advances all K ladders per move (position
  from the temperature's boundary snapshot, cross-node partner, uphill
  acceptance ``u < exp(-d_e/T)`` — the same proposal shape as the host
  kernel);
* ``jax.lax.scan`` runs a full temperature of ``sa_moves`` moves as one
  jitted call, with exactly **one host round-trip per temperature
  boundary** (per-ladder keys, accepted counts, done flags — a few small
  vectors), where the shared boundary protocol
  (:class:`~repro.core.refine.engine.BoundaryController`: best-seen,
  early-kill, restart/retune) runs on the coordinator exactly as it does
  for the serial and sharded engines;
* a ladder within one swap of its start at a temperature boundary (at
  most two positions differ) runs its next temperature at the schedule's
  first (hottest) one, so a ladder ends where it started only by undoing
  two or more swaps in its last temperature (the host engines have no
  such rule; from a start where few swaps raise J_max, as on the 2-D
  Fig. 8 instance, it never fires);
* each ladder additionally tracks its lexicographic **best-seen state on
  device** (the host engines only keep boundary keys), so at equal
  proposal budget the device portfolio's candidate set has up to 2K
  entries — end states plus walk minima — before polish;
* the deterministic rounds and the survivors' polish run the host's swap
  passes, but with integer weights their pairs are scored on the device
  (:mod:`repro.core.refine.device_swap`): the same integer scores, so the
  same swaps and the same layout as numpy scoring.

Draw-for-draw parity with the numpy rng is not feasible (different
generators), so the correctness contract is carried by
``tests/test_device_portfolio.py``: integer-exact count state vs
``evaluate`` after every boundary, alive-mask monotonicity,
seed-determinism of the device rng stream, and the pinned dominance /
K-scaling claims of ``benchmarks/refine_suite.py --device``
(``results/BENCH_7.json``).

Restart ladders use **preallocated slots**: ``restart_slots`` extra rows
ride in the stacked state from the start (inactive until spawned), so a
spawn at a temperature boundary is a row write, never a shape change — the
jitted temperature kernel compiles once per (K + slots, p, N, k) shape.

Runs with a ``max_swaps`` budget or a ``pinned`` repair mask, whose
move-level coupling is host semantics, delegate to the single-process
:class:`~repro.core.refine.portfolio.PortfolioRefiner` with the same seeds
and schedule.

Usage::

    from repro.core import DevicePortfolioRefiner, get_mapper
    res = DevicePortfolioRefiner(k=256).refine(grid, st, a, num_nodes=N)
    m = get_mapper("device[k=1024]:hyperplane")
    m = get_mapper("device[k=64,restarts=auto,retune=true]:kdtree")
"""
from __future__ import annotations

import copy
import functools
import math
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ... import obs
from ..cost_delta import (IncrementalCost, PortfolioCost, neighbor_table,
                          stacked_crossing_counts)
from ..grid import CartGrid
from ..stencil import Stencil, resolve_weighted
from .engine import (BoundaryController, BoundaryReport, LadderEngine,
                     RestartSeeder, phase_stats)
from .portfolio import PortfolioRefiner
from .swap import RefineResult

__all__ = ["DeviceLadderEngine", "DevicePortfolioRefiner"]


def _row_lookup(values, idx):
    """``values[r, idx[r, ...]]`` for ``(R, p)`` values and ``(R, ...)``
    positions, as a select-and-sum over the p positions: one fused,
    lane-parallel reduction (no ``(R, ..., p)`` intermediate is stored).
    A position outside ``[0, p)`` reads 0."""
    import jax
    import jax.numpy as jnp
    R, p = values.shape
    x = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (p,), idx.ndim)
    v = values.reshape((R,) + (1,) * (idx.ndim - 1) + (p,))
    return jnp.where(x == idx[..., None], v, 0).sum(axis=-1)


def _swap_delta(node, pi, qi, a, b, n_nodes, out_valid, out_tgt, in_valid,
                in_src):
    """The exact integer ``count_node`` delta of swapping positions
    ``pi``/``qi`` (whose nodes are ``a``/``b``) in every row of ``node``,
    read with no indexed gather: ``(nbr, d_cn)``.

    Only edges with an endpoint in {p, q} change crossing status: the four
    directed edge groups out of p, out of q, into p and into q, each over
    the k offsets, in-edges from p or q deduped against the out groups.
    ``nbr`` (R, 4, k) is the current node of each group's other endpoint
    (``out_tgt[:, p]``, ``out_tgt[:, q]``, ``in_src[:, p]``,
    ``in_src[:, q]``; 0 where the edge is invalid).  ``d_cn`` (R, N, k)
    is dense over (N, k): group g's edge at offset j moves a crossing of
    offset j off its source's old node ``s_old`` and onto its new one
    ``s_new``, so ``d_cn[r, n, j] = sum_g new_c[r, g, j] [s_new == n] -
    old_c[r, g, j] [s_old == n]``.
    """
    import jax.numpy as jnp
    R, p = node.shape
    k = out_tgt.shape[0]
    # the tables' columns at p and q in one reduction; -1 marks no edge
    tables = jnp.concatenate([jnp.where(out_valid, out_tgt, -1),
                              jnp.where(in_valid, in_src, -1)])    # (2k, p)
    at = jnp.arange(p, dtype=jnp.int32) == jnp.stack(
        [pi, qi], axis=1)[:, :, None]                             # (R, 2, p)
    cols = jnp.where(at[:, :, None, :], tables, 0).sum(axis=-1)  # (R, 2, 2k)
    out_p, out_q = cols[:, 0, :k], cols[:, 1, :k]
    in_p, in_q = cols[:, 0, k:], cols[:, 1, k:]
    nbr = _row_lookup(node, jnp.stack([out_p, out_q, in_p, in_q], axis=1))
    P = jnp.broadcast_to(pi[:, None], (R, k))
    Q = jnp.broadcast_to(qi[:, None], (R, k))
    A = jnp.broadcast_to(a[:, None], (R, k))
    B = jnp.broadcast_to(b[:, None], (R, k))
    src = jnp.stack([P, Q, in_p, in_q], axis=1)                 # (R, 4, k)
    dst = jnp.stack([out_p, out_q, P, Q], axis=1)
    s_old = jnp.stack([A, B, nbr[:, 2], nbr[:, 3]], axis=1)
    d_old = jnp.stack([nbr[:, 0], nbr[:, 1], A, B], axis=1)

    def remap(pos, cur):            # node at pos after the swap
        return jnp.where(pos == pi[:, None, None], b[:, None, None],
                         jnp.where(pos == qi[:, None, None],
                                   a[:, None, None], cur))

    s_new, d_new = remap(src, s_old), remap(dst, d_old)
    valid = jnp.stack([out_p >= 0, out_q >= 0,
                       (in_p >= 0) & (in_p != P) & (in_p != Q),
                       (in_q >= 0) & (in_q != P) & (in_q != Q)], axis=1)
    old_c = valid & (s_old != d_old)
    new_c = valid & (s_new != d_new)
    n = jnp.arange(n_nodes, dtype=node.dtype)[:, None]          # (N, 1)
    inc = (new_c[:, :, None, :] & (s_new[:, :, None, :] == n)).sum(
        axis=1, dtype=jnp.int32)
    dec = (old_c[:, :, None, :] & (s_old[:, :, None, :] == n)).sum(
        axis=1, dtype=jnp.int32)
    return nbr, inc - dec


def _swap_rows(node, pi, qi, a, b, accept):
    """Every accepting row of ``node`` with positions ``pi``/``qi`` set to
    ``b``/``a`` (q's write wins where they coincide, as with two indexed
    sets): one masked select over (R, p), no scatter."""
    import jax
    import jax.numpy as jnp
    x = jax.lax.broadcasted_iota(jnp.int32, node.shape, 1)
    acc = accept[:, None]
    return jnp.where(acc & (x == qi[:, None]), a[:, None],
                     jnp.where(acc & (x == pi[:, None]), b[:, None], node))


@functools.lru_cache(maxsize=16)
def _temperature_kernel(sa_moves: int):
    """Build (and cache) the jitted one-temperature kernel: ``sa_moves``
    is the static ``lax.scan`` length; every array shape is keyed by jax's
    own jit cache, so one callable serves every (rows, p, N, k) problem.

    The kernel replays the host ladder semantics per temperature: boundary
    snapshot once, then ``sa_moves`` batched Metropolis moves — position
    and partner drawn per ladder from the snapshot, the swap's exact
    integer count delta applied on accept, energy
    ``d_J_max + d_J_sum * eps`` — plus device-side best-seen tracking.
    All :math:`O(rows \\cdot p)` state stays on device; only the boundary
    report leaves.  The jitted function keeps the stable name
    ``ladder_temperature_scan`` (module ``jit_ladder_temperature_scan``),
    so a trace reduction can find it after a refactor.

    The scan step holds no indexed gather from, or scatter into, the
    per-row state: the nodes at p and q, the swap's neighbour lookups and
    table columns (:func:`_swap_delta`), its count delta (dense over
    (N, k)) and the row swap (:func:`_swap_rows`) are masked selects and
    compare-and-sums over the positions.  On the TPU an indexed gather or
    scatter is serialised index by index and cost ~30-120 µs a move each
    at the benchmark's sizes, about 80% of the kernel; the dense forms
    give the same integers, so the walks are the same bit for bit.
    """
    import jax
    import jax.numpy as jnp

    def ladder_temperature_scan(node, cn, keys, best_node, best_jmax,
                                best_jsum, done, live, temps, eps, weights,
                                out_valid, out_tgt, in_valid, in_src):
        R, N = cn.shape[:2]

        def loads(c):                           # (R, N, k) int -> (R, N)
            return jnp.einsum("rnk,k->rn", c.astype(jnp.float32), weights)

        def off_sum(c):                         # (R, N, k) int -> (R,)
            return jnp.einsum("rk,k->r",
                              c.sum(axis=1).astype(jnp.float32), weights)

        # temperature-boundary snapshot: a position is on the boundary when
        # it is an endpoint of any crossing edge (same set as the host
        # engine's PortfolioCost.boundary_masks)
        out_cross = out_valid[None] & (node[:, None, :] != node[:, out_tgt])
        in_cross = in_valid[None] & (node[:, None, :] != node[:, in_src])
        bmask = out_cross.any(axis=1) | in_cross.any(axis=1)    # (R, p)
        done = done | (bmask.sum(axis=1) < 2)
        active = live & ~done
        logit_p = jnp.where(bmask, 0.0, -jnp.inf)               # (R, p)

        def move(carry, _):
            node, cn, keys, bnode, bjmax, bjsum, acc = carry
            ks = jax.vmap(lambda kk: jax.random.split(kk, 4))(keys)
            keys_next, kp, kq, ku = ks[:, 0], ks[:, 1], ks[:, 2], ks[:, 3]
            # position, then cross-node partner, both from the snapshot
            # (current node values, like the host kernel's partner check)
            pi = jax.vmap(jax.random.categorical)(kp, logit_p)      # (R,)
            a = _row_lookup(node, pi[:, None])[:, 0]
            partner = bmask & (node != a[:, None])
            has_q = partner.any(axis=1)
            qi = jax.vmap(jax.random.categorical)(
                kq, jnp.where(partner, 0.0, -jnp.inf))
            b = _row_lookup(node, qi[:, None])[:, 0]
            _, d_cn = _swap_delta(node, pi, qi, a, b, N, out_valid, out_tgt,
                                  in_valid, in_src)
            cn_new = cn + d_cn
            jmax_old = loads(cn).max(axis=1)
            jmax_new = loads(cn_new).max(axis=1)
            d_jsum = jnp.einsum("rk,k->r",
                                d_cn.sum(axis=1).astype(jnp.float32), weights)
            d_e = jmax_new - jmax_old + d_jsum * eps
            u = jax.vmap(jax.random.uniform)(ku)
            accept = active & has_q & ((d_e <= 0.0)
                                       | (u < jnp.exp(-d_e / temps)))
            node = _swap_rows(node, pi, qi, a, b, accept)
            cn = jnp.where(accept[:, None, None], cn_new, cn)
            acc = acc + accept.astype(jnp.int32)
            # device-side best-seen: strict lexicographic improvement only,
            # so frozen (inactive) ladders never touch their snapshot
            cur_jmax = jnp.where(accept, jmax_new, jmax_old)
            cur_jsum = off_sum(cn)
            better = (cur_jmax < bjmax) | ((cur_jmax == bjmax)
                                           & (cur_jsum < bjsum))
            bnode = jnp.where(better[:, None], node, bnode)
            bjmax = jnp.where(better, cur_jmax, bjmax)
            bjsum = jnp.where(better, cur_jsum, bjsum)
            return (node, cn, keys_next, bnode, bjmax, bjsum, acc), None

        acc0 = jnp.zeros(R, dtype=jnp.int32)
        carry = (node, cn, keys, best_node, best_jmax, best_jsum, acc0)
        carry, _ = jax.lax.scan(move, carry, None, length=sa_moves)
        node, cn, keys, best_node, best_jmax, best_jsum, acc = carry
        return (node, cn, keys, best_node, best_jmax, best_jsum, done,
                acc, loads(cn).max(axis=1), off_sum(cn))

    return jax.jit(ladder_temperature_scan)


def _threefry_keys(seeds: Sequence[int]) -> np.ndarray:
    """(n, 2) uint32 raw keys, bit for bit ``jax.random.PRNGKey(int(s))``
    of jax's default threefry implementation for each seed, built on the
    host with no device call.  ``PRNGKey`` takes the seed as an int64 (a
    Python int outside int64 raises ``OverflowError`` here too) and keeps
    its high and low 32-bit words; with x64 off jax narrows the seed to
    int32 first, so the high word is 0."""
    import jax
    s = np.asarray([int(x) for x in seeds], dtype=np.int64).view(np.uint64)
    hi = s >> np.uint64(32) if jax.config.jax_enable_x64 \
        else np.zeros_like(s)
    return np.stack([hi, s & np.uint64(0xFFFFFFFF)],
                    axis=1).astype(np.uint32)


@functools.lru_cache(maxsize=1)
def _moved_kernel():
    """Jitted ``(rows, p), (p,) -> (rows,)`` count of positions where each
    row differs from the start."""
    import jax

    def moved_positions(node, start):
        return (node != start[None, :]).sum(axis=1)

    return jax.jit(moved_positions)


class DeviceLadderEngine(LadderEngine):
    """K + ``restart_slots`` annealing ladders resident on the accelerator.

    Rows ``0..K-1`` are the original seeds; rows ``K..`` are restart slots,
    inactive until :meth:`spawn_restart` fills one at a temperature
    boundary.  All per-ladder arrays (``temps``/``eps``/``alive``) are
    full-height (K + slots); the controller's alive mask covers the
    originals and the engine tracks slot liveness itself.
    """

    name = "device"

    def __init__(self, grid: CartGrid, stencil: Stencil, start: np.ndarray,
                 seeds: Sequence[int], num_nodes: Optional[int] = None,
                 weighted=False, restart_slots: int = 0):
        import jax.numpy as jnp
        self._jnp = jnp
        self.grid, self.stencil = grid, stencil
        table = neighbor_table(grid, stencil)
        p = grid.size
        self.k = K = len(seeds)
        self.slots = int(restart_slots)
        R = self.rows = K + self.slots
        self.n_nodes = N = int(num_nodes) if num_nodes is not None \
            else int(np.max(start) + 1)
        self.weighted = resolve_weighted(weighted, stencil)
        weights = (stencil.weight_array() if self.weighted
                   else np.ones(stencil.k))
        # the resident state representation: stacked integer crossing
        # counts (one row per ladder, broadcast from the shared start)
        A = np.broadcast_to(np.asarray(start, dtype=np.int64), (1, p))
        co0, cn0 = stacked_crossing_counts(grid, stencil, A, N)
        per0 = np.zeros(N, dtype=np.float64)
        jsum0 = 0.0
        for j in range(stencil.k):      # host-exact start key
            per0 += weights[j] * cn0[0, :, j]
            jsum0 += float(weights[j]) * float(co0[0, j])
        self.start_key = (float(per0.max(initial=0.0)), float(jsum0))
        # one upload each for the start, its counts and every row's key;
        # the R copies are made on the device
        self._start = jnp.asarray(A[0], jnp.int32)
        self._node = jnp.broadcast_to(self._start, (R, p))
        self._cn = jnp.broadcast_to(jnp.asarray(cn0[0], jnp.int32),
                                    (R, N, stencil.k))
        self._keys = jnp.asarray(
            _threefry_keys(tuple(seeds) + (0,) * self.slots))
        self._best_node = self._node
        self._best_jmax = jnp.full(R, self.start_key[0], jnp.float32)
        self._best_jsum = jnp.full(R, self.start_key[1], jnp.float32)
        self._done = jnp.zeros(R, dtype=bool)
        self._weights = jnp.asarray(weights, jnp.float32)
        self._out_valid = jnp.asarray(table.out_valid)
        self._out_tgt = jnp.asarray(table.out_tgt, jnp.int32)
        self._in_valid = jnp.asarray(table.in_valid)
        self._in_src = jnp.asarray(table.in_src, jnp.int32)
        self._alive = np.ones(K, dtype=bool)
        self.n_spawned = 0
        self.boundaries = 0

    # -- LadderEngine -------------------------------------------------------
    def run_temperature(self, temps: np.ndarray, sa_moves: int,
                        alive: np.ndarray, eps: np.ndarray,
                        budget: Optional[int] = None) -> BoundaryReport:
        """One jitted ``lax.scan`` over ``sa_moves`` moves for every row;
        ``temps``/``eps`` are full-height (K + slots) with restart
        multipliers already folded in by the driver.  Exactly one host
        round-trip: the small boundary report below."""
        assert budget is None, "budgeted runs delegate to the host engine"
        jnp = self._jnp
        self._alive = np.asarray(alive, dtype=bool).copy()
        live = np.zeros(self.rows, dtype=bool)
        live[:self.k] = self._alive[:self.k]
        live[self.k:self.k + self.n_spawned] = True
        (self._node, self._cn, self._keys, self._best_node, self._best_jmax,
         self._best_jsum, self._done, acc, jmax, jsum) = \
            _temperature_kernel(int(sa_moves))(
                self._node, self._cn, self._keys, self._best_node,
                self._best_jmax, self._best_jsum, self._done,
                jnp.asarray(live),
                jnp.asarray(np.asarray(temps, dtype=np.float32)),
                jnp.asarray(np.asarray(eps, dtype=np.float32)),
                self._weights, self._out_valid, self._out_tgt,
                self._in_valid, self._in_src)
        self.boundaries += 1
        return BoundaryReport(j_max=np.asarray(jmax, dtype=np.float64),
                              j_sum=np.asarray(jsum, dtype=np.float64),
                              accepted=np.asarray(acc, dtype=np.int64),
                              done=np.asarray(self._done))

    def states(self) -> np.ndarray:
        return np.asarray(self._node[:self.k], dtype=np.int64)

    def set_alive(self, alive: np.ndarray) -> None:
        self._alive = np.asarray(alive, dtype=bool).copy()

    # -- device-specific surface --------------------------------------------
    def row_state(self, r: int) -> np.ndarray:
        """One row's current assignment (host copy) — the leader fetch the
        restart spawn path needs."""
        return np.asarray(self._node[int(r)], dtype=np.int64)

    def moved_positions(self) -> np.ndarray:
        """(K,) number of positions at which each original ladder's row
        differs from the engine's start (one small device reduction)."""
        return np.asarray(_moved_kernel()(self._node, self._start))[:self.k]

    def counts(self) -> np.ndarray:
        """(rows, N, k) resident integer count state (host copy) — the
        conformance tests recount it from the assignments after every
        boundary."""
        return np.asarray(self._cn, dtype=np.int64)

    def spawn_restart(self, node: np.ndarray, seed: int) -> Optional[int]:
        """Fill the next free restart slot with ``node`` and a fresh rng
        key; returns the slot index, or None when the slots are exhausted
        (the controller's spawn loop then stops without deducting)."""
        if self.n_spawned >= self.slots:
            return None
        jnp = self._jnp
        r = self.k + self.n_spawned
        co, cn = stacked_crossing_counts(
            self.grid, self.stencil, node[None, :], self.n_nodes)
        w = np.asarray(self._weights, dtype=np.float64)
        per = (cn[0].astype(np.float64) * w[None, :]).sum(axis=1)
        jmax = float(per.max(initial=0.0))
        jsum = float((co[0].astype(np.float64) * w).sum())
        self._node = self._node.at[r].set(
            jnp.asarray(node, jnp.int32))
        self._cn = self._cn.at[r].set(jnp.asarray(cn[0], jnp.int32))
        self._keys = self._keys.at[r].set(
            jnp.asarray(_threefry_keys((seed,))[0]))
        self._best_node = self._best_node.at[r].set(
            jnp.asarray(node, jnp.int32))
        self._best_jmax = self._best_jmax.at[r].set(jmax)
        self._best_jsum = self._best_jsum.at[r].set(jsum)
        self._done = self._done.at[r].set(False)
        self.n_spawned += 1
        return r - self.k

    def snapshot(self) -> dict:
        """End-of-run fetch (one transfer): current and best-seen
        assignments for every row, plus the resident count state."""
        return {
            "nodes": np.asarray(self._node, dtype=np.int64),
            "best_nodes": np.asarray(self._best_node, dtype=np.int64),
            "counts": np.asarray(self._cn, dtype=np.int64),
            "best_jmax": np.asarray(self._best_jmax, dtype=np.float64),
            "best_jsum": np.asarray(self._best_jsum, dtype=np.float64),
        }


class DevicePortfolioRefiner:
    """K-start annealing portfolio with device-resident ladders.

    Args mirror :class:`~repro.core.refine.portfolio.PortfolioRefiner`
    (``k``/``seed``/``seeds``, ``kill_factor``, ``polish_top``, the
    schedule parameters) plus the sharded engine's adaptive control
    (``restarts``/``retune``/``accept_band``/``retune_bounds``) and:

      kill_factor: defaults to ``None`` here (the host engines default to
        1.5): killing a ladder in a lock-step vmapped computation saves no
        device work — every row advances anyway — so the only effect would
        be discarding candidates.  Set it to run the kill rule regardless
        (the alive mask is honored exactly: killed ladders freeze).
      restart_slots: preallocated restart rows (static shapes — the
        temperature kernel compiles once).  ``"auto"`` sizes the pool at K
        when ``restarts`` is enabled, 0 otherwise.
      engine_factory: replace :class:`DeviceLadderEngine` (testing seam).
        A factory is an opaque object, so hand-built instances carrying
        one have no stable spelling and their plans are **uncacheable**
        (``as_stage().cacheable`` is False — pinned by
        ``tests/test_plan.py``).

    ``max_swaps`` budgets and ``pinned`` masks couple ladders at move
    granularity on the host; such runs delegate to the single-process
    portfolio with the same seeds and schedule.
    """

    def __init__(self, k: int = 8, seed: int = 0,
                 seeds: Optional[Sequence[int]] = None,
                 kill_factor: Optional[float] = None,
                 polish_top: Optional[int] = 3,
                 restarts=None, retune: bool = False,
                 accept_band: Tuple[float, float] = (0.05, 0.5),
                 retune_bounds: Tuple[float, float] = (0.25, 4.0),
                 restart_slots="auto",
                 objectives: Sequence[str] = ("j_sum", "j_max"),
                 rounds: int = 4, policy: str = "first", max_passes: int = 8,
                 weighted="auto", tol: float = 1e-12, max_partners: int = 32,
                 temperatures: Sequence[float] = (2.0, 1.0, 0.5, 0.25),
                 sa_moves: int = 200, max_swaps: Optional[int] = None,
                 engine_factory=None):
        if restarts not in (None, "auto") and int(restarts) < 0:
            raise ValueError('restarts must be None, "auto", or an int >= 0')
        lo, hi = float(accept_band[0]), float(accept_band[1])
        if not (0.0 <= lo <= hi <= 1.0):
            raise ValueError("accept_band must satisfy 0 <= low <= high <= 1")
        blo, bhi = float(retune_bounds[0]), float(retune_bounds[1])
        if not (0.0 < blo <= 1.0 <= bhi):
            raise ValueError("retune_bounds must bracket 1.0 "
                             "(0 < min <= 1 <= max)")
        if restart_slots != "auto" and int(restart_slots) < 0:
            raise ValueError('restart_slots must be "auto" or an int >= 0')
        self.portfolio = PortfolioRefiner(
            k=k, seed=seed, seeds=seeds, kill_factor=kill_factor,
            polish_top=polish_top, objectives=objectives, rounds=rounds,
            policy=policy, max_passes=max_passes, weighted=weighted, tol=tol,
            max_partners=max_partners, temperatures=temperatures,
            sa_moves=sa_moves, max_swaps=None)
        self.schedule = self.portfolio.schedule
        self.seeds = self.portfolio.seeds
        self.k = self.portfolio.k
        self.kill_factor = self.portfolio.kill_factor
        self.restarts = restarts if restarts in (None, "auto") \
            else int(restarts)
        self.retune = bool(retune)
        self.accept_band = (lo, hi)
        self.retune_bounds = (blo, bhi)
        self.restart_slots = restart_slots if restart_slots == "auto" \
            else int(restart_slots)
        if max_swaps is not None and int(max_swaps) < 0:
            raise ValueError("max_swaps must be >= 0 (or None)")
        self.max_swaps = None if max_swaps is None else int(max_swaps)
        self.engine_factory = engine_factory

    def as_stage(self, budget: Optional[int] = None):
        """Uniform :class:`~repro.core.refine.stage.RefineStage` adapter
        (``budget`` caps this stage's accepted swaps)."""
        from .stage import RefineStage
        return RefineStage(self, budget=budget, prefix="device")

    def config(self) -> dict:
        """Full constructor configuration — the stage layer's canonical
        cache identity for hand-built refiners.  ``engine_factory`` is an
        opaque object when set, which (correctly) marks the stage
        uncacheable."""
        cfg = self.portfolio.config()
        cfg.update({"restarts": self.restarts, "retune": self.retune,
                    "accept_band": self.accept_band,
                    "retune_bounds": self.retune_bounds,
                    "restart_slots": self.restart_slots,
                    "max_swaps": self.max_swaps,
                    "engine_factory": self.engine_factory})
        return cfg

    def _resolved_slots(self) -> int:
        if self.restarts is None:
            return 0
        if self.restart_slots == "auto":
            return self.k
        return int(self.restart_slots)

    # -- delegation ---------------------------------------------------------
    def _delegate(self, reason: str, grid, stencil, node_of_pos, num_nodes,
                  pinned) -> RefineResult:
        delegate = copy.copy(self.portfolio)
        delegate.max_swaps = self.max_swaps
        res = delegate.refine(grid, stencil, node_of_pos, num_nodes,
                              pinned=pinned)
        res.stats.update({"backend": "host-fallback", "delegated": reason})
        return res

    # -- driver -------------------------------------------------------------
    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None,
               pinned: Optional[np.ndarray] = None) -> RefineResult:
        if self.max_swaps is not None:
            return self._delegate("max_swaps", grid, stencil, node_of_pos,
                                  num_nodes, pinned)
        if pinned is not None:
            return self._delegate("pinned", grid, stencil, node_of_pos,
                                  num_nodes, pinned)
        t0 = time.perf_counter()
        sched = self.schedule
        K = self.k
        with obs.recording() as rec:
            # 1. start key and the shared deterministic prefix
            # (seed-independent, run once)
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

                n_nodes = int(num_nodes) if num_nodes is not None \
                    else int(cur.max() + 1)
                weights = (stencil.weight_array()
                           if resolve_weighted(sched.weighted, stencil)
                           else np.ones(stencil.k))
                # the rounds' and the polish's swap pairs are scored on the
                # device where that is exact (integer weights), else numpy
                from . import device_swap
                scorer = device_swap.device_swap_scorer(grid, stencil,
                                                        weights)
                cur, swaps, passes = sched.run_rounds(
                    grid, stencil, cur, num_nodes, consider, max_swaps=None,
                    scorer=scorer)

            # 2. device ladders under the shared boundary protocol
            with obs.span("ladders"):
                t_scale = float(np.mean(weights))
                slots = self._resolved_slots()
                factory = self.engine_factory or DeviceLadderEngine
                with obs.span("engine_init"):
                    eng = factory(grid, stencil, cur, self.seeds,
                                  num_nodes=n_nodes, weighted=sched.weighted,
                                  restart_slots=slots)
                jmax0, jsum0 = eng.start_key
                eps0 = float(1.0 / (1.0 + abs(jsum0)))
                n_temps = len(sched.temperatures)
                ctrl = BoundaryController(
                    k=K, kill_factor=self.kill_factor,
                    start_keys=np.asarray([jmax0, jsum0]),
                    restarts=self.restarts, retune=self.retune,
                    accept_band=self.accept_band,
                    retune_bounds=self.retune_bounds,
                    sa_moves=sched.sa_moves, n_temps=n_temps,
                    seeder=RestartSeeder(self.seeds))
                restarts: List[dict] = []
                accepted = 0
                rows = K + slots
                # a ladder within one swap of its start (at most two
                # positions differ) runs its next temperature at the first
                # one.  Where nearly every node sits at J_max (28 of 31 on
                # the 3-D Fig. 8 instance), nearly every swap raises it: a
                # cooled ladder there may reject every proposal, or, one
                # swap away, take the one downhill move, that swap's
                # reverse, and end at its start.
                near = np.ones(K, dtype=bool)
                T_first = max(sched.temperatures[0] * t_scale, 1e-12)
                cur_keys = np.broadcast_to(np.asarray([jmax0, jsum0]),
                                           (K, 2)).copy()

                def leader() -> Tuple[np.ndarray, float]:
                    """Current portfolio leader (lexicographic best current
                    key, originals then restarts, lowest index wins ties)
                    — one row fetched from the device."""
                    cand = [((cur_keys[i, 0], cur_keys[i, 1], 0, i), i)
                            for i in range(K) if ctrl.alive[i]]
                    cand += [((r["j_max"], r["j_sum"], 1, j), K + r["slot"])
                             for j, r in enumerate(restarts)]
                    key, row = min(cand, key=lambda c: c[0])
                    return eng.row_state(row), float(key[1])

                def spawn(seed: int) -> bool:
                    node, lead_j_sum = leader()
                    slot = eng.spawn_restart(node, seed)
                    if slot is None:
                        return False
                    restarts.append({
                        "slot": slot, "seed": seed, "done": False,
                        "eps": float(1.0 / (1.0 + abs(lead_j_sum))),
                        "t_mult": 1.0,
                        "j_max": math.inf, "j_sum": math.inf,
                        "accepted_last": 0,
                    })
                    return True

                for ti, T0 in enumerate(sched.temperatures):
                    with obs.span("temperature"):
                        T = max(T0 * t_scale, 1e-12)
                        temps = np.full(rows, T)
                        temps[:K][near] = T_first
                        eps = np.full(rows, eps0)
                        for r in restarts:
                            temps[K + r["slot"]] = max(
                                T0 * t_scale * r["t_mult"], 1e-12)
                            eps[K + r["slot"]] = r["eps"]
                        rep = eng.run_temperature(temps, sched.sa_moves,
                                                  ctrl.alive, eps)
                        accepted += int(rep.accepted[:K].sum())
                        if ti + 1 < n_temps:
                            near = eng.moved_positions() <= 2
                        cur_keys = np.stack([rep.j_max[:K], rep.j_sum[:K]],
                                            axis=1)
                        for r in restarts:
                            row = K + r["slot"]
                            accepted += int(rep.accepted[row])
                            r.update(j_max=float(rep.j_max[row]),
                                     j_sum=float(rep.j_sum[row]),
                                     done=bool(rep.done[row]),
                                     accepted_last=int(rep.accepted[row]))
                        # the shared boundary protocol, one host round-trip
                        # per temperature: best-seen, kill (pushed back as
                        # the alive mask), pool accounting / retune /
                        # restart spawn
                        with obs.span("boundary"):
                            ctrl.update_best(cur_keys)
                            newly_killed = ctrl.kill()
                            eng.set_alive(ctrl.alive)
                            ctrl.adapt(ti, newly_killed, restarts, spawn)

            # 3. survivors: end states AND device-tracked best-seen states
            # are candidates; exact host keys come from the shared integer
            # counts representation, then the single-process selection +
            # polish
            with obs.span("survivors"):
                with obs.span("snapshot"):
                    snap = eng.snapshot()
                with obs.span("rekey"):
                    alive_rows = [i for i in range(K) if ctrl.alive[i]]
                    slot_rows = [K + r["slot"] for r in restarts]
                    pick = alive_rows + slot_rows
                    cand = np.concatenate([snap["nodes"][pick],
                                           snap["best_nodes"][pick]])
                    counts = stacked_crossing_counts(grid, stencil, cand,
                                                     n_nodes)
                    cpc = PortfolioCost(grid, stencil, cand,
                                        num_nodes=n_nodes,
                                        weighted=sched.weighted,
                                        table=neighbor_table(grid, stencil),
                                        counts=counts)
                with obs.span("polish"):
                    swaps, passes, polish_order = \
                        self.portfolio._polish_survivors(
                            grid, stencil, num_nodes, consider, cand,
                            cpc.j_max(), cpc.j_sum(),
                            np.ones(cand.shape[0], dtype=bool), swaps,
                            passes, scorer=scorer)
                with obs.span("final"):
                    final = IncrementalCost(grid, stencil, best,
                                            num_nodes=num_nodes,
                                            weighted=sched.weighted).cost()
        wall = time.perf_counter() - t0
        stats = {
            "k": self.k,
            "seeds": self.seeds,
            "backend": f"device[{_backend_name()}]",
            "boundaries": eng.boundaries,
            "proposals": rows * n_temps * sched.sa_moves,
            "sa_accepted": accepted,
            "killed": ctrl.killed,
            "restarted": len(restarts),
            "restart_slots": slots,
            "restart_seeds": [r["seed"] for r in restarts],
            "restart_t_mults": [r["t_mult"] for r in restarts],
            "pool_moves_left": ctrl.pool_moves,
            "polished": len(polish_order),
            "ladder_keys": [(float(j), float(s)) for j, s in cur_keys],
            **phase_stats(rec, wall),
        }
        return RefineResult(assignment=best, initial=initial, final=final,
                            swaps=swaps, passes=passes, wall_time_s=wall,
                            stats=stats)


def _backend_name() -> str:
    import jax
    return jax.default_backend()
