"""The shared boundary protocol of the annealing-portfolio engines.

Every portfolio engine — single-process
(:class:`~repro.core.refine.portfolio.PortfolioRefiner`), process-sharded
(:class:`~repro.core.refine.sharded.ShardedPortfolioRefiner`), and
device-resident (:class:`~repro.core.refine.device.DevicePortfolioRefiner`)
— advances K simulated-annealing ladders one *temperature* at a time and
runs the same coordinator rules at every temperature boundary:

1. **best-seen update** — each ladder's lexicographic best ``(J_max,
   J_sum)`` key over all boundaries so far;
2. **early-kill** — a ladder (never ladder 0) whose best-seen J_max
   exceeds ``kill_factor`` times the alive leader's is killed, and the
   alive mask is monotone non-increasing from then on;
3. **adaptive control** (optional) — killed ladders return their unspent
   proposal budget to a pool that funds *restart ladders* seeded fresh
   from the current leader, and each restart's temperature multiplier is
   retuned from its observed accept rate.

This module is that protocol, factored once:

* :class:`BoundaryReport` — what an engine hands back per temperature
  (per-ladder keys, accepted counts, done flags);
* :class:`LadderEngine` — the engine interface: resident ladder state in,
  one :meth:`~LadderEngine.run_temperature` call per temperature out.
  :class:`SerialLadderEngine` wraps the numpy kernel
  (:func:`~repro.core.refine.portfolio.run_temperature`) and preserves its
  draw order bit for bit; the sharded engine dispatches the same kernel
  per seed block; the device engine replays the protocol with
  ``jax``-resident state;
* :class:`BoundaryController` — rules 1-3 verbatim (the loops formerly
  duplicated between the portfolio and sharded coordinators), engine
  agnostic;
* :class:`RestartSeeder` — fresh restart seeds, guarded against colliding
  with user-supplied explicit ``seeds=`` lists (warn + shift, like the
  portfolio's duplicate-seed dedupe);
* :func:`phase_stats` — the engines' shared time split, read from the
  solve's :mod:`repro.obs` recording.

Float arithmetic order inside the controller is unchanged from the PR-3/5
coordinators, so the refactor is bit-invisible to the engines' pinned
bit-identity tests.
"""
from __future__ import annotations

import abc
import math
import warnings
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..cost_delta import PortfolioCost
from ..grid import CartGrid
from ..stencil import Stencil

__all__ = ["BoundaryReport", "LadderEngine", "SerialLadderEngine",
           "BoundaryController", "RestartSeeder", "phase_stats"]


@dataclass
class BoundaryReport:
    """One engine's per-temperature result: exact per-ladder keys (rows in
    engine order — the K originals first, any restart rows after), the
    accepted-proposal counts of the temperature just run, and the sticky
    done flags (boundary shrank below two positions)."""

    j_max: np.ndarray      # (rows,) float
    j_sum: np.ndarray      # (rows,) float
    accepted: np.ndarray   # (rows,) int
    done: np.ndarray       # (rows,) bool


class LadderEngine(abc.ABC):
    """K resident annealing ladders advanced one temperature per call.

    The contract every engine implements (and
    ``tests/test_device_portfolio.py`` cross-checks): ladder state lives in
    the engine between calls, :meth:`run_temperature` advances every alive,
    not-done ladder through one temperature of ``sa_moves`` Metropolis
    proposals and reports exact keys at the boundary, and
    :meth:`set_alive`'s mask (the kill rule's output) is monotone — a
    ladder marked dead stops proposing and its state freezes.
    """

    #: engine spelling, for stats
    name: str = "engine"

    @abc.abstractmethod
    def run_temperature(self, temps: np.ndarray, sa_moves: int,
                        alive: np.ndarray, eps: np.ndarray,
                        budget: Optional[int] = None) -> BoundaryReport:
        """Advance one temperature (``temps``/``eps`` per-ladder absolute
        values, schedule scale folded in) and report the boundary."""

    @abc.abstractmethod
    def states(self) -> np.ndarray:
        """(K, p) current ladder assignments (host arrays)."""

    def set_alive(self, alive: np.ndarray) -> None:
        """Push the kill rule's alive mask (monotone non-increasing)."""


class SerialLadderEngine(LadderEngine):
    """The host engine: :class:`~repro.core.cost_delta.PortfolioCost` state
    plus the numpy ladder kernel
    (:func:`~repro.core.refine.portfolio.run_temperature`), preserving the
    historical rng draw order bit for bit — this class is a seam, not a
    reimplementation."""

    name = "serial"

    def __init__(self, grid: CartGrid, stencil: Stencil, start: np.ndarray,
                 seeds: Sequence[int], num_nodes: Optional[int] = None,
                 weighted=False, allowed: Optional[np.ndarray] = None):
        K = len(seeds)
        self.pc = PortfolioCost(grid, stencil,
                                np.broadcast_to(start, (K, grid.size)),
                                num_nodes=num_nodes, weighted=weighted)
        self.rngs = [np.random.default_rng(s) for s in seeds]
        self.done = np.zeros(K, dtype=bool)
        self.allowed = allowed

    def run_temperature(self, temps: np.ndarray, sa_moves: int,
                        alive: np.ndarray, eps: np.ndarray,
                        budget: Optional[int] = None) -> BoundaryReport:
        from .portfolio import run_temperature
        accepted = run_temperature(self.pc, self.rngs, alive, self.done,
                                   temps, sa_moves, eps, budget=budget,
                                   allowed=self.allowed)
        return BoundaryReport(j_max=self.pc.j_max(), j_sum=self.pc.j_sum(),
                              accepted=accepted, done=self.done.copy())

    def states(self) -> np.ndarray:
        return self.pc.node


class RestartSeeder:
    """Fresh, deterministic restart-ladder seeds: ``max(seeds) + 1``
    counting upward.  With the default arithmetic that can never collide
    with an original ladder's seed (every original is <= max), but the
    stream is guarded anyway: any candidate that *would* land on a
    user-supplied seed — e.g. a caller-chosen ``start`` base threaded into
    a sparse explicit ``seeds=`` list — is skipped with a warning, the same
    warn-and-shift contract as the portfolio's duplicate-seed dedupe, so a
    restart ladder never replays an original's trajectory."""

    def __init__(self, seeds: Sequence[int], start: Optional[int] = None):
        self._orig = frozenset(int(s) for s in seeds)
        if not self._orig:
            raise ValueError("restart seeding needs at least one original")
        self._next = int(max(self._orig) + 1 if start is None else start)

    def __call__(self) -> int:
        s = self._next
        shifted = 0
        while s in self._orig:
            s += 1
            shifted += 1
        if shifted:
            warnings.warn(
                f"restart seed {self._next} collides with an explicit "
                f"portfolio seed; shifted to {s} so the restart ladder "
                "cannot replay an original trajectory", UserWarning,
                stacklevel=2)
        self._next = s + 1
        return s


class BoundaryController:
    """The coordinator side of the boundary protocol (rules 1-3 of the
    module docstring), shared verbatim by the serial, sharded, and device
    drivers.

    ``alive``/``best_seen``/``killed``/``pool_moves`` are the live
    bookkeeping the drivers read back; ``restarts=None`` disables rule 3
    entirely (the single-process portfolio's historical behavior).
    ``start_keys`` is the (K, 2) per-ladder ``(J_max, J_sum)`` of the
    shared start state.
    """

    def __init__(self, k: int, kill_factor: Optional[float],
                 start_keys: np.ndarray, restarts=None, retune: bool = False,
                 accept_band: Tuple[float, float] = (0.05, 0.5),
                 retune_bounds: Tuple[float, float] = (0.25, 4.0),
                 sa_moves: int = 0, n_temps: int = 0,
                 seeder: Optional[RestartSeeder] = None):
        self.k = int(k)
        self.kill_factor = kill_factor
        self.alive = np.ones(self.k, dtype=bool)
        self.best_seen = np.array(np.broadcast_to(
            np.asarray(start_keys, dtype=np.float64), (self.k, 2)))
        self.restarts = restarts
        self.retune = bool(retune)
        self.accept_band = accept_band
        self.retune_bounds = retune_bounds
        self.sa_moves = int(sa_moves)
        self.n_temps = int(n_temps)
        self.seeder = seeder
        self.killed = 0
        self.pool_moves = 0

    # -- rule 1: best-seen update -------------------------------------------
    def update_best(self, cur_keys: np.ndarray) -> None:
        for i in range(self.k):
            if tuple(cur_keys[i]) < tuple(self.best_seen[i]):
                self.best_seen[i] = cur_keys[i]

    # -- rule 2: early-kill (ladder 0 exempt; alive is monotone) ------------
    def kill(self) -> int:
        newly_killed = 0
        if self.kill_factor is not None:
            lead = self.best_seen[self.alive, 0].min()
            for i in range(1, self.k):
                if self.alive[i] \
                        and self.best_seen[i, 0] > self.kill_factor * lead:
                    self.alive[i] = False
                    self.killed += 1
                    newly_killed += 1
        return newly_killed

    # -- rule 3: pool accounting + retune + restart spawn -------------------
    def adapt(self, ti: int, newly_killed: int, restarts: List[dict],
              spawn: Callable[[int], bool]) -> None:
        """Run the adaptive boundary rules after temperature index ``ti``:
        fund the pool with the newly killed ladders' unspent budgets,
        retune every live restart's temperature multiplier from its accept
        rate, then spawn as many fresh restarts as the pool affords.
        ``restarts`` is the driver's bookkeeping (dicts with ``done`` /
        ``accepted_last`` / ``t_mult``); ``spawn(seed)`` creates one
        restart ladder from the current leader and returns False when the
        engine is out of capacity (nothing is deducted for a refused
        spawn)."""
        rem = self.n_temps - ti - 1
        if self.restarts is None or rem <= 0:
            return
        self.pool_moves += newly_killed * rem * self.sa_moves
        if self.retune:
            lo, hi = self.accept_band
            blo, bhi = self.retune_bounds
            for r in restarts:
                if r["done"]:
                    continue
                rate = r["accepted_last"] / max(1, self.sa_moves)
                if rate < lo:
                    r["t_mult"] = min(r["t_mult"] * 2.0, bhi)
                elif rate > hi:
                    r["t_mult"] = max(r["t_mult"] * 0.5, blo)
        cost = rem * self.sa_moves
        cap = math.inf if self.restarts == "auto" \
            else int(self.restarts) - len(restarts)
        # cost == 0 (sa_moves=0 schedules) would spawn forever: a free
        # restart buys zero proposals, so spawn none
        while cost > 0 and self.pool_moves >= cost and cap > 0:
            if not spawn(self.seeder()):
                break
            self.pool_moves -= cost
            cap -= 1


def phase_stats(rec: dict, wall: float) -> dict:
    """A portfolio solve's time split, from its :func:`repro.obs.recording`
    ``rec``, whose top-level spans are ``rounds`` (start key and the
    deterministic rounds), ``ladders`` (engine set-up through the last
    temperature boundary) and ``survivors``.  ``t_polish_s`` is the rest of
    ``wall``: survivors' rekeying, polish and final key.  The recording's
    spans and counters ride along."""
    t_rounds = rec["spans"]["rounds"][1]
    t_ladders = rec["spans"]["ladders"][1]
    return {"t_rounds_s": t_rounds, "t_ladders_s": t_ladders,
            "t_polish_s": wall - t_rounds - t_ladders,
            "spans": rec["spans"], "counters": rec["counters"]}
