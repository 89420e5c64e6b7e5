"""Sharded adaptive portfolio engine (process-parallel K-start annealing).

The paper's headline is that mapping *search* parallelizes: its distributed
algorithms beat high-quality sequential mappers (Glantz-Meyerhenke-Noe;
Schulz-Träff "Better Process Mapping and Sparse Quadratic Assignment") on
wall-time while matching quality.  :class:`ShardedPortfolioRefiner` is that
scaling step for the portfolio engine: K annealing ladders partitioned into
``shards`` seed blocks, each block advanced one temperature at a time by
:func:`~repro.core.refine.portfolio.run_temperature` inside
``multiprocessing`` workers (a picklable primitives-only task per block),
with the coordinator merging per-ladder keys at every temperature boundary
so the early-kill rule sees the *global* leader — exactly the
single-process rule.

**Bit-identity.**  A ladder's trajectory depends only on its own rng and
start state (the shared kernel guarantees the draw order), and every
cross-ladder coupling — best-seen bookkeeping, the kill rule, survivor
ranking and polish — runs on the coordinator over globally merged state.
``sharded[shards=S,k=K]:<base>`` is therefore bit-identical to
``portfolio[k=K]:<base>`` for any S when adaptive control is off (pinned by
``tests/test_sharded_portfolio.py``).  The one coupling that cannot shard
is a global ``max_swaps`` budget (one shared counter checked per batched
move), so budgeted runs delegate to the single-process engine, which *is*
that semantics.

**Adaptive control** (``restarts="auto"`` or an int cap):

* early-killed ladders return their unspent proposal budget — the
  remaining ``temperatures x sa_moves`` they would have run — to a shared
  pool;
* the pool funds *restart ladders* seeded fresh (``max(seeds)+1+j``, never
  colliding with originals) that start from the current portfolio leader's
  assignment and run the remaining temperatures;
* with ``retune=True``, each restart ladder's temperature is retuned at
  phase boundaries from its own observed accept rate: below
  ``accept_band[0]`` doubles its multiplier (reheat a frozen walk), above
  ``accept_band[1]`` halves it, always clamped to ``retune_bounds``.

Restart ladders never enter the kill rule's leader computation and are
never killed, and retune applies *only* to them — so the original K
ladders replay the single-process portfolio exactly, and the adaptive
engine's candidate set is a strict superset.  That is the structural
guarantee behind "adaptive on is lexicographically never worse on the
(J_max, J_sum) key" (also pinned by tests).

The serial backend computes each block's integer crossing-count state
with the jax kernel
(:func:`~repro.core.cost_delta.stacked_crossing_counts`) and hands it to
the block; the ``multiprocessing`` workers stay numpy-only and build it
with :func:`~repro.core.cost_delta.stacked_count_arrays`.  Counts are
pure integers, so both producers are bit-interchangeable.

Usage::

    from repro.core import ShardedPortfolioRefiner, get_mapper
    res = ShardedPortfolioRefiner(shards=4, k=64).refine(grid, st, a,
                                                         num_nodes=N)
    m = get_mapper("sharded[shards=4,k=64]:hyperplane")
    m = get_mapper("sharded[shards=2,k=16,restarts=auto,retune=true]:kdtree")
"""
from __future__ import annotations

import copy
import math
import multiprocessing
import os
import pickle
import time
from contextlib import contextmanager
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ... import obs
from ..cost_delta import (IncrementalCost, PortfolioCost, neighbor_table,
                          stacked_crossing_counts)
from ..grid import CartGrid
from ..stencil import Stencil, resolve_weighted
from .engine import BoundaryController, RestartSeeder, phase_stats
from .portfolio import PortfolioRefiner, run_temperature
from .swap import RefineResult

__all__ = ["ShardedPortfolioRefiner", "IpcMeter", "measure_ipc"]

#: auto backend: fork+pickle round-trips per temperature only pay off once
#: the per-temperature batched numpy work dominates the IPC (measured
#: crossover on the 16x28 ragged suite instance at K in the tens).
_MP_AUTO_MIN_ELEMS = 1 << 14

_PICKLE_PROTO = pickle.HIGHEST_PROTOCOL

#: active :class:`IpcMeter` (coordinator-thread scoped via
#: :func:`measure_ipc`); ``None`` = no accounting on the dispatch path.
_IPC_METER: Optional["IpcMeter"] = None


class IpcMeter:
    """Measured IPC byte accounting for the stateless sharded protocol.

    Records ``len(pickle.dumps(...))`` of the *actual* ``_block_step``
    payload and result objects each dispatch ships — the bytes the mp
    backend pays per block per temperature (full assignment + rng state
    both directions), measured rather than estimated.  The serial backend
    builds byte-identical task objects, so metering works regardless of
    which backend executed.  ``benchmarks/serve_suite.py`` pins the
    resident-worker serving claim (per-boundary IPC reduction) against
    this baseline.
    """

    def __init__(self):
        self.bytes_out = 0      # coordinator -> worker (payloads)
        self.bytes_in = 0       # worker -> coordinator (results)
        self.messages = 0       # block payloads shipped
        self.dispatches = 0     # step() calls (one per temperature)

    def record(self, payloads, results) -> None:
        self.bytes_out += sum(len(pickle.dumps(p, _PICKLE_PROTO))
                              for p in payloads)
        self.bytes_in += sum(len(pickle.dumps(r, _PICKLE_PROTO))
                             for r in results)
        self.messages += len(payloads)
        self.dispatches += 1

    @property
    def bytes_total(self) -> int:
        return self.bytes_out + self.bytes_in


@contextmanager
def measure_ipc():
    """Meter the stateless protocol's IPC bytes for every sharded refine
    run inside the ``with`` body (single coordinator thread; nesting
    restores the outer meter on exit)."""
    global _IPC_METER
    meter = IpcMeter()
    prev, _IPC_METER = _IPC_METER, meter
    try:
        yield meter
    finally:
        _IPC_METER = prev


def worker_context():
    """The ``multiprocessing`` context of the numpy-only worker pools:
    fork (spawn where there is none).  Fork keeps the workers cheap: no
    re-import, and a script fed on stdin can start them.  The children
    never touch jax, so a parent that already drives a TPU forks safely
    (checked on a v5e: the pool answers and the warm-up completes; JAX
    only warns).  A fork server re-imports the package in every worker,
    which costs more than the per-temperature work it spreads."""
    methods = multiprocessing.get_all_start_methods()
    return multiprocessing.get_context(
        "fork" if "fork" in methods else "spawn")


# ---------------------------------------------------------------------------
# the per-(block, temperature) worker task


def _block_step(payload: dict) -> dict:
    """Advance one seed block through one temperature of proposals.

    Module-level and primitives-only (dims/offsets/arrays/rng generators —
    all picklable) so it ships to ``multiprocessing`` workers; the serial
    backend calls it inline.  The block's cost state is rebuilt from its
    assignment rows each call (integer counts — exact), from the
    ``counts`` the serial coordinator precomputed with the jax kernel when
    the payload carries them.
    """
    grid = payload.get("grid")
    if grid is None:
        grid = CartGrid(tuple(payload["dims"]), periodic=payload["periodic"])
    stencil = Stencil(payload["offsets"], payload["weights"])
    pc = PortfolioCost(grid, stencil, payload["node"],
                       num_nodes=payload["num_nodes"],
                       weighted=payload["weighted"],
                       table=neighbor_table(grid, stencil),
                       counts=payload.get("counts"))
    rngs = payload["rngs"]
    done = np.array(payload["done"], dtype=bool)
    accepted = run_temperature(pc, rngs, np.asarray(payload["alive"]), done,
                               payload["temps"], payload["sa_moves"],
                               payload["eps"])
    return {"node": pc.node, "rngs": rngs, "done": done,
            "accepted": accepted, "j_max": pc.j_max(), "j_sum": pc.j_sum()}


# ---------------------------------------------------------------------------
# the refiner


class ShardedPortfolioRefiner:
    """Shard the K-start annealing portfolio across worker processes, with
    optional adaptive restart/retune control.

    Args:
      shards: number of seed blocks (capped at K); each block is one
        worker task per temperature.
      restarts: adaptive control.  ``None`` (default) disables it — the
        engine is then bit-identical to
        ``PortfolioRefiner(k=K, seed=seed)`` for any shard count.
        ``"auto"`` restarts as many ladders as the killed-budget pool
        affords; an int additionally caps total restarts.
      retune: retune each *restart* ladder's temperature from its observed
        accept rate at phase boundaries (originals are never retuned — that
        is what keeps the dominance guarantee structural).
      accept_band: (low, high) accept-rate band; outside it a restart
        ladder's temperature multiplier doubles/halves.
      retune_bounds: (min, max) clamp on the multiplier.
      backend: ``"serial"`` runs blocks inline (still block-partitioned,
        still bit-identical), ``"mp"`` uses a process pool, ``"auto"``
        picks ``"mp"`` when ``shards > 1`` and the stacked state is large
        enough to amortize IPC.
      workers: process-pool size cap (default: min(shards, cpu count)).
      Remaining arguments are :class:`PortfolioRefiner`'s, same defaults —
      a bare ``sharded:<base>`` equals a bare ``portfolio:<base>``.
    """

    def __init__(self, shards: int = 4, k: int = 8, seed: int = 0,
                 seeds: Optional[Sequence[int]] = None,
                 restarts=None, retune: bool = False,
                 accept_band: Tuple[float, float] = (0.05, 0.5),
                 retune_bounds: Tuple[float, float] = (0.25, 4.0),
                 backend: str = "auto", workers: Optional[int] = None,
                 kill_factor: Optional[float] = 1.5,
                 polish_top: Optional[int] = 3,
                 objectives: Sequence[str] = ("j_sum", "j_max"),
                 rounds: int = 4, policy: str = "first", max_passes: int = 8,
                 weighted="auto", tol: float = 1e-12, max_partners: int = 32,
                 temperatures: Sequence[float] = (2.0, 1.0, 0.5, 0.25),
                 sa_moves: int = 200, max_swaps: Optional[int] = None):
        if int(shards) < 1:
            raise ValueError("shards must be >= 1")
        if restarts not in (None, "auto") and int(restarts) < 0:
            raise ValueError('restarts must be None, "auto", or an int >= 0')
        if backend not in ("auto", "serial", "mp"):
            raise ValueError('backend must be "auto", "serial", or "mp"')
        lo, hi = float(accept_band[0]), float(accept_band[1])
        if not (0.0 <= lo <= hi <= 1.0):
            raise ValueError("accept_band must satisfy 0 <= low <= high <= 1")
        blo, bhi = float(retune_bounds[0]), float(retune_bounds[1])
        if not (0.0 < blo <= 1.0 <= bhi):
            raise ValueError("retune_bounds must bracket 1.0 "
                             "(0 < min <= 1 <= max)")
        self.shards = int(shards)
        self.restarts = restarts if restarts in (None, "auto") \
            else int(restarts)
        self.retune = bool(retune)
        self.accept_band = (lo, hi)
        self.retune_bounds = (blo, bhi)
        self.backend = backend
        self.workers = None if workers is None else int(workers)
        # the single-process engine this one must replicate: seeds,
        # schedule, kill/polish rules, and the budget-delegation target.
        self.portfolio = PortfolioRefiner(
            k=k, seed=seed, seeds=seeds, kill_factor=kill_factor,
            polish_top=polish_top, objectives=objectives, rounds=rounds,
            policy=policy, max_passes=max_passes, weighted=weighted, tol=tol,
            max_partners=max_partners, temperatures=temperatures,
            sa_moves=sa_moves, max_swaps=None)
        self.schedule = self.portfolio.schedule
        self.seeds = self.portfolio.seeds
        self.k = self.portfolio.k
        #: restart ladder j is seeded ``max(seeds) + 1 + j`` — fresh and
        #: deterministic; the stream is issued through
        #: :class:`~repro.core.refine.engine.RestartSeeder`, which guards
        #: (warn + shift) against ever colliding with a user-supplied
        #: explicit ``seeds=`` list, so a restart can never replay an
        #: original ladder's trajectory.
        self._restart_seed_base = max(self.seeds) + 1
        if max_swaps is not None and int(max_swaps) < 0:
            raise ValueError("max_swaps must be >= 0 (or None)")
        self.max_swaps = None if max_swaps is None else int(max_swaps)

    def as_stage(self, budget: Optional[int] = None):
        """Uniform :class:`~repro.core.refine.stage.RefineStage` adapter
        (``budget`` caps this stage's accepted swaps)."""
        from .stage import RefineStage
        return RefineStage(self, budget=budget, prefix="sharded")

    def config(self) -> dict:
        """Full constructor configuration — the stage layer's canonical
        cache identity for hand-built refiners.  Execution-only knobs
        (backend/workers) are included for faithfulness even though every
        backend returns bit-identical results."""
        cfg = self.portfolio.config()
        cfg.update({"shards": self.shards, "restarts": self.restarts,
                    "retune": self.retune, "accept_band": self.accept_band,
                    "retune_bounds": self.retune_bounds,
                    "backend": self.backend, "workers": self.workers,
                    "max_swaps": self.max_swaps})
        return cfg

    # -- backend ------------------------------------------------------------
    def _resolve_backend(self, problem_size: int) -> str:
        if self.backend != "auto":
            return self.backend
        if self.shards > 1 and self.k * problem_size >= _MP_AUTO_MIN_ELEMS:
            return "mp"
        return "serial"

    # -- driver -------------------------------------------------------------
    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None) -> RefineResult:
        if self.max_swaps is not None:
            # a global accepted-swap budget couples every ladder at move
            # granularity (one shared counter, checked per batched move) —
            # exactly the coupling sharding removes.  The single-process
            # engine IS that semantics, so budgeted runs delegate to it.
            delegate = copy.copy(self.portfolio)
            delegate.max_swaps = self.max_swaps
            res = delegate.refine(grid, stencil, node_of_pos, num_nodes)
            res.stats.update({"shards": 1, "backend": "single-process",
                              "restarted": 0, "delegated": "max_swaps"})
            return res
        t0 = time.perf_counter()
        sched = self.schedule
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

                cur, swaps, passes = sched.run_rounds(
                    grid, stencil, cur, num_nodes, consider, max_swaps=None)

            # 2. sharded ladders with coordinator-side boundaries
            with obs.span("ladders"):
                lad = self._sharded_ladders(grid, stencil, cur, num_nodes)
                swaps += lad["sa_accepted"]

            with obs.span("survivors"):
                with obs.span("polish"):
                    # 3. original survivors: the exact single-process
                    # selection + polish
                    swaps, passes, polish_order = \
                        self.portfolio._polish_survivors(
                            grid, stencil, num_nodes, consider, lad["nodes"],
                            lad["lad_j_max"], lad["lad_j_sum"], lad["alive"],
                            swaps, passes)

                    # 4. adaptive extras: restart ladders are pure
                    # additional candidates (raw + their own ranked
                    # polish), so the adaptive engine can only improve on
                    # the base portfolio's selection.
                    restart_polished = 0
                    restarts = lad["restarts"]
                    for r in restarts:
                        consider(r["node"].copy(), (r["j_max"], r["j_sum"]))
                    ranked = sorted(range(len(restarts)),
                                    key=lambda j: (restarts[j]["j_max"],
                                                   restarts[j]["j_sum"], j))
                    top = self.portfolio.polish_top
                    r_budget = len(ranked) if top is None else top
                    seen = set()
                    for j in ranked:
                        if restart_polished >= r_budget:
                            break
                        key = restarts[j]["node"].tobytes()
                        if key in seen:
                            continue
                        seen.add(key)
                        _, s, p = sched.polish(grid, stencil,
                                               restarts[j]["node"].copy(),
                                               num_nodes, consider,
                                               max_swaps=None)
                        swaps += s
                        passes += p
                        restart_polished += 1

                with obs.span("final"):
                    final = IncrementalCost(grid, stencil, best,
                                            num_nodes=num_nodes,
                                            weighted=sched.weighted).cost()
        wall = time.perf_counter() - t0
        stats = {
            "k": self.k,
            "seeds": self.seeds,
            "shards": lad["shards"],
            "backend": lad["backend"],
            "sa_accepted": lad["sa_accepted"],
            "killed": lad["killed"],
            "restarted": len(restarts),
            "pool_moves_left": lad["pool_moves"],
            "restart_seeds": [r["seed"] for r in restarts],
            "restart_t_mults": [r["t_mult"] for r in restarts],
            "polished": len(polish_order),
            "restart_polished": restart_polished,
            "ladder_keys": [(float(j), float(s)) for j, s in
                            zip(lad["lad_j_max"], lad["lad_j_sum"])],
            **phase_stats(rec, wall),
        }
        return RefineResult(assignment=best, initial=initial, final=final,
                            swaps=swaps, passes=passes, wall_time_s=wall,
                            stats=stats)

    # -- the sharded ladder coordinator -------------------------------------
    def _sharded_ladders(self, grid: CartGrid, stencil: Stencil,
                         start: np.ndarray,
                         num_nodes: Optional[int]) -> dict:
        sched, port = self.schedule, self.portfolio
        K = self.k
        S = min(self.shards, K)
        n_nodes = int(num_nodes) if num_nodes is not None \
            else int(start.max() + 1)
        weighted = resolve_weighted(sched.weighted, stencil)
        weights = stencil.weight_array() if weighted else np.ones(stencil.k)
        t_scale = float(np.mean(weights))
        backend = self._resolve_backend(grid.size)
        precount = backend == "serial"

        # per-ladder start bookkeeping, identical floats to the
        # single-process engine (same integer counts, same ascending-offset
        # accumulation order)
        start_ic = IncrementalCost(grid, stencil, start, num_nodes=n_nodes,
                                   weighted=weighted)
        j_sum0, j_max0 = start_ic.j_sum, start_ic.j_max
        eps0 = float(1.0 / (1.0 + np.abs(j_sum0)))
        n_temps = len(sched.temperatures)
        ctrl = BoundaryController(
            k=K, kill_factor=port.kill_factor,
            start_keys=np.asarray([j_max0, j_sum0]),
            restarts=self.restarts, retune=self.retune,
            accept_band=self.accept_band, retune_bounds=self.retune_bounds,
            sa_moves=sched.sa_moves, n_temps=n_temps,
            seeder=RestartSeeder(self.seeds, start=self._restart_seed_base))
        alive = ctrl.alive
        cur_keys = np.broadcast_to(
            np.asarray([j_max0, j_sum0]), (K, 2)).copy()

        idx_blocks = [b for b in np.array_split(np.arange(K), S) if b.size]
        blocks = [{
            "node": np.broadcast_to(start, (b.size, grid.size)).copy(),
            "rngs": [np.random.default_rng(self.seeds[i]) for i in b],
            "done": np.zeros(b.size, dtype=bool),
        } for b in idx_blocks]
        base_payload = {
            "dims": tuple(grid.dims), "periodic": tuple(grid.periodic),
            "offsets": stencil.offsets, "weights": stencil.weights,
            "weighted": weighted, "num_nodes": n_nodes,
            "sa_moves": sched.sa_moves,
        }
        if type(grid) is not CartGrid:
            # graph-backed (GraphGrid) or masked grids answer shift_ranks
            # from their own structure — rebuilding a plain CartGrid from
            # dims in the worker would silently drop it.  Both pickle
            # fine (numpy arrays), so ship the object whole.
            base_payload["grid"] = grid
        restarts: List[dict] = []
        accepted = 0

        executor = None
        if backend == "mp" and S > 1:
            # the executor — unlike multiprocessing.Pool — *raises*
            # BrokenProcessPool when a worker dies at startup (e.g. spawn
            # under a non-importable __main__, REPL/stdin scripts), so a
            # broken pool degrades to the inline path instead of hanging a
            # map() forever.
            from concurrent.futures import ProcessPoolExecutor
            ctx = worker_context()
            n_proc = min(S, os.cpu_count() or 1)
            if self.workers is not None:
                n_proc = max(1, min(n_proc, self.workers))
            try:
                executor = ProcessPoolExecutor(max_workers=n_proc,
                                               mp_context=ctx)
            except (OSError, ValueError):    # pragma: no cover - no procs
                executor = None
        pool_ok = executor is not None

        def step(payloads):
            nonlocal pool_ok, backend
            results = None
            if pool_ok and len(payloads) > 1:
                try:
                    results = list(executor.map(_block_step, payloads))
                except Exception:
                    # dead workers (broken spawn main, OOM-killed child, a
                    # task that raised): results are bit-identical either
                    # way, so finish the run inline rather than failing the
                    # mapping.  The executor itself is NOT torn down here —
                    # the enclosing try/finally joins it exactly once,
                    # crash or not, so worker processes are never orphaned.
                    pool_ok = False
                    backend = "serial-fallback"
            if results is None:
                results = [_block_step(p) for p in payloads]
            if _IPC_METER is not None and payloads:
                _IPC_METER.record(payloads, results)
            return results

        def leader_state() -> Tuple[np.ndarray, float]:
            """Current portfolio leader (lexicographic best current key,
            originals then restarts, lowest index wins ties)."""
            cand = [((cur_keys[i, 0], cur_keys[i, 1], 0, i), None)
                    for i in range(K) if alive[i]]
            cand += [((r["j_max"], r["j_sum"], 1, j), r)
                     for j, r in enumerate(restarts)]
            key, r = min(cand, key=lambda c: c[0])
            if r is not None:
                return r["node"], r["j_sum"]
            i = key[3]
            for b, blk in zip(idx_blocks, blocks):
                pos = np.nonzero(b == i)[0]
                if pos.size:
                    return blk["node"][int(pos[0])], float(cur_keys[i, 1])
            raise AssertionError("leader not found")  # pragma: no cover

        try:
            for ti, T0 in enumerate(sched.temperatures):
                T = max(T0 * t_scale, 1e-12)
                payloads, specs = [], []
                for bi, b in enumerate(idx_blocks):
                    blk = blocks[bi]
                    if not (alive[b] & ~blk["done"]).any():
                        continue    # every ladder killed/ended: nothing to
                        # advance — skip the state rebuild (and, under mp,
                        # the round-trip); cur_keys[b] stays frozen, which
                        # is exactly what a no-op dispatch would produce
                    payload = {**base_payload, "node": blk["node"],
                               "rngs": blk["rngs"], "alive": alive[b],
                               "done": blk["done"],
                               "temps": np.full(b.size, T),
                               "eps": np.full(b.size, eps0)}
                    if precount:
                        payload["counts"] = stacked_crossing_counts(
                            grid, stencil, blk["node"], n_nodes)
                    payloads.append(payload)
                    specs.append(("orig", bi, b))
                active = [r for r in restarts if not r["done"]]
                if active:
                    # blocking only buys parallel dispatch; ladder
                    # trajectories are blocking-invariant, so the serial
                    # backend batches all restarts into one kernel call
                    n_chunks = min(S, len(active)) if pool_ok else 1
                    for chunk in np.array_split(np.arange(len(active)),
                                                n_chunks):
                        if not chunk.size:
                            continue
                        rs = [active[int(c)] for c in chunk]
                        payloads.append({
                            **base_payload,
                            "node": np.stack([r["node"] for r in rs]),
                            "rngs": [r["rng"] for r in rs],
                            "alive": np.ones(len(rs), dtype=bool),
                            "done": np.array([r["done"] for r in rs]),
                            "temps": np.array(
                                [max(T0 * t_scale * r["t_mult"], 1e-12)
                                 for r in rs]),
                            "eps": np.array([r["eps"] for r in rs]),
                        })
                        specs.append(("restart", None, rs))
                for (kind, bi, ref), res in zip(specs, step(payloads)):
                    accepted += int(res["accepted"].sum())
                    if kind == "orig":
                        blocks[bi].update(node=res["node"],
                                          rngs=res["rngs"],
                                          done=res["done"])
                        cur_keys[ref] = np.stack(
                            [res["j_max"], res["j_sum"]], axis=1)
                    else:
                        for li, r in enumerate(ref):
                            r.update(node=res["node"][li],
                                     rng=res["rngs"][li],
                                     done=bool(res["done"][li]),
                                     j_max=float(res["j_max"][li]),
                                     j_sum=float(res["j_sum"][li]),
                                     accepted_last=int(res["accepted"][li]))
                # temperature boundary: the shared protocol
                # (:class:`~repro.core.refine.engine.BoundaryController`)
                # over globally merged keys — best-seen update, the
                # single-process kill rule (restarts never feed it), then
                # adaptive control: killed ladders fund restarts from the
                # leader; restart temperatures retune from accept rates
                ctrl.update_best(cur_keys)
                newly_killed = ctrl.kill()

                def spawn(seed: int) -> bool:
                    node, lead_j_sum = leader_state()
                    restarts.append({
                        "node": node.copy(),
                        "rng": np.random.default_rng(seed),
                        "seed": seed,
                        "done": False,
                        "eps": float(1.0 / (1.0 + abs(lead_j_sum))),
                        "t_mult": 1.0,
                        "j_max": math.inf, "j_sum": math.inf,
                        "accepted_last": 0,
                    })
                    return True

                ctrl.adapt(ti, newly_killed, restarts, spawn)
        finally:
            if executor is not None:
                # wait=True even on the crash path: shutdown(wait=False)
                # there would leave the worker processes unjoined (orphaned
                # children outliving the refine — the satellite regression
                # pinned by test_sharded_crash_leaves_no_orphans)
                executor.shutdown(wait=True, cancel_futures=True)

        nodes = np.empty((K, grid.size), dtype=np.int64)
        for b, blk in zip(idx_blocks, blocks):
            nodes[b] = blk["node"]
        # every restart ran at least one temperature (the spawn loop is
        # gated on rem > 0), so its key is finite and usable as a candidate
        assert all(math.isfinite(r["j_max"]) for r in restarts)
        return {"nodes": nodes, "lad_j_max": cur_keys[:, 0].copy(),
                "lad_j_sum": cur_keys[:, 1].copy(), "alive": alive,
                "restarts": restarts, "sa_accepted": accepted,
                "killed": ctrl.killed, "pool_moves": ctrl.pool_moves,
                "shards": S, "backend": backend}
