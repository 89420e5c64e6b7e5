"""Persistent sharded-portfolio workers (the plan server's solve engine).

The stateless sharded engine (:mod:`repro.core.refine.sharded`) re-ships
every block's full state — the (b, p) assignment rows plus rng generators
— to a worker process *per block per temperature*, and ships the same
back: k x p x 8 bytes each way per boundary.  That is the right trade for
a one-shot refine (workers are stateless, any pool shape works), but a
resident server solving a stream of requests can do much better: keep the
block state *in* the worker across temperatures.

:class:`ShardWorkerPool` holds long-lived worker processes speaking a tiny
framed-pickle protocol over pipes; each worker keeps its blocks'
:class:`~repro.core.cost_delta.PortfolioCost` (assignment rows + integer
crossing counts) and rng generators resident between messages.  Per
temperature boundary only the small control plane crosses the wire:

* coordinator -> worker: the global alive mask slice, this temperature's
  scalar ``T`` and acceptance ``eps`` — O(b) bytes;
* worker -> coordinator: per-ladder leader keys ``(j_max, j_sum)``,
  accepted counts and done flags — O(b) bytes.

Everything trajectory-sized (assignments, rng state, crossing counts)
crosses exactly twice per request: once at ``init``, once at ``collect``.
All transport goes through ``send_bytes``/``recv_bytes`` of explicit
pickles, so the pool's byte counters are *measured* IPC, byte-exact — the
numbers ``benchmarks/serve_suite.py`` pins against the stateless
baseline's :func:`~repro.core.refine.sharded.measure_ipc`.

:class:`ResidentShardedRefiner` drives the pool.  It subclasses
:class:`~repro.core.refine.sharded.ShardedPortfolioRefiner` and overrides
*only* the ladder dispatch (``_sharded_ladders``): the shared prefix,
:class:`~repro.core.refine.engine.BoundaryController` kill/restart/retune
semantics, survivor selection and polish all run the inherited code, and
the workers advance ladders with the same
:func:`~repro.core.refine.portfolio.run_temperature` kernel on the same
resident integer count state — so results are **bit-identical** to
``sharded[...]`` at equal configuration (pinned by
``tests/test_serving.py`` and ``results/BENCH_9.json``).

Anytime mode: every temperature boundary is a valid cut point (ladder
rows always realize the scheduler cardinalities), so a deadline-bounded
refine stops at the first boundary past its deadline and selects from the
rounds output, the current rows, each row's *best-seen* boundary snapshot
(tracked worker-side, returned at collect) and any finished restarts —
always a valid plan, never a partial one.  Deadline-cut results are
timing-dependent and are therefore never cached under the deterministic
plan key (the server enforces this).
"""
from __future__ import annotations

import math
import os
import pickle
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.cost_delta import IncrementalCost, PortfolioCost, neighbor_table
from ..core.grid import CartGrid
from ..core.refine.engine import BoundaryController, RestartSeeder
from ..core.refine.sharded import (ShardedPortfolioRefiner, _block_step,
                                   worker_context)
from ..core.refine.portfolio import run_temperature
from ..core.refine.swap import RefineResult
from ..core.stencil import Stencil, resolve_weighted

__all__ = ["ShardWorkerPool", "ResidentShardedRefiner", "WorkerPoolError"]

_PROTO = pickle.HIGHEST_PROTOCOL

#: a worker that hasn't answered in this long is wedged, not slow — treat
#: the pool as broken rather than blocking a server thread forever.
_RECV_TIMEOUT_S = 600.0


class WorkerPoolError(RuntimeError):
    """A persistent worker died or stopped answering; the pool must be
    torn down (the refiner falls back to the inline engine)."""


# ---------------------------------------------------------------------------
# worker-process side


class _WorkerBlock:
    """One resident seed block: assignment rows + integer crossing counts
    (:class:`PortfolioCost`) + rng generators, persistent across
    temperatures.  Counts are integers, so the resident state is bit-equal
    to the state the stateless engine rebuilds from rows each temperature
    — residency changes bytes shipped, never trajectories."""

    def __init__(self, payload: dict):
        grid = CartGrid(tuple(payload["dims"]),
                        periodic=payload["periodic"])
        stencil = Stencil(payload["offsets"], payload["weights"])
        self.pc = PortfolioCost(grid, stencil,
                                np.asarray(payload["node"], dtype=np.int64),
                                num_nodes=payload["num_nodes"],
                                weighted=payload["weighted"],
                                table=neighbor_table(grid, stencil))
        self.rngs = [np.random.default_rng(s) for s in payload["seeds"]]
        self.done = np.zeros(len(self.rngs), dtype=bool)
        self.sa_moves = int(payload["sa_moves"])
        # best-seen boundary snapshot per row (anytime-cut candidates);
        # seeded from the start state, so it is always finite and valid
        self.best_keys = np.stack([self.pc.j_max(), self.pc.j_sum()], axis=1)
        self.best_node = self.pc.node.copy()

    def step(self, alive: np.ndarray, temp: float, eps: float) -> dict:
        b = len(self.rngs)
        accepted = run_temperature(self.pc, self.rngs,
                                   np.asarray(alive, dtype=bool), self.done,
                                   np.full(b, float(temp)), self.sa_moves,
                                   np.full(b, float(eps)))
        j_max, j_sum = self.pc.j_max(), self.pc.j_sum()
        better = ((j_max < self.best_keys[:, 0]) |
                  ((j_max == self.best_keys[:, 0]) &
                   (j_sum < self.best_keys[:, 1])))
        if better.any():
            self.best_keys[better] = np.stack([j_max[better],
                                               j_sum[better]], axis=1)
            self.best_node[better] = self.pc.node[better]
        return {"j_max": j_max, "j_sum": j_sum,
                "accepted": np.asarray(accepted), "done": self.done.copy()}

    def fetch(self, row: int) -> np.ndarray:
        return self.pc.node[int(row)].copy()

    def collect(self) -> dict:
        return {"node": self.pc.node.copy(),
                "best_node": self.best_node.copy(),
                "best_keys": self.best_keys.copy()}


def _worker_main(conn) -> None:
    """Persistent worker loop: framed-pickle request/response over one
    pipe.  Module-level so it survives the spawn start method."""
    blocks: Dict[int, _WorkerBlock] = {}
    while True:
        try:
            msg = pickle.loads(conn.recv_bytes())
        except (EOFError, OSError):       # coordinator went away
            return
        try:
            kind = msg[0]
            if kind == "shutdown":
                conn.send_bytes(pickle.dumps(("bye",), _PROTO))
                return
            if kind == "ping":
                out = ("pong", os.getpid())
            elif kind == "reset":
                blocks.clear()
                out = ("ok",)
            elif kind == "init":
                blocks[int(msg[1])] = _WorkerBlock(msg[2])
                out = ("ok",)
            elif kind == "step":
                out = ("ok", blocks[int(msg[1])].step(**msg[2]))
            elif kind == "fetch":
                out = ("ok", blocks[int(msg[1])].fetch(msg[2]))
            elif kind == "collect":
                out = ("ok", blocks[int(msg[1])].collect())
            elif kind == "crash":         # test hook: die mid-protocol
                os._exit(17)
            else:
                out = ("error", f"unknown message kind {kind!r}")
        except Exception as e:            # never wedge the loop: report
            out = ("error", f"{type(e).__name__}: {e}")
        try:
            conn.send_bytes(pickle.dumps(out, _PROTO))
        except (BrokenPipeError, OSError):
            return


# ---------------------------------------------------------------------------
# coordinator side


class ShardWorkerPool:
    """Long-lived worker processes with per-worker pipes and measured byte
    accounting (``bytes_out`` / ``bytes_in`` count the exact framed pickle
    payloads).  Workers are daemonic (a dying server never strands them)
    and numpy-only (fork-safe; jax is never touched in children); they
    start from :func:`~repro.core.refine.sharded.worker_context`.
    """

    def __init__(self, workers: int = 2):
        if int(workers) < 1:
            raise ValueError("workers must be >= 1")
        ctx = worker_context()
        self._procs = []
        self._conns = []
        for _ in range(int(workers)):
            parent, child = ctx.Pipe()
            proc = ctx.Process(target=_worker_main, args=(child,),
                               daemon=True)
            proc.start()
            child.close()
            self._procs.append(proc)
            self._conns.append(parent)
        self.bytes_out = 0
        self.bytes_in = 0
        self.messages = 0
        self._closed = False

    @property
    def workers(self) -> int:
        return len(self._procs)

    @property
    def alive(self) -> bool:
        return (not self._closed and
                all(p.is_alive() for p in self._procs))

    def _send(self, w: int, msg) -> None:
        data = pickle.dumps(msg, _PROTO)
        try:
            self._conns[w].send_bytes(data)
        except (BrokenPipeError, OSError) as e:
            raise WorkerPoolError(f"worker {w} unreachable: {e}") from e
        self.bytes_out += len(data)
        self.messages += 1

    def _recv(self, w: int):
        try:
            if not self._conns[w].poll(_RECV_TIMEOUT_S):
                raise WorkerPoolError(f"worker {w} timed out")
            data = self._conns[w].recv_bytes()
        except (EOFError, OSError) as e:
            raise WorkerPoolError(f"worker {w} died: {e}") from e
        self.bytes_in += len(data)
        out = pickle.loads(data)
        if out[0] == "error":
            raise WorkerPoolError(f"worker {w}: {out[1]}")
        return out[1] if len(out) > 1 else None

    def request(self, w: int, msg):
        """One synchronous round-trip to worker ``w``."""
        self._send(w, msg)
        return self._recv(w)

    def request_many(self, msgs: Sequence[Tuple[int, object]]) -> list:
        """Pipelined fan-out: send every message, then collect replies in
        send order (a worker answers its own messages in order, so
        multiple blocks on one worker serialize correctly)."""
        for w, msg in msgs:
            self._send(w, msg)
        return [self._recv(w) for w, _ in msgs]

    def broadcast(self, msg) -> list:
        return self.request_many([(w, msg) for w in range(self.workers)])

    def ipc_stats(self) -> Dict[str, int]:
        return {"bytes_out": self.bytes_out, "bytes_in": self.bytes_in,
                "bytes_total": self.bytes_out + self.bytes_in,
                "messages": self.messages, "workers": self.workers}

    def close(self, timeout: float = 5.0) -> None:
        """Graceful shutdown: ask, join, then terminate stragglers.  Every
        worker process is joined — the pool never orphans children."""
        if self._closed:
            return
        self._closed = True
        for w, conn in enumerate(self._conns):
            try:
                conn.send_bytes(pickle.dumps(("shutdown",), _PROTO))
            except (BrokenPipeError, OSError):
                pass
        for proc in self._procs:
            proc.join(timeout=timeout)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=timeout)
        for conn in self._conns:
            try:
                conn.close()
            except OSError:
                pass

    def __enter__(self) -> "ShardWorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - gc safety net
        try:
            self.close(timeout=0.5)
        except Exception:
            pass


class ResidentShardedRefiner(ShardedPortfolioRefiner):
    """Sharded portfolio refiner whose ladder state lives in a persistent
    :class:`ShardWorkerPool` instead of being re-shipped per temperature.

    Every inherited phase — the deterministic rounds prefix, boundary
    control (kill/restart/retune via the shared
    :class:`BoundaryController`), survivor selection, polish — runs the
    superclass code unchanged; only the per-temperature block dispatch is
    replaced.  Restart ladders run inline on the coordinator through the
    same :func:`_block_step` task the stateless engine uses (ladder
    trajectories are batch-composition invariant), so an undeadlined
    refine is bit-identical to ``sharded[...]`` at equal configuration.

    ``pool=None`` lazily creates (and owns) a pool sized
    ``min(shards, cpu)``; pass a shared pool to amortize worker startup
    across requests (the server does).  If a worker dies mid-refine the
    undeadlined path falls back to the inline serial engine (still
    bit-identical — correctness never depends on the pool), and the
    deadline path degrades to the best candidate seen so far.

    :meth:`refine_anytime` adds the deadline mode; see the module
    docstring for the cut invariants.
    """

    def __init__(self, pool: Optional[ShardWorkerPool] = None, **kwargs):
        kwargs.setdefault("backend", "serial")   # fallback path stays inline
        super().__init__(**kwargs)
        self._pool = pool
        self._owns_pool = False
        self._deadline_at: Optional[float] = None
        self._last_ipc: Optional[dict] = None

    def refine(self, grid: CartGrid, stencil: Stencil,
               node_of_pos: np.ndarray,
               num_nodes: Optional[int] = None) -> RefineResult:
        self._last_ipc = None
        res = super().refine(grid, stencil, node_of_pos, num_nodes)
        if self._last_ipc is not None:
            res.stats["ipc"] = self._last_ipc
        return res

    # -- pool plumbing -------------------------------------------------------
    def _ensure_pool(self) -> ShardWorkerPool:
        if self._pool is None or not self._pool.alive:
            if self._pool is not None and self._owns_pool:
                self._pool.close()
            self._pool = ShardWorkerPool(
                workers=min(max(1, self.shards), os.cpu_count() or 1))
            self._owns_pool = True
        return self._pool

    def close(self) -> None:
        if self._pool is not None and self._owns_pool:
            self._pool.close()
        self._pool = None

    def __enter__(self) -> "ResidentShardedRefiner":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- the resident ladder dispatch ---------------------------------------
    def _sharded_ladders(self, grid: CartGrid, stencil: Stencil,
                         start: np.ndarray,
                         num_nodes: Optional[int]) -> dict:
        try:
            return self._resident_ladders(grid, stencil, start, num_nodes,
                                          self._deadline_at)
        except WorkerPoolError:
            if self._deadline_at is not None:
                raise    # anytime caller degrades to its best-so-far
            # undeadlined: correctness must never depend on the pool — the
            # inline serial engine produces bit-identical ladders
            if self._pool is not None and self._owns_pool:
                self._pool.close()
                self._pool = None
            lad = super()._sharded_ladders(grid, stencil, start, num_nodes)
            lad["backend"] = "resident-fallback"
            lad.setdefault("cut_at", len(self.schedule.temperatures))
            return lad

    def _resident_ladders(self, grid: CartGrid, stencil: Stencil,
                          start: np.ndarray, num_nodes: Optional[int],
                          deadline_at: Optional[float]) -> dict:
        sched, port = self.schedule, self.portfolio
        K = self.k
        S = min(self.shards, K)
        pool = self._ensure_pool()
        W = pool.workers
        n_nodes = int(num_nodes) if num_nodes is not None \
            else int(start.max() + 1)
        weighted = resolve_weighted(sched.weighted, stencil)
        weights = stencil.weight_array() if weighted \
            else np.ones(stencil.k)
        t_scale = float(np.mean(weights))

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
        block_worker = [bi % W for bi in range(len(idx_blocks))]
        done_blocks = [np.zeros(b.size, dtype=bool) for b in idx_blocks]
        base_payload = {
            "dims": tuple(grid.dims), "periodic": tuple(grid.periodic),
            "offsets": stencil.offsets, "weights": stencil.weights,
            "weighted": weighted, "num_nodes": n_nodes,
            "sa_moves": sched.sa_moves,
        }
        restarts: List[dict] = []
        accepted = 0
        bytes0 = pool.bytes_out + pool.bytes_in

        # one-time state up: broadcast start rows + seeds per block
        pool.broadcast(("reset",))
        pool.request_many([
            (block_worker[bi],
             ("init", bi, {**base_payload,
                           "node": np.broadcast_to(
                               start, (b.size, grid.size)).copy(),
                           "seeds": [int(self.seeds[i]) for i in b]}))
            for bi, b in enumerate(idx_blocks)])
        init_bytes = pool.bytes_out + pool.bytes_in - bytes0

        def leader_state() -> Tuple[np.ndarray, float]:
            """Identical ranking to the stateless coordinator: alive
            originals then restarts on current lexicographic key, lowest
            index wins ties; an original leader's row is fetched from its
            worker (one p-row, only on restart spawn)."""
            cand = [((cur_keys[i, 0], cur_keys[i, 1], 0, i), None)
                    for i in range(K) if alive[i]]
            cand += [((r["j_max"], r["j_sum"], 1, j), r)
                     for j, r in enumerate(restarts)]
            key, r = min(cand, key=lambda c: c[0])
            if r is not None:
                return r["node"], r["j_sum"]
            i = key[3]
            for bi, b in enumerate(idx_blocks):
                pos = np.nonzero(b == i)[0]
                if pos.size:
                    row = pool.request(block_worker[bi],
                                       ("fetch", bi, int(pos[0])))
                    return np.asarray(row, dtype=np.int64), \
                        float(cur_keys[i, 1])
            raise AssertionError("leader not found")  # pragma: no cover

        boundary_s: List[float] = []
        cut_at = n_temps
        step_bytes0 = pool.bytes_out + pool.bytes_in
        for ti, T0 in enumerate(sched.temperatures):
            if deadline_at is not None:
                # predictive cut: don't start a boundary the last one's
                # duration says won't finish in time (the first boundary
                # has no estimate and may overshoot by its own length)
                est = boundary_s[-1] if boundary_s else 0.0
                if time.perf_counter() + est >= deadline_at:
                    cut_at = ti
                    break
            tb0 = time.perf_counter()
            T = max(T0 * t_scale, 1e-12)
            msgs, specs = [], []
            for bi, b in enumerate(idx_blocks):
                if not (alive[b] & ~done_blocks[bi]).any():
                    continue    # same skip rule as the stateless engine:
                    # cur_keys[b] stays frozen, no dispatch
                msgs.append((block_worker[bi],
                             ("step", bi, {"alive": alive[b],
                                           "temp": T, "eps": eps0})))
                specs.append(("orig", bi, b))
            # restart ladders advance inline through the *stateless* task
            # (their state is coordinator-resident already; trajectories
            # are batch-composition invariant, so one stacked batch is
            # bit-identical to the stateless engine's chunking)
            active = [r for r in restarts if not r["done"]]
            payloads = []
            if active:
                payloads.append({
                    **base_payload,
                    "node": np.stack([r["node"] for r in active]),
                    "rngs": [r["rng"] for r in active],
                    "alive": np.ones(len(active), dtype=bool),
                    "done": np.array([r["done"] for r in active]),
                    "temps": np.array(
                        [max(T0 * t_scale * r["t_mult"], 1e-12)
                         for r in active]),
                    "eps": np.array([r["eps"] for r in active]),
                })
            results = pool.request_many(msgs)
            for (kind, bi, b), res in zip(specs, results):
                accepted += int(res["accepted"].sum())
                done_blocks[bi] = res["done"]
                cur_keys[b] = np.stack([res["j_max"], res["j_sum"]], axis=1)
            for payload in payloads:
                res = _block_step(payload)
                accepted += int(res["accepted"].sum())
                for li, r in enumerate(active):
                    r.update(node=res["node"][li], rng=res["rngs"][li],
                             done=bool(res["done"][li]),
                             j_max=float(res["j_max"][li]),
                             j_sum=float(res["j_sum"][li]),
                             accepted_last=int(res["accepted"][li]))
            # temperature boundary: shared protocol over global keys
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
            boundary_s.append(time.perf_counter() - tb0)
        step_bytes = pool.bytes_out + pool.bytes_in - step_bytes0

        # one-time state down: final rows + per-row best-seen snapshots
        coll0 = pool.bytes_out + pool.bytes_in
        nodes = np.empty((K, grid.size), dtype=np.int64)
        best_nodes = np.empty((K, grid.size), dtype=np.int64)
        best_keys = np.empty((K, 2), dtype=np.float64)
        colls = pool.request_many([(block_worker[bi], ("collect", bi))
                                   for bi in range(len(idx_blocks))])
        for b, coll in zip(idx_blocks, colls):
            nodes[b] = coll["node"]
            best_nodes[b] = coll["best_node"]
            best_keys[b] = coll["best_keys"]
        collect_bytes = pool.bytes_out + pool.bytes_in - coll0

        if cut_at >= n_temps:
            # completed run: every restart ran >= 1 temperature (spawns
            # are gated on remaining budget), so its key is finite
            assert all(math.isfinite(r["j_max"]) for r in restarts)
        else:
            # a restart spawned at the cut boundary never ran: not a
            # candidate (its key is inf), drop it
            restarts = [r for r in restarts
                        if math.isfinite(r["j_max"])]
        n_boundaries = max(1, len(boundary_s))
        self._last_ipc = {"init_bytes": init_bytes,
                          "step_bytes": step_bytes,
                          "collect_bytes": collect_bytes,
                          "boundaries": len(boundary_s),
                          "step_bytes_per_boundary":
                              step_bytes / n_boundaries}
        return {"nodes": nodes, "lad_j_max": cur_keys[:, 0].copy(),
                "lad_j_sum": cur_keys[:, 1].copy(), "alive": alive,
                "restarts": restarts, "sa_accepted": accepted,
                "killed": ctrl.killed, "pool_moves": ctrl.pool_moves,
                "shards": S, "backend": "resident",
                "cut_at": cut_at, "boundary_s": boundary_s,
                "best_nodes": best_nodes, "best_keys": best_keys,
                "ipc": dict(self._last_ipc)}

    # -- anytime ------------------------------------------------------------
    def refine_anytime(self, grid: CartGrid, stencil: Stencil,
                       node_of_pos: np.ndarray,
                       num_nodes: Optional[int] = None,
                       deadline_s: Optional[float] = None) -> RefineResult:
        """Deadline-bounded refine: the best valid plan found within
        ``deadline_s`` seconds.

        Cut invariants: (1) phases are checked against the deadline at
        every boundary — before the rounds prefix, before each ladder
        temperature — and the first boundary past it stops the run; (2)
        every candidate considered (start, rounds output, current ladder
        rows, worker-side best-seen snapshots, finished restarts)
        realizes the scheduler cardinalities, so the returned assignment
        is always valid no matter where the cut lands; (3) the anytime
        path never polishes — its completed-run result is a deterministic
        function of the inputs, which is what lets the server cache
        *uncut* anytime results (cut results are timing-dependent and are
        never cached).  ``deadline_s=None`` delegates to the bit-identical
        undeadlined :meth:`refine`.
        """
        if deadline_s is None:
            return self.refine(grid, stencil, node_of_pos, num_nodes)
        t0 = time.perf_counter()
        deadline_at = t0 + max(0.0, float(deadline_s))
        sched = self.schedule
        cur = np.asarray(node_of_pos, dtype=np.int64).copy()
        initial = IncrementalCost(grid, stencil, cur, num_nodes=num_nodes,
                                  weighted=sched.weighted).cost()
        best, best_key = cur.copy(), (initial.j_max, initial.j_sum)

        def consider(candidate: np.ndarray, key: Tuple[float, float]):
            nonlocal best, best_key
            if key < best_key:
                best, best_key = candidate.copy(), key

        swaps = passes = 0
        cut_stage = "start"
        if time.perf_counter() < deadline_at:
            cur, swaps, passes = sched.run_rounds(grid, stencil, cur,
                                                  num_nodes, consider,
                                                  max_swaps=None)
            cut_stage = "rounds"
        lad = None
        if time.perf_counter() < deadline_at:
            self._deadline_at = deadline_at
            try:
                lad = self._sharded_ladders(grid, stencil, cur, num_nodes)
                cut_stage = "ladders"
            except WorkerPoolError:
                lad = None    # degrade: best-so-far is still valid
            finally:
                self._deadline_at = None
        cut_at, boundary_s = 0, []
        if lad is not None:
            swaps += lad["sa_accepted"]
            cut_at = lad.get("cut_at", len(sched.temperatures))
            boundary_s = lad.get("boundary_s", [])
            for i in range(self.k):
                consider(lad["nodes"][i],
                         (float(lad["lad_j_max"][i]),
                          float(lad["lad_j_sum"][i])))
            if "best_nodes" in lad:
                for i in range(self.k):
                    consider(lad["best_nodes"][i],
                             (float(lad["best_keys"][i, 0]),
                              float(lad["best_keys"][i, 1])))
            for r in lad["restarts"]:
                consider(r["node"].copy(), (r["j_max"], r["j_sum"]))

        final = IncrementalCost(grid, stencil, best, num_nodes=num_nodes,
                                weighted=sched.weighted).cost()
        wall = time.perf_counter() - t0
        n_temps = len(sched.temperatures)
        stats = {
            "k": self.k, "seeds": self.seeds,
            "shards": lad["shards"] if lad else 0,
            "backend": "resident-anytime",
            "deadline_s": float(deadline_s),
            "cut": lad is None or cut_at < n_temps,
            "cut_stage": cut_stage, "cut_at": cut_at, "n_temps": n_temps,
            "boundary_s": boundary_s,
            "max_boundary_s": max(boundary_s) if boundary_s else 0.0,
            "overshoot_s": max(0.0, wall - float(deadline_s)),
            "sa_accepted": lad["sa_accepted"] if lad else 0,
            "killed": lad["killed"] if lad else 0,
            "restarted": len(lad["restarts"]) if lad else 0,
            "polished": 0,
            "ipc": lad.get("ipc") if lad else None,
        }
        return RefineResult(assignment=best, initial=initial, final=final,
                            swaps=swaps, passes=passes, wall_time_s=wall,
                            stats=stats)
