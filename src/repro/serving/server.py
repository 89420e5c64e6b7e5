"""The resident plan server: mapping-as-a-service over the plan layer.

``cart_create`` is one call, but every call is a cold solver spin-up.
Production traffic is many concurrent ``cart_create``/re-mesh/repair
requests against a shared machine model — the regime where mapping cost
must be amortized against the application's communication volume.
:class:`PlanServer` is the serving loop for mappings, analogous to
``runtime/serve_loop.py``'s slot scheduler for training jobs:

* it **owns the shared** :class:`~repro.core.plan.PlanCache` (TTL +
  ``invalidate(problem_hash)`` + size-bounded disk spill — the PR-9 cache
  extensions) and warms it with a sweep over a registry of known
  topologies (:func:`register_topology` / :meth:`PlanServer.warm_up`);
* a **bounded admission queue** (``max_queue``) with per-request
  deadlines: a full queue rejects at submit time
  (:class:`AdmissionError`) instead of queueing unbounded latency;
* solver threads, each holding a persistent
  :class:`~repro.serving.workers.ShardWorkerPool` — ``sharded[...]``
  plans run on the resident engine
  (:class:`~repro.serving.workers.ResidentShardedRefiner`), whose results
  are bit-identical to the stateless engine and are therefore cached
  under the *same* plan key;
* an **anytime mode**: a request with ``deadline_ms`` returns the best
  valid plan found within its deadline (every portfolio temperature
  boundary is a valid cut point).  Deadline-*cut* results are
  timing-dependent and never enter the cache; an anytime run that
  completed uncut is deterministic (the anytime path never polishes) and
  is cached under ``<plan key>@anytime`` — never under the undeadlined
  key, which would poison warm full-quality serves.

:class:`~repro.serving.client.PlanClient` is the ergonomic front
(``submit`` / ``cart_create_async`` / ``stats``).
"""
from __future__ import annotations

import copy
import itertools
import math
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import obs
from ..core.plan import (MappingPlan, MappingProblem, MappingSolution,
                         PlanCache, blocked_node_sizes, parse_plan,
                         _jsonable_stats)
from ..core.refine.stage import RefineStage
from ..core.stencil import Stencil
from .workers import ResidentShardedRefiner, ShardWorkerPool

__all__ = ["PlanServer", "PlanTicket", "AdmissionError",
           "register_topology", "known_topologies", "DEFAULT_SERVE_PLAN"]

#: the server's default plan: resident-sharded refinement over the
#: hyperplane base (the spelling is the cache identity — the resident
#: engine serves it bit-identically to the stateless ``sharded:``).
DEFAULT_SERVE_PLAN = "sharded[shards=2,k=8,restarts=auto]:hyperplane"

#: completed requests whose latency and queue wait :meth:`PlanServer.stats`
#: summarizes (a ring: the newest this many)
STATS_WINDOW = 2048


class AdmissionError(RuntimeError):
    """Request rejected at submit time (queue full or server stopped)."""


# ---------------------------------------------------------------------------
# warm-up registry


_topology_registry: "OrderedDict[str, Callable[[], MappingProblem]]" = \
    OrderedDict()
_registry_lock = threading.Lock()


def register_topology(name: str,
                      factory: Callable[[], MappingProblem]) -> None:
    """Register a known topology for warm-up sweeps.  ``factory`` builds
    the :class:`MappingProblem` lazily (registration stays import-cheap);
    re-registering a name replaces it."""
    if not callable(factory):
        raise TypeError("factory must be a zero-arg MappingProblem factory")
    with _registry_lock:
        _topology_registry[str(name)] = factory


def known_topologies() -> Tuple[str, ...]:
    """Names registered for warm-up, in registration order."""
    with _registry_lock:
        return tuple(_topology_registry)


def _registry_get(names: Optional[Sequence[str]]) \
        -> List[Tuple[str, Callable[[], MappingProblem]]]:
    with _registry_lock:
        if names is None:
            return list(_topology_registry.items())
        return [(n, _topology_registry[n]) for n in names]


def _register_defaults() -> None:
    """Default registry: modest blocked v5e-style allocations (mesh shape,
    16-chip pods) — the shapes the quickstart and serve smoke warm."""
    register_topology(
        "v5e-4pod-8x8",
        lambda: MappingProblem((8, 8), Stencil.nearest_neighbor(2),
                               blocked_node_sizes(64, 16)))
    register_topology(
        "v5e-8pod-16x8",
        lambda: MappingProblem((16, 8), Stencil.nearest_neighbor(2),
                               blocked_node_sizes(128, 16)))


_register_defaults()


# ---------------------------------------------------------------------------
# tickets


class PlanTicket:
    """Future-shaped handle for one submitted request.  ``started_at`` is
    set when a serve thread takes it off the queue, and ``queue_wait_s``
    is the time it waited there."""

    def __init__(self, deadline_s: Optional[float]):
        self.submitted_at = time.perf_counter()
        self.started_at: Optional[float] = None
        self.queue_wait_s: Optional[float] = None
        self.deadline_s = deadline_s
        self._event = threading.Event()
        self._solution: Optional[MappingSolution] = None
        self._error: Optional[BaseException] = None
        self.latency_s: Optional[float] = None
        self.deadline_missed = False
        self.anytime_cut = False

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> MappingSolution:
        """Block until served; re-raises the solver's exception if the
        request failed."""
        if not self._event.wait(timeout):
            raise TimeoutError("plan request still in flight")
        if self._error is not None:
            raise self._error
        return self._solution

    # -- server side --------------------------------------------------------
    def _complete(self, solution: Optional[MappingSolution],
                  error: Optional[BaseException]) -> None:
        self.latency_s = time.perf_counter() - self.submitted_at
        if self.deadline_s is not None:
            self.deadline_missed = self.latency_s > self.deadline_s
        self._solution, self._error = solution, error
        self._event.set()


class _Request:
    __slots__ = ("kind", "args", "ticket")

    def __init__(self, kind: str, args: dict, ticket: PlanTicket):
        self.kind, self.args, self.ticket = kind, args, ticket


# ---------------------------------------------------------------------------
# the server


class PlanServer:
    """Long-lived mapping server: shared plan cache + bounded admission +
    persistent shard workers + deadlines/anytime.  See module docstring.

    Args:
      cache: the shared :class:`PlanCache` (default: a fresh one with
        ``maxsize=512``).  Hand one built with ``ttl_s`` /
        ``max_disk_bytes`` / ``disk_dir`` to get expiring, size-bounded
        spill behavior.
      threads: solver threads; each lazily creates one persistent
        :class:`ShardWorkerPool` of ``shard_workers`` processes.
      shard_workers: worker processes per solver thread's pool.
      max_queue: admission bound — submits beyond it raise
        :class:`AdmissionError` (and count as ``rejected``).
      default_plan: plan used when a request doesn't name one.
    """

    def __init__(self, cache: Optional[PlanCache] = None, threads: int = 2,
                 shard_workers: int = 2, max_queue: int = 64,
                 default_plan: Union[str, MappingPlan] = DEFAULT_SERVE_PLAN):
        if int(threads) < 1:
            raise ValueError("threads must be >= 1")
        if int(max_queue) < 1:
            raise ValueError("max_queue must be >= 1")
        self.cache = cache if cache is not None else PlanCache(maxsize=512)
        self.threads = int(threads)
        self.shard_workers = int(shard_workers)
        self.default_plan = default_plan
        self._queue: "queue.Queue[_Request]" = queue.Queue(
            maxsize=int(max_queue))
        self._stop = threading.Event()
        self._workers: List[threading.Thread] = []
        self._pools: List[ShardWorkerPool] = []
        self._pools_lock = threading.Lock()
        self._local = threading.local()
        self._stats_lock = threading.Lock()
        # (latency_s, queue_wait_s) of the newest completed requests
        self._samples: deque = deque(maxlen=STATS_WINDOW)
        self._seq = itertools.count()
        # single-flight: per-solution-key latch so concurrent cold misses
        # on one key run the solve once (followers wait, then hit cache)
        self._inflight_keys: Dict[str, threading.Event] = {}
        self._inflight_lock = threading.Lock()
        self.single_flight_waits = 0
        self.completed = 0
        self.errors = 0
        self.rejected = 0
        self.deadline_misses = 0
        self.anytime_cuts = 0
        self.inflight = 0
        self.warmed = 0
        self._started_at: Optional[float] = None

    # -- lifecycle ----------------------------------------------------------
    def start(self, warm: bool = False) -> "PlanServer":
        if self._workers:
            raise RuntimeError("server already started")
        self._stop.clear()
        self._started_at = time.perf_counter()
        for i in range(self.threads):
            t = threading.Thread(target=self._serve_loop,
                                 name=f"plan-server-{i}", daemon=True)
            t.start()
            self._workers.append(t)
        if warm:
            self.warm_up()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Drain-free stop: running requests finish, queued requests are
        failed with :class:`AdmissionError`, worker pools close (every
        shard process joined)."""
        self._stop.set()
        for t in self._workers:
            t.join(timeout=timeout)
        self._workers = []
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            req.ticket._complete(None, AdmissionError("server stopped"))
        with self._pools_lock:
            pools, self._pools = self._pools, []
        for pool in pools:
            pool.close()

    def __enter__(self) -> "PlanServer":
        return self.start() if not self._workers else self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- admission ----------------------------------------------------------
    def submit(self, problem: Optional[MappingProblem] = None, *,
               mesh_shape: Optional[Sequence[int]] = None,
               stencil: Optional[Stencil] = None,
               node_sizes: Optional[Sequence[int]] = None,
               chips_per_pod: Optional[int] = None,
               periodic: Optional[Sequence[bool]] = None,
               objective: str = "lex",
               plan: Union[None, str, MappingPlan] = None,
               deadline_ms: Optional[float] = None) -> PlanTicket:
        """Enqueue one mapping request; returns a :class:`PlanTicket`.

        Pass either a built :class:`MappingProblem` or the
        ``cart_create``-style fields (``mesh_shape`` + ``node_sizes`` /
        ``chips_per_pod`` + optional ``stencil``/``periodic``).
        ``deadline_ms`` makes the request anytime: the ticket resolves to
        the best valid plan found within the deadline."""
        if problem is None:
            if mesh_shape is None:
                raise ValueError("submit needs a problem or a mesh_shape")
            mesh_shape = tuple(int(d) for d in mesh_shape)
            p = math.prod(mesh_shape)
            if stencil is None:
                stencil = Stencil.nearest_neighbor(len(mesh_shape))
            if node_sizes is not None and chips_per_pod is not None:
                raise ValueError("pass node_sizes or chips_per_pod, "
                                 "not both")
            if node_sizes is not None:
                node_sizes = tuple(int(n) for n in node_sizes)
            elif chips_per_pod is not None:
                node_sizes = blocked_node_sizes(p, chips_per_pod)
            else:
                raise ValueError("submit needs node_sizes or chips_per_pod")
            problem = MappingProblem(mesh_shape, stencil, node_sizes,
                                     objective=objective,
                                     periodic=None if periodic is None
                                     else tuple(periodic))
        deadline_s = None if deadline_ms is None \
            else max(0.0, float(deadline_ms)) / 1e3
        ticket = PlanTicket(deadline_s)
        self._admit(_Request("solve", {"problem": problem, "plan": plan},
                             ticket))
        return ticket

    def submit_repair(self, previous, node_sizes: Sequence[int], *,
                      deadline_ms: Optional[float] = None,
                      **repair_options) -> PlanTicket:
        """Enqueue a warm-start repair (the runtime/remap churn path):
        equivalent to :func:`repro.core.remap.repair_layout` against the
        server's shared cache, but admission-controlled and counted like
        any other request."""
        deadline_s = None if deadline_ms is None \
            else max(0.0, float(deadline_ms)) / 1e3
        ticket = PlanTicket(deadline_s)
        self._admit(_Request("repair",
                             {"previous": previous,
                              "node_sizes": tuple(int(s)
                                                  for s in node_sizes),
                              "options": dict(repair_options)},
                             ticket))
        return ticket

    def _admit(self, req: _Request) -> None:
        if self._stop.is_set() or not self._workers:
            raise AdmissionError("server is not running")
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            with self._stats_lock:
                self.rejected += 1
            raise AdmissionError(
                f"admission queue full ({self._queue.maxsize} pending)")

    # -- cache control -------------------------------------------------------
    def invalidate(self, problem: Union[str, MappingProblem]) -> int:
        """Drop every cached entry for one problem (accepts the problem or
        its ``content_hash()``)."""
        h = problem.content_hash() if isinstance(problem, MappingProblem) \
            else str(problem)
        return self.cache.invalidate(h)

    def warm_up(self, names: Optional[Sequence[str]] = None,
                plan: Union[None, str, MappingPlan] = None) -> dict:
        """Sweep the topology registry (or ``names``) through the solve
        path so production requests hit a warm cache.  Runs in the calling
        thread — a server can warm before opening admission.  ``backends``
        names, per topology, the engine its final stage ran on."""
        solved = hits = 0
        backends: Dict[str, Optional[str]] = {}
        for name, factory in _registry_get(names):
            problem = factory()
            sol = self._solve(problem, self._resolve_plan(plan), None, None)
            hits += int(sol.from_cache)
            solved += 1
            backends[name] = next((st["backend"] for st in
                                   reversed(sol.stage_stats)
                                   if "backend" in st), None)
        with self._stats_lock:
            self.warmed += solved
        return {"swept": solved, "already_cached": hits,
                "backends": backends}

    # -- solve path ----------------------------------------------------------
    def _resolve_plan(self, plan: Union[None, str, MappingPlan]) \
            -> MappingPlan:
        if plan is None:
            plan = self.default_plan
        # parse fresh (never share stage objects across threads): the
        # resident swap mutates the final stage's refiner
        return parse_plan(plan) if isinstance(plan, str) else plan

    def _thread_pool(self) -> ShardWorkerPool:
        pool = getattr(self._local, "pool", None)
        if pool is None or not pool.alive:
            pool = ShardWorkerPool(workers=self.shard_workers)
            self._local.pool = pool
            with self._pools_lock:
                self._pools.append(pool)
        return pool

    @staticmethod
    def _resident_stage(plan: MappingPlan) -> Optional[RefineStage]:
        """The final stage when this plan is resident-eligible: a
        ``sharded`` refine stage with no stage budget (a budget threads
        ``max_swaps``, which the sharded engine delegates to the
        single-process portfolio anyway)."""
        if not plan.stages:
            return None
        stage = plan.stages[-1]
        if (isinstance(stage, RefineStage) and stage.prefix == "sharded"
                and stage.budget is None
                and getattr(stage.refiner, "max_swaps", None) is None):
            return stage
        return None

    def _make_resident(self, stage: RefineStage) -> ResidentShardedRefiner:
        cfg = dict(stage.refiner.config())
        cfg["backend"] = "serial"          # fallback path stays inline
        return ResidentShardedRefiner(pool=self._thread_pool(), **cfg)

    def _solve(self, problem: MappingProblem,
               plan: MappingPlan, deadline_s: Optional[float],
               ticket: Optional[PlanTicket]) -> MappingSolution:
        stage = self._resident_stage(plan)
        if stage is not None:
            # never mutate the caller's plan: shallow-copy the final stage
            # before swapping its refiner (spec()/key are unchanged)
            stage = copy.copy(stage)
            plan = MappingPlan(tuple(plan.stages[:-1]) + (stage,),
                               name=plan.name, graph=plan.graph_flavor)
        if deadline_s is not None and stage is not None:
            return self._solve_anytime(problem, plan, stage,
                                       deadline_s, ticket)
        if stage is not None:
            # resident persistent-worker engine, bit-identical to the
            # stateless sharded engine -> same result, same cache key
            stage.refiner = self._make_resident(stage)
        if not plan.cacheable:
            return self.cache.solve(problem, plan)
        # single-flight: concurrent cold misses on one key would each run
        # the full solve (up to `threads` redundant anneals).  The first
        # arrival becomes the leader and solves; followers park on the
        # key's latch and re-enter when it publishes — their solve is then
        # a cache hit.  A follower that re-enters after a leader *failure*
        # simply becomes the next leader (retry, not deadlock).
        key = f"sol:{problem.content_hash()}:{plan.key}"
        while True:
            with self._inflight_lock:
                ev = self._inflight_keys.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight_keys[key] = ev
                    leader = True
                else:
                    leader = False
            if leader:
                try:
                    return self.cache.solve(problem, plan)
                finally:
                    with self._inflight_lock:
                        self._inflight_keys.pop(key, None)
                    ev.set()
            with self._stats_lock:
                self.single_flight_waits += 1
            ev.wait()

    def _solve_anytime(self, problem: MappingProblem, plan: MappingPlan,
                       stage: RefineStage, deadline_s: float,
                       ticket: Optional[PlanTicket]) -> MappingSolution:
        """Deadline-bounded solve.  The undeadlined cache entry serves
        instantly when present (strictly better than any cut); otherwise
        the uncut-anytime entry (``@anytime``) does.  A fresh run cuts at
        the first boundary past the deadline; only *uncut* runs — which
        are deterministic, the anytime path never polishes — are cached,
        under the ``@anytime`` key."""
        t0 = time.perf_counter()
        anytime_key = None
        if plan.cacheable:
            full = self.cache.get(f"sol:{problem.content_hash()}:{plan.key}")
            if full is None:
                anytime_key = (f"sol:{problem.content_hash()}:"
                               f"{plan.key}@anytime")
                full = self.cache.get(anytime_key)
            if full is not None:
                return MappingSolution(
                    assignment=np.array(full["assignment"], dtype=np.int64),
                    j_sum=float(full["j_sum"]), j_max=float(full["j_max"]),
                    problem=problem, plan_key=plan.key,
                    stage_stats=_jsonable_stats(full["stage_stats"]),
                    wall_time_s=float(full["wall_time_s"]), from_cache=True)

        grid, stencil = problem.grid(), problem.stencil
        sizes = problem.node_sizes
        assignment = None
        stage_stats: List[dict] = []
        for st in plan.stages[:-1]:
            r = st.run(grid, stencil, sizes, assignment)
            assignment = r.assignment
            stage_stats.append(r.stats)
        refiner = self._make_resident(stage)
        remaining = max(0.0, deadline_s - (time.perf_counter() - t0))
        res = refiner.refine_anytime(grid, stencil, assignment,
                                     num_nodes=len(sizes),
                                     deadline_s=remaining)
        cut = bool(res.stats.get("cut", False))
        if ticket is not None:
            ticket.anytime_cut = cut
        if cut:
            with self._stats_lock:
                self.anytime_cuts += 1
        stage_stats.append({"stage": stage.spec() + "@anytime",
                            "kind": "refine", **res.stats,
                            "initial": (res.initial.j_max,
                                        res.initial.j_sum),
                            "final": (res.final.j_max, res.final.j_sum)})
        wall = time.perf_counter() - t0
        sol = MappingSolution(
            assignment=res.assignment, j_sum=res.final.j_sum,
            j_max=res.final.j_max, problem=problem, plan_key=plan.key,
            stage_stats=_jsonable_stats(stage_stats), wall_time_s=wall,
            from_cache=False)
        if not cut and anytime_key is not None:
            # deterministic (uncut, unpolished) -> cacheable under the
            # @anytime key; cut results are timing-dependent: never cached
            self.cache.put(anytime_key, {
                "assignment": np.array(sol.assignment, dtype=np.int64),
                "j_sum": sol.j_sum, "j_max": sol.j_max,
                "stage_stats": sol.stage_stats,
                "wall_time_s": sol.wall_time_s,
            })
        return sol

    # -- the serve loop ------------------------------------------------------
    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            try:
                req = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            ticket = req.ticket
            ticket.started_at = time.perf_counter()
            ticket.queue_wait_s = ticket.started_at - ticket.submitted_at
            # the request's sequence number ties its spans together in a
            # profiler trace
            with obs.span("serve.request", request=next(self._seq)):
                self._serve_one(req)

    def _serve_one(self, req: _Request) -> None:
        ticket = req.ticket
        with self._stats_lock:
            self.inflight += 1
        try:
            if req.kind == "repair":
                from ..core.remap import repair_layout
                sol = repair_layout(req.args["previous"],
                                    req.args["node_sizes"],
                                    cache=self.cache,
                                    **req.args["options"])
            else:
                plan = self._resolve_plan(req.args["plan"])
                deadline_s = ticket.deadline_s
                if deadline_s is not None:
                    # deadline is end-to-end: queue wait eats budget
                    deadline_s = max(
                        0.0, deadline_s - (time.perf_counter()
                                           - ticket.submitted_at))
                sol = self._solve(req.args["problem"], plan,
                                  deadline_s, ticket)
            ticket._complete(sol, None)
            with self._stats_lock:
                self.completed += 1
                self._samples.append((ticket.latency_s,
                                      ticket.queue_wait_s))
                if ticket.deadline_missed:
                    self.deadline_misses += 1
        except BaseException as e:          # noqa: BLE001 - report all
            ticket._complete(None, e)
            with self._stats_lock:
                self.errors += 1
        finally:
            with self._stats_lock:
                self.inflight -= 1

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        """Queue depth, throughput/latency, deadline and cache health —
        the numbers the serving dashboard would scrape.  Latency and queue
        wait percentiles cover the newest ``STATS_WINDOW`` completed
        requests."""
        with self._stats_lock:
            samples = list(self._samples)
            out = {
                "queue_depth": self._queue.qsize(),
                "inflight": self.inflight,
                "completed": self.completed,
                "errors": self.errors,
                "rejected": self.rejected,
                "deadline_misses": self.deadline_misses,
                "anytime_cuts": self.anytime_cuts,
                "single_flight_waits": self.single_flight_waits,
                "warmed": self.warmed,
                "threads": self.threads,
                "uptime_s": (0.0 if self._started_at is None
                             else time.perf_counter() - self._started_at),
            }
        if samples:
            for name, column in zip(("latency", "queue_wait"),
                                    zip(*samples)):
                v = sorted(column)
                out[f"{name}_p50_ms"] = 1e3 * v[len(v) // 2]
                out[f"{name}_p95_ms"] = 1e3 * v[min(len(v) - 1,
                                                    int(0.95 * len(v)))]
        cs = self.cache.stats()
        looks = cs["hits"] + cs["misses"]
        out["cache"] = cs
        out["cache_hit_rate"] = (cs["hits"] / looks) if looks else 0.0
        with self._pools_lock:
            out["shard_workers"] = sum(p.workers for p in self._pools)
            out["ipc"] = {
                "bytes_out": sum(p.bytes_out for p in self._pools),
                "bytes_in": sum(p.bytes_in for p in self._pools),
                "messages": sum(p.messages for p in self._pools),
            }
        return out
