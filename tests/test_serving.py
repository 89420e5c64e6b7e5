"""Serving layer: persistent shard workers + the resident plan server.

Pinned invariants:

* **bit-identity** — the resident persistent-worker engine
  (:class:`~repro.serving.ResidentShardedRefiner`) returns exactly the
  stateless ``sharded[...]`` engine's assignment and ladder keys at equal
  config (the property that lets the server cache resident results under
  the unchanged plan key), including with restarts/retune on;
* **pool lifecycle** — worker processes all join on close (no orphans),
  close is idempotent, a crashed pool degrades to the stateless fallback
  with the identical result;
* **server protocol** — submits are admission-bounded
  (:class:`~repro.serving.AdmissionError` when the queue is full), warm
  repeats are cache hits, ``invalidate`` forces recompute, concurrent
  submits all complete with consistent counters;
* **anytime** — a deadlined request always resolves to a *valid*
  assignment (scheduler cardinalities realized); uncut anytime reruns are
  deterministic; deadline-cut results never enter the cache;
* **repair routing** — ``remap.repair_layout(server=...)`` returns the
  same solution as the direct call, through the server's queue.
"""
import multiprocessing
import threading
import time

import numpy as np
import pytest

from repro.core import CartGrid, Stencil, evaluate, get_mapper, parse_plan
from repro.core.plan import MappingProblem, PlanCache
from repro.core.refine.sharded import ShardedPortfolioRefiner
from repro.serving import (AdmissionError, PlanClient, PlanServer,
                           ResidentShardedRefiner, ShardWorkerPool,
                           register_topology)

DIMS, SIZES = (6, 8), (16, 16, 10, 6)
PLAN = "sharded[shards=2,k=4,restarts=auto]:hyperplane"


def _instance():
    grid = CartGrid(DIMS)
    stencil = Stencil.nearest_neighbor(2)
    start = get_mapper("hyperplane").assignment(grid, stencil, list(SIZES))
    return grid, stencil, start


def _assert_valid(assignment, sizes=SIZES):
    np.testing.assert_array_equal(
        np.sort(np.bincount(np.asarray(assignment), minlength=len(sizes))),
        np.sort(np.asarray(sizes)))


# ---------------------------------------------------------------------------
# resident engine: bit-identity + pool lifecycle


@pytest.mark.parametrize("kw", [
    dict(shards=2, k=4, restarts="auto"),
    dict(shards=3, k=8, restarts="auto", retune=True),
])
def test_resident_bit_identical_to_stateless(kw):
    grid, stencil, start = _instance()
    kw = dict(kw, seed=7, rounds=1, max_passes=2, sa_moves=40)
    want = ShardedPortfolioRefiner(backend="serial", **kw).refine(
        grid, stencil, start.copy(), num_nodes=len(SIZES))
    with ResidentShardedRefiner(backend="serial", **kw) as resident:
        got = resident.refine(grid, stencil, start.copy(),
                              num_nodes=len(SIZES))
    np.testing.assert_array_equal(got.assignment, want.assignment)
    assert got.stats["ladder_keys"] == want.stats["ladder_keys"]
    assert (got.final.j_max, got.final.j_sum) \
        == (want.final.j_max, want.final.j_sum)
    assert got.stats["ipc"]["step_bytes"] > 0


def test_worker_pool_lifecycle_no_orphans():
    before = set(p.pid for p in multiprocessing.active_children())
    pool = ShardWorkerPool(workers=2)
    assert pool.alive and pool.workers == 2
    pids = pool.broadcast(("ping",))
    assert sorted(pids) == sorted(p.pid for p in pool._procs)
    pool.close()
    pool.close()                               # idempotent
    assert not pool.alive
    after = set(p.pid for p in multiprocessing.active_children())
    assert after <= before


def test_crashed_pool_falls_back_to_stateless():
    """Workers dying mid-run must degrade to the stateless engine with the
    bit-identical result (and without wedging the coordinator)."""
    grid, stencil, start = _instance()
    kw = dict(shards=2, k=4, seed=3, rounds=1, max_passes=2, sa_moves=40)
    want = ShardedPortfolioRefiner(backend="serial", **kw).refine(
        grid, stencil, start.copy(), num_nodes=len(SIZES))
    pool = ShardWorkerPool(workers=2)
    orig_rm = pool.request_many

    def sabotage(msgs):
        # kill every worker the moment the first temperature dispatches:
        # the ("crash",) hook os._exit()s the children, so the pending
        # recv raises WorkerPoolError mid-run
        if msgs and msgs[0][1][0] == "step":
            pool.request_many = orig_rm
            pool.broadcast(("crash",))
        return orig_rm(msgs)

    pool.request_many = sabotage
    refiner = ResidentShardedRefiner(pool=pool, backend="serial", **kw)
    got = refiner.refine(grid, stencil, start.copy(), num_nodes=len(SIZES))
    np.testing.assert_array_equal(got.assignment, want.assignment)
    assert got.stats["ladder_keys"] == want.stats["ladder_keys"]
    assert got.stats["backend"] == "resident-fallback"
    pool.close()


def test_dead_pool_self_heals_before_run():
    """A pool found dead *before* the run is replaced with a fresh owned
    pool (self-healing), keeping the resident path — not the fallback."""
    grid, stencil, start = _instance()
    kw = dict(shards=2, k=4, seed=3, rounds=1, max_passes=2, sa_moves=40)
    want = ShardedPortfolioRefiner(backend="serial", **kw).refine(
        grid, stencil, start.copy(), num_nodes=len(SIZES))
    dead = ShardWorkerPool(workers=2)
    dead.close()
    refiner = ResidentShardedRefiner(pool=dead, backend="serial", **kw)
    got = refiner.refine(grid, stencil, start.copy(), num_nodes=len(SIZES))
    np.testing.assert_array_equal(got.assignment, want.assignment)
    assert got.stats["backend"] == "resident"
    refiner.close()


# ---------------------------------------------------------------------------
# the server


def test_server_serves_bit_identical_and_warm_hits():
    problem = MappingProblem(DIMS, Stencil.nearest_neighbor(2), SIZES)
    want = parse_plan(PLAN).solve(problem)
    with PlanServer(threads=1, shard_workers=2) as srv:
        cold = srv.submit(problem, plan=PLAN).result(timeout=300)
        assert not cold.from_cache
        np.testing.assert_array_equal(cold.assignment, want.assignment)
        assert (cold.j_max, cold.j_sum) == (want.j_max, want.j_sum)
        warm = srv.submit(problem, plan=PLAN).result(timeout=60)
        assert warm.from_cache
        np.testing.assert_array_equal(warm.assignment, want.assignment)
        # invalidate forces a recompute to the same answer
        assert srv.invalidate(problem) == 1
        again = srv.submit(problem, plan=PLAN).result(timeout=300)
        assert not again.from_cache
        np.testing.assert_array_equal(again.assignment, want.assignment)
        st = srv.stats()
        assert st["completed"] == 3 and st["errors"] == 0
        assert "latency_p50_ms" in st


def test_server_bounded_admission_rejects_when_full():
    srv = PlanServer(threads=1, shard_workers=1, max_queue=1)
    gate = threading.Event()
    orig = srv._solve

    def gated(*args, **kwargs):
        gate.wait(timeout=60)
        return orig(*args, **kwargs)

    srv._solve = gated
    problem = MappingProblem(DIMS, Stencil.nearest_neighbor(2), SIZES)
    with srv:
        t1 = srv.submit(problem, plan="blocked")
        deadline = time.perf_counter() + 10
        while srv.inflight == 0 and time.perf_counter() < deadline:
            time.sleep(0.005)                   # t1 now held by the gate
        t2 = srv.submit(problem, plan="blocked")    # fills the queue
        with pytest.raises(AdmissionError):
            srv.submit(problem, plan="blocked")
        assert srv.stats()["rejected"] == 1
        gate.set()
        assert t1.result(timeout=60) is not None
        assert t2.result(timeout=60) is not None
    with pytest.raises(AdmissionError):         # stopped server rejects
        srv.submit(problem, plan="blocked")


def test_server_concurrent_submits_all_complete():
    with PlanServer(threads=2, shard_workers=1, max_queue=64) as srv:
        cli = PlanClient(srv)
        tickets = [
            cli.cart_create_async(DIMS, node_sizes=SIZES,
                                  plan="refined:hyperplane")
            for _ in range(12)
        ]
        results = [t.result(timeout=300) for t in tickets]
        for r in results:
            np.testing.assert_array_equal(r.layout, results[0].layout)
        st = srv.stats()
        assert st["completed"] == 12 and st["errors"] == 0
        assert st["queue_depth"] == 0 and st["inflight"] == 0
        # at most one cold solve per solver thread can race the first
        # miss (no single-flight dedup); everything else is a cache hit
        assert sum(1 for r in results if r.from_cache) >= 12 - srv.threads


def test_server_error_requests_surface_to_ticket():
    with PlanServer(threads=1) as srv:
        t = srv.submit(mesh_shape=(4, 4), node_sizes=(8, 8),
                       plan="no-such-plan")
        with pytest.raises(KeyError):
            t.result(timeout=60)
        assert srv.stats()["errors"] == 1


def test_server_warm_up_registry():
    name = "test-serving-tiny"
    register_topology(name, lambda: MappingProblem(
        (4, 4), Stencil.nearest_neighbor(2), (4, 4, 4, 4)))
    with PlanServer(threads=1, default_plan="refined:hyperplane") as srv:
        first = srv.warm_up(names=[name])
        # refined: reports no engine backend of its own
        assert first == {"swept": 1, "already_cached": 0,
                         "backends": {name: None}}
        second = srv.warm_up(names=[name])
        assert second == {"swept": 1, "already_cached": 1,
                          "backends": {name: None}}
        t = srv.submit(mesh_shape=(4, 4), node_sizes=(4, 4, 4, 4))
        assert t.result(timeout=60).from_cache
        assert srv.stats()["warmed"] == 2


def test_queue_wait_is_at_most_latency_and_counts_a_wait_behind_a_slow_one():
    srv = PlanServer(threads=1, shard_workers=1)
    orig = srv._solve
    slow = threading.Event()

    def first_slow(*args, **kwargs):
        if not slow.is_set():
            slow.set()
            time.sleep(0.3)
        return orig(*args, **kwargs)

    srv._solve = first_slow
    problem = MappingProblem(DIMS, Stencil.nearest_neighbor(2), SIZES)
    with srv:
        t1 = srv.submit(problem, plan="blocked")
        t2 = srv.submit(problem, plan="blocked")    # waits behind t1
        t1.result(timeout=60)
        t2.result(timeout=60)
        for t in (t1, t2):
            assert t.started_at >= t.submitted_at
            assert 0.0 <= t.queue_wait_s <= t.latency_s
        assert t2.queue_wait_s >= 0.2
        st = srv.stats()
        assert st["queue_wait_p95_ms"] >= 200.0
        assert st["queue_wait_p50_ms"] <= st["latency_p50_ms"]


def test_stats_ring_stays_bounded(monkeypatch):
    import repro.serving.server as server_mod
    monkeypatch.setattr(server_mod, "STATS_WINDOW", 4)
    problem = MappingProblem(DIMS, Stencil.nearest_neighbor(2), SIZES)
    with PlanServer(threads=1, shard_workers=1) as srv:
        for _ in range(10):
            srv.submit(problem, plan="blocked").result(timeout=60)
        st = srv.stats()
        assert st["completed"] == 10
        assert len(srv._samples) == 4
        assert "latency_p95_ms" in st and "queue_wait_p95_ms" in st


# ---------------------------------------------------------------------------
# anytime


def test_server_anytime_valid_and_deterministic_uncut():
    problem = MappingProblem(DIMS, Stencil.nearest_neighbor(2), SIZES)
    with PlanServer(threads=1, shard_workers=2) as srv:
        # generous deadline: run completes uncut, result is deterministic
        a1 = srv.submit(problem, plan=PLAN, deadline_ms=300_000)
        r1 = a1.result(timeout=300)
        _assert_valid(r1.assignment)
        assert not a1.anytime_cut
        a2 = srv.submit(problem, plan=PLAN, deadline_ms=300_000)
        r2 = a2.result(timeout=300)
        np.testing.assert_array_equal(r2.assignment, r1.assignment)
        assert r2.from_cache                   # uncut -> @anytime cached
        # near-zero deadline: still a valid plan, flagged cut, not cached
        srv.cache.clear()
        a3 = srv.submit(problem, plan=PLAN, deadline_ms=1)
        r3 = a3.result(timeout=300)
        _assert_valid(r3.assignment)
        assert a3.anytime_cut
        assert srv.stats()["anytime_cuts"] == 1
        a4 = srv.submit(problem, plan=PLAN, deadline_ms=1)
        assert not a4.result(timeout=300).from_cache
        cost = evaluate(CartGrid(DIMS), problem.stencil, r3.assignment,
                        num_nodes=len(SIZES))
        assert (cost.j_max, cost.j_sum) == (r3.j_max, r3.j_sum)


def test_anytime_never_worse_than_start():
    """The deadline-cut result must always dominate the start candidate
    (consider() keeps the lexicographic best seen)."""
    grid, stencil, start = _instance()
    base = evaluate(grid, stencil, start, num_nodes=len(SIZES))
    kw = dict(shards=2, k=4, seed=11, rounds=1, max_passes=2, sa_moves=40)
    for deadline in (0.0, 0.05):
        with ResidentShardedRefiner(backend="serial", **kw) as r:
            res = r.refine_anytime(grid, stencil, start.copy(),
                                   num_nodes=len(SIZES),
                                   deadline_s=deadline)
        _assert_valid(res.assignment)
        assert (res.final.j_max, res.final.j_sum) \
            <= (base.j_max, base.j_sum)
        assert res.stats["polished"] == 0


# ---------------------------------------------------------------------------
# repair routing


def test_repair_routes_through_server():
    from repro.core.remap import repair_layout
    problem = MappingProblem((8, 8), Stencil.nearest_neighbor(2),
                             (16,) * 4)
    prev = parse_plan("refined:hyperplane").solve(problem)
    survivors = (16, 16, 22, 10)
    direct = repair_layout(prev, survivors, cache=False)
    with PlanServer(threads=1) as srv:
        served = repair_layout(prev, survivors, server=srv)
        np.testing.assert_array_equal(served.assignment, direct.assignment)
        assert (served.j_max, served.j_sum) == (direct.j_max, direct.j_sum)
        assert srv.stats()["completed"] == 1
        with pytest.raises(ValueError):
            repair_layout(prev, survivors, server=srv, cache=PlanCache())
