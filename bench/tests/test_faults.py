"""Whole runs on the CPU at a small size: a sound run is correct, and a
run with the timed path broken underneath is not -- once for each fault
this cell can have.  (It runs on one chip, so there is no exchange
between chips to leave out.)"""
import time

import numpy as np

from conftest import CPU
from benchlib.cell import finish, run_window


def _run(cell, seed=11, seconds=1.0):
    m = run_window(cell, seed, seconds, None, CPU, time.perf_counter())
    return finish(cell, m, CPU)


def _failing(result):
    return {k for k, v in result["checks"].items() if v["value"] > v["limit"]}


def test_sound_run_is_correct(tiny_cell):
    res = _run(tiny_cell)
    assert res["correct"] is True and not _failing(res)
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s", "jmax_vs_blocked",
                                   "jsum_vs_blocked", "setup_s"}
    assert list(res)[-1] == "checks"


def _wrap_kernel(monkeypatch, after):
    """Replace the device engine's temperature kernel by the real one
    followed by ``after(inputs, outputs) -> outputs``."""
    import jax.numpy as jnp
    from repro.core.refine import device
    real = device._temperature_kernel

    def kernel(sa_moves):
        run = real(sa_moves)

        def broken(*args):
            return after(jnp, args, list(run(*args)))
        return broken
    monkeypatch.setattr(device, "_temperature_kernel", kernel)


def _keys(jnp, cn):
    per = cn.sum(axis=2).astype(jnp.float32)
    return per.max(axis=1), per.sum(axis=1)


def test_state_returned_unchanged(tiny_cell, monkeypatch):
    def after(jnp, args, out):
        node, cn, keys, bnode, bjmax, bjsum, done = args[:7]
        jmax, jsum = _keys(jnp, cn)
        return (node, cn, out[2], bnode, bjmax, bjsum, done,
                jnp.zeros_like(out[7]), jmax, jsum)
    _wrap_kernel(monkeypatch, after)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert "unmoved_rows" in _failing(res)


def test_half_the_ladders_left_out(tiny_cell, monkeypatch):
    def after(jnp, args, out):
        live = args[7]                          # the ladders this step runs
        skip = live & (jnp.cumsum(live) > live.sum() // 2)
        for i in (0, 1, 3, 4, 5):               # node, cn, best state
            mask = skip.reshape((-1,) + (1,) * (args[i].ndim - 1))
            out[i] = jnp.where(mask, args[i], out[i])
        out[7] = jnp.where(skip, 0, out[7])
        out[8], out[9] = _keys(jnp, out[1])
        return tuple(out)
    _wrap_kernel(monkeypatch, after)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert "unmoved_rows" in _failing(res)


def test_count_state_altered_on_device(tiny_cell, monkeypatch):
    def after(jnp, args, out):
        out[1] = out[1].at[0, 0, 0].add(1)
        return tuple(out)
    _wrap_kernel(monkeypatch, after)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert "count_gap" in _failing(res)


def test_served_layout_altered(tiny_cell, monkeypatch):
    """Two positions on different nodes swapped in the plan's answer after
    its costs were counted."""
    from repro.core.plan import MappingPlan
    real = MappingPlan.solve

    def solve(self, problem, cache=None):
        sol = real(self, problem, cache)
        if cache is None:
            a = sol.assignment
            q = int(np.nonzero(a != a[0])[0][-1])
            a[0], a[q] = a[q], a[0]
        return sol
    monkeypatch.setattr(MappingPlan, "solve", solve)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert "j_gap" in _failing(res)


def test_refine_off_the_device(tiny_cell, monkeypatch):
    """The device refiner delegating to its host engine."""
    import dataclasses
    cell = dataclasses.replace(tiny_cell, config=dict(
        tiny_cell.config,
        plan="device[k=4,restarts=auto,sa_moves=20,max_swaps=100000,"
             "seed={seed}]:hyperplane"))
    res = _run(cell)
    assert res["correct"] is False
    assert {"off_device", "engine_missing"} <= _failing(res)


def test_request_that_fails(tiny_cell, monkeypatch):
    """A solve that raises once set-up is done: the window stops at the
    failed request, which counts as unserved."""
    from repro.core.plan import MappingPlan
    real = MappingPlan.solve
    calls = []

    def solve(self, problem, cache=None):
        if cache is None:
            calls.append(problem)
            if len(calls) > 1:
                raise RuntimeError("planted failure")
        return real(self, problem, cache)
    monkeypatch.setattr(MappingPlan, "solve", solve)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert res["failed"] == 1
    assert "unserved" in _failing(res)


def test_ladder_keys_missing(tiny_cell, monkeypatch):
    """The refine stage's stats without the ladders' end keys: the keys'
    comparison cannot run, and that fails the run."""
    from repro.core.refine.device import DevicePortfolioRefiner
    real = DevicePortfolioRefiner.refine

    def refine(self, *args, **kwargs):
        res = real(self, *args, **kwargs)
        res.stats.pop("ladder_keys")
        return res
    monkeypatch.setattr(DevicePortfolioRefiner, "refine", refine)
    res = _run(tiny_cell)
    assert res["correct"] is False
    assert "engine_missing" in _failing(res)


def test_cache_hit_with_another_layout(tiny_cell):
    """A request served from the plan cache is held to the layout served
    for its problem before."""
    from benchlib import checks
    m = run_window(tiny_cell, 5, 1.0, None, CPU, time.perf_counter())
    first = m.records[0]
    hit = dict(first, index=len(m.records), engines=[],
               solution=dict(first["solution"], from_cache=True))
    ok = checks.check_run(tiny_cell.config, m.records + [hit], "cpu", m.table)
    assert ok["cache_faults"] == 0
    a = hit["solution"]["assignment"].copy()
    q = int(np.nonzero(a != a[0])[0][-1])
    a[0], a[q] = a[q], a[0]
    hit["solution"] = dict(hit["solution"], assignment=a)
    bad = checks.check_run(tiny_cell.config, m.records + [hit], "cpu",
                           m.table)
    assert bad["cache_faults"] == 1
