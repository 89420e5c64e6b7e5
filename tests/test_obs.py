"""The in-program span and counter recorder (:mod:`repro.obs`) and what the
refiners record with it.

Pinned:

* **paths** — a span's path is the names of the open recorded spans joined
  by ``/``; its entry is ``[calls, inclusive seconds]``; a counter lands
  under the current path; outside a recording neither records anything;
* **contexts** — two threads recording at once never see each other's
  spans;
* **the device solve** — a ``device[...]`` plan's engine stage carries
  ``spans``/``counters``; its ``t_rounds_s``/``t_ladders_s``/``t_polish_s``
  are the ``rounds``/``ladders`` spans and the rest of the wall time; the
  polish scores pairs; the number of spans recorded is bounded by the
  passes and temperatures, never by swaps or rows;
* **the trace** — under ``jax.profiler.trace`` the host plane holds the
  ``repro.`` spans, children nested in time inside their parents.
"""
import glob
import threading
import time

import pytest

from repro import obs
from repro.core import Stencil, parse_plan
from repro.core.plan import MappingProblem

jax = pytest.importorskip("jax")

PLAN = "device[k=4,restarts=auto,sa_moves=20]:hyperplane"
PROBLEM = MappingProblem((10, 9), Stencil.nearest_neighbor(2), (15,) * 6)
TEMPS = 4                       # the schedule's default temperature count


def _engine_stage(sol):
    return next(st for st in reversed(sol.stage_stats) if "backend" in st)


@pytest.fixture(scope="module")
def device_stage():
    sol = parse_plan(PLAN).solve(PROBLEM)
    return _engine_stage(sol), sol


# ---------------------------------------------------------------------------
# the recorder


def test_nesting_paths_inclusive_times_and_counters():
    with obs.recording() as rec:
        with obs.span("outer"):
            obs.count("items", 3)
            for _ in range(2):
                with obs.span("inner", step=1):
                    time.sleep(0.01)
                    obs.count("items", 2)
            obs.count("items", 4)
        obs.count("top", 1)
    spans, counters = rec["spans"], rec["counters"]
    assert set(spans) == {"outer", "outer/inner"}
    assert spans["outer"][0] == 1 and spans["outer/inner"][0] == 2
    assert spans["outer/inner"][1] >= 0.02
    assert spans["outer"][1] >= spans["outer/inner"][1]
    assert counters == {"outer/items": 7, "outer/inner/items": 4, "top": 1}


def test_nothing_recorded_outside_a_recording():
    with obs.span("loose"):
        obs.count("loose", 1)
    with obs.recording() as rec:
        pass
    assert rec == {"spans": {}, "counters": {}}


def test_span_records_its_time_when_the_block_raises():
    with obs.recording() as rec:
        with pytest.raises(ValueError):
            with obs.span("failing"):
                raise ValueError("boom")
        with obs.span("after"):
            pass
    assert rec["spans"]["failing"][0] == 1
    assert "after" in rec["spans"]          # the stack was unwound


def test_inner_recording_takes_its_own_spans():
    with obs.recording() as outer:
        with obs.span("a"):
            with obs.recording() as inner:
                with obs.span("b"):
                    pass
            with obs.span("c"):
                pass
    assert set(inner["spans"]) == {"b"}
    assert set(outer["spans"]) == {"a", "a/c"}


def test_two_threads_recording_at_once_do_not_mix():
    barrier = threading.Barrier(2, timeout=30)
    out = {}

    def work(name):
        with obs.recording() as rec:
            with obs.span(name):
                barrier.wait()              # both spans open at once
                obs.count("n", 1)
                barrier.wait()
        out[name] = rec

    threads = [threading.Thread(target=work, args=(n,)) for n in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for name in "xy":
        assert set(out[name]["spans"]) == {name}
        assert out[name]["counters"] == {f"{name}/n": 1}


# ---------------------------------------------------------------------------
# the device refiner


def test_device_solve_carries_spans_and_counters(device_stage):
    stage, _ = device_stage
    assert stage["backend"].startswith("device[")
    spans, counters = stage["spans"], stage["counters"]
    for path in ("rounds", "ladders", "ladders/engine_init",
                 "ladders/temperature", "ladders/temperature/boundary",
                 "survivors", "survivors/snapshot", "survivors/rekey",
                 "survivors/polish", "survivors/final",
                 "survivors/polish/swap.score"):
        assert path in spans, path
    assert spans["ladders/temperature"][0] == TEMPS
    assert spans["ladders/temperature/boundary"][0] == TEMPS
    assert counters["survivors/polish/swap.pairs"] > 0
    assert counters["survivors/polish/swap.passes"] > 0


def test_phase_times_are_the_spans(device_stage):
    stage, sol = device_stage
    spans = stage["spans"]
    assert stage["t_rounds_s"] == spans["rounds"][1]
    assert stage["t_ladders_s"] == spans["ladders"][1]
    # t_polish_s is the wall time less the two: the survivors span and
    # the few statements between the top-level spans
    assert stage["t_polish_s"] >= spans["survivors"][1]
    assert stage["t_polish_s"] < spans["survivors"][1] + 0.05
    # children never outlast their parents
    kids = sum(spans[f"survivors/{c}"][1]
               for c in ("snapshot", "rekey", "polish", "final"))
    assert kids <= spans["survivors"][1]
    assert spans["ladders/engine_init"][1] + \
        spans["ladders/temperature"][1] <= spans["ladders"][1]


def test_span_count_is_bounded_by_passes_not_swaps(device_stage):
    stage, _ = device_stage
    spans, counters = stage["spans"], stage["counters"]
    passes = sum(v for k, v in counters.items()
                 if k.endswith("swap.passes"))
    swap_calls = sum(c for k, (c, _) in spans.items()
                     if k.rsplit("/", 1)[-1].startswith("swap."))
    other_calls = sum(c for k, (c, _) in spans.items()
                      if not k.rsplit("/", 1)[-1].startswith("swap."))
    # one score with one frontier inside it, and at most one apply span,
    # per pass
    assert swap_calls <= 3 * passes
    for phase in ("rounds", "survivors/polish"):
        assert spans[f"{phase}/swap.score/swap.frontier"][0] \
            == spans[f"{phase}/swap.score"][0]
    # rounds, ladders, engine_init, survivors and its four children, and
    # two per temperature: none per row or per move
    assert other_calls == 8 + 2 * TEMPS
    assert len(spans) < 20
    applied = counters.get("survivors/polish/swap.applied", 0)
    assert applied > 0 and spans["survivors/polish/swap.apply"][0] <= passes


def test_host_portfolio_times_through_the_same_spans():
    sol = parse_plan("portfolio[k=2,sa_moves=20]:hyperplane").solve(PROBLEM)
    stage = sol.stage_stats[-1]
    assert stage["t_rounds_s"] == stage["spans"]["rounds"][1]
    assert stage["t_ladders_s"] == stage["spans"]["ladders"][1]
    assert stage["counters"]["survivors/polish/swap.pairs"] > 0


# ---------------------------------------------------------------------------
# the profiler trace


def test_spans_nest_in_time_on_the_host_plane(tmp_path, device_stage):
    from jax.profiler import ProfileData
    plan = parse_plan(PLAN)
    with jax.profiler.trace(str(tmp_path)):
        plan.solve(PROBLEM)
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(paths) == 1
    data = ProfileData.from_file(paths[0])
    events = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    for name in ("repro.rounds", "repro.ladders", "repro.engine_init",
                 "repro.temperature", "repro.boundary", "repro.survivors",
                 "repro.snapshot", "repro.rekey", "repro.polish",
                 "repro.final", "repro.swap.score"):
        assert name in events, name
    (s0, s1), = events["repro.survivors"]
    for child in ("repro.snapshot", "repro.rekey", "repro.polish",
                  "repro.final"):
        (c0, c1), = events[child]
        assert s0 <= c0 <= c1 <= s1, child
    (l0, l1), = events["repro.ladders"]
    assert l1 <= s0
    assert len(events["repro.temperature"]) == TEMPS
    for t0, t1 in events["repro.temperature"]:
        assert l0 <= t0 <= t1 <= l1


def test_jitted_programs_keep_stable_names():
    import jax.numpy as jnp
    from repro.core.refine.device import _temperature_kernel
    from repro.core.cost_delta import _jit_stacked_counts
    assert _temperature_kernel(5).__name__ == "ladder_temperature_scan"
    text = _jit_stacked_counts(3).lower(
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((4, 6), bool),
        jnp.zeros((4, 6), jnp.int32)).as_text()
    assert "module @jit_stacked_crossing_counts_one" in text
