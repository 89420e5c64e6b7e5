"""The ``fig8-3d-cold`` cell: the runner finds its files, a short CPU
window of it at a small size reads ``correct``, and its two per-layer
metrics, ``polish_frontier_s`` and ``polish_chunk_fill``, read the
program's ``swap.frontier`` span and ``swap.device_slots`` counter."""
import dataclasses
import json
import time

import pytest

from conftest import BENCH, CPU, ROOT

CELL = "fig8-3d-cold"
NEW_METRICS = {"polish_frontier_s", "polish_chunk_fill"}
CHUNK = 16
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _read(name, run):
    from benchlib.cell import _load_reader
    return _load_reader(BENCH / "metrics" / f"{name}.py")(run)


def _small_3d_cell():
    """The cell on a (7, 4, 2) grid of 7 full nodes of 8 (one node one
    4 x 2 slab, as one node is one 8 x 4 slab at full size), K=4."""
    from benchlib.cell import resolve
    from benchlib.traffic import TrafficSpec
    cell = resolve(ROOT, CELL)
    cfg = dict(cell.config, dims=[7, 4, 2], processes=56,
               allocation={"nodes": 7, "slots_per_node": 8},
               plan="device[k=4,restarts=auto,sa_moves=20,seed={seed}]"
                    ":hyperplane")
    return dataclasses.replace(
        cell, config=cfg, traffic=TrafficSpec.parse({"requests": 40}, cfg))


@pytest.fixture(scope="module")
def window():
    """One short window of the small cell, with the polish's device
    scorer told that the CPU is an accelerator and given a chunk small
    enough that passes span several."""
    from benchlib.cell import run_window
    from repro.core.refine import device_swap
    build = device_swap.device_swap_scorer
    cell = _small_3d_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device_swap, "_accelerator", lambda: True)
        mp.setattr(device_swap, "device_swap_scorer",
                   lambda grid, stencil, w: build(grid, stencil, w,
                                                  chunk=CHUNK))
        m = run_window(cell, 2**31 + 23, 1.0, None, CPU,
                       time.perf_counter())
    return cell, m


def test_resolve_finds_the_configuration_traffic_and_its_metrics():
    from benchlib.cell import resolve
    cell = resolve(ROOT, CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "fig8-3d-nn-n31p32"
    assert cell.config["dims"] == [31, 8, 4]
    assert len(cell.config["stencil"]["offsets"]) == 6
    assert cell.config["allocation"] == {"nodes": 31, "slots_per_node": 32}
    assert cell.traffic.requests == 400
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(cell.per_layer) == listed == NEW_METRICS


def test_a_short_window_of_the_small_cell_reads_correct(window):
    from benchlib.cell import finish
    cell, m = window
    res = finish(cell, m, CPU)
    assert res["correct"] is True, res["checks"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["metrics"]["solve_s"]["value"] > 0


def test_the_new_metrics_read_the_window(window):
    from benchlib.cell import RunData
    _, m = window
    run = RunData(records=m.records, window_compiles=m.window_compiles)
    assert run.solved()
    frontier = _read("polish_frontier_s", run)
    assert 0.0 < frontier <= _read("polish_score_s", run)
    counters = [r["solution"]["engine_stage"]["counters"]
                for r in run.solved()]
    pairs = sum(c["survivors/polish/swap.device_pairs"] for c in counters)
    slots = sum(c["survivors/polish/swap.device_slots"] for c in counters)
    assert 0 < pairs <= slots and slots % CHUNK == 0
    assert _read("polish_chunk_fill", run) == pairs / slots


class _Records:
    """Solved requests whose engine stages carry the given spans and
    counters."""

    def __init__(self, *stages):
        self.stages = stages

    def solved(self):
        return [{"solution": {"engine_stage": s}} for s in self.stages]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reads_nothing_without_its_span_or_counter(name):
    older_program = _Records(
        {"spans": {"survivors/polish/swap.score": [3, 0.5]},
         "counters": {"survivors/polish/swap.pairs": 100,
                      "survivors/polish/swap.device_pairs": 100}},
        {"spans": {}, "counters": {}}, None)
    assert _read(name, older_program) is None


def test_frontier_seconds_are_the_mean_over_solves():
    path = "survivors/polish/swap.score/swap.frontier"
    run = _Records({"spans": {path: [4, 0.25]}, "counters": {}},
                   {"spans": {path: [6, 0.75]}, "counters": {}},
                   {"spans": {"survivors/polish/swap.score": [1, 0.1]},
                    "counters": {}})
    assert _read("polish_frontier_s", run) == pytest.approx(0.5)


def test_chunk_fill_is_pairs_over_slots_summed_over_solves():
    def stage(pairs, slots):
        return {"spans": {}, "counters": {
            "survivors/polish/swap.pairs": pairs,
            "survivors/polish/swap.device_pairs": pairs,
            "survivors/polish/swap.device_slots": slots}}
    run = _Records(stage(86_384 + 40_000, 131_072 + 65_536),
                   stage(60_000, 65_536),
                   {"spans": {}, "counters": {
                       "survivors/polish/swap.pairs": 10}})
    assert _read("polish_chunk_fill", run) == pytest.approx(
        (126_384 + 60_000) / (196_608 + 65_536))
