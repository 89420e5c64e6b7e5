"""The per-layer metrics that read the program's own spans and counters
(``repro.obs``, carried in the device refine stage's stats), on a window of
the small cell on the CPU; and a file for every per-layer metric."""
import json
import time

import pytest

from conftest import BENCH, CPU, ROOT, small_cell

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

SECONDS = ["engine_init_s", "boundary_s", "rekey_s", "polish_score_s",
           "polish_apply_s"]
PROGRAM_METRICS = SECONDS + ["polish_pairs", "polish_yield"]


@pytest.fixture(scope="module")
def window_run():
    """The records of one short window of the ``tiny_cell`` configuration
    (10x9 grid on 6 nodes of 15, K=4 ladders), as a metric reads them."""
    from benchlib.cell import RunData, run_window
    cell = small_cell(
        dims=[10, 9], processes=90,
        allocation={"nodes": 6, "slots_per_node": 15},
        plan="device[k=4,restarts=auto,sa_moves=20,seed={seed}]:hyperplane")
    m = run_window(cell, 2**31 + 17, 1.0, None, CPU, time.perf_counter())
    run = RunData(records=m.records, window_compiles=m.window_compiles)
    assert run.solved()
    return run


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_reads_the_programs_spans(window_run, name):
    from benchlib.cell import _load_reader
    value = _load_reader(BENCH / "metrics" / f"{name}.py")(window_run)
    assert value is not None
    if name == "polish_yield":
        assert 0.0 < value < 1.0
    else:
        assert value > 0


def test_rekey_and_boundary_are_parts_of_their_phases(window_run):
    from benchlib.cell import _load_reader

    def read(name):
        return _load_reader(BENCH / "metrics" / f"{name}.py")(window_run)
    assert read("engine_init_s") + read("boundary_s") <= read("ladders_s")
    assert read("rekey_s") + read("polish_score_s") + read("polish_apply_s") \
        <= read("polish_s")


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_its_file(name):
    assert (BENCH / "metrics" / f"{name}.py").is_file()
