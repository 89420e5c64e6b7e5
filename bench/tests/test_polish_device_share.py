"""The ``polish_device_share`` metric: the share of the polish's swap pairs
scored on the device, read from the refiner's counters on a window of the
small cell on the CPU."""
import time

import pytest

from conftest import BENCH, CPU, small_cell


@pytest.fixture(scope="module")
def window_run():
    """The records of one short window of the ``tiny_cell`` configuration
    (10x9 grid on 6 nodes of 15, K=4 ladders).  The polish's device scorer
    serves only accelerators: it is told here that the CPU is one."""
    from benchlib.cell import RunData, run_window
    from repro.core.refine import device_swap
    cell = small_cell(
        dims=[10, 9], processes=90,
        allocation={"nodes": 6, "slots_per_node": 15},
        plan="device[k=4,restarts=auto,sa_moves=20,seed={seed}]:hyperplane")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device_swap, "_accelerator", lambda: True)
        m = run_window(cell, 2**31 + 17, 1.0, None, CPU,
                       time.perf_counter())
    run = RunData(records=m.records, window_compiles=m.window_compiles)
    assert run.solved()
    return run


def _read(run):
    from benchlib.cell import _load_reader
    return _load_reader(BENCH / "metrics" / "polish_device_share.py")(run)


def test_reads_one_where_every_pass_is_scored_on_the_device(window_run):
    assert _read(window_run) == 1.0     # unit weights: every pass


def test_reads_nothing_without_its_counter(window_run):
    """A program without the device scorer has ``swap.pairs`` but no
    ``swap.device_pairs``."""

    class WithoutScorer:
        def solved(self):
            out = []
            for r in window_run.solved():
                stage = dict(r["solution"]["engine_stage"])
                stage["counters"] = {
                    k: v for k, v in stage["counters"].items()
                    if not k.endswith("swap.device_pairs")}
                out.append({"solution": {"engine_stage": stage}})
            return out
    assert _read(WithoutScorer()) is None
