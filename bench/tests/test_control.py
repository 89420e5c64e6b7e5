"""The control at each configuration's grid (992 positions on 31 nodes of
32), with K=4 ladders so that the CPU holds it: the program's readings
pass the verdict, the same readings with the device's keys computed in
bfloat16 do not."""
import time

import pytest

from conftest import CPU, small_cell
from benchlib import checks
from benchlib.cell import run_window
from control import control_readings


@pytest.mark.parametrize("config", ["fig8-2d-nn-n31p32",
                                    "fig8-3d-nn-n31p32"])
def test_control_fails_where_the_program_passes(config):
    cell = small_cell(config,
                      plan="device[k=4,restarts=auto,sa_moves=50,"
                           "seed={seed}]:hyperplane")
    m = run_window(cell, 2 ** 31 + 5, 0.5, None, CPU, time.perf_counter())
    program = checks.check_run(cell.config, m.records, "cpu", m.table)
    assert checks.verdict(program), program
    control = control_readings(program, m.records, m.table)
    assert control["key_gap"] > checks.LIMITS["key_gap"]
    assert not checks.verdict(control)
