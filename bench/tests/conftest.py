"""Shared set-up of the benchmark's own tests (CPU, small sizes).

Run from the repository root:  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_cell(config_name=None, **config):
    """The ``fig8-2d-cold`` cell, on the configuration file named (by
    default its own), changed as given."""
    import json
    from benchlib.cell import resolve
    from benchlib.traffic import TrafficSpec
    cell = resolve(ROOT, "fig8-2d-cold")
    base = cell.config if config_name is None else json.loads(
        (BENCH / "configs" / f"{config_name}.json").read_text())
    cfg = dict(base, **config)
    return dataclasses.replace(
        cell, config=cfg, traffic=TrafficSpec.parse({"requests": 40}, cfg))


@pytest.fixture
def tiny_cell():
    """10x9 grid on 6 nodes of 15; K=4 ladders."""
    return small_cell(
        dims=[10, 9], processes=90,
        allocation={"nodes": 6, "slots_per_node": 15},
        plan="device[k=4,restarts=auto,sa_moves=20,seed={seed}]:hyperplane")
