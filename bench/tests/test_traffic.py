"""The generator: a function of the seed, no repeats inside a cold run,
Zipf repeats from a pool."""
import json

import pytest

from conftest import BENCH, small_cell
from benchlib.traffic import TrafficSpec, build_requests, warm_request

SEEDS = [0, 7, 2 ** 31 + 12345, 2 ** 33 + 1]


@pytest.fixture(scope="module")
def cell():
    return small_cell()


@pytest.mark.parametrize("seed", SEEDS)
def test_deterministic_in_seed(cell, seed):
    a = build_requests(cell.config, cell.traffic, seed)
    b = build_requests(cell.config, cell.traffic, seed)
    assert a == b
    assert warm_request(cell.config, cell.traffic, seed) == \
        warm_request(cell.config, cell.traffic, seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_no_repeats_and_capacities(cell, seed):
    reqs = build_requests(cell.config, cell.traffic, seed)
    plans = [r.plan for r in reqs]
    warm = warm_request(cell.config, cell.traffic, seed)
    assert len(set(plans)) == len(plans)
    assert len({r.problem for r in reqs}) == len(reqs)
    assert warm.plan not in plans
    alloc = cell.config["allocation"]
    for r in reqs + [warm]:
        assert r.capacities == (alloc["slots_per_node"],) * alloc["nodes"]
        assert sum(r.capacities) == cell.config["processes"]
        assert r.plan == cell.config["plan"].format(
            seed=r.plan.split("seed=")[1].split("]")[0])


def test_seeds_differ(cell):
    a = build_requests(cell.config, cell.traffic, 1)
    b = build_requests(cell.config, cell.traffic, 2)
    assert [r.plan for r in a] != [r.plan for r in b]


@pytest.mark.parametrize("seed", SEEDS)
def test_pool_repeats_with_zipf_popularity(cell, seed):
    spec = TrafficSpec.parse({"requests": 400, "pool": 8, "zipf_s": 1.2},
                             cell.config)
    reqs = build_requests(cell.config, spec, seed)
    assert reqs == build_requests(cell.config, spec, seed)
    by_problem = {}
    for r in reqs:
        assert by_problem.setdefault(r.problem, r.plan) == r.plan
    assert len(set(by_problem.values())) == len(by_problem) <= 8
    counts = [sum(r.problem == j for r in reqs) for j in range(8)]
    assert counts[0] > counts[-1]
    assert warm_request(cell.config, spec, seed).plan not in by_problem.values()


def test_the_cells_traffic_parses():
    for cfg_path in (BENCH / "configs").glob("*.json"):
        cfg = json.loads(cfg_path.read_text())
        for path in (BENCH / "traffic").glob("*.json"):
            spec = TrafficSpec.parse(json.loads(path.read_text()), cfg)
            assert len(build_requests(cfg, spec, 3)) == spec.requests


@pytest.mark.parametrize("raw", [{"requests": 3, "rate": 2},
                                 {"requests": 3, "pool": 4},
                                 {"requests": 0}])
def test_bad_traffic_refused(cell, raw):
    with pytest.raises(ValueError):
        TrafficSpec.parse(raw, cell.config)


def test_plan_without_seed_refused(cell):
    cfg = dict(cell.config, plan="device[k=4]:hyperplane")
    with pytest.raises(ValueError):
        TrafficSpec.parse({"requests": 3}, cfg)
