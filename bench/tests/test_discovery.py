"""The runner finds a cell's files by name: a new configuration file, a
new traffic mix, a new per-layer metric file and their entries are picked
up without an edit to any file that is there."""
import json
import shutil
import time

from conftest import BENCH, CPU, ROOT
from benchlib.cell import finish, resolve, run_cell, run_window


def _copy_bench(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    return bench, json.loads((ROOT / "BENCHMARK.json").read_text())


def _tiny_config(bench):
    cfg = json.loads((BENCH / "configs" / "fig8-2d-nn-n31p32.json")
                     .read_text())
    cfg.update(name="tiny-2d", dims=[10, 9], processes=90,
               allocation={"nodes": 6, "slots_per_node": 15},
               plan="device[k=4,restarts=auto,sa_moves=20,seed={seed}]"
                    ":hyperplane")
    (bench / "configs" / "tiny-2d.json").write_text(json.dumps(cfg))


def test_new_config_and_metric_picked_up(tmp_path):
    bench, spec = _copy_bench(tmp_path)
    _tiny_config(bench)
    (bench / "traffic" / "few.json").write_text(json.dumps({"requests": 30}))
    (bench / "metrics" / "served_count.py").write_text(
        "def read(run):\n    return len(run.served())\n")
    spec["configs"].append({"name": "tiny-2d", "source": "test",
                            "file": "bench/configs/tiny-2d.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-cold", "config": "tiny-2d",
                              "traffic": "few", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "served_count", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving", "moves": "solve_s",
                              "workloads": ["tiny-cold"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = resolve(tmp_path, "tiny-cold", bench_dir=bench)
    assert cell.config["name"] == "tiny-2d"
    assert cell.traffic.requests == 30
    assert set(cell.per_layer) == {"served_count"}
    old = resolve(tmp_path, "fig8-2d-cold", bench_dir=bench)
    assert "served_count" not in old.per_layer

    res = run_cell(cell, 3, 0.5, False, CPU, time.perf_counter())
    assert res["correct"] is True
    assert res["metrics"]["solve_s"]["value"] > 0


def test_new_traffic_mix_picked_up(tmp_path):
    """A second mix, data only: repeats drawn from a pool of problems, so
    that most requests are served from the plan cache."""
    bench, spec = _copy_bench(tmp_path)
    _tiny_config(bench)
    (bench / "traffic" / "warm-zipf.json").write_text(json.dumps(
        {"requests": 20000, "pool": 3, "zipf_s": 1.1}))
    spec["configs"].append({"name": "tiny-2d", "source": "test",
                            "file": "bench/configs/tiny-2d.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-warm", "config": "tiny-2d",
                              "traffic": "warm-zipf", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = resolve(tmp_path, "tiny-warm", bench_dir=bench)
    assert cell.traffic.pool == 3
    m = run_window(cell, 2 ** 31 + 9, 1.0, None, CPU, time.perf_counter())
    hits = [r for r in m.records if r["solution"]["from_cache"]]
    assert hits and len(m.records) - len(hits) <= 3
    res = finish(cell, m, CPU)
    assert res["correct"] is True, res["checks"]
