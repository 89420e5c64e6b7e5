"""One run of one cell: set-up, the measured window, the check, the numbers.

:func:`resolve` reads ``BENCHMARK.json`` and finds the cell's files by
name: the configuration's ``file``, ``traffic/<traffic>.json`` and
``metrics/<name>.py`` for each per-layer metric that lists the cell (or
lists none).  :func:`run_cell` drives the program and returns the result
line; it takes the platform as given, so the tests can drive it on the
CPU, while ``run.py`` refuses anything but a TPU before calling it.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import checks, reference
from .jaxenv import CompileClock, memory_peak_bytes
from .taps import Taps
from .traffic import Request, TrafficSpec, build_requests, warm_request

__all__ = ["Cell", "Measured", "RunData", "finish", "resolve", "run_cell",
           "run_window"]

BENCH_DIR = Path(__file__).resolve().parents[1]

#: seconds past the window's close that a request may still take
LATE_S = 60.0
#: seconds the set-up's warm-up solve may take (it compiles on a cold cache)
WARM_TIMEOUT_S = 900.0


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: TrafficSpec
    end_to_end: List[dict]
    per_layer: Dict[str, Callable]          # metric name -> read(run)
    metric_units: Dict[str, str]


@dataclass
class RunData:
    """What a per-layer metric's reader may read."""
    records: List[dict]                  # window requests, in order
    window_compiles: int
    trace: Optional[object] = None       # trace.TraceSummary, traced runs

    def served(self) -> List[dict]:
        return [r for r in self.records if r.get("solution") is not None]

    def solved(self) -> List[dict]:
        """Served requests that were solved, not taken from the cache."""
        return [r for r in self.served() if not r["solution"]["from_cache"]]


def _load_reader(path: Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(root: Path, workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    per_layer = {m["name"]: _load_reader(bench_dir / "metrics"
                                         / f"{m['name']}.py")
                 for m in spec["per_layer"] if _applies(m, workload)}
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=TrafficSpec.parse(traffic, config),
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=per_layer, metric_units=units)


# ---------------------------------------------------------------------------
# the closed loop


def _engine_stage(stage_stats: List[dict]) -> Optional[dict]:
    for st in reversed(stage_stats):
        if "backend" in st:
            return st
    return None


def _send(client, config: dict, req: Request, timeout: float,
          taps: Taps) -> dict:
    """One request through the client, timed from the client's side."""
    import jax
    from repro.core import Stencil
    stencil = Stencil(tuple(tuple(o) for o in config["stencil"]["offsets"]))
    rec = {"index": req.index, "problem": req.problem,
           "capacities": req.capacities}
    with jax.profiler.TraceAnnotation("bench.request"):
        t0 = time.perf_counter()
        ticket = client.cart_create_async(
            tuple(config["dims"]), stencil, node_sizes=req.capacities,
            periodic=tuple(config["periodic"]), plan=req.plan)
        try:
            cart = ticket.result(max(0.0, timeout))
        except Exception as e:                  # noqa: BLE001 - recorded
            rec["error"] = f"{type(e).__name__}: {e}"
            cart = None
        t1 = time.perf_counter()
    rec.update(sent=t0, done=t1, latency_s=t1 - t0,
               engines=taps.take())
    if cart is not None:
        sol = cart.solution
        rec["solution"] = {
            "assignment": np.asarray(sol.assignment, dtype=np.int64),
            "j_max": float(sol.j_max), "j_sum": float(sol.j_sum),
            "wall_time_s": float(sol.wall_time_s),
            "from_cache": bool(cart.from_cache),
            "engine_stage": _engine_stage(sol.stage_stats),
        }
    return rec


def closed_loop(client, config: dict, requests: List[Request],
                seconds: float, taps: Taps) -> tuple:
    """One client that sends a request, waits for it, and sends the next.
    The window opens at the first send and closes when the request in
    flight at ``seconds`` comes back.  Returns ``(records, t0, t1)``."""
    import jax
    records = []
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        for req in requests:
            rec = _send(client, config, req,
                        t0 + seconds + LATE_S - time.perf_counter(), taps)
            records.append(rec)
            if rec["done"] - t0 >= seconds or "solution" not in rec:
                break
        else:
            raise RuntimeError(
                f"all {len(requests)} requests were served within "
                f"{seconds} s: the traffic file's 'requests' is too small")
        t1 = time.perf_counter()
    return records, t0, t1


# ---------------------------------------------------------------------------
# one run


def _ratios(table, records: List[dict],
            baselines: Dict[int, tuple]) -> tuple:
    """Served over blocked, each summed over the served requests and both
    recounted by the reference."""
    served = [r for r in records if r.get("solution") is not None]
    if not served:
        return None, None
    A = np.stack([r["solution"]["assignment"] for r in served])
    N = len(served[0]["capacities"])
    jmax, jsum = reference.keys(reference.count_state(table, A, N))
    bmax = sum(baselines[r["index"]][0] for r in served)
    bsum = sum(baselines[r["index"]][1] for r in served)
    return float(jmax.sum()) / bmax, float(jsum.sum()) / bsum


def _baselines(table, requests: List[Request]) -> Dict[int, tuple]:
    """The blocked layout's (J_max, J_sum) of each request."""
    keys: Dict[tuple, tuple] = {}
    for caps in {req.capacities for req in requests}:
        jmax, jsum = reference.keys(reference.count_state(
            table, reference.blocked(caps), len(caps)))
        keys[caps] = (int(jmax[0]), int(jsum[0]))
    return {req.index: keys[req.capacities] for req in requests}


@dataclass
class Measured:
    """What set-up and the window left for the check and the numbers."""
    records: List[dict]
    baselines: Dict[int, tuple]
    table: tuple
    window_s: float
    window_compiles: int
    setup_s: float
    memory_peak_bytes: Optional[int]
    trace_dir: Optional[str]


def run_window(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str],
               device: dict, t_start: float,
               log: Callable[[str], None] = lambda m: None) -> Measured:
    """Set-up, then the measured window; the server is stopped on return.

    ``device`` is the run's device block (``platform``/``kind``/
    ``count``); ``t_start`` the host clock at process start, from which
    ``setup_s`` is counted.  With ``trace_dir`` the window is traced there.
    """
    import jax
    from repro.core import PlanCache
    from repro.serving import PlanClient, PlanServer
    config = cell.config
    table = checks.neighbour_table(config)
    with Taps() as taps, \
            PlanServer(cache=PlanCache(maxsize=4 * cell.traffic.requests),
                       threads=1) as server:
        client = PlanClient(server)
        with CompileClock() as setup_clock:
            requests = build_requests(config, cell.traffic, seed)
            baselines = _baselines(table, requests)
            warm = _send(client, config,
                         warm_request(config, cell.traffic, seed),
                         WARM_TIMEOUT_S, taps)
        if "solution" not in warm:
            raise RuntimeError(f"the warm-up solve failed: {warm['error']}")
        log(f"set-up: {setup_clock.count} programs built in "
            f"{setup_clock.seconds:.3f} s, {setup_clock.cache_hits} from "
            f"the persistent cache; warm-up solve {warm['latency_s']:.3f} s")
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        try:
            setup_s = time.perf_counter() - t_start
            with CompileClock() as window_clock:
                records, t0, t1 = closed_loop(client, config, requests,
                                              seconds, taps)
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        peak = memory_peak_bytes(device["count"])
    served = sum("solution" in r for r in records)
    log(f"window: {len(records)} requests, {served} served in "
        f"{t1 - t0:.3f} s, {window_clock.count} programs built inside it")
    return Measured(records=records, baselines=baselines, table=table,
                    window_s=t1 - t0, window_compiles=window_clock.count,
                    setup_s=setup_s, memory_peak_bytes=peak,
                    trace_dir=trace_dir)


def finish(cell: Cell, m: Measured, device: dict) -> dict:
    """The check against the reference and the cell's numbers: the result
    line as a dict, with ``checks`` as its last key."""
    config = cell.config
    readings = checks.check_run(config, m.records, device["platform"],
                                m.table)
    run = RunData(records=m.records, window_compiles=m.window_compiles)
    metrics: Dict[str, dict] = {}
    out = {"correct": checks.verdict(readings), "attempted": len(m.records),
           "failed": len(m.records) - len(run.served()), "metrics": metrics}
    device = dict(device, memory_peak_bytes=m.memory_peak_bytes)
    if m.trace_dir is not None:
        from .trace import find_trace, reduce_trace
        run.trace = reduce_trace(find_trace(m.trace_dir))
        for name, read in cell.per_layer.items():
            value = read(run)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": cell.metric_units[name]}
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        top_ops = sorted(run.trace.ops_ns.items(), key=lambda kv: -kv[1])
        out["breakdown"] = {
            "device_ops": [[n, v / 1e9] for n, v in top_ops[:10]],
            "idle_gaps": [[n, v] for n, v in run.trace.gaps],
        }
    else:
        jmax_ratio, jsum_ratio = _ratios(m.table, m.records, m.baselines)
        served = len(run.served())
        values = {"solve_s": m.window_s / served if served else None,
                  "jmax_vs_blocked": jmax_ratio,
                  "jsum_vs_blocked": jsum_ratio,
                  "setup_s": m.setup_s}
        for e in cell.end_to_end:
            if e["name"] not in values:
                raise KeyError(f"no end-to-end metric {e['name']!r}")
            if values[e["name"]] is not None:    # None: nothing was served
                metrics[e["name"]] = {"value": values[e["name"]],
                                      "unit": e["unit"]}
    out["device"] = device
    out["checks"] = {name: {"value": readings[name],
                            "limit": checks.LIMITS[name]}
                     for name in checks.LIMITS}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float,
             log: Callable[[str], None] = lambda m: None) -> dict:
    """One run: :func:`run_window`, then :func:`finish`."""
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        m = run_window(cell, seed, seconds, trace_dir, device, t_start, log)
        return finish(cell, m, device)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no per-call Python events
    opts.host_tracer_level = 1          # the benchmark's spans and XLA's
    opts.enable_hlo_proto = False
    return opts
