"""The trace reduction: on made-up intervals, and on a small trace
recorded on a TPU v5e chip (``data/tiny-v5e.xplane.pb.gz``: one request of
an 8x8 grid on 5 nodes, ``device[k=4,sa_moves=20]``, traced by the
benchmark's own window)."""
import gzip
import shutil
from pathlib import Path

import pytest

from conftest import BENCH
from benchlib import trace

FIXTURE = BENCH / "tests" / "data" / "tiny-v5e.xplane.pb.gz"


def test_union_merges_and_clips():
    got = trace.union([(5, 8), (1, 3), (2, 4), (9, 12), (7, 9)], 0, 11)
    assert got == [(1, 4), (5, 11)]
    assert trace.union([(1, 2)], 3, 4) == []


def test_self_times_subtract_nested_ops():
    ops = [(0, 10, "%while.1 = (...) while(...)"),
           (1, 3, "%fusion.2 = s32[8] fusion(...)"),
           (4, 8, "%fusion.3 = s32[8] fusion(...)"),
           (5, 6, "%inner = s32[8] copy(...)"),
           (12, 15, "%fusion.2 = s32[8] fusion(...)")]
    got = trace.self_times(ops)
    assert got == {"%while.1": 4, "%fusion.2": 5, "%fusion.3": 3,
                   "%inner": 1}
    assert sum(got.values()) == 13           # the busy time


def test_idle_time_is_split_by_the_innermost_span():
    spans = [(0, 100, "bench.request"), (10, 20, "bench.rounds"),
             (30, 90, "bench.polish"), (40, 50, "bench.inner"),
             (200, 210, "bench.request")]
    got = trace._attribute([(-5, 15), (25, 45), (95, 205), (215, 230)],
                           spans)
    assert dict(got) == {trace.OUTSIDE: [5, 100, 15],
                         "bench.request": [10, 5, 5, 5],
                         "bench.rounds": [5], "bench.polish": [10],
                         "bench.inner": [5]}


def _sweep_busy(intervals, lo, hi):
    """Busy time by counting open intervals at every edge (a second way to
    the same number)."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e in intervals
                      if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def test_recorded_trace(recorded):
    from jax.profiler import ProfileData
    s = trace.reduce_trace(str(recorded))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    # the busy time again, straight from the device plane's op events
    data = ProfileData.from_file(str(recorded))
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    ops = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    lo, hi = s.window_ns
    assert s.busy_ns == pytest.approx(_sweep_busy(ops, lo, hi), abs=1.0)
    assert sum(s.ops_ns.values()) == pytest.approx(s.busy_ns, rel=1e-9)
    # every temperature's kernel runs inside the span around its call
    temps = s.spans_named("bench.ladder_temperature")
    assert len(temps) == 4
    kernel = [m for m in s.modules if m[2].startswith("jit_run(")]
    assert len(kernel) == 4
    assert s.module_ns_within(temps) == pytest.approx(
        sum(e - b for b, e, _ in kernel))
    idle = sum(v for _, v in s.gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    labels = " ".join(n for n, _ in s.gaps)
    assert "bench.polish" in labels


def test_recorded_trace_metrics(recorded):
    """The per-layer readers that read the trace, on the recorded one."""
    from benchlib.cell import RunData, _load_reader
    s = trace.reduce_trace(str(recorded))
    run = RunData(records=[{"solution": {"from_cache": False}}],
                  window_compiles=0, trace=s)
    idle = _load_reader(BENCH / "metrics" / "device_idle_pct.py")(run)
    assert 0 < idle < 100
    kernel = _load_reader(BENCH / "metrics" / "ladder_kernel_ms.py")(run)
    assert kernel == pytest.approx(s.module_ns_within(
        s.spans_named("bench.ladder_temperature")) / 1e6)
