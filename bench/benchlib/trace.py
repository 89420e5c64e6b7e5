"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace holds one plane per device (``/device:TPU:<n>``) and one for
the host (``/host:CPU``).  A device plane's ``XLA Ops`` line has one event
per operation run, its ``XLA Modules`` line one per program run.  The
host plane's lines are threads; the benchmark's own spans (names starting
``bench.``) sit on them, on the same clock as the device events.

From these :func:`reduce_trace` computes:

* the traced window: the span named ``bench.window``;
* per device, the union of its operations' intervals inside the window,
  which is the time the device was busy, and its mean over the devices
  the run used (those with an operation in the window: the program runs
  on one device, also on a host of four);
* device time per program (module), and self time per operation name
  (an operation's time less that of the operations nested in it, as a
  loop's body is nested in the loop), so that the names' times add up to
  the busy time;
* the idle time (window minus busy), split at the benchmark's span edges
  and each piece placed under the innermost (shortest) span covering it,
  so that an idle stretch across the polish, the next request's base
  stage and its rounds is shared out among them.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

__all__ = ["Interval", "TraceSummary", "find_trace", "reduce_trace",
           "union", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "outside benchmark spans"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float, str]          # start_ns, end_ns, name


@dataclass
class TraceSummary:
    window_ns: Tuple[float, float]
    devices: int                          # devices with work in the window
    busy_ns: float                        # mean over those devices
    ops_ns: Dict[str, float]              # op name -> summed self time
    modules: List[Interval]               # module runs, all devices
    spans: List[Interval]                 # the benchmark's host spans
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def spans_named(self, name: str) -> List[Interval]:
        return [s for s in self.spans if s[2] == name]

    def module_ns_within(self, spans: List[Interval]) -> float:
        """Device time of the module runs whose midpoint lies inside one of
        ``spans`` (which do not overlap one another)."""
        spans = sorted(spans)
        starts = [s[0] for s in spans]
        total = 0.0
        for s, e, _ in self.modules:
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and spans[i][0] <= mid <= spans[i][1]:
                total += e - s
        return total


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Tuple[float, float]],
          lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line) -> List[Interval]:
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             ev.name) for ev in line.events]


def op_name(hlo: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``%fusion.12``."""
    return hlo.split(" = ", 1)[0]


def self_times(ops: List[Interval]) -> Dict[str, float]:
    """Summed self time per operation name: each event's duration less the
    durations of the events directly nested in it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [end, name, child time]
    def close(entry):
        end, name, start, child = entry
        out[name] += (end - start) - child
        if stack:
            stack[-1][3] += end - start
    for s, e, name in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, op_name(name), s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def _segments(spans: List[Interval]) -> List[Interval]:
    """The timeline cut at every span edge, each piece named by the
    innermost (shortest) span that covers it."""
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[Interval] = []
    starts = sorted(spans)
    active: List[Interval] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][0] <= a:
            active.append(starts[i])
            i += 1
        active = [s for s in active if s[1] > a]
        best = min(active, key=lambda s: s[1] - s[0], default=None)
        out.append((a, b, best[2] if best else OUTSIDE))
    return out


def _attribute(idle: List[Tuple[float, float]],
               spans: List[Interval]) -> Dict[str, List[float]]:
    """Split sorted idle intervals along :func:`_segments`: the idle time
    under each span name, as a list of pieces."""
    out: Dict[str, List[float]] = defaultdict(list)
    segs = _segments(spans)
    j = 0
    for s, e in idle:
        t = s
        while j < len(segs) and segs[j][1] <= t:
            j += 1
        k = j
        while t < e:
            if k < len(segs) and segs[k][0] <= t:
                end = min(e, segs[k][1])
                label = segs[k][2]
            else:                      # before, between or after all spans
                end = min(e, segs[k][0]) if k < len(segs) else e
                label = OUTSIDE
            out[label].append(end - t)
            t = end
            if k < len(segs) and t >= segs[k][1]:
                k += 1
    return out


def reduce_trace(path: str, max_entries: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Interval] = []
    device_ops: List[List[Interval]] = []
    modules: List[Interval] = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[2].startswith(SPAN_PREFIX)]
        elif plane.name.startswith(DEVICE_PREFIX):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    modules += _events(line)
            device_ops.append(ops)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0][0], windows[0][1]
    busy_per_device = []
    gaps_read = False
    ops_ns: Dict[str, float] = defaultdict(float)
    gaps_by_label: Dict[str, List[float]] = defaultdict(list)
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    for ops in device_ops:
        busy = union([(s, e) for s, e, _ in ops], lo, hi)
        if not busy:
            continue
        busy_per_device.append(sum(e - s for s, e in busy))
        for name, t in self_times([ev for ev in ops
                                   if ev[0] >= lo and ev[1] <= hi]).items():
            ops_ns[name] += t
        if not gaps_read:           # idle gaps of the first used device
            gaps_read = True
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            for label, pieces in _attribute(idle, inner).items():
                gaps_by_label[label] += pieces
    if not busy_per_device:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane has an "
                         f"operation in the window")
    gaps = sorted(((f"{label} ({len(v)} pieces, longest {max(v) / 1e9:.6f} s)",
                    sum(v) / 1e9) for label, v in gaps_by_label.items()),
                  key=lambda g: -g[1])[:max_entries]
    return TraceSummary(
        window_ns=(lo, hi), devices=len(busy_per_device),
        busy_ns=sum(busy_per_device) / len(busy_per_device),
        ops_ns=dict(ops_ns),
        modules=[m for m in modules if m[0] >= lo and m[1] <= hi],
        spans=spans, gaps=gaps)
