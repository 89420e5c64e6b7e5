"""Taps on the program's device refiner, installed by the benchmark.

Each tap wraps one method of the program for the length of a run and
restores it afterwards; none changes an argument or a result.

* ``DeviceLadderEngine.__init__`` and ``.snapshot``: the engine's start
  assignment and its end-of-run fetch (current and best-seen rows, the
  integer count state, the best-seen keys).  The refiner fetches the
  snapshot once per solve anyway; the tap keeps a reference to it, so the
  correctness check can recount what the device computed.
* ``DevicePortfolioRefiner.refine``: the ladders' end keys as the device
  computed them (``ladder_keys`` in the refiner's stats, which the plan's
  stage stats do not pass on), kept with the engine that refine built.
* Host spans (``jax.profiler.TraceAnnotation``) around the calls into
  each part of a solve: the base stage (``BaseStage.run``), the rounds
  (``ScheduledRefiner.run_rounds``), the engine's set-up
  (``DeviceLadderEngine.__init__``), each temperature's scan and boundary
  (``.run_temperature``), the end-of-run fetch (``.snapshot``) and the
  polish (``PortfolioRefiner._polish_survivors``).  They cost a few
  microseconds when no trace is taken, and place the device's idle gaps
  in a traced run.
"""
from __future__ import annotations

import threading
from typing import Callable, List, Optional

import numpy as np

__all__ = ["EngineRecord", "Taps", "SPAN_TEMPERATURE"]

SPAN_BASE = "bench.base"
SPAN_ROUNDS = "bench.rounds"
SPAN_ENGINE_INIT = "bench.engine_init"
SPAN_TEMPERATURE = "bench.ladder_temperature"
SPAN_SNAPSHOT = "bench.snapshot"
SPAN_POLISH = "bench.polish"


class EngineRecord:
    """What one device engine was given and what it handed back."""

    __slots__ = ("start", "snapshot", "rows", "k", "ladder_keys")

    def __init__(self, start: np.ndarray, rows: int, k: int):
        self.start = start
        self.rows = rows
        self.k = k
        self.snapshot: Optional[dict] = None
        self.ladder_keys: Optional[list] = None


class Taps:
    """Context manager: installs the taps, collects one
    :class:`EngineRecord` per device engine built while it is open."""

    def __init__(self):
        self.records: List[EngineRecord] = []
        self._lock = threading.Lock()
        self._current = threading.local()   # the engine this thread built
        self._undo: List[Callable[[], None]] = []

    def take(self) -> List[EngineRecord]:
        """The records since the last call, oldest first."""
        with self._lock:
            out, self.records = self.records, []
        return out

    def _wrap(self, owner, name: str, make) -> None:
        orig = getattr(owner, name)
        setattr(owner, name, make(orig))
        self._undo.append(lambda: setattr(owner, name, orig))

    def __enter__(self) -> "Taps":
        import jax
        from repro.core.refine.device import (DeviceLadderEngine,
                                              DevicePortfolioRefiner)
        from repro.core.refine.portfolio import PortfolioRefiner
        from repro.core.refine.schedule import ScheduledRefiner
        from repro.core.refine.stage import BaseStage
        taps = self

        def init(orig):
            def tapped(self, grid, stencil, start, *args, **kwargs):
                with jax.profiler.TraceAnnotation(SPAN_ENGINE_INIT):
                    orig(self, grid, stencil, start, *args, **kwargs)
                rec = EngineRecord(np.array(start, dtype=np.int64),
                                   rows=self.rows, k=self.k)
                self._bench_record = rec
                taps._current.record = rec
                with taps._lock:
                    taps.records.append(rec)
            return tapped

        def refine(orig):
            def tapped(*args, **kwargs):
                taps._current.record = None
                res = orig(*args, **kwargs)
                rec = taps._current.record
                if rec is not None:
                    rec.ladder_keys = (res.stats or {}).get("ladder_keys")
                return res
            return tapped

        def snapshot(orig):
            def tapped(self, *args, **kwargs):
                with jax.profiler.TraceAnnotation(SPAN_SNAPSHOT):
                    snap = orig(self, *args, **kwargs)
                rec = getattr(self, "_bench_record", None)
                if rec is not None:
                    rec.snapshot = snap
                return snap
            return tapped

        def span(label):
            def make(orig):
                def tapped(*args, **kwargs):
                    with jax.profiler.TraceAnnotation(label):
                        return orig(*args, **kwargs)
                return tapped
            return make

        self._wrap(DeviceLadderEngine, "__init__", init)
        self._wrap(DeviceLadderEngine, "snapshot", snapshot)
        self._wrap(DevicePortfolioRefiner, "refine", refine)
        self._wrap(DeviceLadderEngine, "run_temperature",
                   span(SPAN_TEMPERATURE))
        self._wrap(BaseStage, "run", span(SPAN_BASE))
        self._wrap(ScheduledRefiner, "run_rounds", span(SPAN_ROUNDS))
        self._wrap(PortfolioRefiner, "_polish_survivors", span(SPAN_POLISH))
        return self

    def __exit__(self, *exc) -> None:
        while self._undo:
            self._undo.pop()()
