"""Spans and counters inside the program, on the profiler trace's clock.

Three calls:

* ``span(name, **meta)`` -- a context manager around one piece of work.
  It always opens ``jax.profiler.TraceAnnotation("repro." + name, **meta)``,
  so a profiler trace shows the span on the host plane, on the same clock
  as the device's ops.  Inside :func:`recording` it also adds its
  inclusive wall time under its *path*: the names of the open spans
  joined by ``/``, e.g. ``survivors/polish/swap.score``.
* ``count(name, n)`` -- adds ``n`` under the current path plus ``/name``
  (``survivors/polish/swap.pairs``).  Counters add batch sizes: no span or
  count belongs inside a per-row, per-move or per-swap loop.
* ``recording()`` -- opens a recorder for the current context (a
  :class:`contextvars.ContextVar`, so threads never mix) and yields the
  dict it fills::

      {"spans": {path: [calls, seconds]}, "counters": {path: int}}

  complete once the ``with`` block has closed its spans.  Nothing is
  written anywhere; the caller puts the dict where it belongs (the device
  refiner puts it in its stats).

Outside a recorder a span only annotates the trace (about a microsecond
when no profiler runs).  The annotation is taken from ``jax`` only when
``jax`` is already imported: a process that never imported it cannot be
running its profiler, and a numpy-only worker need not pay the import.

Usage::

    from repro import obs
    with obs.recording() as rec:
        with obs.span("ladders"):
            with obs.span("engine_init"):
                ...
            obs.count("rows", 512)
    rec["spans"]["ladders/engine_init"]   # -> [1, 0.61]
    rec["counters"]["ladders/rows"]       # -> 512
"""
from __future__ import annotations

import contextlib
import contextvars
import sys
import time
from typing import Dict, Iterator, List, Optional

__all__ = ["count", "recording", "span", "PREFIX"]

#: every span's name in a profiler trace starts with this
PREFIX = "repro."


class _Recorder:
    __slots__ = ("out", "stack")

    def __init__(self):
        self.out: dict = {"spans": {}, "counters": {}}
        self.stack: List[str] = []          # paths of the open spans


_ACTIVE: contextvars.ContextVar[Optional[_Recorder]] = \
    contextvars.ContextVar("repro_obs_recorder", default=None)


def _annotation(name: str, meta: dict):
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@contextlib.contextmanager
def span(name: str, **meta) -> Iterator[None]:
    """Annotate the trace with ``repro.<name>`` (``meta`` as its
    arguments) and, inside :func:`recording`, add the block's inclusive
    seconds under its path."""
    rec = _ACTIVE.get()
    with _annotation(name, meta):
        if rec is None:
            yield
            return
        path = f"{rec.stack[-1]}/{name}" if rec.stack else name
        rec.stack.append(path)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            rec.stack.pop()
            entry = rec.out["spans"].setdefault(path, [0, 0.0])
            entry[0] += 1
            entry[1] += dt


def count(name: str, n: int) -> None:
    """Add ``n`` under the current span path plus ``/name`` (no-op
    outside :func:`recording`)."""
    rec = _ACTIVE.get()
    if rec is None:
        return
    path = f"{rec.stack[-1]}/{name}" if rec.stack else name
    counters: Dict[str, int] = rec.out["counters"]
    counters[path] = counters.get(path, 0) + int(n)


@contextlib.contextmanager
def recording() -> Iterator[dict]:
    """Record the spans and counters of this context until the block
    exits; yields ``{"spans": ..., "counters": ...}``.  A recording opened
    inside another takes its spans for its own length."""
    rec = _Recorder()
    token = _ACTIVE.set(rec)
    try:
        yield rec.out
    finally:
        _ACTIVE.reset(token)
