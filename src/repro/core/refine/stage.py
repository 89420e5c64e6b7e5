"""Composable mapping-plan stages (the unit the plan API is built from).

A :class:`Stage` transforms a node-of-position assignment; a
:class:`~repro.core.plan.MappingPlan` is an ordered stage list.  Two kinds
exist:

* :class:`BaseStage` — produces the *initial* assignment by running a base
  mapping algorithm (any :class:`~repro.core.mapping.Mapper`), optionally
  falling back to a second base when the first is inapplicable (the
  elastic path uses ``fallback="blocked"`` so homogeneous-only algorithms
  still yield a refinable start on ragged pods).
* :class:`RefineStage` — improves an existing assignment with any refiner
  exposing ``refine(grid, stencil, node_of_pos, num_nodes)``
  (:class:`~repro.core.refine.SwapRefiner`,
  :class:`~repro.core.refine.ScheduledRefiner`,
  :class:`~repro.core.refine.PortfolioRefiner` — each also exposes
  ``as_stage(budget=...)``).  An optional per-stage ``budget`` caps the
  stage's accepted swaps (threaded into the refiner's ``max_swaps``).

Stages are deterministic and stateless across runs, so a stage chain's
output is a pure function of ``(grid, stencil, node_sizes)`` — which is
what makes :class:`~repro.core.plan.PlanCache` keys sound.

Usage::

    stages = [BaseStage("hyperplane"),
              RefineStage(SwapRefiner(), budget=50),
              ScheduledRefiner(anneal=True).as_stage()]
    assignment = None                    # BaseStage produces the first one
    for s in stages:
        assignment = s.run(grid, stencil, node_sizes, assignment).assignment
"""
from __future__ import annotations

import abc
import copy
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Union

import numpy as np

from ..grid import CartGrid
from ..stencil import Stencil
from ..mapping.base import Mapper, MapperInapplicable

__all__ = ["Stage", "StageResult", "BaseStage", "RefineStage"]


def _canon_value(v) -> str:
    """Canonical spelling of one option value for plan keys (stable across
    equal configurations; tuples/lists render without spaces)."""
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canon_value(x) for x in v) + ")"
    return str(v)


def canon_options(options: Dict[str, object]) -> str:
    """``{"seed": 3, "k": 8}`` -> ``"k=8,seed=3"`` (sorted, canonical)."""
    return ",".join(f"{k}={_canon_value(options[k])}" for k in sorted(options))


#: value types whose canonical spelling is stable across processes (an
#: object attribute would render as a repr with a memory address — never a
#: sound cache key).
_PLAIN_TYPES = (int, float, bool, str, type(None))

#: refiner stats a refine stage passes through: the engine that ran
#: (``device[tpu]``, ``host-fallback``, ``resident``, ...), the reason it
#: delegated, the portfolio engines' time split, and the solve's
#: :mod:`repro.obs` spans and counters
_ENGINE_STATS = ("backend", "delegated", "t_rounds_s", "t_ladders_s",
                 "t_polish_s", "spans", "counters")


def _is_plain(v) -> bool:
    if isinstance(v, _PLAIN_TYPES):
        return True
    if isinstance(v, (tuple, list)):
        return all(_is_plain(x) for x in v)
    return False


def _instance_config(obj):
    """Canonical configuration of a hand-built component, as
    ``(config_dict, cacheable)``: its ``config()`` dict when it has one,
    else its public instance attributes — but only *plain* values
    (numbers/strings/tuples) yield ``cacheable=True``; anything holding
    nested objects is unkeyable (reprs carry memory addresses, which are
    neither stable nor collision-free) and must never enter a
    :class:`~repro.core.plan.PlanCache`."""
    if hasattr(obj, "config"):
        cfg = dict(obj.config())
    else:
        cfg = {k: v for k, v in sorted(vars(obj).items())
               if not k.startswith("_")
               and k not in ("plan_key", "last_result")}
    return cfg, all(_is_plain(v) for v in cfg.values())


@dataclass
class StageResult:
    """One stage's output: the (new) assignment, JSON-able ``stats``, and —
    for refine stages — the full :class:`~repro.core.refine.RefineResult`."""

    assignment: np.ndarray
    stats: Dict[str, object] = field(default_factory=dict)
    result: Optional[object] = None   # RefineResult for RefineStage


class Stage(abc.ABC):
    """One step of a mapping plan: assignment in (or None), assignment out."""

    #: False when this stage's configuration has no stable spelling (e.g. a
    #: hand-built component holding nested objects) — plans containing such
    #: a stage are solved uncached.
    cacheable: bool = True

    #: True for stages that *produce* a plan's first assignment (run with
    #: ``assignment=None``): :class:`BaseStage` and
    #: :class:`~repro.core.repair.RepairStage`.  A plan's first stage must
    #: be initial; no later stage may be.
    is_initial: bool = False

    #: stable spelling of this stage, used in plan keys (cache identity)
    @abc.abstractmethod
    def spec(self) -> str:
        ...

    @abc.abstractmethod
    def run(self, grid: CartGrid, stencil: Stencil,
            node_sizes: Sequence[int],
            assignment: Optional[np.ndarray] = None) -> StageResult:
        ...

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"<{type(self).__name__} {self.spec()}>"


class BaseStage(Stage):
    """Produce the initial assignment with a base mapping algorithm.

    ``mapper`` is a registered base name, a :class:`Mapper` subclass, or an
    instance; ``kwargs`` go to the algorithm's constructor.  ``fallback``
    (same forms) is used when the primary raises
    :class:`MapperInapplicable` — without one, the exception propagates so
    plan callers can fall back themselves.
    """

    is_initial = True

    def __init__(self, mapper: Union[Mapper, type, str] = "hyperplane",
                 fallback: Union[Mapper, type, str, None] = None, **kwargs):
        was_instance = isinstance(mapper, Mapper)
        self.mapper = self._resolve(mapper, kwargs)
        self.fallback = None if fallback is None else self._resolve(fallback, {})
        # spec identity: spelled kwargs when built from a name/class (empty
        # = the algorithm's defaults, unambiguous); a hand-built instance
        # derives its configuration so differently-configured instances
        # never share a cache key — underivable ones mark the stage
        # uncacheable instead.
        if was_instance:
            self.kwargs, self.cacheable = _instance_config(self.mapper)
        else:
            self.kwargs, self.cacheable = dict(kwargs), True

    @staticmethod
    def _resolve(mapper, kwargs) -> Mapper:
        if isinstance(mapper, Mapper):
            if kwargs:
                raise ValueError("kwargs need a mapper name/class, "
                                 "not an instance")
            return mapper
        if isinstance(mapper, type) and issubclass(mapper, Mapper):
            return mapper(**kwargs)
        from ..mapping import MAPPERS
        try:
            cls = MAPPERS[mapper]
        except KeyError:
            raise KeyError(f"unknown base mapper {mapper!r}; choose from "
                           f"{sorted(MAPPERS)}")
        return cls(**kwargs)

    def spec(self) -> str:
        s = self.mapper.name
        if self.kwargs:
            s += "{" + canon_options(self.kwargs) + "}"
        if self.fallback is not None:
            s += f"@fallback={self.fallback.name}"
        return s

    def run(self, grid: CartGrid, stencil: Stencil,
            node_sizes: Sequence[int],
            assignment: Optional[np.ndarray] = None) -> StageResult:
        if assignment is not None:
            raise ValueError("BaseStage must be the first stage of a plan")
        used_fallback = False
        try:
            a = self.mapper.assignment(grid, stencil, node_sizes)
        except MapperInapplicable:
            if self.fallback is None:
                raise
            a = self.fallback.assignment(grid, stencil, node_sizes)
            used_fallback = True
        return StageResult(assignment=a,
                           stats={"stage": self.spec(), "kind": "base",
                                  "used_fallback": used_fallback})


class RefineStage(Stage):
    """Improve an assignment with a refiner; preserves the per-node
    cardinalities (the scheduler allocation) by construction and asserts
    it after every run.

    ``budget`` caps the stage's accepted swaps by threading the refiner's
    ``max_swaps`` (all shipped refiners support it; for a foreign refiner
    without the attribute the budget is recorded in stats but cannot be
    enforced).  ``prefix`` is the registry spelling this stage answers to
    (``refined`` / ``refined2`` / ``annealed`` / ``portfolio``), used for
    plan keys; ``options`` are the *spelled* refiner options for the same
    purpose — when None (hand-built stage), the refiner's full ``config()``
    is derived instead, so two differently-configured refiners never share
    a cache key ({} means "the spelling's defaults", which is unambiguous).
    """

    def __init__(self, refiner, budget: Optional[int] = None,
                 prefix: Optional[str] = None,
                 options: Optional[Dict[str, object]] = None):
        if not hasattr(refiner, "refine"):
            raise TypeError(f"refiner {refiner!r} has no refine() method")
        if budget is not None and int(budget) < 0:
            raise ValueError("budget must be >= 0 (or None)")
        self.refiner = refiner
        self.budget = None if budget is None else int(budget)
        self.prefix = prefix if prefix is not None \
            else type(refiner).__name__.lower()
        if options is None:
            self.options, self.cacheable = _instance_config(refiner)
        else:
            self.options, self.cacheable = dict(options), True

    def spec(self) -> str:
        s = self.prefix
        if self.options:
            s += "[" + canon_options(self.options) + "]"
        if self.budget is not None:
            s += f"@budget={self.budget}"
        return s

    def _budgeted(self):
        """The refiner to run: a shallow copy with ``max_swaps`` capped at
        the stage budget (min-combined with any existing cap)."""
        if self.budget is None or not hasattr(self.refiner, "max_swaps"):
            return self.refiner
        r = copy.copy(self.refiner)
        cur = getattr(r, "max_swaps", None)
        r.max_swaps = self.budget if cur is None else min(int(cur), self.budget)
        return r

    def run(self, grid: CartGrid, stencil: Stencil,
            node_sizes: Sequence[int],
            assignment: Optional[np.ndarray] = None) -> StageResult:
        if assignment is None:
            raise ValueError("RefineStage needs an assignment to refine "
                             "(put a BaseStage first)")
        assignment = np.asarray(assignment, dtype=np.int64)
        n = len(node_sizes)
        sizes = np.asarray([int(s) for s in node_sizes], dtype=np.int64)
        if not np.array_equal(np.bincount(assignment, minlength=n), sizes):
            raise AssertionError(
                "input assignment does not realize node_sizes (the blocked "
                "scheduler allocation)")
        res = self._budgeted().refine(grid, stencil, assignment, num_nodes=n)
        if not np.array_equal(np.bincount(res.assignment, minlength=n),
                              sizes):
            raise AssertionError("refinement changed per-node cardinalities")
        stats = {
            "stage": self.spec(), "kind": "refine", "budget": self.budget,
            "swaps": res.swaps, "passes": res.passes,
            "wall_time_s": res.wall_time_s,
            "initial": (res.initial.j_max, res.initial.j_sum),
            "final": (res.final.j_max, res.final.j_sum),
        }
        # where the refiner ran, and why it delegated if it did
        engine_stats = res.stats or {}
        for key in _ENGINE_STATS:
            if key in engine_stats:
                stats[key] = engine_stats[key]
        return StageResult(assignment=res.assignment, stats=stats, result=res)
