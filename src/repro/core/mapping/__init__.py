"""Process-to-node mapping algorithms (paper §V + baselines §III).

Mapper-name resolution contract
-------------------------------

:func:`get_mapper` turns a string into a ready :class:`Mapper` instance.
Names resolve in two layers:

1. **Base algorithms** — exact keys of :data:`MAPPERS` (``"blocked"``,
   ``"random"``, ``"nodecart"``, ``"hyperplane"``, ``"kdtree"``,
   ``"stencil_strips"``, ``"graphgreedy"``).  ``kwargs`` go to the
   algorithm's constructor.  Bases take bracket options too —
   ``"graphgreedy[seed=3,max_passes=2]"`` — same ``key=value`` syntax
   and coercion as refinement prefixes, merged over ``kwargs`` (bracket
   wins), rendered canonically in the plan key
   (``graphgreedy{max_passes=2,seed=3}``).
2. **Refinement prefixes** — ``"<prefix>[<options>]:<base>"`` recursively
   resolves ``<base>`` (so a base's own name rules apply unchanged) and
   wraps it in a :class:`~repro.core.refine.RefinedMapper`.  Refiner
   configuration comes from the optional *bracket options* — a
   comma-separated ``key=value`` list, e.g. ``"portfolio[k=8,seed=3]:"``,
   with values coerced ``int`` → ``float`` → ``bool`` → ``str`` — merged
   over any ``kwargs`` (bracket options win; the spelled name is the more
   specific spec).  Either way they configure the *refiner*, never the
   base algorithm:

   ============ ===================================================== =========
   prefix       refiner                                               objective
   ============ ===================================================== =========
   refined:     :class:`~repro.core.refine.SwapRefiner`               J_sum
   refined2:    :class:`~repro.core.refine.ScheduledRefiner`          (J_max, J_sum)
   annealed:    ScheduledRefiner(anneal=True) — adds the SA ladder    (J_max, J_sum)
   portfolio:   :class:`~repro.core.refine.PortfolioRefiner` — K      (J_max, J_sum)
                batched annealing starts, never worse than annealed:
   sharded:     :class:`~repro.core.refine.ShardedPortfolioRefiner`   (J_max, J_sum)
                — the portfolio partitioned into seed blocks run in
                parallel worker processes; bit-identical to
                ``portfolio[k=K]:`` for any shard count, plus optional
                adaptive restart/retune control (``restarts=auto``)
   device:      :class:`~repro.core.refine.DevicePortfolioRefiner`    (J_max, J_sum)
                — the portfolio's K ladders resident on the
                accelerator (vmapped Metropolis moves over stacked
                crossing-count state, one ``lax.scan`` per
                temperature); same boundary protocol, scales to
                K=1024
   hier:        :class:`~repro.core.refine.HierRefiner` — recursive   (J_max, J_sum)
                multilevel mapping down a topology tree: group the
                nodes by per-level fan-outs
                (``hier[fanouts=16x16]:``), solve each level's much
                smaller restricted problem with any registered
                refiner (default ``annealed``; per level via
                ``hier[levels=rack:portfolio[k=8],pod:annealed]:``),
                recurse into each subtree
   ============ ===================================================== =========

Every spelling accepted here is accepted everywhere a mapper name appears:
``device_layout`` / ``mapped_device_array`` (:mod:`repro.core.remap`),
``make_mapped_mesh`` (:mod:`repro.launch.mesh`), the benchmark drivers,
and :func:`~repro.core.plan.cart_create`.  Prefixes chain —
``"portfolio[k=8]:refined:hyperplane"`` applies swap refinement, then the
portfolio, inner-first — because ``<base>`` is itself resolved by this
grammar.

The grammar's one implementation is :func:`~repro.core.plan.parse_plan`,
which turns a spelling into a typed, composable
:class:`~repro.core.plan.MappingPlan` (stage chain); :func:`get_mapper` is
its Mapper-shaped front-end, re-packaging the parsed stages as nested
:class:`~repro.core.refine.RefinedMapper` wrappers.  Programs wanting
stage chains, per-stage budgets, or cached solves should use ``parse_plan``
/ :class:`~repro.core.plan.PlanCache` directly.  :func:`split_mapper_name`
exposes the raw parse (prefix, options, base) for callers that need to
inspect a spelling without instantiating it.

Usage::

    get_mapper("hyperplane")                       # paper §V.B
    get_mapper("refined:kdtree", policy="steepest")
    get_mapper("annealed:nodecart", seed=7).assignment(grid, stencil, sizes)
    get_mapper("portfolio[k=4,kill_factor=1.25]:hyperplane")
    get_mapper("annealed[tol=1e-9,seed=-3]:kdtree")  # scientific/negative ok
    get_mapper("sharded[shards=4,k=64,restarts=auto]:hyperplane")
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple, Type

from .base import Mapper, MapperInapplicable, aggregate_node_size, check_bijection
from .blocked import BlockedMapper
from .graphgreedy import GraphGreedyMapper
from .hyperplane import HyperplaneMapper
from .kdtree import KDTreeMapper
from .nodecart import NodecartMapper
from .random_map import RandomMapper
from .stencil_strips import StencilStripsMapper

MAPPERS: Dict[str, Type[Mapper]] = {
    "blocked": BlockedMapper,
    "random": RandomMapper,
    "nodecart": NodecartMapper,
    "hyperplane": HyperplaneMapper,
    "kdtree": KDTreeMapper,
    "stencil_strips": StencilStripsMapper,
    "graphgreedy": GraphGreedyMapper,
}

#: Prefix turning any registered mapper into its local-search variant.
REFINED_PREFIX = "refined:"
#: Prefix for the alternating j_sum/j_max scheduled refiner.
SCHEDULED_PREFIX = "refined2:"
#: Prefix for the scheduled refiner with the simulated-annealing ladder.
ANNEALED_PREFIX = "annealed:"
#: Prefix for the K-start batched annealing portfolio.
PORTFOLIO_PREFIX = "portfolio:"
#: Prefix for the process-sharded adaptive portfolio engine.
SHARDED_PREFIX = "sharded:"
#: Prefix for the device-resident (jax) annealing portfolio engine.
DEVICE_PREFIX = "device:"
#: Prefix for the recursive multilevel (topology-tree) mapping stage.
HIER_PREFIX = "hier:"

#: All refinement prefixes, in registry-listing order.
REFINE_PREFIXES = (REFINED_PREFIX, SCHEDULED_PREFIX, ANNEALED_PREFIX,
                   PORTFOLIO_PREFIX, SHARDED_PREFIX, DEVICE_PREFIX,
                   HIER_PREFIX)

#: the leading ``<prefix>`` of an option-bearing prefixed spelling; the
#: bracket body is scanned with balanced-depth counting (not a regex) so
#: option values may themselves carry brackets
#: (``hier[levels=rack:portfolio[k=8],pod:annealed]:<base>``).
_PREFIX_HEAD_RE = re.compile(r"^(?P<prefix>[a-z][a-z0-9_]*)")

#: a plain option key (what may appear left of ``=``); anything else left
#: of the first ``=`` marks a continuation of the previous option's value.
_OPTION_KEY_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def _coerce_option(value: str):
    """Bracket-option values: int, then float, then bool / None, else
    string.  Everything Python's numeric constructors accept works —
    negative numbers, scientific notation (``t0=1e-2`` / ``seed=-3``,
    pinned by tests), ``inf``, underscore groupings."""
    for cast in (int, float):
        try:
            return cast(value)
        except ValueError:
            pass
    if value in ("true", "True"):
        return True
    if value in ("false", "False"):
        return False
    if value in ("none", "None"):
        return None
    return value


def _spelling(name: Optional[str]) -> str:
    """Error-message suffix naming the full spelling being parsed."""
    return f" in mapper name {name!r}" if name else ""


def _split_depth0(body: str) -> list:
    """Split on commas at bracket depth 0 only, so option values may carry
    bracketed sub-spellings (``levels=rack:portfolio[k=8,seed=3]``)."""
    parts, cur, depth = [], [], 0
    for ch in body:
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth = max(0, depth - 1)
        cur.append(ch)
    parts.append("".join(cur))
    return parts


def parse_mapper_options(opts: str,
                         name: Optional[str] = None) -> Dict[str, object]:
    """Parse a bracket-option body (``"k=8,seed=-3,tol=1e-9"``) into kwargs.

    Splitting happens on depth-0 commas only, and an item that is *not* a
    plain ``key=value`` (its text left of the first ``=`` is no identifier,
    or it has no ``=`` but contains a ``:`` sub-spelling) **continues the
    previous option's value** — that is how
    ``hier[levels=rack:portfolio[k=8],pod:annealed]`` keeps
    ``pod:annealed`` inside ``levels`` while a bare ``annealed[k]`` still
    raises.  ``name`` (the full spelling the body came from) is quoted in
    every error message so a failure deep in a chained prefix stays
    attributable."""
    out: Dict[str, object] = {}
    last_key: Optional[str] = None
    for item in _split_depth0(opts):
        item = item.strip()
        if not item:
            continue
        key, sep, value = item.partition("=")
        key = key.strip()
        plain_key = bool(sep) and bool(_OPTION_KEY_RE.match(key))
        if not plain_key:
            # continuation of the previous value (a comma inside a nested
            # spelling, e.g. per-level solver lists) — only recognizable
            # as such when it carries a `:`-sub-spelling or an `=` deeper
            # inside; a bare word stays the pinned key=value error.
            if last_key is not None and (":" in item or sep):
                prev = out[last_key]
                out[last_key] = (prev if isinstance(prev, str)
                                 else str(prev)) + "," + item
                continue
            raise ValueError(
                f"bad mapper option {item!r}{_spelling(name)}: "
                f"expected key=value")
        if key in out:
            raise ValueError(
                f"duplicate mapper option {key!r}{_spelling(name)}")
        out[key] = _coerce_option(value.strip())
        last_key = key
    return out


def split_mapper_list(spec: str) -> list:
    """Split a comma-separated list of mapper spellings on commas *outside*
    bracket options: ``"blocked,portfolio[k=8,seed=3]:kdtree"`` -> two
    entries (depth-counted, so nested brackets nest).  The one splitter
    the CLI drivers share."""
    parts, cur, depth = [], [], 0
    for ch in spec:
        if ch == "," and depth == 0:
            if cur:
                parts.append("".join(cur))
            cur = []
            continue
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth = max(0, depth - 1)
        cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def split_mapper_name(name: str, full_name: Optional[str] = None) \
        -> Optional[Tuple[str, Dict[str, object], str]]:
    """Split a refinement-prefixed spelling into ``(prefix, options,
    base_name)``; None if ``name`` is not a refinement spelling.  The
    prefix is returned without the colon (``"portfolio"``), options as a
    kwargs dict (empty when no bracket is present).  The bracket body is
    scanned with balanced-depth counting, so values may nest brackets
    (``hier[levels=rack:portfolio[k=8]]:<base>``).  ``full_name`` names
    the enclosing spelling in option-parse errors (chained prefixes hand
    the original spelling down)."""
    m = _PREFIX_HEAD_RE.match(name)
    if m is None or m.group("prefix") + ":" not in REFINE_PREFIXES:
        return None
    prefix = m.group("prefix")
    i = m.end()
    opts = ""
    if i < len(name) and name[i] == "[":
        depth = 0
        j = i
        for j in range(i, len(name)):
            if name[j] == "[":
                depth += 1
            elif name[j] == "]":
                depth -= 1
                if depth == 0:
                    break
        if depth != 0:                    # unbalanced bracket: not ours
            return None
        opts = name[i + 1:j]
        i = j + 1
    if i >= len(name) or name[i] != ":" or i + 1 >= len(name):
        return None
    return (prefix,
            parse_mapper_options(opts, name=full_name or name),
            name[i + 1:])


def _make_refiner(prefix: str, kwargs: Dict[str, object]):
    from ..refine import (DevicePortfolioRefiner, HierRefiner,
                          PortfolioRefiner, ScheduledRefiner,
                          ShardedPortfolioRefiner)
    if prefix == "refined":
        return None                       # RefinedMapper's default SwapRefiner
    if prefix == "refined2":
        return ScheduledRefiner(**kwargs)
    if prefix == "annealed":
        return ScheduledRefiner(anneal=True, **kwargs)
    if prefix == "portfolio":
        return PortfolioRefiner(**kwargs)
    if prefix == "sharded":
        return ShardedPortfolioRefiner(**kwargs)
    if prefix == "device":
        return DevicePortfolioRefiner(**kwargs)
    if prefix == "hier":
        return HierRefiner(**kwargs)
    raise KeyError(prefix)  # pragma: no cover - guarded by split_mapper_name


def get_mapper(name: str, **kwargs) -> Mapper:
    """Instantiate a mapper by name (see the module docstring for the full
    resolution contract; :func:`~repro.core.plan.parse_plan` is the
    grammar's implementation — this is its Mapper-shaped front-end).

    ``"refined:<base>"`` wraps ``<base>`` with swap-refinement local search,
    ``"refined2:<base>"`` with the alternating j_sum/j_max schedule,
    ``"annealed:<base>"`` adds the simulated-annealing ladder, and
    ``"portfolio:<base>"`` runs K batched annealing starts; prefixes chain
    (``"portfolio:refined:<base>"``).  ``kwargs`` and bracket options
    (``"portfolio[k=8]:<base>"``; bracket wins on conflict) configure the
    outermost refiner, not the base algorithm; every prefix composes with
    every key in :data:`MAPPERS`.  The returned mapper carries the
    canonical ``plan_key`` spelling, so :class:`~repro.core.plan.PlanCache`
    can key solved assignments off it.
    """
    from ..plan import parse_plan
    return parse_plan(name, **kwargs).to_mapper()


def available_mappers(include_refined: bool = True) -> list:
    """All resolvable mapper names (base + their refined variants)."""
    names = sorted(MAPPERS)
    if include_refined:
        for prefix in REFINE_PREFIXES:
            names += [prefix + n for n in sorted(MAPPERS)]
    return names


__all__ = [
    "Mapper", "MapperInapplicable", "aggregate_node_size", "check_bijection",
    "BlockedMapper", "RandomMapper", "NodecartMapper", "HyperplaneMapper",
    "KDTreeMapper", "StencilStripsMapper", "GraphGreedyMapper",
    "MAPPERS", "REFINED_PREFIX", "SCHEDULED_PREFIX", "ANNEALED_PREFIX",
    "PORTFOLIO_PREFIX", "SHARDED_PREFIX", "DEVICE_PREFIX", "HIER_PREFIX",
    "REFINE_PREFIXES", "get_mapper",
    "available_mappers", "split_mapper_name", "split_mapper_list",
    "parse_mapper_options",
]
