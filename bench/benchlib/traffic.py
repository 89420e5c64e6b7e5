"""The one traffic generator: turns a traffic file's parameters, a
configuration and ``--seed`` into the list of requests a run sends.

A request is one job of the configuration: its process grid and stencil
on its allocation (``nodes`` full nodes of ``slots_per_node`` processes
each, the paper's N x P), solved with the configuration's plan.  What
makes two requests two problems is the plan's anneal seed, the ``{seed}``
in the configuration's ``plan``, drawn from ``--seed``: a new seed is a
new content hash and a fresh anneal, at the same shapes.

Parameters a traffic file may set (every other key is refused):

* ``requests``: how many requests set-up builds, in sending order (a run
  that needs more fails rather than repeating one);
* ``pool``: absent -- every request is a problem of its own, so every one
  is a cold miss; a number M -- the requests draw from M problems with
  Zipf popularity, so that repeats are served from the plan cache;
* ``zipf_s``: the exponent of that popularity (given with ``pool``);
* ``why``: a sentence for the reader.

One closed-loop client sends them (:func:`cell.closed_loop`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["Request", "TrafficSpec", "build_requests", "capacities",
           "warm_request"]

_KEYS = {"requests", "pool", "zipf_s", "why"}


@dataclass(frozen=True)
class TrafficSpec:
    requests: int
    pool: Optional[int] = None
    zipf_s: Optional[float] = None

    @classmethod
    def parse(cls, raw: dict, config: dict) -> "TrafficSpec":
        unknown = set(raw) - _KEYS
        if unknown:
            raise ValueError(f"unknown traffic keys {sorted(unknown)}")
        spec = cls(requests=int(raw["requests"]), pool=raw.get("pool"),
                   zipf_s=raw.get("zipf_s"))
        if spec.requests < 1:
            raise ValueError("requests must be >= 1")
        if (spec.pool is None) != (spec.zipf_s is None):
            raise ValueError("pool and zipf_s are given together")
        if spec.pool is not None and (spec.pool < 1 or spec.zipf_s <= 0):
            raise ValueError("pool must be >= 1 and zipf_s > 0")
        if "{seed}" not in config["plan"]:
            raise ValueError("the configuration's plan has no '{seed}', so "
                             "its requests could not differ")
        capacities(config)
        return spec


@dataclass(frozen=True)
class Request:
    index: int
    problem: int                    # requests of one problem are repeats
    plan: str
    capacities: Tuple[int, ...]


def capacities(config: dict) -> Tuple[int, ...]:
    """Per-node capacities of the configuration's allocation."""
    alloc = config["allocation"]
    caps = (int(alloc["slots_per_node"]),) * int(alloc["nodes"])
    if sum(caps) != config["processes"]:
        raise ValueError(f"{alloc['nodes']} nodes x "
                         f"{alloc['slots_per_node']} slots is not the "
                         f"grid's {config['processes']} processes")
    return caps


def _anneal_seeds(seed: int, n: int) -> List[int]:
    """``n`` distinct anneal seeds, a function of ``seed``; the first is
    the warm-up's, so no window request repeats it."""
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 0])
    out: List[int] = []
    while len(out) < n:
        s = int(rng.integers(0, 2 ** 31 - 1))
        if s not in out:
            out.append(s)
    return out


def build_requests(config: dict, spec: TrafficSpec, seed: int) \
        -> List[Request]:
    """The run's requests, in sending order; a function of the seed."""
    caps = capacities(config)
    problems = spec.requests if spec.pool is None else spec.pool
    seeds = _anneal_seeds(seed, problems + 1)[1:]
    if spec.pool is None:
        order = np.arange(spec.requests)
    else:
        rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 1])
        w = 1.0 / np.arange(1, spec.pool + 1) ** spec.zipf_s
        order = rng.choice(spec.pool, size=spec.requests, p=w / w.sum())
    return [Request(index=i, problem=int(j),
                    plan=config["plan"].format(seed=seeds[j]),
                    capacities=caps)
            for i, j in enumerate(order)]


def warm_request(config: dict, spec: TrafficSpec, seed: int) -> Request:
    """The set-up's warm-up request: the window's shapes, a problem of its
    own."""
    return Request(index=-1, problem=-1,
                   plan=config["plan"].format(seed=_anneal_seeds(seed, 1)[0]),
                   capacities=capacities(config))
