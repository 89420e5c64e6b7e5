"""Local-search refinement of process-to-node mappings.

The paper's mappers (§V) are one-shot constructions; related work
(Glantz/Meyerhenke/Noe; Schulz/Träff "Better Process Mapping and Sparse
Quadratic Assignment"; Faraj/van der Grinten/Meyerhenke "High-Quality
Hierarchical Process Mapping") shows that cheap local search on top of a
good initial mapping recovers most of the remaining J_sum/J_max gap.  This
package supplies that pass in three tiers:

* :class:`SwapRefiner` — boundary swap local search scored in batches
  (:meth:`~repro.core.cost_delta.IncrementalCost.batch_swap_deltas`):
  the whole candidate frontier is evaluated per sweep in a handful of
  vectorized passes.
* :class:`ScheduledRefiner` — alternates j_sum/j_max SwapRefiner phases
  (optionally with a simulated-annealing temperature ladder) so bottleneck
  relief doesn't stall at the first J_max plateau.
* :class:`PortfolioRefiner` — K independent annealing starts advanced as
  one batched computation (:class:`~repro.core.cost_delta.PortfolioCost`),
  with early-kill of dominated ladders; never worse than a single
  ``annealed`` ladder on the same seed.
* :class:`ShardedPortfolioRefiner` — the portfolio partitioned into seed
  blocks run in parallel worker processes (K into the hundreds),
  bit-identical to the single-process portfolio for any shard count, with
  optional adaptive control: killed ladders' unspent budgets fund restarts
  from the leader, and restart temperatures retune from accept rates.
* :class:`DevicePortfolioRefiner` — the portfolio's K ladders resident on
  the accelerator (:mod:`repro.core.refine.device`): vmapped Metropolis
  moves over stacked integer crossing-count state, one ``lax.scan`` per
  temperature, one host round-trip per boundary.  Scales to K=1024; the
  shared boundary protocol lives in :mod:`repro.core.refine.engine`
  (:class:`LadderEngine` / :class:`BoundaryController`), so serial,
  sharded, and device drivers run identical kill/restart/retune rules.
* :class:`RefinedMapper` — packages any refiner as a drop-in
  :class:`~repro.core.mapping.Mapper`, so ``get_mapper("refined:<base>")``,
  ``"refined2:<base>"``, ``"annealed:<base>"`` and ``"portfolio:<base>"``
  (with bracket options, e.g. ``"portfolio[k=8]:<base>"``) upgrade any
  registered algorithm (see :mod:`repro.core.mapping` for the
  name-resolution contract).

Every refiner also exposes ``as_stage(budget=...)`` — the uniform
:class:`~repro.core.refine.stage.RefineStage` adapter the plan API
(:mod:`repro.core.plan`) composes into :class:`MappingPlan` chains, with
an optional per-stage accepted-swap budget.
"""
from .swap import RefineResult, SwapRefiner, refine_assignment
from .schedule import ScheduledRefiner
from .engine import (BoundaryController, BoundaryReport, LadderEngine,
                     RestartSeeder, SerialLadderEngine)
from .portfolio import PortfolioRefiner, run_temperature
from .sharded import ShardedPortfolioRefiner
from .device import DeviceLadderEngine, DevicePortfolioRefiner
from .hier import HierRefiner, MaskedGrid, hier_subtree_cache
from .stage import BaseStage, RefineStage, Stage, StageResult
from .mapper import RefinedMapper

__all__ = ["SwapRefiner", "ScheduledRefiner", "PortfolioRefiner",
           "ShardedPortfolioRefiner", "DevicePortfolioRefiner",
           "HierRefiner", "MaskedGrid", "hier_subtree_cache",
           "run_temperature",
           "LadderEngine", "SerialLadderEngine", "DeviceLadderEngine",
           "BoundaryController", "BoundaryReport", "RestartSeeder",
           "RefineResult", "refine_assignment", "RefinedMapper",
           "Stage", "StageResult", "BaseStage", "RefineStage"]
