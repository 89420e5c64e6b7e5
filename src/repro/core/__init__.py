"""Core library: the paper's contribution — stencil-aware process-to-node
mapping for Cartesian grids (Hunold et al., CS.DC 2020)."""
from .cost import MappingCost, blocked_assignment, evaluate, node_of_rank_blocked
from .cost_delta import (BatchSwapDelta, Delta, IncrementalCost,
                         NeighborTable, PortfolioCost, PortfolioSwapDelta,
                         stacked_crossing_counts)
from .graph import CommGraph, GraphGrid, MaskedGraphGrid, arch_comm_graph
from .grid import CartGrid, dims_create
from .mapping import (ANNEALED_PREFIX, DEVICE_PREFIX, HIER_PREFIX, MAPPERS,
                      PORTFOLIO_PREFIX, REFINE_PREFIXES, REFINED_PREFIX,
                      SCHEDULED_PREFIX, SHARDED_PREFIX, BlockedMapper,
                      GraphGreedyMapper, HyperplaneMapper, KDTreeMapper,
                      Mapper, MapperInapplicable, NodecartMapper,
                      RandomMapper, StencilStripsMapper, available_mappers,
                      get_mapper, parse_mapper_options, split_mapper_name)
from .refine import (BaseStage, DevicePortfolioRefiner, HierRefiner,
                     MaskedGrid, PortfolioRefiner,
                     RefinedMapper, RefineResult, RefineStage,
                     ScheduledRefiner, ShardedPortfolioRefiner, Stage,
                     StageResult, SwapRefiner, hier_subtree_cache,
                     refine_assignment)
from .plan import (CartResult, MappingPlan, MappingProblem, MappingSolution,
                   PlanCache, cart_create, default_plan_cache, graph_create,
                   parse_plan)
from .remap import (device_layout, elastic_portfolio_plan, ensure_refined,
                    layout_cost, mapped_device_array, repair_layout)
from .repair import (RepairInapplicable, RepairSeed, RepairStage,
                     absorbed_node_sizes, downweighted_node_sizes,
                     repair_plan, repair_seed, transfer_positions)
from .stencil import Stencil, resolve_weighted

__all__ = [
    "CommGraph", "GraphGrid", "MaskedGraphGrid", "arch_comm_graph",
    "CartGrid", "dims_create", "Stencil", "resolve_weighted", "MappingCost",
    "evaluate", "blocked_assignment", "node_of_rank_blocked",
    "BatchSwapDelta", "Delta", "IncrementalCost", "NeighborTable",
    "PortfolioCost", "PortfolioSwapDelta",
    "Mapper", "MapperInapplicable", "MAPPERS", "REFINED_PREFIX",
    "SCHEDULED_PREFIX", "ANNEALED_PREFIX", "PORTFOLIO_PREFIX",
    "SHARDED_PREFIX", "DEVICE_PREFIX", "HIER_PREFIX", "REFINE_PREFIXES",
    "get_mapper",
    "available_mappers",
    "split_mapper_name", "parse_mapper_options",
    "BlockedMapper", "RandomMapper", "NodecartMapper", "HyperplaneMapper",
    "KDTreeMapper", "StencilStripsMapper", "GraphGreedyMapper",
    "SwapRefiner", "ScheduledRefiner", "PortfolioRefiner",
    "ShardedPortfolioRefiner", "DevicePortfolioRefiner",
    "HierRefiner", "MaskedGrid", "hier_subtree_cache",
    "stacked_crossing_counts", "RefineResult",
    "refine_assignment", "RefinedMapper",
    "Stage", "StageResult", "BaseStage", "RefineStage",
    "MappingProblem", "MappingPlan", "MappingSolution", "parse_plan",
    "PlanCache", "default_plan_cache", "cart_create", "graph_create",
    "CartResult",
    "device_layout", "layout_cost", "mapped_device_array", "ensure_refined",
    "elastic_portfolio_plan", "repair_layout",
    "RepairInapplicable", "RepairSeed", "RepairStage", "repair_seed",
    "repair_plan", "transfer_positions", "absorbed_node_sizes",
    "downweighted_node_sizes",
]
