"""Unified query-execution layer: one staged hash -> probe -> gather ->
verify -> merge plan for every index topology.

The paper's query algorithm is a single pipeline; this package is its single
implementation.  `repro.exec.stages` holds the pure stage functions,
`repro.exec.plan` compiles them into cached `SearchPlan`s per (topology,
SearchParams, index structure, query shape), and topology adapters --
"monolithic" and "segmented" here, "sharded" registered by `repro.shard` --
decide only how stages fan out and merge.  `execute` is the one entry point
every public search API (`LCCSIndex.search`, `SegmentedLCCSIndex.search`,
`ShardedLCCSIndex.search`, `jit_search`, `jit_sharded_search`,
`RetrievalEngine.serve_batch`) now routes through::

    from repro.exec import execute, plan_cache

    ids, dists = execute(index, queries, SearchParams(k=10, lam=200))
    plan_cache().stats()   # {"hits": ..., "misses": ..., ...}: misses are
                           # compiles -- a flat miss counter proves a serving
                           # loop is not silently retracing
"""
from .plan import (
    PlanCache,
    SearchPlan,
    TopologyAdapter,
    available_topologies,
    compile_plan,
    execute,
    get_topology,
    named_jit,
    plan_cache,
    register_topology,
    resolve_params,
    topology_of,
)
from . import stages
from . import topology  # registers the monolithic + segmented adapters

__all__ = [
    "PlanCache",
    "SearchPlan",
    "TopologyAdapter",
    "available_topologies",
    "compile_plan",
    "execute",
    "get_topology",
    "named_jit",
    "plan_cache",
    "register_topology",
    "resolve_params",
    "stages",
    "topology",
    "topology_of",
]
