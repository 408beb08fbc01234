"""SearchPlan: one compiled, cached execution plan per (params, topology).

`compile_plan(index, queries, params)` resolves the user's `SearchParams`
against the index's topology (source rewrites, kernel-toggle pinning, store /
shard-count validation), builds the staged executable for that topology, and
caches it in an explicit `PlanCache` keyed on

    (topology, resolved params, index pytree structure + leaf shapes/dtypes,
     query batch shape)

-- exactly what `jax.jit` retraces on, made visible: a cache *hit* is a
guarantee of no retrace, a *miss* is a compile, and the counters are surfaced
through `RetrievalEngine.stats` so serving never silently retraces.

Topologies register through `register_topology` the same way candidate
sources register in `repro.core.sources`: the monolithic and segmented
adapters live in `repro.exec.topology`, the sharded adapter in
`repro.shard.search` (imported via `repro.core`, so all three are present
whenever the package is).  An adapter is two functions:

    resolve(index, params) -> SearchParams   validate + rewrite (host-side,
                                             before any tracing)
    build(index, params)   -> run(index, queries) -> (ids, dists)
                                             construct the plan's executable;
                                             `run` owns its own jit objects,
                                             so one plan == one compile

New topologies (replicated read-split indexes, hierarchical shard trees, a
fused Pallas CSA-probe dispatch, ...) plug in without touching the index
classes -- the single dispatch point the exec refactor exists to provide.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial
from typing import TYPE_CHECKING, Any, Callable

import jax
import numpy as np

if TYPE_CHECKING:  # pragma: no cover -- leaf module: core imports stay lazy
    from repro.core.params import SearchParams

Runner = Callable[[Any, jax.Array], tuple[jax.Array, jax.Array]]


def named_jit(fn: Callable, name: str, **static) -> Callable:
    """`jax.jit(partial(fn, **static))` whose program is `jit_<name>` in the
    HLO and in profiler traces (a bare partial compiles as `jit__unknown`)."""
    f = partial(fn, **static)
    f.__name__ = name  # type: ignore[attr-defined]
    return jax.jit(f)


# ---------------------------------------------------------------------------
# Topology adapter registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TopologyAdapter:
    name: str
    resolve: Callable[[Any, SearchParams], SearchParams]
    build: Callable[[Any, SearchParams], Runner]
    # opt-in instrumented variant: same results, but staged with
    # block_until_ready fences between hash/probe/gather/rerank/merge and
    # per-stage timing into the repro.obs registry.  None = a generic
    # whole-plan span wrapper around `build`'s runner.
    build_instrumented: Callable[[Any, SearchParams], Runner] | None = None


_TOPOLOGIES: dict[str, TopologyAdapter] = {}


def register_topology(name: str, *, resolve, build,
                      build_instrumented=None) -> TopologyAdapter:
    """Register a topology adapter (re-registering overwrites, mirroring
    `register_source`)."""
    adapter = TopologyAdapter(name=name, resolve=resolve, build=build,
                              build_instrumented=build_instrumented)
    _TOPOLOGIES[name] = adapter
    return adapter


def available_topologies() -> tuple[str, ...]:
    return tuple(sorted(_TOPOLOGIES))


def topology_of(index) -> str:
    """An index declares its topology via a `topology` class attribute
    ("monolithic" | "segmented" | "sharded"); unmarked index-likes (test
    doubles, external classes serving the LCCSIndex protocol) default to
    monolithic."""
    return getattr(index, "topology", "monolithic")


def get_topology(name: str) -> TopologyAdapter:
    try:
        return _TOPOLOGIES[name]
    except KeyError:
        raise KeyError(
            f"unknown index topology {name!r}; available: "
            f"{available_topologies()}"
        ) from None


# ---------------------------------------------------------------------------
# The plan + its cache
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SearchPlan:
    """A compiled (or compile-on-first-call) staged search pipeline, pinned
    to one (topology, resolved params, index structure, query shape) key.
    Calling it with any index/queries matching the key reuses the same
    executable -- leaf *values* may vary freely, shapes may not."""

    topology: str
    params: "SearchParams"  # resolved: sources rewritten, kernel toggle pinned
    key: tuple = field(repr=False)
    run: Runner = field(repr=False)
    instrumented: bool = False

    def __call__(self, index, queries):
        return self.run(index, queries)


# the global scope label for unattributed cache activity (scope=None callers)
_UNSCOPED = ""


class PlanCache:
    """LRU cache of `SearchPlan`s with explicit hit/miss/eviction counters,
    carried on the unified metrics registry (`repro.obs`) with per-scope
    labels -- `hits`/`misses`/`evictions` and `scopes` below are views over
    the registry counters, so a Prometheus scrape and `stats()` can never
    disagree.

    misses == number of plans built == number of pipeline compiles (each
    plan's executables are private to it and only ever see one shape), so
    `stats()` is a retrace audit: a serving loop whose miss counter is flat
    is provably not recompiling.  Evictions are attributed to the scope that
    *built* the evicted plan: the replica churning through plan shapes is
    the one named, not whoever happened to insert plan #257."""

    def __init__(self, maxsize: int = 256):
        from repro.obs.registry import registry

        self.maxsize = maxsize
        self._hits = registry().counter(
            "repro_plan_cache_hits_total",
            "compiled search plans reused from the exec plan cache",
            labelnames=("scope",),
        )
        self._misses = registry().counter(
            "repro_plan_cache_misses_total",
            "staged-pipeline compiles (plan cache misses)",
            labelnames=("scope",),
        )
        self._evictions = registry().counter(
            "repro_plan_cache_evictions_total",
            "plans evicted from the LRU plan cache, labeled by the scope "
            "that built them",
            labelnames=("scope",),
        )
        # key -> (plan, builder scope): the scope rides along so an eviction
        # can be attributed to the caller whose compile it undoes
        self._plans: OrderedDict[tuple, tuple[SearchPlan, str]] = OrderedDict()  # guarded-by: _lock
        self._lock = threading.Lock()

    # -- registry-backed counter views ---------------------------------------

    @property
    def hits(self) -> int:
        return int(self._hits.value())

    @property
    def misses(self) -> int:
        return int(self._misses.value())

    @property
    def evictions(self) -> int:
        return int(self._evictions.value())

    @property
    def scopes(self) -> dict[str, dict[str, int]]:
        """Per-scope {hits, misses, evictions} attribution (the serving
        front passes each replica engine's name as its scope)."""
        out: dict[str, dict[str, int]] = {}
        for field_name, counter in (("hits", self._hits),
                                    ("misses", self._misses),
                                    ("evictions", self._evictions)):
            for (scope,), val in counter.collect().items():
                if scope == _UNSCOPED:
                    continue
                out.setdefault(
                    scope, {"hits": 0, "misses": 0, "evictions": 0}
                )[field_name] = int(val)
        return out

    def scope_evictions(self, scope: str | None) -> int:
        """Evictions charged to one scope (engine stats mirror this)."""
        if scope is None:
            return 0
        return int(self._evictions.value(scope=scope))

    def get_or_build(self, key: tuple, builder: Callable[[], SearchPlan],
                     scope: str | None = None) -> tuple:
        """Fetch or build the plan for `key`.  Returns (plan, hit): callers
        that attribute cache activity (engine stats) use the per-call `hit`
        flag rather than diffing the global counters, which would misattribute
        concurrent callers' activity.  `scope` additionally labels the
        outcome in the registry counters (per-replica attribution)."""
        label = _UNSCOPED if scope is None else scope
        with self._lock:
            entry = self._plans.get(key)
            if entry is not None:
                self._plans.move_to_end(key)
        if entry is not None:
            self._hits.inc(scope=label)
            return entry[0], True
        # build outside the lock: plan construction may be slow (jit setup)
        # and double-building on a race is harmless (last writer wins)
        plan = builder()
        evicted: list[str] = []
        with self._lock:
            self._plans[key] = (plan, label)
            self._plans.move_to_end(key)
            while len(self._plans) > self.maxsize:
                _, (_, owner) = self._plans.popitem(last=False)
                evicted.append(owner)
        self._misses.inc(scope=label)
        for owner in evicted:
            self._evictions.inc(scope=owner)
        return plan, False

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "size": len(self),
            "scopes": self.scopes,
        }

    def clear(self) -> None:
        """Drop every plan and zero the counters (test isolation)."""
        with self._lock:
            self._plans.clear()
        self._hits.reset()
        self._misses.reset()
        self._evictions.reset()


_CACHE = PlanCache()


def plan_cache() -> PlanCache:
    """The process-global plan cache (one per process, like jit's)."""
    return _CACHE


# ---------------------------------------------------------------------------
# compile / execute
# ---------------------------------------------------------------------------


def _leaf_sig(x) -> tuple:
    shape = np.shape(x)
    dtype = getattr(x, "dtype", None)
    return (shape, str(dtype) if dtype is not None else type(x).__name__)


def _index_signature(index) -> tuple:
    """Hashable (treedef, leaf shapes/dtypes) fingerprint of an index pytree:
    the part of the index `jax.jit` specializes on.  Mutating leaf values
    (inserts, deletes, device moves) preserves it; growing a buffer or
    compacting a segment stack (treedef / shape change) does not."""
    leaves, treedef = jax.tree_util.tree_flatten(index)
    return (treedef, tuple(_leaf_sig(x) for x in leaves))


def _default_params():
    from repro.core.params import SearchParams, _suppress_width_warning

    # params=None means "the documented defaults": constructing them inside
    # the library must not fire the WindowWidthWarning from an internal
    # frame -- the warning is for params the caller actually spelled out
    with _suppress_width_warning():
        return SearchParams()


def resolve_params(index, params: "SearchParams | None") -> "SearchParams":
    """Topology-aware params resolution only (no plan build): source
    rewrites, kernel pinning, store/shard validation."""
    adapter = get_topology(topology_of(index))
    return adapter.resolve(index, params or _default_params())


def _generic_instrumented(adapter: TopologyAdapter, index,
                          p: "SearchParams") -> Runner:
    """Fallback instrumented builder for adapters without a staged variant:
    the ordinary runner timed as one `search` stage (still fenced, still in
    the stage histogram -- just without per-stage resolution)."""
    from repro.obs.trace import stage as _stage

    run = adapter.build(index, p)

    def instrumented(idx, queries):
        with _stage(adapter.name, "search"):
            out = run(idx, queries)
            jax.block_until_ready(out)
        return out

    return instrumented


def compile_plan(index, queries, params: "SearchParams | None" = None,
                 *, return_hit: bool = False, scope: str | None = None,
                 instrument: bool = False):
    """Resolve + build (or fetch) the plan for searching `index` with query
    batches shaped like `queries` (an array, or a plain (B, d) shape tuple).
    The heavy XLA compile itself still happens lazily on the plan's first
    call; one plan compiles at most once.  With `return_hit=True` returns
    (plan, hit) -- the race-free way for a caller to attribute this call's
    cache outcome to itself (diffing the global counters would absorb
    concurrent callers' activity).  `scope` labels the outcome in the cache's
    per-scope tallies (`plan_cache().stats()["scopes"]`); the serving front
    passes each replica engine's name so a deployment can attribute every
    compile to the replica that triggered it.

    `instrument=True` builds the topology's *staged* variant: the same
    arithmetic split into separately-jitted stages with `block_until_ready`
    fences, timing each into `repro_exec_stage_seconds{topology,stage}`.
    Instrumented plans are keyed distinctly in the cache, so flipping
    instrumentation never invalidates (or pollutes the miss audit of) the
    fused fast-path plans."""
    adapter = get_topology(topology_of(index))
    p = adapter.resolve(index, params or _default_params())
    if isinstance(queries, tuple):  # plain shape: execute() casts to float32
        qsig = (tuple(queries), "float32")
    else:
        qsig = _leaf_sig(queries)  # shape AND dtype: a same-shape batch of a
        # different dtype would retrace inside the plan's jit, so it must be
        # a different plan for the hit == no-retrace audit to hold
    instrument = bool(instrument)
    key = (adapter.name, instrument, p, _index_signature(index), qsig)
    if instrument:
        build_i = adapter.build_instrumented
        builder = (lambda: SearchPlan(
            topology=adapter.name, params=p, key=key, instrumented=True,
            run=(build_i(index, p) if build_i is not None
                 else _generic_instrumented(adapter, index, p)),
        ))
    else:
        builder = (lambda: SearchPlan(
            topology=adapter.name, params=p, key=key,
            run=adapter.build(index, p),
        ))
    plan, hit = _CACHE.get_or_build(key, builder, scope=scope)
    return (plan, hit) if return_hit else plan


def execute(index, queries, params: "SearchParams | None" = None,
            *, instrument: bool = False):
    """The unified search entry point: every topology, every store, every
    candidate source -- one staged hash -> probe -> gather -> verify -> merge
    plan, compiled once per (params, shapes) and cached explicitly.
    Returns (ids (B, k), dists (B, k)).

    `instrument=True` routes through the staged per-stage-timed plan variant
    (bit-identical results, separate cache key -- see `compile_plan`)."""
    import jax.numpy as jnp

    queries = jnp.asarray(queries, jnp.float32)
    plan = compile_plan(index, queries, params, instrument=instrument)
    return plan.run(index, queries)
