"""Monolithic + segmented topology adapters (the sharded adapter lives in
`repro.shard.search`, next to its shard_map plumbing).

Both adapters run the SAME staged pipeline (`search_pipeline` below); the
segmented index differs only in params resolution -- its per-segment fan-out
and exact candidate merge are inside the registered "segmented" candidate
source, which is itself built from `repro.exec.stages` -- so the adapter
bodies stay a few lines each.  The one genuinely different *execution shape*
is the disk-lazy rerank tail: a quantized monolithic index whose fp32 rows
live in an .npy cannot gather them inside a trace, so its plan splits into
jitted stage 1 (hash -> probe -> survivors), a host memmap gather, and the
shared jitted rerank stage.  That orchestration lives here -- in exactly one
place -- and nowhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.params import SearchParams, _suppress_width_warning
from repro.obs.trace import stage as _obs_stage
from repro.store import tail as tail_mod

from . import stages
from .plan import named_jit, register_topology, topology_of


# ---------------------------------------------------------------------------
# Pure pipeline bodies (composable, trace-friendly)
# ---------------------------------------------------------------------------


def search_pipeline(index, queries: jax.Array, params: SearchParams):
    """hash -> probe -> verify over one resident-data index: the staged form
    of the paper's full query algorithm.  Pure function of a pytree index;
    `params` must be static under jit.  Each stage runs in a
    `jax.named_scope` of its name, so a profiler trace attributes device
    time by stage (scopes are metadata: the compiled ops are the same)."""
    with jax.named_scope("hash"):
        qh = stages.hash_queries(index.family, queries)
    with jax.named_scope("probe"):
        cand_ids, _ = stages.probe(index, queries, qh, params)
    with jax.named_scope("verify"):
        return stages.verify(
            index.store, index.tail, queries, cand_ids, params,
            params.metric or index.metric,
        )


def survivor_pipeline(index, queries: jax.Array, params: SearchParams):
    """hash -> probe -> stage-1 survivors only: the jitted front half of the
    disk-tail split plan.  Returns survivor ids (B, R)."""
    with jax.named_scope("hash"):
        qh = stages.hash_queries(index.family, queries)
    with jax.named_scope("probe"):
        cand_ids, _ = stages.probe(index, queries, qh, params)
    with jax.named_scope("survivors"):
        surv, _ = stages.survivors(
            index.store, queries, cand_ids, params,
            params.metric or index.metric
        )
    return surv


def has_disk_tail(index) -> bool:
    """True when the index's exact rerank rows live on disk (quantized store,
    no resident tail, `tail_path` set) -- the one layout that cannot serve
    from a single jit."""
    return (
        not index.store.exact
        and index.tail is None
        and bool(getattr(index, "tail_path", None))
    )


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------


def _resolve_common(index, p: SearchParams) -> SearchParams:
    # pin the tri-state kernel toggle to a concrete bool so the resolved
    # value participates in the plan key (a later env-var change cannot be
    # seen by an already-compiled executable).  Derived copies suppress the
    # construction-time WindowWidthWarning: the user's params already warned.
    if p.use_gather_kernel is None:
        with _suppress_width_warning():
            p = p.replace(use_gather_kernel=stages.resolve_use_kernel(None))
    if p.use_probe_kernel is None:
        with _suppress_width_warning():
            p = p.replace(
                use_probe_kernel=stages.resolve_use_probe_kernel(None)
            )
    # host-side early validation: same error the verify stage raises at
    # trace time, surfaced before any compilation work
    stages.check_store_kind(index.store, p)
    return p


def _monolithic_resolve(index, p: SearchParams) -> SearchParams:
    return _resolve_common(index, p)


def _monolithic_build(index, p: SearchParams):
    if not has_disk_tail(index):
        return named_jit(search_pipeline, "search_pipeline", params=p)

    stage1 = named_jit(survivor_pipeline, "survivor_pipeline", params=p)

    def run(idx, queries):
        # split plan: jitted stage 1 -> host memmap gather -> jitted rerank
        surv = stage1(idx, queries)
        rows = jnp.asarray(tail_mod.gather_tail(idx.tail_path, surv))
        return stages.rerank_rows(rows, queries, surv, p.k,
                                  p.metric or idx.metric)

    return run


# -- instrumented (staged) variant -----------------------------------------
#
# The same arithmetic as `_monolithic_build`, but each pipeline stage is its
# own jit with a `block_until_ready` fence inside an `obs.stage` timer, so
# `repro_exec_stage_seconds{topology,stage}` sees device-inclusive per-stage
# walls.  Compiled separately and keyed distinctly in the plan cache
# (`compile_plan(..., instrument=True)`): the fused fast path is untouched.


def _probe_ids(index, queries, qh, *, params):
    cand_ids, _ = stages.probe(index, queries, qh, params)
    return cand_ids


def _exact_dists(index, queries, cand_ids, *, metric, use_kernel):
    return index.store.gather_dist(cand_ids, queries, metric=metric,
                                   use_kernel=use_kernel)


def _survivors_stage(index, queries, cand_ids, *, params, metric):
    return stages.survivors(index.store, queries, cand_ids, params, metric)


def _survivor_ids(index, queries, cand_ids, *, params, metric):
    surv, _ = stages.survivors(index.store, queries, cand_ids, params, metric)
    return surv


def _gather_rows(index, surv_ids):
    return stages.gather_fp32(index.store, index.tail, surv_ids)


def _monolithic_build_instrumented(index, p: SearchParams):
    topo = topology_of(index)
    metric = p.metric or index.metric
    use_k = stages.resolve_use_kernel(p.use_gather_kernel)
    block = jax.block_until_ready
    hash_j = named_jit(stages.hash_queries, "hash_queries")
    probe_j = named_jit(_probe_ids, "probe", params=p)

    if has_disk_tail(index):
        surv_j = named_jit(_survivor_ids, "survivors", params=p,
                           metric=metric)

        def run(idx, queries):
            with _obs_stage(topo, "hash_queries"):
                qh = block(hash_j(idx.family, queries))
            with _obs_stage(topo, "probe"):
                cand = block(probe_j(idx, queries, qh))
            with _obs_stage(topo, "survivors"):
                surv = block(surv_j(idx, queries, cand))
            with _obs_stage(topo, "gather"):  # host memmap gather
                rows = block(jnp.asarray(tail_mod.gather_tail(idx.tail_path,
                                                              surv)))
            with _obs_stage(topo, "rerank"):
                out = block(stages.rerank_rows(rows, queries, surv, p.k,
                                               p.metric or idx.metric))
            return out

        return run

    if index.store.exact:
        dist_j = named_jit(_exact_dists, "gather", metric=metric,
                           use_kernel=use_k)
        merge_j = named_jit(stages.topk_ids, "merge", k=p.k)

        def run(idx, queries):
            with _obs_stage(topo, "hash_queries"):
                qh = block(hash_j(idx.family, queries))
            with _obs_stage(topo, "probe"):
                cand = block(probe_j(idx, queries, qh))
            with _obs_stage(topo, "gather"):  # exact store: distance gather
                dist = block(dist_j(idx, queries, cand))
            with _obs_stage(topo, "merge"):
                out = block(merge_j(dist, cand))
            return out

        return run

    surv_j = named_jit(_survivors_stage, "survivors", params=p,
                       metric=metric)
    gather_j = named_jit(_gather_rows, "gather")

    def run(idx, queries):
        with _obs_stage(topo, "hash_queries"):
            qh = block(hash_j(idx.family, queries))
        with _obs_stage(topo, "probe"):
            cand = block(probe_j(idx, queries, qh))
        with _obs_stage(topo, "survivors"):
            surv, _ = surv_j(idx, queries, cand)
            block(surv)
        with _obs_stage(topo, "gather"):
            rows = block(gather_j(idx, surv))
        with _obs_stage(topo, "rerank"):
            out = block(stages.rerank_rows(rows, queries, surv, p.k, metric))
        return out

    return run


def _segmented_resolve(index, p: SearchParams) -> SearchParams:
    # `p.source` names the *per-segment* source; rewrite it onto the
    # registered "segmented" wrapper (source="segmented", inner=<source>)
    if p.source != "segmented":
        with _suppress_width_warning():
            p = p.replace(source="segmented", inner=p.source)
    return _resolve_common(index, p)


register_topology(
    "monolithic", resolve=_monolithic_resolve, build=_monolithic_build,
    build_instrumented=_monolithic_build_instrumented,
)
# a segmented index always keeps its rerank tail resident (disk-lazy tails
# are a static-index feature), so its executable is the plain one-jit body
register_topology(
    "segmented", resolve=_segmented_resolve, build=_monolithic_build,
    build_instrumented=_monolithic_build_instrumented,
)
