"""Sharded query pipeline: shard_map over the shared exec stages, finished
by an all_gather + the shared global top-k merge.

Every shard runs the SAME staged pipeline a monolithic `LCCSIndex` runs over
its local rows -- the registered candidate source named by ``params.inner``
(``params.source`` is "sharded"), then the `repro.exec.stages` verification
over the shard's own `VectorStore` slice.  The probe/verify budget is
*apportioned*: each shard runs its source with lam_local = ceil(lam / S)
(and a ceil(W / S) window when the width is derived -- see `_local_params`),
so S shards together spend the monolithic candidate budget rather than S
times it.  The verification stages then split:

  exact stores   `stages.exact_topk` per shard (global ids reported) ->
                 all_gather (B, S*k) -> `stages.merge_topk`.  Identical to
                 the monolithic result over the union of per-shard candidates
                 (LCCS scoring and verification are pointwise per row).
  inexact stores per-shard `stages.survivors` keeps the best
                 R = min(k * rerank_mult, lam) local survivors and
                 `stages.gather_fp32` fetches their rerank rows; survivors
                 (ids, approx dists, rows) are all_gather'd,
                 `stages.cut_survivors` reproduces the monolithic stage-1
                 survivor set, and one `stages.rerank_rows` runs replicated
                 on every shard.

This module owns ONLY the shard_map plumbing and collectives; the two-stage
rerank and every top-k merge are the same functions the monolithic and
segmented paths call (DESIGN.md §2).  Global ids come from the per-shard
`gid` arrays via `stages.local_to_global`, so uneven splits are exact:
padded rows carry gid = -1 and are masked out before the merge, never
silently aliased onto real rows (the `shard_id * (n // S)` arithmetic of the
old `core.distributed` sketch was wrong whenever ``n % S != 0``).

The "sharded" candidate-source registry entry exposes candidate generation
alone (global ids, merged by LCP), and the "sharded" *topology adapter*
registered here plugs the whole pipeline into `repro.exec.compile_plan`, so
`execute`/`jit_search` serve a `ShardedLCCSIndex` through the same plan
cache as every other index.

Everything is expressed with `shard_map` so the collective schedule (one
all_gather of k or R rows per shard per query batch) is explicit and
auditable in the dry-run HLO.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.csa import CSA
from repro.core.index import LCCSIndex
from repro.core.params import SearchParams, _suppress_width_warning
from repro.core.sources import get_source, register_source
from repro.exec import execute as _execute, named_jit, register_topology
from repro.exec import stages

from .index import ShardedLCCSIndex, _row_spec


def _inner_name(params: SearchParams) -> str:
    return params.inner if params.source == "sharded" else params.source


def _local_params(params: SearchParams, shards: int) -> SearchParams:
    """Apportion the per-shard probe budget by the shard's row share.

    Each shard holds ~1/S of the rows, so its local candidate cut (and hence
    the all_gather payload of the "sharded" source) is top-ceil(lam/S), not
    top-lam: the total candidate budget across shards equals the monolithic
    lam instead of S x lam.  When the window width is derived (width=None),
    the per-shard k-LCCS window likewise shrinks to ceil(W/S), keeping the
    total probe bandwidth at 2W sorted positions per shift.  Without this the
    per-shard probe + verify cost is *constant* in S -- S shards do S x the
    monolithic work and fig13's sharded throughput regresses below 1 shard.

    Exactness guarantees survive apportioning: complete coverage lam >= n
    implies ceil(lam/S) >= ceil(n/S) >= every padded shard's row count, and
    an *explicit* width is honoured unscaled (so lam >= n plus width >= n
    still makes every shard's candidate set complete).  The floor keeps
    lam_local >= k so each shard can always fill the merge's k slots."""
    if shards <= 1:
        return params
    lam_l = max(params.k, -(-params.lam // shards))
    with _suppress_width_warning():  # derived copy: user params already warned
        width_l = (params.width if params.width is not None
                   else max(4, -(-params.resolved_width() // shards)))
        return params.replace(lam=lam_l, width=width_l)


def _local_view(family, store, h, csa, gid, tail, metric):
    """Rebuild a plain LCCSIndex over one shard's rows from the size-1
    leading-axis blocks shard_map hands the local function."""
    sq = lambda t: jax.tree.map(lambda x: x[0], t)
    view = LCCSIndex(
        family=family,
        store=sq(store),
        h=h[0],
        csa=None if csa is None else CSA(
            *(None if x is None else x[0] for x in csa)
        ),
        metric=metric,
        tail=None if tail is None else tail[0],
    )
    return view, gid[0]


def _shard_call(index: ShardedLCCSIndex, local_fn, out_specs):
    """shard_map plumbing shared by search and the "sharded" source: the
    index's pytrees go in row-partitioned over `index.axis`, the family and
    the queries replicated."""
    axis = index.axis
    rep = lambda t: jax.tree.map(lambda _: P(), t)
    shd = lambda t: jax.tree.map(lambda x: _row_spec(x, axis), t)
    return jax.shard_map(
        local_fn,
        mesh=index.mesh,
        in_specs=(
            rep(index.family),
            shd(index.store),
            _row_spec(index.h, axis),
            shd(index.csa),
            _row_spec(index.gid, axis),
            shd(index.tail),
            P(),  # queries replicated
            P(),  # query hash strings replicated
        ),
        out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Full pipeline: probe -> per-shard verify stages -> all_gather + merge stage
# ---------------------------------------------------------------------------


def _probe_local(view, gid_l, queries, qh, params, shards):
    """Per-shard probe half: the inner source under the apportioned budget,
    local ids mapped to global and padded rows masked.  Returns
    (ids_l (B, lam_l) local ids, g (B, lam_l) global ids)."""
    p_l = _local_params(params, shards)  # per-shard budget share
    ids_l, _ = get_source(_inner_name(p_l))(view, queries, qh, p_l)
    g = stages.local_to_global(ids_l, gid_l)
    return jnp.where(g >= 0, ids_l, -1), g  # mask padded rows before gathers


def _verify_local(view, gid_l, ids_l, g, queries, params, metric, shards):
    """Per-shard verify half -> this shard's pre-merge payload: an exact
    store yields its local (ids_k, d_k) top-k, an inexact one its stage-1
    (global survivor ids, approx dists, fp32 rerank rows)."""
    use_kernel = stages.resolve_use_kernel(params.use_gather_kernel)
    if view.store.exact:
        # single-stage: shard-local exact_topk (global ids reported)
        return stages.exact_topk(
            view.store, queries, ids_l, g, params.k, metric, use_kernel
        )
    # two-stage: per-shard stage-1 scan under the LOCAL budget share
    p_l = _local_params(params, shards)
    surv_l, approx = stages.survivors(view.store, queries, ids_l,
                                      p_l, metric)
    g_surv = stages.local_to_global(surv_l, gid_l)
    rows_f = stages.gather_fp32(view.store, view.tail, surv_l)  # (B, R, d)
    return g_surv, approx, rows_f


def _merge_global(parts, queries, params, metric, exact: bool):
    """Global merge half over the pooled per-shard payloads (each (B, S*...)
    along axis 1).  The merge stages keep the GLOBAL params: cut_survivors
    reproduces the monolithic min(k*rerank_mult, lam) stage-1 survivor set --
    each shard's local top-R is a superset of its members of the global
    top-R, so nothing is lost."""
    if exact:
        all_ids, all_d = parts
        return stages.merge_topk(all_d, all_ids, params.k)
    all_ids, all_a, all_rows = parts
    ids_sel, rows_sel = stages.cut_survivors(all_ids, all_a, all_rows, params)
    return stages.rerank_rows(rows_sel, queries, ids_sel, params.k, metric)


def _local_search(family, store, h, csa, gid, tail, queries, qh,
                  *, params, metric, axis, shards):
    view, gid_l = _local_view(family, store, h, csa, gid, tail, metric)
    with jax.named_scope("probe"):
        ids_l, g = _probe_local(view, gid_l, queries, qh, params, shards)
    # verify spans the per-shard scan and the global merge, as the
    # monolithic verify spans its scan and its top-k
    with jax.named_scope("verify"):
        parts = _verify_local(view, gid_l, ids_l, g, queries, params,
                              metric, shards)
        B = queries.shape[0]
        pool = lambda x: jax.lax.all_gather(x, axis, axis=1).reshape(
            (B, -1) + x.shape[2:]
        )
        return _merge_global(tuple(pool(x) for x in parts), queries, params,
                             metric, view.store.exact)


def _search_impl(index: ShardedLCCSIndex, queries: jax.Array,
                 *, params: SearchParams):
    """The traced sharded pipeline body (no guards): hash once, shard_map the
    per-shard stages, merge globally."""
    queries = jnp.asarray(queries, jnp.float32)
    with jax.named_scope("hash"):
        qh = stages.hash_queries(index.family, queries)
    metric = params.metric or index.metric
    fn = _shard_call(
        index,
        partial(_local_search, params=params, metric=metric, axis=index.axis,
                shards=index.shards),
        out_specs=(P(), P()),
    )
    return fn(index.family, index.store, index.h, index.csa, index.gid,
              index.tail, queries, qh)


def search(index: ShardedLCCSIndex, queries: jax.Array, params: SearchParams):
    """Full sharded c-k-ANNS: hash -> per-shard source -> per-shard verify ->
    all_gather + exact global top-k.  Pure function of the index pytree;
    `params` must be static under jit (compose your own, or use
    `jit_sharded_search` / `repro.exec.execute` for the plan-cached route)."""
    if not isinstance(index, ShardedLCCSIndex):
        raise TypeError(
            "repro.shard.search needs a ShardedLCCSIndex; monolithic indexes "
            "use repro.core.index.search"
        )
    return _search_impl(index, queries, params=params)


def jit_sharded_search(index, queries, params: SearchParams):
    """Compiled sharded search -- a thin wrapper over
    `repro.exec.compile_plan` (the "sharded" topology adapter below), sharing
    the process plan cache and its retrace counters."""
    return _execute(index, queries, params)


# ---------------------------------------------------------------------------
# The "sharded" topology adapter (repro.exec plan integration)
# ---------------------------------------------------------------------------


def _sharded_resolve(index, p: SearchParams) -> SearchParams:
    from repro.core.params import _suppress_width_warning

    if p.source == "segmented":
        raise ValueError(
            "source='segmented' needs a SegmentedLCCSIndex; a sharded "
            "index runs per-shard sources ('lccs', 'bruteforce', ...)"
        )
    with _suppress_width_warning():  # derived copy: user params already warned
        if p.source != "sharded":
            p = p.replace(source="sharded", inner=p.source)
        if p.use_gather_kernel is None:  # concrete bool -> plan key
            p = p.replace(use_gather_kernel=stages.resolve_use_kernel(None))
        if p.use_probe_kernel is None:
            p = p.replace(
                use_probe_kernel=stages.resolve_use_probe_kernel(None)
            )
    if p.shards is not None and p.shards != index.shards:
        raise ValueError(
            f"SearchParams(shards={p.shards}) does not match this index's "
            f"{index.shards} shards"
        )
    stages.check_store_kind(index.store, p)
    return p


def _sharded_build(index, p: SearchParams):
    return named_jit(_search_impl, "search_pipeline", params=p)


# -- instrumented (staged) variant -----------------------------------------
#
# The same arithmetic as `_sharded_build`, split at the natural collective
# boundaries so `repro_exec_stage_seconds{topology="sharded"}` times each
# stage (hash_queries / probe / verify / merge) with `block_until_ready`
# fences.  The probe and verify halves each run as their own shard_map whose
# out_specs `P(None, axis)` concatenate the per-shard (B, x) payloads into
# (B, S*x) along axis 1 -- the SAME ordering `all_gather(..., axis=1)
# .reshape(B, -1)` produces inside the fused plan -- and the verify
# shard_map's `P(None, axis)` in_specs hand each shard exactly its own block
# back, so the staged results are bit-identical to the fused ones.


def _shard_call_staged(index: ShardedLCCSIndex, local_fn, out_specs,
                       extra_in_specs):
    """`_shard_call` with trailing pre-sharded extras: the index pytrees and
    queries go in as usual, plus `extra_in_specs`-partitioned arrays (the
    probe half's pooled output fed back to the verify half)."""
    axis = index.axis
    rep = lambda t: jax.tree.map(lambda _: P(), t)
    shd = lambda t: jax.tree.map(lambda x: _row_spec(x, axis), t)
    return jax.shard_map(
        local_fn,
        mesh=index.mesh,
        in_specs=(
            rep(index.family),
            shd(index.store),
            _row_spec(index.h, axis),
            shd(index.csa),
            _row_spec(index.gid, axis),
            shd(index.tail),
            P(),  # queries replicated
        ) + tuple(extra_in_specs),
        out_specs=out_specs,
        check_vma=False,
    )


def _sharded_build_instrumented(index, p: SearchParams):
    from repro.obs.trace import stage as _obs_stage

    axis = index.axis
    metric = p.metric or index.metric
    exact = index.store.exact
    shards = index.shards
    block = jax.block_until_ready
    col = P(None, axis)  # (B, S*x) pooled along axis 1, mesh device order

    hash_j = named_jit(stages.hash_queries, "hash_queries")

    def probe_local(family, store, h, csa, gid, tail, queries, qh):
        view, gid_l = _local_view(family, store, h, csa, gid, tail, metric)
        return _probe_local(view, gid_l, queries, qh, p, shards)

    probe_j = named_jit(_shard_call_staged(
        index, probe_local, out_specs=(col, col), extra_in_specs=(P(),)
    ), "probe")

    def verify_local(family, store, h, csa, gid, tail, queries, ids_l, g):
        view, gid_l = _local_view(family, store, h, csa, gid, tail, metric)
        return _verify_local(view, gid_l, ids_l, g, queries, p, metric,
                             shards)

    verify_j = named_jit(_shard_call_staged(
        index, verify_local,
        out_specs=(col, col) if exact else (col, col, col),
        extra_in_specs=(col, col),
    ), "verify")

    merge_j = named_jit(_merge_global, "merge", params=p, metric=metric,
                        exact=exact)

    def run(idx, queries):
        with _obs_stage("sharded", "hash_queries"):
            qh = block(hash_j(idx.family, queries))
        with _obs_stage("sharded", "probe"):
            ids_all, g_all = probe_j(idx.family, idx.store, idx.h, idx.csa,
                                     idx.gid, idx.tail, queries, qh)
            block((ids_all, g_all))
        with _obs_stage("sharded", "verify"):
            parts = verify_j(idx.family, idx.store, idx.h, idx.csa, idx.gid,
                             idx.tail, queries, ids_all, g_all)
            block(parts)
        with _obs_stage("sharded", "merge"):
            out = block(merge_j(parts, queries))
        return out

    return run


register_topology("sharded", resolve=_sharded_resolve, build=_sharded_build,
                  build_instrumented=_sharded_build_instrumented)


# ---------------------------------------------------------------------------
# The "sharded" candidate source (registry integration)
# ---------------------------------------------------------------------------


@register_source("sharded")
def sharded_source(index, queries, qh, params):
    """Candidate generation over all shards: run `params.inner` per shard,
    map local ids to global via the per-shard gid arrays, and merge the
    per-shard top-lambda sets by LCP (exact -- shards hold disjoint rows).
    Returns (ids (B, lam), lcps (B, lam)) with global ids, like any source."""
    if not isinstance(index, ShardedLCCSIndex):
        raise TypeError(
            "source='sharded' needs a ShardedLCCSIndex; monolithic LCCSIndex "
            "callers should pick 'lccs'/'bruteforce'/'multiprobe-*'"
        )

    def local(family, store, h, csa, gid, tail, queries_l, qh_l):
        view, gid_l = _local_view(family, store, h, csa, gid, tail,
                                  params.metric or index.metric)
        # local budget share: the all_gather below ships (B, ceil(lam/S))
        # per shard -- the merged pool is ~lam candidates total, not S*lam
        p_l = _local_params(params, index.shards)
        ids_l, lcps = get_source(p_l.inner)(view, queries_l, qh_l, p_l)
        g = stages.local_to_global(ids_l, gid_l)
        lcps = jnp.where(g >= 0, lcps, -1)
        B = queries_l.shape[0]
        all_g = jax.lax.all_gather(g, index.axis, axis=1).reshape(B, -1)
        all_l = jax.lax.all_gather(lcps, index.axis, axis=1).reshape(B, -1)
        return stages.merge_candidates(all_g, all_l, params.lam)

    fn = _shard_call(index, local, out_specs=(P(), P()))
    return fn(index.family, index.store, index.h, index.csa, index.gid,
              index.tail, queries, qh)
