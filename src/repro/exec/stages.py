"""The staged query pipeline: pure, composable stage functions.

The paper's query algorithm is one fixed pipeline -- hash the query, probe
the CSA for the lambda-LCCS candidate set (Algorithm 2 / the §4.2 multiprobe
variants), verify candidates by true distance -- and every index topology in
this repo (monolithic `LCCSIndex`, segmented `SegmentedLCCSIndex`, sharded
`ShardedLCCSIndex`) serves exactly that pipeline.  This module is the single
home of the stage implementations; topologies differ only in how they fan
stages out and merge the results (see `repro.exec.plan` / DESIGN.md §2):

    embed/hash   hash_queries         query vectors -> (B, m) hash strings
    probe        probe                candidate source -> (B, lam) ids + LCPs
    gather       gather_fp32          candidate ids -> fp32 rows (tail or
                                      dequantized store reconstruction)
    verify       exact_topk           exact single-stage scan + nearest-k
                 survivors            stage 1 of the two-stage path: the
                                      approximate scan's best R = min(
                                      k*rerank_mult, lam) candidates
                 rerank_rows          stage 2: exact fp32 rerank of gathered
                                      rows (in-jit or host-gathered alike)
                 cut_survivors        cut a merged survivor pool back to the
                                      monolithic stage-1 budget R
                 verify               the composed per-part verification
    merge        merge_candidates     exact union of candidate sets (max-LCP
                                      dedupe + top-lambda), used by the
                                      segmented and sharded probe merges
                 merge_topk           exact union of verified result sets
                                      (global nearest-k), used by the sharded
                                      all_gather merge and every local top-k
    id algebra   local_to_global      per-segment / per-shard local row ids
                 mask_dead            -> global ids, tombstones masked

Everything here is pure JAX over store/tail/id arrays: stages trace into one
`jax.jit` when the data is resident, and the same functions are called from
host orchestration when it is not (the disk-lazy tail plan).  Exact stores
collapse verification to `exact_topk` -- bit-identical to the seed
`verify_candidates` on the reference route; quantized stores run
`survivors -> gather_fp32 -> rerank_rows` with one kernel dispatch point
(`resolve_use_kernel`) shared by the fp32 (`kernels.gather_l2`) and int8
(`kernels.gather_q`) Pallas kernels, which are opt-in: the default on every
backend, TPU included, is the XLA gather.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

# NOTE: this module is imported *during* repro.core's own package init (by
# core.verify and core.segments), so repro.core symbols (lsh.distance,
# search.dedupe_topk) are imported lazily inside the stage functions -- the
# submodules are guaranteed loaded by call time, never at import time.

ENV_GATHER_KERNEL = "REPRO_GATHER_KERNEL"
ENV_PROBE_KERNEL = "REPRO_PROBE_KERNEL"

# The canonical stage vocabulary: instrumented plans (repro.exec.plan with
# instrument=True) label `repro_exec_stage_seconds{stage=...}` and their
# trace spans (`exec.<stage>`) from exactly this set, so dashboards and the
# bench stage-breakdown report never see ad-hoc names.  Which subset appears
# depends on the plan shape: exact stores verify as gather+merge, quantized
# ones as survivors+gather+rerank, the sharded topology adds verify+merge.
STAGE_NAMES = (
    "hash_queries",  # query vectors -> hash strings
    "probe",         # candidate generation (CSA probe / source dispatch)
    "survivors",     # stage-1 approximate cut (quantized stores)
    "gather",        # row/distance gather (device or host memmap)
    "rerank",        # exact fp32 rerank of gathered rows
    "verify",        # fused per-shard verification (sharded topology)
    "merge",         # final top-k merge
    "search",        # whole-plan fallback for adapters without staging
)


# ---------------------------------------------------------------------------
# embed/hash + probe
# ---------------------------------------------------------------------------


def hash_queries(family, queries: jax.Array) -> jax.Array:
    """Hash stage: (B, d) float32 queries -> (B, m) int32 hash strings under
    the index's LSH family (one shared family per index, every topology)."""
    return family.hash(queries)


def probe(index, queries: jax.Array, qh: jax.Array, params):
    """Probe stage: dispatch to the registered candidate source named by
    `params.source`.  Returns (ids (B, lam), lcps (B, lam)), -1 padded."""
    from repro.core.sources import get_source  # lazy: sources imports stages

    return get_source(params.source)(index, queries, qh, params)


# ---------------------------------------------------------------------------
# verify stages
# ---------------------------------------------------------------------------


def resolve_use_kernel(flag: bool | None) -> bool:
    """Tri-state resolution of `SearchParams.use_gather_kernel`.

    The default is the XLA gather on every backend.  The Pallas gather
    kernels (`kernels.gather_l2`, `kernels.gather_q`) DMA one (1, d) row
    block per candidate, which the TPU lowering refuses (the last two block
    dims must divide by 8 and 128), so they run only when asked for, and
    only in interpret mode off-TPU.

    Plan building (`repro.exec.plan`) resolves None to a concrete bool
    *before* jitting, so the choice is part of the plan key.  Direct callers
    of the pure pipeline functions passing None get trace-time resolution
    instead: correct on first compile, but a later env-var flip will not
    invalidate an already-cached executable -- pass an explicit bool for
    that."""
    if flag is not None:
        return bool(flag)
    env = os.environ.get(ENV_GATHER_KERNEL)
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off")
    return False


def resolve_use_probe_kernel(flag: bool | None) -> bool:
    """Tri-state resolution of `SearchParams.use_probe_kernel` -- the probe
    stage's dispatch between the fused CSA probe (`kernels.csa_probe`) and
    the legacy `core.search` window path.  On by default on TPU backends,
    where the fused probe runs as XLA (its jnp form, `csa_probe/ref.py`):
    the Pallas `csa_probe` kernel is refused by the TPU lowering and is
    VMEM-bounded to n <= ~32k at m=64 besides.  Same contract as
    `resolve_use_kernel`: plan building pins None to a concrete bool before
    jitting so the choice keys the plan; direct callers passing None get
    trace-time resolution (a later env flip cannot invalidate a cached
    executable)."""
    if flag is not None:
        return bool(flag)
    env = os.environ.get(ENV_PROBE_KERNEL)
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off")
    return jax.default_backend() == "tpu"


def check_store_kind(store, params) -> None:
    """Enforce the `SearchParams.store` pin against the index's actual
    store.  Called host-side at plan build and again at trace time, so the
    pin holds on every route (including the split disk-tail pipeline)."""
    if params.store is not None and params.store != store.kind:
        raise ValueError(
            f"SearchParams(store={params.store!r}) does not match the index's "
            f"store {store.kind!r}; rebuild the index or drop the param"
        )


def topk_ids(dist: jax.Array, ids: jax.Array, k: int):
    """Nearest-k (ids, dists) with -1/inf padding -- THE top-k merge.  Every
    result-set merge in the repo is this function: a local per-shard top-k,
    the sharded post-all_gather global merge, and the final monolithic cut
    are all instances over different (dist, ids) pools."""
    kk = min(k, ids.shape[1])
    neg, idx = jax.lax.top_k(-dist, kk)
    out_ids = jnp.take_along_axis(ids, idx, axis=1)
    out_d = -neg
    out_ids = jnp.where(jnp.isfinite(out_d), out_ids, -1)
    if kk < k:
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
        out_d = jnp.pad(out_d, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
    return out_ids, out_d


merge_topk = topk_ids  # result-set merge: same operation, reads as a stage


def exact_topk(store, queries, cand_ids, report_ids, k: int, metric: str,
               use_kernel: bool):
    """Single-stage exact verification: scan `cand_ids` against `store` and
    return the nearest k of `report_ids` (pass `report_ids=cand_ids` for a
    monolithic index; segment/shard callers pass the global-id view so the
    merge works on one id space)."""
    dist = store.gather_dist(cand_ids, queries, metric=metric,
                             use_kernel=use_kernel)
    return topk_ids(dist, report_ids, k)


def survivor_budget(params, pool: int) -> int:
    """R, the stage-1 over-fetch budget: min(k * rerank_mult, lam, pool)."""
    return min(max(params.k * params.rerank_mult, params.k), params.lam, pool)


def survivors(store, queries, cand_ids, params, metric: str):
    """Stage 1 of the two-stage path: approximate scan + over-fetch.
    Returns (ids (B, R), approx dists (B, R)) with R = `survivor_budget`."""
    check_store_kind(store, params)
    use_kernel = resolve_use_kernel(params.use_gather_kernel)
    dist = store.gather_dist(cand_ids, queries, metric=metric,
                             use_kernel=use_kernel)
    r = survivor_budget(params, cand_ids.shape[1])
    neg, idx = jax.lax.top_k(-dist, r)
    return jnp.take_along_axis(cand_ids, idx, axis=1), -neg


def gather_fp32(store, tail, ids: jax.Array) -> jax.Array:
    """Gather stage: (B, R) candidate ids -> (B, R, d) fp32 rows for the
    exact rerank -- the resident fp32 tail when one exists, else the store's
    (possibly dequantized) reconstruction.  Disk-lazy tails are gathered on
    the host by the plan instead (`repro.store.tail.gather_tail`)."""
    if tail is not None:
        return tail[jnp.maximum(ids, 0)]
    return store.gather(ids)


@partial(jax.jit, static_argnames=("k", "metric"))
def rerank_rows(
    rows: jax.Array,  # (B, R, d) float32 candidate rows (pre-gathered)
    queries: jax.Array,  # (B, d)
    cand_ids: jax.Array,  # (B, R) int32, -1 padded
    k: int,
    metric: str,
):
    """Stage 2: exact distance + top-k over already-gathered rows.  Shared by
    the in-jit path (tail rows indexed inside the trace), the sharded merged
    rerank, and the disk path (rows memmap-gathered on host)."""
    from repro.core.lsh import distance

    dist = distance(rows, queries[:, None, :], metric)
    dist = jnp.where(cand_ids >= 0, dist, jnp.inf)
    return topk_ids(dist, cand_ids, k)


def cut_survivors(ids: jax.Array, approx: jax.Array, rows: jax.Array, params):
    """Cut a merged survivor pool (e.g. the sharded all_gather of per-shard
    survivor sets) back to the global stage-1 budget R by approximate
    distance.  Each part's local top-R is a superset of its members of the
    global top-R, so the cut reproduces the monolithic survivor set exactly.
    Returns (ids (B, R), rows (B, R, d))."""
    r = survivor_budget(params, ids.shape[1])
    _, sel = jax.lax.top_k(-approx, r)
    ids_sel = jnp.take_along_axis(ids, sel, axis=1)
    rows_sel = jnp.take_along_axis(rows, sel[..., None], axis=1)
    return ids_sel, rows_sel


def verify(store, tail, queries, cand_ids, params, metric: str):
    """The composed verification stage over one part's rows: single-stage
    `exact_topk` for exact stores, `survivors -> gather_fp32 -> rerank_rows`
    for quantized ones.  Pure JAX -- traces into one jit.

    tail=None on an inexact store means rerank against the store's own
    dequantized rows: ranking equals stage 1, but callers still get distances
    in the dequantized geometry (used when the fp32 tail is disk-resident and
    the plan orchestrates the exact rerank itself, and by approx-only setups
    that accept quantized distances)."""
    check_store_kind(store, params)
    if store.exact:
        use_kernel = resolve_use_kernel(params.use_gather_kernel)
        return exact_topk(store, queries, cand_ids, cand_ids, params.k,
                          metric, use_kernel)
    surv_ids, _ = survivors(store, queries, cand_ids, params, metric)
    rows = gather_fp32(store, tail, surv_ids)
    return rerank_rows(rows, queries, surv_ids, params.k, metric)


# ---------------------------------------------------------------------------
# merge stages + id algebra (segmented / sharded fan-out)
# ---------------------------------------------------------------------------


def merge_candidates(ids: jax.Array, lcps: jax.Array, lam: int):
    """Candidate-set merge: max-LCP dedupe per id + global top-lambda over a
    concatenated (B, sum_parts) pool.  Exact because LCCS scoring is
    pointwise per object -- the property both the segmented and the sharded
    fan-outs rely on (DESIGN.md §2)."""
    from repro.core.search import dedupe_topk

    return jax.vmap(lambda i, l: dedupe_topk(i, l, lam))(ids, lcps)


def pad_candidates(ids: jax.Array, vals: jax.Array, lam: int):
    """(B, j) -> (B, lam), -1 padded, for j <= lam (part-local top-k sets
    narrower than the merge width)."""
    j = ids.shape[1]
    if j < lam:
        ids = jnp.pad(ids, ((0, 0), (0, lam - j)), constant_values=-1)
        vals = jnp.pad(vals, ((0, 0), (0, lam - j)), constant_values=-1)
    return ids, vals


def local_to_global(local_ids: jax.Array, gid: jax.Array) -> jax.Array:
    """Map part-local candidate ids through a part's (rows,) global-id array;
    -1 padding (and padded rows, gid -1) stays -1.  One function serves both
    the segmented gid-offset and the sharded row-offset mapping."""
    rows = gid.shape[0]
    return jnp.where(
        local_ids >= 0, gid[jnp.clip(local_ids, 0, rows - 1)], -1
    )


def mask_dead(gids: jax.Array, vals: jax.Array, alive: jax.Array):
    """Tombstone mask: candidates whose global id is dead (or padding) are
    dropped from the merge (id -> -1, score -> -1)."""
    live = (gids >= 0) & alive[jnp.maximum(gids, 0)]
    return jnp.where(live, gids, -1), jnp.where(live, vals, -1)
