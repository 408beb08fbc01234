"""LCCSIndex -- the public API of the paper's scheme, jit-first.

Indexing phase (§4.1): hash every object with m i.i.d. LSH functions into a
hash string; build the CSA.  Query phase: a *candidate source* proposes
lambda candidates (lambda-LCCS search, multiprobe variants, or brute force),
true distances are verified, and the nearest k are returned.

The search API has three pieces (see also `repro.core.params` and
`repro.core.sources`):

  * `SearchParams` -- a frozen, hashable dataclass holding every query-phase
    knob (k, lam, source, mode, width, probes, metric, ...).  It is the single
    static argument threaded through core, serve, launch, benchmarks, and
    examples.
  * `LCCSIndex` is a registered JAX pytree (as are `CSA` and all LSH
    families): an index is a first-class JAX value that can be passed through
    `jax.jit`, `jax.device_put`, and sharding APIs.  `jit_search` compiles the
    entire hash -> candidates -> verify path once per (params, shapes).
  * Candidate sources are selected by name from a registry
    ("bruteforce" | "lccs" | "multiprobe-full" | "multiprobe-skip"); new
    backends plug in via `repro.core.sources.register_source` without
    touching this class.

Canonical usage::

    from repro.core import LCCSIndex, SearchParams

    index = LCCSIndex.build(X, m=64, family="euclidean", w=4.0)
    params = SearchParams(k=10, lam=200, source="multiprobe-skip", probes=17)
    ids, dists = index.search(Q, params)          # jitted end to end

    # or functionally, e.g. to control jit/donation/sharding yourself:
    from repro.core.index import search, jit_search
    ids, dists = jit_search(index, Q, params)

Deprecation note: the seed-era kwargs API ``index.query(Q, k=, lam=, width=,
mode=, probes=)`` and ``index.candidates(Q, lam, ...)`` still work as thin
shims that map the kwargs onto a `SearchParams` via
`SearchParams.from_legacy` (mode="bruteforce" becomes source="bruteforce";
probes>1 selects a multiprobe source).  They emit `DeprecationWarning` and
will be removed once external callers migrate.

Mutable corpora: `LCCSIndex` is build-once (a corpus change means a full
O(nm log n) rebuild).  If the corpus takes online inserts/deletes, use
`repro.core.segments.SegmentedLCCSIndex` -- same SearchParams / jit_search
pipeline over an LSM-style stack of CSA segments plus a delta buffer.

Corpus storage is pluggable (`repro.store`): ``build(..., store="int8")``
quantizes the vectors on ingest (symmetric per-row int8, ~4x smaller) and
search switches to the two-stage verify path -- approximate scan over the
quantized store, exact fp32 rerank of the best ``k * rerank_mult`` survivors
against the tail (in-memory by default; pass ``tail_path=`` to keep it on
disk and drop resident fp32 entirely).  ``store="bf16"`` halves memory with
near-fp32 accuracy; ``store="fp32"`` is the seed layout and single-stage.

Execution: every search route here is a thin wrapper over the unified
query-execution layer (`repro.exec`, DESIGN.md §2) -- one staged
hash -> probe -> gather -> verify -> merge plan per (SearchParams, index
structure, query shape), compiled once and cached explicitly
(`repro.exec.plan_cache`).  The pure function `search` below remains the
traced monolithic/segmented pipeline body for callers composing their own
transforms; `jit_search` and the `search` methods go through the plan cache.
"""
from __future__ import annotations

import pickle
import warnings
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import ReproDeprecationWarning
from repro.store import make_store
from repro.store import stores as store_mod
from repro.store import tail as tail_mod
from repro.store.stores import concat_stores
from repro.exec import execute as _execute, stages as exec_stages

from . import lsh as lsh_mod
from .csa import CSA, build_csa, circular_ranks, csa_from_chunk_ranks
from .params import SearchParams


def iter_row_blocks(data, chunk_rows: int):
    """Slice `data` into (<=chunk_rows, d) row blocks without materialising
    the whole array: plain `__getitem__` slicing, so an `np.memmap` (or any
    lazily-indexed source) is read one block at a time."""
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    n = data.shape[0]
    for lo in range(0, n, chunk_rows):
        yield data[lo : min(lo + chunk_rows, n)]


def _reblock(chunks, chunk_rows: int):
    """Re-block a chunk stream to exactly `chunk_rows` rows per yielded
    block (the last may be short).  Buffers at most one outgoing block plus
    one incoming chunk -- still O(chunk) memory."""
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    buf: list[np.ndarray] = []
    fill = 0
    for chunk in chunks:
        chunk = np.asarray(chunk)
        lo, n = 0, chunk.shape[0]
        while lo < n:
            take = min(chunk_rows - fill, n - lo)
            buf.append(chunk[lo : lo + take])
            fill += take
            lo += take
            if fill == chunk_rows:
                yield buf[0] if len(buf) == 1 else np.concatenate(buf)
                buf, fill = [], 0
    if fill:
        yield buf[0] if len(buf) == 1 else np.concatenate(buf)


@partial(jax.jit, static_argnames=("k", "metric"))
def verify_candidates(
    data: jax.Array,  # (n, d)
    queries: jax.Array,  # (B, d)
    cand_ids: jax.Array,  # (B, lam) int32, -1 padded
    k: int,
    metric: str,
):
    """Compute true distances for candidates and return the nearest k
    (seed-era entry point; the gather + rerank stages live in
    `repro.exec.stages`).  Returns (ids (B, k), dists (B, k)); missing slots
    are id=-1, dist=inf."""
    rows = data[jnp.maximum(cand_ids, 0)]  # (B, lam, d)
    return exec_stages.rerank_rows(rows, queries, cand_ids, k, metric)


@dataclass
class LCCSIndex:
    """Static (build-once) LCCS-LSH index: hash strings + CSA snapshot.

    Any corpus change requires a full rebuild; for online insert/delete use
    `repro.core.segments.SegmentedLCCSIndex`, which serves the same
    SearchParams/jit_search pipeline over CSA segments plus a delta buffer.

    Vectors live in a pluggable `repro.store.VectorStore` (`store` field);
    inexact (quantized) stores pair with an fp32 `tail` for the exact rerank
    stage -- a pytree leaf when in memory, or `tail_path` when disk-lazy.
    """

    family: Any  # LSH family (lsh.py) -- itself a pytree
    store: Any  # repro.store.VectorStore holding the (n, d) corpus vectors
    h: jax.Array  # (n, m) int32 hash strings
    csa: CSA | None  # None for bruteforce-only indexes
    metric: str
    tail: jax.Array | None = None  # (n, d) fp32 rerank rows (inexact stores)
    tail_path: str | None = field(default=None)  # disk-lazy rerank target

    # topology marker consumed by the repro.exec plan dispatch
    topology = "monolithic"

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(
        data: jax.Array | np.ndarray,
        *,
        m: int = 64,
        family: str = "euclidean",
        seed: int = 0,
        build_csa_structure: bool = True,
        store: str = "fp32",
        tail_path: str | Path | None = None,
        chunk_rows: int | None = None,
        **family_kw,
    ) -> "LCCSIndex":
        """Hash + CSA build over `data`, stored as the named vector store.

        store="fp32" (default) keeps exact rows -- the seed behaviour.
        Quantized stores ("bf16", "int8") verify in two stages; their fp32
        rerank tail is held in memory unless `tail_path` is given, in which
        case it is written to disk as .npy and gathered lazily per batch
        (use `index.search`; a disk tail cannot live inside one jit).

        `chunk_rows` switches to the out-of-core path (`build_streaming`
        over row slices of `data`): rows are hashed + quantized one block at
        a time and the CSA is merged from per-chunk sorted orders, so a
        quantized store never holds the fp32 rows twice -- peak build memory
        is O(chunk_rows) fp32 + O(n) quantized (+ the fp32 tail on disk when
        `tail_path` is set).  The result is bit-identical to the monolithic
        build for every chunk size."""
        if chunk_rows is not None:
            return LCCSIndex.build_streaming(
                iter_row_blocks(data, chunk_rows),
                m=m, family=family, seed=seed,
                build_csa_structure=build_csa_structure,
                store=store, tail_path=tail_path, **family_kw,
            )
        data = jnp.asarray(data, dtype=jnp.float32)
        n, d = data.shape
        fam = lsh_mod.make_family(family, jax.random.key(seed), d, m, **family_kw)
        h = lsh_mod.hash_rows(fam, data)
        csa = build_csa(h) if build_csa_structure else None
        vstore = make_store(store, data)
        tail = None
        tail_p = None
        if not vstore.exact:
            if tail_path is not None:
                tail_p = tail_mod.write_tail(tail_path, data)
            else:
                tail = data
        return LCCSIndex(family=fam, store=vstore, h=h, csa=csa,
                         metric=fam.metric, tail=tail, tail_path=tail_p)

    @staticmethod
    def build_streaming(
        chunks,
        *,
        m: int = 64,
        family: str = "euclidean",
        seed: int = 0,
        build_csa_structure: bool = True,
        store: str = "fp32",
        tail_path: str | Path | None = None,
        chunk_rows: int | None = None,
        **family_kw,
    ) -> "LCCSIndex":
        """Out-of-core `build`: consume an iterator of (c_i, d) row blocks.

        Per block: hash on device, quantize into a per-chunk store (per-row
        quantization makes the chunk-wise quantize bit-identical to the
        monolithic one), stream the fp32 rows to the disk tail (`tail_path`)
        when the store is inexact, and run `circular_ranks` on the chunk
        alone -- the only device transients are O(chunk, m).  The per-chunk
        sorted orders are then merged into the global CSA
        (`csa_from_chunk_ranks`, DESIGN.md §10), bit-identical to
        `build(concat(chunks))` for every chunking of the same rows.

        `chunk_rows` re-blocks the incoming stream to that exact block size
        (the producer's chunking then doesn't matter); by default each
        yielded chunk is one CSA chunk.  Memory: O(chunk) fp32 + O(n)
        quantized + the (n, m) hash/rank tables -- the full fp32 corpus is
        never resident unless the store needs an in-memory tail (inexact
        store with `tail_path=None`) or *is* the fp32 store."""
        if chunk_rows is not None:
            chunks = _reblock(chunks, chunk_rows)
        fam = None
        writer: tail_mod.TailWriter | None = None
        h_parts: list[np.ndarray] = []
        sizes: list[int] = []
        ranks: list[np.ndarray] = []
        store_parts: list[Any] = []
        tail_parts: list[jax.Array] = []
        for chunk in chunks:
            rows = jnp.asarray(chunk, dtype=jnp.float32)
            if rows.ndim != 2 or rows.shape[0] == 0:
                raise ValueError(f"chunks must be non-empty (c, d) blocks, "
                                 f"got shape {rows.shape}")
            if fam is None:
                fam = lsh_mod.make_family(
                    family, jax.random.key(seed), rows.shape[1], m, **family_kw
                )
            hc = lsh_mod.hash_rows(fam, rows)
            h_parts.append(np.asarray(hc, np.int32))
            sizes.append(rows.shape[0])
            if build_csa_structure:
                ranks.append(np.asarray(circular_ranks(hc), np.int32))
            part = make_store(store, rows)
            store_parts.append(part)
            if not part.exact:
                if tail_path is not None:
                    if writer is None:
                        writer = tail_mod.TailWriter(tail_path, rows.shape[1])
                    writer.append(np.asarray(rows))
                else:
                    tail_parts.append(rows)
            del rows, hc
        if fam is None:
            raise ValueError("build_streaming needs at least one chunk")
        vstore = concat_stores(store_parts)
        del store_parts
        h_host = np.concatenate(h_parts) if len(h_parts) > 1 else h_parts[0]
        del h_parts
        csa = None
        if build_csa_structure:
            csa = csa_from_chunk_ranks(h_host, sizes, ranks)
            del ranks
        h = jnp.asarray(h_host)
        del h_host
        tail = None
        tail_p = writer.finalize() if writer is not None else None
        if tail_parts:
            tail = (jnp.concatenate(tail_parts) if len(tail_parts) > 1
                    else tail_parts[0])
        return LCCSIndex(family=fam, store=vstore, h=h, csa=csa,
                         metric=fam.metric, tail=tail, tail_path=tail_p)

    @property
    def data(self) -> jax.Array:
        """(n, d) float32 corpus view: the exact tail when resident, else the
        store's (possibly dequantized) reconstruction."""
        return self.tail if self.tail is not None else self.store.dense()

    @property
    def n(self) -> int:
        return self.store.n

    @property
    def m(self) -> int:
        return self.h.shape[1]

    def index_bytes(self) -> int:
        """CSA + hash strings footprint (paper's 'index size')."""
        tot = self.h.size * 4
        if self.csa is not None:
            tot += self.csa.I.size * 4 + self.csa.P.size * 4 + self.csa.Hd.size * 4
            if self.csa.L is not None:
                tot += self.csa.L.size * 4
        return tot

    def store_bytes(self) -> int:
        """Resident vector bytes: the store itself + any in-memory fp32 tail
        (a disk-lazy tail costs 0 resident bytes)."""
        tot = self.store.nbytes()
        if self.tail is not None:
            tot += self.tail.size * 4
        return tot

    def total_bytes(self) -> int:
        """Full serving footprint: search structure + resident vectors."""
        return self.index_bytes() + self.store_bytes()

    # -- search (canonical API) ---------------------------------------------

    def search(self, queries, params: SearchParams | None = None):
        """c-k-ANNS: candidate generation + true-distance verification,
        jit-compiled end to end via the plan cache (`repro.exec`).  Returns
        (ids (B, k), dists (B, k)).

        With a disk-lazy tail (built with `tail_path=`) the compiled plan
        splits: jitted stage 1 (hash -> candidates -> approximate scan ->
        survivors), host memmap gather of the survivors' fp32 rows, jitted
        exact rerank."""
        return _execute(self, queries, params)

    # -- multi-device partitioning ------------------------------------------

    def shard(self, mesh, *, axis: str = "data"):
        """Partition this index's rows over `mesh`'s `axis`: one CSA + one
        vector-store slice per shard under the shared family.  Returns a
        `repro.shard.ShardedLCCSIndex` serving the same SearchParams pipeline
        via shard_map + exact global top-k merge (uneven row counts are
        padded and masked, never mis-addressed)."""
        from repro.shard import shard_index

        return shard_index(self, mesh, axis=axis)

    # -- legacy kwargs shims (deprecated) -----------------------------------

    def query(self, queries, k: int = 10, lam: int = 100, **kw):
        """Deprecated: use `search(queries, SearchParams(...))`."""
        warnings.warn(
            "LCCSIndex.query(k=, lam=, ...) is deprecated; use "
            "LCCSIndex.search(queries, SearchParams(...))",
            ReproDeprecationWarning,
            stacklevel=2,
        )
        return self.search(queries, SearchParams.from_legacy(k=k, lam=lam, **kw))

    def candidates(self, queries, lam: int, **kw):
        """Deprecated: use `repro.core.index.candidates(index, queries,
        SearchParams(...))`.  Returns (ids, lcps): (B, lam) each."""
        warnings.warn(
            "LCCSIndex.candidates(lam, ...) is deprecated; use "
            "repro.core.index.candidates(index, queries, SearchParams(...))",
            ReproDeprecationWarning,
            stacklevel=2,
        )
        params = SearchParams.from_legacy(lam=lam, **kw)
        return candidates(self, jnp.asarray(queries, dtype=jnp.float32), params)

    # -- persistence ---------------------------------------------------------

    def save(self, path: str | Path) -> None:
        import dataclasses

        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        fam_fields = {
            k: (np.asarray(v) if isinstance(v, jax.Array) else v)
            for k, v in dataclasses.asdict(self.family).items()
        }
        store_fields = {
            f.name: np.asarray(getattr(self.store, f.name))
            for f in dataclasses.fields(self.store)
        }
        # a disk-lazy tail is embedded so the pickle is self-contained: the
        # .npy may not exist wherever (or whenever) the index is loaded
        tail_arr = None if self.tail is None else np.asarray(self.tail)
        if tail_arr is None and self.tail_path:
            tail_arr = np.load(self.tail_path)
        blob = {
            "family_cls": type(self.family).__name__,
            "family_fields": fam_fields,
            "store_kind": self.store.kind,
            "store_fields": store_fields,
            "tail": tail_arr,
            "tail_in_memory": self.tail is not None,
            "tail_path": self.tail_path,
            "h": np.asarray(self.h),
            "csa": None if self.csa is None else [
                None if x is None else np.asarray(x) for x in self.csa
            ],
            "metric": self.metric,
        }
        tmp = path.with_suffix(".tmp")
        with open(tmp, "wb") as f:
            pickle.dump(blob, f)
        tmp.rename(path)  # atomic

    @staticmethod
    def load(path: str | Path) -> "LCCSIndex":
        from repro.store import get_store_cls

        with open(path, "rb") as f:
            blob = pickle.load(f)
        cls = getattr(lsh_mod, blob["family_cls"])
        fields = {
            k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
            for k, v in blob["family_fields"].items()
        }
        fam = cls(**fields)
        csa = None if blob["csa"] is None else CSA(
            *[None if x is None else jnp.asarray(x) for x in blob["csa"]]
        )
        if "store_kind" in blob:
            store_cls = get_store_cls(blob["store_kind"])
            vstore = store_cls(**{k: jnp.asarray(v)
                                  for k, v in blob["store_fields"].items()})
            tail_path = blob["tail_path"]
            if blob["tail"] is not None and not blob.get("tail_in_memory", True):
                # disk-lazy index: the embedded tail is the truth -- always
                # re-materialise it (a pre-existing file at the same path may
                # belong to a different index and would poison the rerank)
                tail_path = tail_mod.write_tail(tail_path, blob["tail"])
                tail = None
            else:
                tail = None if blob["tail"] is None else jnp.asarray(blob["tail"])
        else:  # pre-store pickles: raw fp32 "data" array
            vstore = store_mod.Fp32Store.from_dense(blob["data"])
            tail, tail_path = None, None
        return LCCSIndex(
            family=fam,
            store=vstore,
            h=jnp.asarray(blob["h"]),
            csa=csa,
            metric=blob["metric"],
            tail=tail,
            tail_path=tail_path,
        )


# An index is a first-class JAX value: arrays (and the family/store/CSA
# subtrees) are leaves; the metric string and disk-tail path are static aux.
jax.tree_util.register_dataclass(
    LCCSIndex,
    data_fields=["family", "store", "h", "csa", "tail"],
    meta_fields=["metric", "tail_path"],
)


# ---------------------------------------------------------------------------
# Functional search API (the jit boundary)
# ---------------------------------------------------------------------------


def candidates(index: LCCSIndex, queries: jax.Array, params: SearchParams):
    """Candidate generation only: the hash + probe stages (dispatch to the
    registered source).  Returns (ids, lcps): (B, lam) each, -1 padded."""
    if getattr(index, "sharded", False) and params.source != "sharded":
        raise TypeError(
            f"a ShardedLCCSIndex holds per-shard CSAs; source="
            f"{params.source!r} would read them as one flat index -- use "
            f"SearchParams(source='sharded', inner={params.source!r})"
        )
    queries = jnp.asarray(queries, dtype=jnp.float32)
    qh = exec_stages.hash_queries(index.family, queries)
    return exec_stages.probe(index, queries, qh, params)


def search(index: LCCSIndex, queries: jax.Array, params: SearchParams):
    """Full c-k-ANNS pipeline: hash -> probe -> gather -> verify, the staged
    body from `repro.exec.topology.search_pipeline`.  Pure function of a
    pytree index; `params` must be static under jit -- compose it with your
    own `jax.jit`/`vmap`/sharding, or call `jit_search` for the plan-cached
    route.

    Verification runs against the index's vector store: single-stage for
    exact stores, approximate-scan + fp32 rerank for quantized ones (the
    stages live in `repro.exec.stages`).  A disk-lazy tail cannot be traced
    -- use `index.search` / `jit_search`, whose compiled plan orchestrates
    the split pipeline on the host."""
    from repro.exec.topology import search_pipeline

    if getattr(index, "sharded", False):
        raise TypeError(
            "a ShardedLCCSIndex verifies per shard before the global merge; "
            "call index.search(queries, params) or repro.shard.search -- "
            "this monolithic pipeline would mis-gather its stacked store"
        )
    if not index.store.exact and index.tail is None and index.tail_path:
        raise ValueError(
            "this index's fp32 rerank tail is disk-lazy (tail_path="
            f"{index.tail_path!r}); a traced pipeline cannot gather from "
            "disk -- call index.search(queries, params) (or jit_search, "
            "whose plan splits the pipeline) instead"
        )
    queries = jnp.asarray(queries, dtype=jnp.float32)
    return search_pipeline(index, queries, params)


def jit_search(index, queries, params: SearchParams):
    """Compiled search -- a thin wrapper over the unified execution layer
    (`repro.exec.compile_plan`): resolves `params` for the index's topology
    (monolithic, segmented, or sharded -- all are accepted), fetches or
    builds the staged plan, and runs it.  Compiles once per (params, index
    structure, query shape); `repro.exec.plan_cache().stats()` audits it."""
    return _execute(index, queries, params)


jit_candidates = jax.jit(candidates, static_argnames="params")
