"""Segmented dynamic LCCS index: online insert/delete over an LSM-style
segment stack (beyond-paper; the paper's indexing phase §4.1 is build-once).

Why this shape: LCCS candidate scoring is pointwise per object, so per-segment
top-lambda candidate sets merge *exactly* (the same property `repro.shard`
exploits across device shards).  That makes a mutable corpus an LSM problem,
not an algorithm problem:

  * a small append-only *delta buffer* holds the newest hash strings and is
    scored brute-force with `circ_run_lengths` (exact LCCS lengths; the dense
    sweep beats pointer-chasing at buffer scale),
  * a stack of immutable CSA *segments* (each built with the existing
    `build_csa`) answers lambda-LCCS searches via any registered candidate
    source, sharing ONE LSH family so hash strings are comparable everywhere,
  * a *tombstone* mask over global ids makes `delete` an O(batch) bit-flip;
    dead rows are filtered at candidate time, and their hash strings are
    physically dropped at the next compaction (the vector *store* is
    global-id addressed, so its rows are only reclaimed by `vacuum()`,
    which renumbers ids),
  * `compact()` is a size-tiered merge (LSM level merge): the buffer plus
    every segment smaller than the running merge total is rebuilt into one
    new CSA segment -- O(n_merged * m log n_merged), amortised, instead of a
    full O(nm log n) rebuild per batch.

Jit story: `SegmentedLCCSIndex` is a registered pytree and the `"segmented"`
candidate source is pure JAX, so `jit_search(index, Q, params)` compiles the
whole multi-segment pipeline as one computation.  Segment sizes and the
buffer capacity are padded to a power-of-two schedule, so the jit cache sees
a handful of shapes: inserts and deletes mutate leaves (cache hit), only a
capacity growth or a compaction changes the treedef (retrace).

Usage::

    from repro.core import SegmentedLCCSIndex, SearchParams

    index = SegmentedLCCSIndex.create(d=128, m=64, family="euclidean", w=4.0)
    ids = index.insert(X0)                  # global ids, O(batch)
    index.delete(ids[:10])                  # tombstones, O(batch)
    index.compact()                         # size-tiered merge -> CSA segment
    out_ids, dists = index.search(Q, SearchParams(k=10, lam=200))

`params.source` names the *per-segment* source ("lccs", "bruteforce",
"multiprobe-*"); `search` rewrites it to the registered "segmented" source
with `inner=<source>`.  Static corpora should keep using `LCCSIndex`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.exec import execute as _execute, stages as exec_stages
from repro.store import make_store

from . import lsh as lsh_mod
from .bruteforce import circ_run_lengths, top_lengths
from .csa import CSA, build_csa, build_csa_chunked
from .index import LCCSIndex, _reblock
from .params import SearchParams
from .sources import get_source, register_source

_PAD_HASH = np.iinfo(np.int32).max  # sentinel hash value for padded rows
_MIN_CAP = 8


def _pow2_at_least(x: int) -> int:
    return max(_MIN_CAP, 1 << max(0, int(x) - 1).bit_length())


@dataclass
class Segment:
    """One immutable CSA segment.  Rows are padded to a power-of-two size
    with sentinel hash strings (gid = -1); padded rows sort past every real
    string and are masked out of the merged candidate set by gid."""

    h: jax.Array  # (cap_i, m) int32, sentinel-padded
    csa: CSA
    gid: jax.Array  # (cap_i,) int32 global ids, -1 on padded rows

    @property
    def cap(self) -> int:
        return self.h.shape[0]

    @staticmethod
    def build(h_rows: np.ndarray, gids: np.ndarray,
              *, chunk_rows: int | None = None) -> "Segment":
        """Pad + CSA-build.  `chunk_rows` routes the CSA through the
        out-of-core chunked merge (`build_csa_chunked`, bit-identical to the
        monolithic build -- the sentinel pad rows are just maximal strings),
        so bulk ingest never traces an (n, m) rank construction."""
        n, m = h_rows.shape
        cap = _pow2_at_least(n)
        h = np.full((cap, m), _PAD_HASH, np.int32)
        h[:n] = h_rows
        g = np.full((cap,), -1, np.int32)
        g[:n] = gids
        if chunk_rows is not None:
            csa = build_csa_chunked(h, chunk_rows=chunk_rows)
            return Segment(h=jnp.asarray(h), csa=csa, gid=jnp.asarray(g))
        hj = jnp.asarray(h)
        return Segment(h=hj, csa=build_csa(hj), gid=jnp.asarray(g))


jax.tree_util.register_dataclass(
    Segment, data_fields=["h", "csa", "gid"], meta_fields=[]
)


@dataclass
class SegmentedLCCSIndex:
    """Dynamic LCCS-LSH index: CSA segments + delta buffer + tombstones.

    Pytree fields (traced under jit):
      family    shared LSH family (itself a pytree)
      store     `repro.store.VectorStore` over all vectors ever inserted,
                indexed by global id (quantized stores quantize on ingest)
      tail      (cap_n, d) fp32 rerank rows when the store is inexact; None
                for fp32 stores (the dynamic index keeps its tail in memory
                -- disk-lazy tails are a static-index feature)
      alive     (cap_n,) bool tombstone mask (False = deleted or unallocated)
      segments  tuple of immutable `Segment`s
      buf_h     (cap_b, m) delta-buffer hash strings, sentinel-padded
      buf_gid   (cap_b,) delta-buffer global ids, -1 on free slots
      n_alloc   () int32: number of allocated global ids
      buf_fill  () int32: used delta-buffer slots

    The two scalar counters are pytree leaves (not host attributes) so a
    flatten/unflatten round trip -- `jax.device_put`, sharding -- yields an
    index that is still safe to mutate.
    """

    family: Any
    store: Any  # repro.store.VectorStore, global-id addressed
    alive: jax.Array
    segments: tuple[Segment, ...]
    buf_h: jax.Array
    buf_gid: jax.Array
    n_alloc: jax.Array
    buf_fill: jax.Array
    metric: str
    tail: jax.Array | None = None

    # a disk-lazy tail is a static-index feature; the attribute exists so the
    # shared verify stage treats both index classes alike
    tail_path = None
    # topology marker consumed by the repro.exec plan dispatch
    topology = "segmented"

    # -- construction -------------------------------------------------------

    @staticmethod
    def create(
        d: int,
        *,
        m: int = 64,
        family: str = "euclidean",
        seed: int = 0,
        store: str = "fp32",
        **family_kw,
    ) -> "SegmentedLCCSIndex":
        """An empty dynamic index over R^d (same family construction --
        and therefore the same hash functions -- as `LCCSIndex.build`).
        `store` picks the vector layout; quantized stores ("bf16"/"int8")
        quantize each inserted batch on ingest and keep an in-memory fp32
        tail for the exact rerank stage."""
        fam = lsh_mod.make_family(family, jax.random.key(seed), d, m, **family_kw)
        vstore = make_store(store, jnp.zeros((_MIN_CAP, d), jnp.float32))
        return SegmentedLCCSIndex(
            family=fam,
            store=vstore,
            alive=jnp.zeros((_MIN_CAP,), bool),
            segments=(),
            buf_h=jnp.full((_MIN_CAP, m), _PAD_HASH, jnp.int32),
            buf_gid=jnp.full((_MIN_CAP,), -1, jnp.int32),
            n_alloc=jnp.int32(0),
            buf_fill=jnp.int32(0),
            metric=fam.metric,
            tail=None if vstore.exact else jnp.zeros((_MIN_CAP, d), jnp.float32),
        )

    @staticmethod
    def build(
        data,
        *,
        m: int = 64,
        family: str = "euclidean",
        seed: int = 0,
        compact: bool = True,
        store: str = "fp32",
        **family_kw,
    ) -> "SegmentedLCCSIndex":
        """Bulk-load: create + insert; `compact=True` immediately rolls the
        buffer into one CSA segment (the static-index layout)."""
        data = np.asarray(data, np.float32)
        idx = SegmentedLCCSIndex.create(
            data.shape[1], m=m, family=family, seed=seed, store=store,
            **family_kw
        )
        idx.insert(data)
        if compact:
            idx.compact(full=True)
        return idx

    # -- introspection ------------------------------------------------------

    @property
    def data(self) -> jax.Array:
        """(cap_n, d) fp32 view of the vector store (exact tail when the
        store is quantized)."""
        return self.tail if self.tail is not None else self.store.dense()

    @property
    def d(self) -> int:
        return self.store.d

    @property
    def m(self) -> int:
        return self.buf_h.shape[1]

    @property
    def n_ids(self) -> int:
        return int(self.n_alloc)

    @property
    def n_live(self) -> int:
        return int(np.asarray(self.alive).sum())

    @property
    def buffer_count(self) -> int:
        return int(self.buf_fill)

    def segment_sizes(self) -> list[int]:
        """Live row count per segment (largest first by construction)."""
        alive = np.asarray(self.alive)
        return [
            int(alive[g[g >= 0]].sum())
            for g in (np.asarray(s.gid) for s in self.segments)
        ]

    def index_bytes(self) -> int:
        tot = self.buf_h.size * 4
        for s in self.segments:
            tot += s.h.size * 4 + s.csa.I.size * 4 + s.csa.P.size * 4 + s.csa.Hd.size * 4
            if s.csa.L is not None:
                tot += s.csa.L.size * 4
        return tot

    def store_bytes(self) -> int:
        """Resident vector bytes: store + in-memory fp32 tail (if inexact)."""
        tot = self.store.nbytes()
        if self.tail is not None:
            tot += self.tail.size * 4
        return tot

    def total_bytes(self) -> int:
        """Full serving footprint: search structure + resident vectors."""
        return self.index_bytes() + self.store_bytes()

    # -- mutation (host-side, O(batch) on the buffer) ------------------------

    def insert(self, X) -> np.ndarray:
        """Append a batch of vectors; returns their assigned global ids.
        O(batch) buffer appends -- no CSA work until `compact()`."""
        X = jnp.asarray(X, jnp.float32)
        if X.ndim == 1:
            X = X[None, :]
        b = X.shape[0]
        if b == 0:
            return np.zeros((0,), np.int32)
        h = lsh_mod.hash_rows(self.family, X)
        n_ids, fill = self.n_ids, self.buffer_count
        gids = np.arange(n_ids, n_ids + b, dtype=np.int32)
        self._grow_store(n_ids + b)
        rows = jnp.asarray(gids)
        self.store = self.store.set_rows(rows, X)  # quantize on ingest
        if self.tail is not None:
            self.tail = self.tail.at[rows].set(X)
        self.alive = self.alive.at[rows].set(True)
        self._grow_buffer(fill + b)
        slots = jnp.arange(fill, fill + b)
        self.buf_h = self.buf_h.at[slots].set(h)
        self.buf_gid = self.buf_gid.at[slots].set(rows)
        self.n_alloc = jnp.int32(n_ids + b)
        self.buf_fill = jnp.int32(fill + b)
        return gids

    def ingest_chunks(self, chunks, *, chunk_rows: int | None = None,
                      compact: bool = True) -> np.ndarray:
        """Bulk streaming ingest -- the out-of-core fast path.

        Each chunk goes through the same writer as one `insert` batch (hash
        on device, quantize-on-ingest into the store, tail + tombstone
        bookkeeping), but the hash rows bypass the delta buffer: with
        `compact=True` (default) they are rolled straight into ONE new CSA
        segment built with the chunked merge (`Segment.build(chunk_rows=)`),
        so neither the buffer nor the CSA construction ever materialises an
        O(n)-row transient.  Equivalent to `insert(chunk) for chunk in
        chunks; compact()` -- same gids, same store, same search results --
        without the per-batch buffer churn.  `compact=False` falls back to
        buffer appends (chunks land exactly as `insert` batches).

        `chunk_rows` re-blocks the incoming stream (and sizes the CSA merge
        chunks); by default each yielded chunk is one block.  Returns the
        assigned global ids."""
        if chunk_rows is not None:
            chunks = _reblock(chunks, chunk_rows)
        if not compact:
            parts = [self.insert(chunk) for chunk in chunks]
            return (np.concatenate(parts) if parts
                    else np.zeros((0,), np.int32))
        h_parts: list[np.ndarray] = []
        gid_parts: list[np.ndarray] = []
        max_chunk = 0
        for chunk in chunks:
            X = jnp.asarray(chunk, jnp.float32)
            if X.ndim == 1:
                X = X[None, :]
            b = X.shape[0]
            if b == 0:
                continue
            h = lsh_mod.hash_rows(self.family, X)
            n_ids = self.n_ids
            gids = np.arange(n_ids, n_ids + b, dtype=np.int32)
            self._grow_store(n_ids + b)
            rows = jnp.asarray(gids)
            self.store = self.store.set_rows(rows, X)  # quantize on ingest
            if self.tail is not None:
                self.tail = self.tail.at[rows].set(X)
            self.alive = self.alive.at[rows].set(True)
            self.n_alloc = jnp.int32(n_ids + b)
            h_parts.append(np.asarray(h, np.int32))
            gid_parts.append(gids)
            max_chunk = max(max_chunk, b)
            del X, h
        if not h_parts:
            return np.zeros((0,), np.int32)
        seg = Segment.build(
            np.concatenate(h_parts) if len(h_parts) > 1 else h_parts[0],
            np.concatenate(gid_parts),
            chunk_rows=max_chunk,
        )
        self.segments = tuple(
            sorted(self.segments + (seg,), key=lambda s: -int(s.cap))
        )
        return np.concatenate(gid_parts)

    def delete(self, ids) -> int:
        """Tombstone a batch of global ids (idempotent); returns the number
        of rows that were live.  Physical removal happens at `compact()`."""
        ids = np.unique(np.atleast_1d(np.asarray(ids, np.int32)))
        if ids.size == 0:
            return 0
        if (ids < 0).any() or (ids >= self.n_ids).any():
            raise IndexError(
                f"delete ids must be in [0, {self.n_ids}), got "
                f"[{ids.min()}, {ids.max()}]"
            )
        was_live = int(np.asarray(self.alive)[ids].sum())
        self.alive = self.alive.at[jnp.asarray(ids)].set(False)
        return was_live

    def compact(self, *, full: bool = False) -> int:
        """Size-tiered merge (LSM style): roll the live delta-buffer rows,
        plus every segment no larger than the running merge total (smallest
        first), into one new CSA segment; drop tombstoned rows physically.
        `full=True` merges everything into a single segment.  Returns the
        number of rows in the new segment (0 = nothing to merge)."""
        alive = np.asarray(self.alive)
        bg = np.asarray(self.buf_gid)[: self.buffer_count]
        buf_live = bg[(bg >= 0) & alive[np.maximum(bg, 0)]]

        keep: list[Segment] = []
        merged: list[tuple[np.ndarray, np.ndarray]] = []
        total = int(buf_live.size)
        # smallest-first cascade: a segment joins the merge while its live
        # size is <= the rows already being merged (tiering invariant), so
        # big segments are rewritten only when the merge has grown to match.
        order = sorted(self.segments, key=lambda s: int(s.cap))
        for seg in order:
            g = np.asarray(seg.gid)
            live = g >= 0
            live[live] = alive[g[live]]
            n_live = int(live.sum())
            if full or n_live == 0 or n_live <= max(total, 1):
                merged.append((np.asarray(seg.h)[live], g[live]))
                total += n_live
            else:
                keep.append(seg)

        if total == 0:
            new_segments = keep
        else:
            buf_mask = (bg >= 0) & alive[np.maximum(bg, 0)]
            h_rows = [np.asarray(self.buf_h)[: self.buffer_count][buf_mask]]
            gid_rows = [bg[buf_mask]]
            for h_part, g_part in merged:
                h_rows.append(h_part)
                gid_rows.append(g_part)
            new_segments = keep + [
                Segment.build(
                    np.concatenate(h_rows, axis=0),
                    np.concatenate(gid_rows),
                )
            ]
        self.segments = tuple(
            sorted(new_segments, key=lambda s: -int(s.cap))
        )
        self.buf_h = jnp.full_like(self.buf_h[:_MIN_CAP], _PAD_HASH)
        self.buf_gid = jnp.full_like(self.buf_gid[:_MIN_CAP], -1)
        self.buf_fill = jnp.int32(0)
        return total

    def vacuum(self) -> np.ndarray:
        """Reclaim the vector store: drop tombstoned rows (which `compact`
        cannot touch -- global ids are store addresses) and renumber the live
        rows densely in insertion order, rebuilding one CSA segment.  Returns
        the old->new id map, -1 for dead ids; previously handed-out gids are
        invalid afterwards.  O(n_live * m log n_live) -- run it when the dead
        fraction of the store is worth the rebuild."""
        n_ids = self.n_ids
        alive = np.asarray(self.alive)[:n_ids]
        old = alive.nonzero()[0]
        remap = np.full((n_ids,), -1, np.int32)
        remap[old] = np.arange(old.size, dtype=np.int32)
        # rebuild from the exact tail when present; requantization of already
        # dequantized rows is lossless for the symmetric int8 layout
        live_vecs = np.asarray(self.data)[old]
        kind = self.store.kind
        self.store = make_store(kind, jnp.zeros((_MIN_CAP, self.d), jnp.float32))
        if self.tail is not None:
            self.tail = jnp.zeros((_MIN_CAP, self.d), jnp.float32)
        self.alive = jnp.zeros((_MIN_CAP,), bool)
        self.buf_h = jnp.full((_MIN_CAP, self.m), _PAD_HASH, jnp.int32)
        self.buf_gid = jnp.full((_MIN_CAP,), -1, jnp.int32)
        self.n_alloc = jnp.int32(0)
        self.buf_fill = jnp.int32(0)
        self.segments = ()
        if old.size:
            self.insert(live_vecs)  # same family -> identical hash strings
            self.compact(full=True)
        return remap

    def _grow_store(self, need: int) -> None:
        cap = self.store.n
        if need <= cap:
            return
        new_cap = _pow2_at_least(need)
        self.store = self.store.padded_to(new_cap)
        if self.tail is not None:
            self.tail = jnp.concatenate(
                [self.tail, jnp.zeros((new_cap - cap, self.d), jnp.float32)]
            )
        self.alive = jnp.concatenate(
            [self.alive, jnp.zeros((new_cap - cap,), bool)]
        )

    def _grow_buffer(self, need: int) -> None:
        cap = self.buf_h.shape[0]
        if need <= cap:
            return
        new_cap = _pow2_at_least(need)
        self.buf_h = jnp.concatenate(
            [self.buf_h, jnp.full((new_cap - cap, self.m), _PAD_HASH, jnp.int32)]
        )
        self.buf_gid = jnp.concatenate(
            [self.buf_gid, jnp.full((new_cap - cap,), -1, jnp.int32)]
        )

    # -- search -------------------------------------------------------------

    def search(self, queries, params: SearchParams | None = None):
        """c-k-ANNS over the live corpus, jitted end to end via the plan
        cache (`repro.exec`).  `params.source` picks the per-segment
        candidate source; the segmented topology adapter rewrites it onto
        the "segmented" registry entry (source="segmented", inner=<source>)
        and pins the kernel toggle."""
        return _execute(self, queries, params)


jax.tree_util.register_dataclass(
    SegmentedLCCSIndex,
    data_fields=["family", "store", "alive", "segments", "buf_h", "buf_gid",
                 "n_alloc", "buf_fill", "tail"],
    meta_fields=["metric"],
)


# ---------------------------------------------------------------------------
# The "segmented" candidate source
# ---------------------------------------------------------------------------


def _buffer_topk(index: SegmentedLCCSIndex, qh: jax.Array, lam: int):
    """Exact LCCS scoring of the delta buffer; dead/free slots masked."""
    ok = (index.buf_gid >= 0) & index.alive[jnp.maximum(index.buf_gid, 0)]

    def one(q):
        lens = jnp.where(ok, circ_run_lengths(index.buf_h, q), -1)
        kk = min(lam, lens.shape[0])
        vals, slot = top_lengths(lens, kk, index.m)
        ids = jnp.where(vals >= 0, index.buf_gid[slot], -1)
        return ids, jnp.where(vals >= 0, vals, -1)

    ids, vals = jax.vmap(one)(qh)
    return exec_stages.pad_candidates(ids, vals, lam)


@register_source("segmented")
def segmented_source(index, queries, qh, params):
    """Per-segment `params.inner` search + delta-buffer scorer: the shared
    exec stages map local ids to global ids (`local_to_global`), mask
    tombstones (`mask_dead`), and merge the per-part top-lambda sets exactly
    (`merge_candidates` -- LCCS scoring is pointwise)."""
    if not isinstance(index, SegmentedLCCSIndex):
        raise TypeError(
            "source='segmented' needs a SegmentedLCCSIndex; monolithic "
            "LCCSIndex callers should pick 'lccs'/'bruteforce'/'multiprobe-*'"
        )
    inner = get_source(params.inner)
    parts_ids, parts_lcps = [], []
    for seg in index.segments:
        view = LCCSIndex(
            family=index.family, store=index.store, h=seg.h, csa=seg.csa,
            metric=index.metric, tail=index.tail,
        )
        local_ids, lcps = inner(view, queries, qh, params)
        g = exec_stages.local_to_global(local_ids, seg.gid)
        g, lcps = exec_stages.mask_dead(g, lcps, index.alive)
        parts_ids.append(g)
        parts_lcps.append(lcps)
    b_ids, b_lcps = _buffer_topk(index, qh, params.lam)
    parts_ids.append(b_ids)
    parts_lcps.append(b_lcps)
    all_ids = jnp.concatenate(parts_ids, axis=1)
    all_lcps = jnp.concatenate(parts_lcps, axis=1)
    return exec_stages.merge_candidates(all_ids, all_lcps, params.lam)
