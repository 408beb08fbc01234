"""Retrieval serving engine: model embeddings + LCCS-LSH ANN (the paper's
workload with one of the assigned backbones in the loop).

  build:  corpus token sequences -> backbone final-hidden mean-pool
          embeddings -> LCCSIndex (hash strings + CSA), or -- with
          ``dynamic=True`` -- a SegmentedLCCSIndex that absorbs online
          inserts/deletes without a full rebuild.
  serve:  batched requests -> embed -> candidate source -> verified top-k,
          with a micro-batching request queue.  `serve_stream` interleaves
          update requests -- ("insert", tokens) / ("delete", ids) /
          ("compact",) -- with query micro-batches, flushing queued queries
          before each update so every query sees a consistent corpus.

All query-phase knobs arrive as one `SearchParams` (static under jit): the
engine holds a default, and both the embedding and the whole
hash -> candidates -> verify pipeline run as compiled computations.  Every
search -- monolithic, segmented, or sharded -- goes through the unified
execution layer (`repro.exec.execute`): one staged plan per (params, index
structure, query shape), cached explicitly.  The engine's `stats` carry the
plan-cache hit/miss deltas attributable to its own serving calls, so a
deployment can assert it never silently retraces (`plan_misses` flat while
`plan_hits` grows == every batch reused a compiled plan).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, fields, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import ReproDeprecationWarning
from repro.core import LCCSIndex, SearchParams, SegmentedLCCSIndex
from repro.exec import compile_plan, plan_cache
from repro.models import lm
from repro.obs.trace import span as _span
from repro.obs.registry import registry
from repro.shard import make_shard_mesh

DEFAULT_PARAMS = SearchParams(k=5, lam=64)


@dataclass
class ServeStats:
    requests: int = 0
    batches: int = 0
    embed_s: float = 0.0
    search_s: float = 0.0
    inserts: int = 0
    deletes: int = 0
    compactions: int = 0
    # plan-cache deltas from this engine's serving calls (repro.exec):
    # plan_misses counts staged-pipeline compiles, plan_hits reuses -- a
    # steady-state serving loop must only ever grow plan_hits.
    # plan_evictions counts this engine's compiled plans later pushed out of
    # the LRU cache: nonzero means the cache is thrashing (each eviction is
    # a future recompile) and the fleet's plan diversity exceeds its size.
    plan_hits: int = 0
    plan_misses: int = 0
    plan_evictions: int = 0

    def snapshot(self) -> "ServeStats":
        """An independent copy -- the window baseline the serving front
        (`repro.router`) diffs against to attribute activity per replica
        per measurement window."""
        return replace(self)

    def delta(self, baseline: "ServeStats") -> "ServeStats":
        """Field-wise `self - baseline`: the activity since `baseline` was
        snapshotted."""
        return ServeStats(**{
            f.name: getattr(self, f.name) - getattr(baseline, f.name)
            for f in fields(self)
        })

    def reset(self) -> None:
        """Zero every counter in place."""
        for f in fields(self):
            setattr(self, f.name, f.default)


class PendingBatch:
    """An in-flight micro-batch from `serve_batch_nowait`: embed + staged
    search are dispatched (JAX async) but not blocked.  `result()` blocks,
    finalizes the engine stats exactly once, and returns host arrays.  The
    dispatch-to-result gap is where a caller overlaps work -- the serving
    front's workers form and dispatch batch k+1 while batch k completes.

    Stage attribution: called promptly (the router does), the embed/search
    split matches `serve_batch`; a late `result()` shifts the idle wall
    time into the stage sums, so callers that care about the split collect
    promptly."""

    def __init__(self, engine: "RetrievalEngine", q_emb, ids, dists,
                 n_live: int, hit: bool, t0: float):
        self._engine = engine
        self._q_emb = q_emb
        self._ids = ids
        self._dists = dists
        self._n_live = n_live
        self._hit = hit
        self._t0 = t0
        self._out: tuple[np.ndarray, np.ndarray] | None = None

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        if self._out is not None:
            return self._out
        with _span("engine.wait"):
            jax.block_until_ready(self._q_emb)
            t1 = time.perf_counter()
            jax.block_until_ready(self._dists)
            t2 = time.perf_counter()
        self._engine._record_serve(self._n_live, t1 - self._t0,
                                   max(t2 - t1, 0.0), self._hit)
        with _span("engine.to_host"):
            self._out = (np.asarray(self._ids), np.asarray(self._dists))
        return self._out


class RetrievalEngine:
    def __init__(self, cfg, params, *, m: int = 64, metric: str = "angular",
                 max_batch: int = 32,
                 search_params: SearchParams = DEFAULT_PARAMS,
                 store: str = "fp32", shards: int | None = None,
                 name: str | None = None, instrument: bool = False):
        self.cfg = cfg
        # `name` labels this engine's plan-cache activity (repro.exec scope
        # attribution); the replica router names its engines replica-0..N
        self.name = name
        self.params = params
        self.m = m
        self.metric = metric
        self.max_batch = max_batch
        self.search_params = search_params
        # `store` picks the corpus-vector layout (repro.store): "fp32" serves
        # exact single-stage verification; "bf16"/"int8" quantize on ingest
        # and serve the two-stage rerank path (search_params.rerank_mult)
        self.store = store
        # shards > 1 partitions the built index over that many devices
        # (repro.shard): shard-local search + exact global top-k merge
        self.shards = shards
        # instrument=True serves through the staged per-stage-timed plan
        # variants (repro.exec `instrument`): bit-identical results, every
        # exec stage lands in repro_exec_stage_seconds and the trace
        self.instrument = instrument
        self.index: LCCSIndex | None = None
        self.stats = ServeStats()
        # registry twins of the ServeStats counters: `stats` stays the cheap
        # windowed per-engine view (snapshot/delta), the registry series --
        # labeled by engine -- are what Prometheus and StatsLogger read
        self._obs_label = name or "default"
        reg = registry()
        self._c_requests = reg.counter(
            "repro_serve_requests_total", "queries served",
            labelnames=("engine",))
        self._c_batches = reg.counter(
            "repro_serve_batches_total", "serving micro-batches completed",
            labelnames=("engine",))
        self._c_embed_s = reg.counter(
            "repro_serve_embed_seconds_total",
            "wall seconds in the embedding stage", labelnames=("engine",))
        self._c_search_s = reg.counter(
            "repro_serve_search_seconds_total",
            "wall seconds in the staged search", labelnames=("engine",))
        self._c_updates = reg.counter(
            "repro_serve_updates_total",
            "corpus updates applied (insert/delete/compact)",
            labelnames=("engine", "op"))
        # eviction attribution is engine-side delta tracking over the plan
        # cache's per-scope counter (the cache can't push, so we pull)
        self._last_evictions = plan_cache().scope_evictions(self.name)
        self._embed = jax.jit(self._embed_fn)

    def _record_serve(self, n: int, embed_s: float, search_s: float,
                      hit: bool) -> None:
        """Finalize one served micro-batch into both stats surfaces."""
        s = self.stats
        s.requests += n
        s.batches += 1
        s.embed_s += embed_s
        s.search_s += search_s
        s.plan_hits += int(hit)
        s.plan_misses += int(not hit)
        ev = plan_cache().scope_evictions(self.name)
        s.plan_evictions += ev - self._last_evictions
        self._last_evictions = ev
        self._c_requests.inc(n, engine=self._obs_label)
        self._c_batches.inc(engine=self._obs_label)
        self._c_embed_s.inc(embed_s, engine=self._obs_label)
        self._c_search_s.inc(search_s, engine=self._obs_label)

    def _embed_fn(self, tokens):
        hidden, _ = lm.forward(self.params, tokens, self.cfg, mode="train")
        emb = jnp.mean(hidden, axis=1)
        return emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)

    def embed(self, tokens: np.ndarray) -> jax.Array:
        out = []
        for lo in range(0, tokens.shape[0], self.max_batch):
            out.append(self._embed(jnp.asarray(tokens[lo : lo + self.max_batch])))
        return out[0] if len(out) == 1 else jnp.concatenate(out)

    def build_index(self, corpus_tokens: np.ndarray, *, seed: int = 0,
                    dynamic: bool = False, chunk_rows: int | None = None):
        """Embed + index the corpus.  `dynamic=True` builds a
        SegmentedLCCSIndex so `insert`/`delete`/`compact` work afterwards.
        The engine's `store` kind decides the vector layout; quantized
        stores verify in two stages (insert paths quantize on ingest).
        With `shards` > 1 the built index is partitioned over that many
        devices (static corpora only -- the sharded layout is immutable).
        `chunk_rows` routes static builds through the out-of-core streaming
        path (`LCCSIndex.build(chunk_rows=)` -- bit-identical, O(chunk)
        build transients) for corpora that dwarf the embedding batches."""
        emb = self.embed(corpus_tokens)
        fam = "angular" if self.metric == "angular" else "euclidean"
        if self.shards and self.shards > 1:
            if dynamic:
                raise ValueError(
                    "sharded serving needs a static corpus: shards > 1 and "
                    "dynamic=True are mutually exclusive"
                )
            self.index = LCCSIndex.build(
                emb, m=self.m, family=fam, seed=seed, store=self.store,
                chunk_rows=chunk_rows,
            ).shard(make_shard_mesh(self.shards))
            return self.index
        if dynamic:
            self.index = SegmentedLCCSIndex.build(
                emb, m=self.m, family=fam, seed=seed, store=self.store
            )
        else:
            self.index = LCCSIndex.build(
                emb, m=self.m, family=fam, seed=seed, store=self.store,
                chunk_rows=chunk_rows,
            )
        return self.index

    # -- dynamic corpus (SegmentedLCCSIndex only) ----------------------------

    def _dynamic_index(self) -> SegmentedLCCSIndex:
        assert self.index is not None, "build_index first"
        if not isinstance(self.index, SegmentedLCCSIndex):
            raise TypeError(
                "corpus updates need build_index(..., dynamic=True); this "
                "engine holds a static LCCSIndex"
            )
        return self.index

    def insert(self, corpus_tokens: np.ndarray) -> np.ndarray:
        """Embed + insert new corpus documents; returns their global ids."""
        gids = self._dynamic_index().insert(self.embed(corpus_tokens))
        self.stats.inserts += len(gids)
        self._c_updates.inc(len(gids), engine=self._obs_label, op="insert")
        return gids

    def delete(self, ids) -> int:
        """Tombstone corpus documents by global id."""
        n = self._dynamic_index().delete(ids)
        self.stats.deletes += n
        self._c_updates.inc(n, engine=self._obs_label, op="delete")
        return n

    def compact(self, *, full: bool = False) -> int:
        """Roll the delta buffer (and small segments) into a CSA segment."""
        n = self._dynamic_index().compact(full=full)
        self.stats.compactions += 1
        self._c_updates.inc(engine=self._obs_label, op="compact")
        return n

    def _resolve_params(self, params, legacy) -> SearchParams:
        if legacy:
            warnings.warn(
                "k=/lam=/probes= kwargs to serve_batch/serve_stream are "
                "deprecated; pass a SearchParams",
                ReproDeprecationWarning,
                stacklevel=3,
            )
            base = params or self.search_params
            legacy.setdefault("k", base.k)
            legacy.setdefault("lam", base.lam)
            return SearchParams.from_legacy(**legacy)
        return params or self.search_params

    def serve_batch(self, query_tokens: np.ndarray,
                    params: SearchParams | None = None, **legacy):
        """One micro-batched serving step.  Returns (ids, dists)."""
        assert self.index is not None, "build_index first"
        p = self._resolve_params(params, legacy)
        with _span("serve_batch", batch=int(query_tokens.shape[0])):
            t0 = time.perf_counter()
            with _span("embed"):
                q_emb = self.embed(query_tokens)
                # the embedding is dispatched asynchronously: without an
                # explicit block the device work would drain inside the search
                # timing below, silently crediting embed time to search_s
                jax.block_until_ready(q_emb)
            t1 = time.perf_counter()
            # one entry point for every topology: the plan resolves the source
            # rewrite ("segmented"/"sharded") and caches the compiled
            # pipeline.  return_hit attributes THIS call's cache outcome
            # race-free (other engines/threads may be compiling concurrently).
            with _span("search"):
                plan, hit = compile_plan(self.index, q_emb, p,
                                         return_hit=True, scope=self.name,
                                         instrument=self.instrument)
                ids, dists = plan.run(self.index,
                                      jnp.asarray(q_emb, jnp.float32))
                jax.block_until_ready(dists)
            t2 = time.perf_counter()
        self._record_serve(int(query_tokens.shape[0]), t1 - t0, t2 - t1, hit)
        return np.asarray(ids), np.asarray(dists)

    def serve_batch_nowait(self, query_tokens: np.ndarray,
                           params: SearchParams | None = None, *,
                           n_live: int | None = None) -> PendingBatch:
        """Non-blocking `serve_batch`: dispatch the embed and the staged
        search without waiting for device work and return a `PendingBatch`;
        stats (including the embed/search split and this call's plan-cache
        outcome) land when its `result()` is called.  `n_live` is the
        number of real requests when the caller padded the batch to a
        bucketed shape (the router does), so `stats.requests` counts users,
        not padding."""
        assert self.index is not None, "build_index first"
        p = self._resolve_params(params, {})
        t0 = time.perf_counter()
        with _span("engine.dispatch"):
            q_emb = self.embed(query_tokens)
            plan, hit = compile_plan(self.index, q_emb, p, return_hit=True,
                                     scope=self.name,
                                     instrument=self.instrument)
            ids, dists = plan.run(self.index,
                                  jnp.asarray(q_emb, jnp.float32))
        n = query_tokens.shape[0] if n_live is None else n_live
        return PendingBatch(self, q_emb, ids, dists, n, hit, t0)

    def serve_stream(self, requests: list,
                     params: SearchParams | None = None, **legacy):
        """Greedy micro-batching over a request stream (batched requests
        deliverable): coalesce up to max_batch queued requests per step.

        A request is either a query (token array) or -- against a dynamic
        index -- a corpus update tuple:

            ("insert", tokens (b, L))   -> ("inserted", global ids)
            ("delete", ids)             -> ("deleted", n_live_removed)
            ("compact",)                -> ("compacted", rows_merged)

        Updates flush queued queries first, so results stay in stream order
        and every query is answered against the corpus state at its arrival.
        Mixed token lengths are fine: a query whose length differs from the
        queued batch flushes it first, so every micro-batch is rectangular
        (np.stack would otherwise die on the ragged stack) and no query is
        ever padded with tokens it did not contain.
        Returns one entry per request: (ids, dists) for queries, the ack
        tuples above for updates."""
        p = self._resolve_params(params, legacy)
        results: list = []
        queue: list[np.ndarray] = []

        def flush():
            if not queue:
                return
            batch = np.stack(queue)
            ids, dists = self.serve_batch(batch, p)
            results.extend(zip(ids, dists))
            queue.clear()

        for r in requests:
            if isinstance(r, tuple) and r and isinstance(r[0], str):
                op = r[0]
                if op in ("insert", "delete", "compact") and not isinstance(
                        self.index, SegmentedLCCSIndex):
                    # fail before touching the index internals: a monolithic
                    # or sharded layout has no update path at all
                    raise ValueError(
                        f"stream op {op!r} needs a dynamic corpus, but this "
                        f"engine holds a static "
                        f"{type(self.index).__name__}; build the index with "
                        f"build_index(..., dynamic=True)"
                    )
                flush()  # queries queued before the update see the old corpus
                if op == "insert":
                    results.append(("inserted", self.insert(r[1])))
                elif op == "delete":
                    results.append(("deleted", self.delete(r[1])))
                elif op == "compact":
                    results.append(("compacted", self.compact()))
                else:
                    raise ValueError(f"unknown stream op {op!r}")
                continue
            r = np.asarray(r)
            if queue and r.shape != queue[0].shape:
                flush()  # length change: close the rectangular micro-batch
            queue.append(r)
            if len(queue) >= self.max_batch:
                flush()
        flush()
        return results
