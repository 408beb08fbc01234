"""End-to-end system behaviour: train -> embed -> index -> serve, with
fault tolerance in the loop."""
import time

import numpy as np
import jax
import pytest

from repro.configs import ARCHS
from repro.core import SearchParams
from repro.data import DataPipeline, lm_token_batches
from repro.models import api
from repro.serve import RetrievalEngine
from repro.train.trainer import Trainer, TrainerConfig


@pytest.mark.slow
def test_train_then_serve_roundtrip(tmp_path):
    """The full production path: train a (reduced) backbone with
    checkpointing, restore it, build an LCCS index over its embeddings,
    serve batched requests, and find the planted neighbours."""
    cfg = ARCHS["gemma-2b"].smoke()
    pipe = DataPipeline(lm_token_batches(vocab=cfg.vocab, seed=0),
                        global_batch=4, seq_len=32)
    trainer = Trainer(cfg, pipe, TrainerConfig(
        steps=30, ckpt_every=10, ckpt_dir=str(tmp_path), log_every=10, warmup=5,
    ))
    out = trainer.run()
    assert out["final_step"] == 30
    assert out["final_loss"] < out["history"][0]["loss"]  # it learned

    # restore the trained params from the checkpoint (fault-tolerance path)
    params = trainer.init_or_restore()[0].params

    engine = RetrievalEngine(cfg, params, m=32, metric="angular", max_batch=16)
    rng = np.random.default_rng(0)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=1)(0, 128, 32)
    engine.build_index(corpus)
    picks = rng.integers(0, 128, 32)
    ids, dists = engine.serve_batch(corpus[picks], SearchParams(k=5, lam=48))
    hits = sum(int(picks[i] in ids[i]) for i in range(len(picks)))
    assert hits >= 29, f"self-retrieval {hits}/32"
    assert np.isfinite(dists[ids >= 0]).all()


def test_serve_stream_microbatching():
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=8)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=2)(0, 64, 16)
    engine.build_index(corpus)
    requests = [corpus[i] for i in range(20)]
    results = engine.serve_stream(requests, SearchParams(k=3, lam=16))
    assert len(results) == 20
    assert engine.stats.batches == 3  # 8 + 8 + 4
    hits = sum(int(i in results[i][0]) for i in range(20))
    assert hits >= 18


def test_serve_batch_stats_split_embed_vs_search():
    """embed_s and search_s must each measure their own stage: the embedding
    is blocked before the search timestamp (async dispatch would otherwise
    credit embed work to search_s), both are positive, and together they
    bound the measured wall time of the call."""
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=8)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=4)(0, 64, 16)
    engine.build_index(corpus)
    p = SearchParams(k=3, lam=16)
    engine.serve_batch(corpus[:8], p)  # warm both jit caches
    before_e, before_s = engine.stats.embed_s, engine.stats.search_s
    t0 = time.perf_counter()
    engine.serve_batch(corpus[:8], p)
    wall = time.perf_counter() - t0
    de = engine.stats.embed_s - before_e
    ds = engine.stats.search_s - before_s
    assert de > 0.0 and ds > 0.0, (de, ds)
    assert de + ds <= wall * 1.05, (de, ds, wall)
    assert engine.stats.batches == 2 and engine.stats.requests == 16


def test_serve_stream_ragged_query_lengths():
    """Mixed token lengths in one stream must not crash the micro-batcher
    (np.stack on a ragged list) nor pad queries with alien tokens: the
    queue flushes on a length change, so every batch is rectangular."""
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=8)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=5)(0, 64, 16)
    engine.build_index(corpus)
    p = SearchParams(k=3, lam=48)
    long_q = np.concatenate([corpus[7], corpus[7]])  # length 32 vs 16
    stream = [corpus[0], corpus[1], long_q, corpus[2], corpus[3], long_q]
    results = engine.serve_stream(stream, p)
    assert len(results) == len(stream)
    # same-length runs were batched, length changes flushed: 4 micro-batches
    assert engine.stats.batches == 4
    assert engine.stats.requests == len(stream)
    # the normal-length queries still retrieve their own documents
    hits = sum(int(doc in results[j][0])
               for j, doc in [(0, 0), (1, 1), (3, 2), (4, 3)])
    assert hits >= 3, hits


def test_serve_sharded_matches_monolithic():
    """shards=2: the engine partitions the index over two (fake) devices and
    serve_batch answers identically to the monolithic engine."""
    from conftest import run_multidevice

    out = run_multidevice(
        """
        import numpy as np, jax
        from repro.configs import ARCHS
        from repro.core import SearchParams
        from repro.data import lm_token_batches
        from repro.models import api
        from repro.serve import RetrievalEngine
        from repro.shard import ShardedLCCSIndex

        cfg = ARCHS["gemma-2b"].smoke()
        params = api.init_model(jax.random.key(0), cfg)
        corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=6)(0, 48, 16)
        p = SearchParams(k=3, lam=64, use_gather_kernel=False)

        mono = RetrievalEngine(cfg, params, m=16, metric="angular")
        mono.build_index(corpus)
        ids_m, d_m = mono.serve_batch(corpus[:8], p)

        eng = RetrievalEngine(cfg, params, m=16, metric="angular", shards=2)
        eng.build_index(corpus)
        assert isinstance(eng.index, ShardedLCCSIndex)
        assert eng.index.shards == 2
        ids_s, d_s = eng.serve_batch(corpus[:8], p.replace(shards=2))
        np.testing.assert_allclose(np.sort(d_s, axis=1), np.sort(d_m, axis=1),
                                   rtol=1e-5)
        for a, b in zip(ids_s, ids_m):
            assert set(a.tolist()) == set(b.tolist())
        # dynamic + sharded is refused
        try:
            eng.build_index(corpus, dynamic=True)
        except ValueError as e:
            assert "mutually exclusive" in str(e)
        else:
            raise AssertionError("dynamic+sharded should raise")
        print("ENGINE-SHARDED-OK")
        """,
        n_dev=2,
    )
    assert "ENGINE-SHARDED-OK" in out


def test_serve_stream_interleaves_corpus_updates():
    """Dynamic serving: insert/delete/compact requests ride the same stream
    as query micro-batches; queries queued before an update are answered
    against the pre-update corpus, later queries see the new one."""
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=4)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=3)(0, 40, 16)
    engine.build_index(corpus[:32], dynamic=True)
    p = SearchParams(k=3, lam=48)

    stream = [
        corpus[0], corpus[1],
        ("insert", corpus[32:40]),   # docs 32..39 get gids 32..39
        corpus[35],                  # must now find itself
        ("delete", np.arange(8)),
        corpus[2],                   # its own doc is gone from the corpus
        ("compact",),
        corpus[36],                  # still found after the merge
    ]
    results = engine.serve_stream(stream, p)
    assert len(results) == len(stream)
    assert results[2][0] == "inserted"
    assert results[2][1].tolist() == list(range(32, 40))
    assert results[4] == ("deleted", 8)
    # size-tiered: only the 8 buffered rows merge (the 24-live segment is
    # larger than the merge total, so it is not rewritten)
    assert results[6][0] == "compacted" and results[6][1] == 8
    assert engine.index.n_live == 32 and engine.index.buffer_count == 0
    assert sorted(engine.index.segment_sizes()) == [8, 24]

    q_before, q_self, q_deleted, q_after = (
        results[0], results[1], results[5], results[7]
    )
    assert 0 in q_before[0] and 1 in q_self[0]
    assert 35 in results[3][0]
    assert 2 not in q_deleted[0]  # tombstoned rows never surface
    assert 36 in q_after[0]
    # a static engine refuses update ops up front (a clear ValueError naming
    # the dynamic=True fix, not a failure deep in the index internals)
    static = RetrievalEngine(cfg, params, m=16, metric="angular")
    static.build_index(corpus[:8])
    with pytest.raises(ValueError, match="dynamic=True"):
        static.serve_stream([("delete", [0])], p)


def test_serve_stream_compact_on_static_index_raises():
    """A ("compact",) stream op against a non-segmented index must fail
    with a ValueError that names build_index(..., dynamic=True), before any
    queued queries are flushed or index internals touched."""
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=4)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=5)(0, 16, 16)
    engine.build_index(corpus)  # monolithic: no update path
    with pytest.raises(ValueError, match=r"dynamic=True"):
        engine.serve_stream([corpus[0], ("compact",)], SearchParams(k=3, lam=16))
    # nothing was served: the op was rejected before the flush
    assert engine.stats.batches == 0 and engine.stats.compactions == 0
    # unknown ops still get the dedicated message
    with pytest.raises(ValueError, match="unknown stream op"):
        engine.serve_stream([("vacuum",)], SearchParams(k=3, lam=16))


def test_serve_stats_snapshot_reset_delta():
    """ServeStats windowing hooks (the router's per-replica attribution):
    snapshot() is an independent copy, delta() is field-wise subtraction,
    reset() zeroes in place."""
    from repro.serve.engine import ServeStats

    s = ServeStats(requests=10, batches=3, embed_s=1.25, search_s=0.5,
                   plan_hits=2, plan_misses=1)
    snap = s.snapshot()
    s.requests += 6
    s.batches += 1
    s.embed_s += 0.75
    s.plan_hits += 4
    assert snap.requests == 10 and snap.batches == 3  # unaffected copy
    d = s.delta(snap)
    assert (d.requests, d.batches, d.plan_hits, d.plan_misses) == (6, 1, 4, 0)
    assert d.embed_s == pytest.approx(0.75) and d.search_s == 0.0
    s.reset()
    assert s == ServeStats()
    assert snap.requests == 10  # reset is in place, snapshots survive


def test_serve_batch_nowait_matches_serve_batch():
    """The non-blocking batch entry point returns the same answers as
    serve_batch and finalizes stats exactly once, on result()."""
    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    engine = RetrievalEngine(cfg, params, m=16, metric="angular", max_batch=8)
    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=6)(0, 32, 16)
    engine.build_index(corpus)
    p = SearchParams(k=3, lam=16)

    ids_sync, dists_sync = engine.serve_batch(corpus[:8], p)
    before = engine.stats.snapshot()
    pending = engine.serve_batch_nowait(corpus[:8], p)
    assert engine.stats.batches == before.batches  # nothing landed yet
    ids, dists = pending.result()
    np.testing.assert_array_equal(ids, ids_sync)
    np.testing.assert_allclose(dists, dists_sync, rtol=1e-6)
    d = engine.stats.delta(before)
    assert d.batches == 1 and d.requests == 8
    assert d.plan_hits == 1 and d.plan_misses == 0  # same plan as the warmup
    assert d.embed_s > 0.0 and d.search_s >= 0.0
    ids2, _ = pending.result()  # idempotent: stats land exactly once
    assert engine.stats.delta(before).batches == 1
    np.testing.assert_array_equal(ids2, ids)
    # padded bucketed serving: n_live attributes users, not padding rows
    before = engine.stats.snapshot()
    engine.serve_batch_nowait(corpus[:8], p, n_live=3).result()
    assert engine.stats.delta(before).requests == 3


def test_compile_cache_env_dir_wins_else_fixed_checkout_dir(monkeypatch,
                                                            tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` is left to JAX; without it the cache goes
    to `.jax_cache/` at the checkout's root, the same path on every run."""
    from pathlib import Path

    from repro.launch.compile_cache import ENV_CACHE_DIR, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV_CACHE_DIR, str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv(ENV_CACHE_DIR)
        fixed = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert enable_compile_cache() == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cpu_benchmark_workers_refuse_an_accelerator_host(monkeypatch):
    """The figure benchmarks time CPU-only child processes: on a host whose
    JAX sees an accelerator they refuse rather than time the CPU in its
    place (or fight the parent for the chip)."""
    from benchmarks.common import cpu_worker_env

    env = cpu_worker_env(2)
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="'tpu'"):
        cpu_worker_env(2)
