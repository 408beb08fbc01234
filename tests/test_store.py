"""Store subsystem: quantization error bounds, pytree/persistence round
trips, the two-stage rerank path, kernel-dispatch toggles, and the int8
recall-parity property across monolithic and segmented indexes."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LCCSIndex,
    SearchParams,
    SegmentedLCCSIndex,
    available_stores,
    jit_search,
    make_store,
)
from repro.store import Int8Store, get_store_cls

ALL_STORES = ("fp32", "bf16", "int8")


def _clustered(n=1500, d=48, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(15, d)) * 5.0
    X = (centers[rng.integers(0, 15, n)]
         + rng.normal(size=(n, d))).astype(np.float32)
    Q = X[:12] + rng.normal(size=(12, d)).astype(np.float32) * 0.05
    return X, Q


def _recall(ids, gt):
    ids = np.asarray(ids)
    return np.mean([
        len(set(ids[i].tolist()) & set(gt[i].tolist())) / gt.shape[1]
        for i in range(gt.shape[0])
    ])


# -- registry / protocol -------------------------------------------------------


def test_registry_has_builtin_stores():
    assert set(ALL_STORES) <= set(available_stores())
    assert get_store_cls("int8") is Int8Store
    with pytest.raises(KeyError, match="available"):
        get_store_cls("no-such-store")


@pytest.mark.parametrize("kind", ALL_STORES)
def test_store_shape_and_bytes(kind):
    X = np.random.default_rng(0).normal(size=(100, 32)).astype(np.float32)
    s = make_store(kind, X)
    assert s.shape == (100, 32) and s.n == 100 and s.d == 32
    per_row = {"fp32": 32 * 4, "bf16": 32 * 2, "int8": 32 + 4}[kind]
    assert s.nbytes() == 100 * per_row
    assert s.exact == (kind == "fp32")


# -- quantization round-trip error bounds --------------------------------------


def test_fp32_roundtrip_exact():
    X = np.random.default_rng(1).normal(size=(64, 16)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(make_store("fp32", X).dense()), X)


def test_bf16_roundtrip_error_bound():
    """bf16 has an 8-bit significand: relative error <= 2^-8 elementwise."""
    X = np.random.default_rng(2).normal(size=(200, 32)).astype(np.float32)
    deq = np.asarray(make_store("bf16", X).dense())
    assert (np.abs(deq - X) <= np.abs(X) * 2.0**-8 + 1e-12).all()


def test_int8_roundtrip_error_bound():
    """Symmetric per-row int8: |x - deq(x)| <= scale/2 = max|row| / 254."""
    X = np.random.default_rng(3).normal(size=(200, 32)).astype(np.float32)
    X[7] = 0.0  # zero rows must be represented exactly
    s = make_store("int8", X)
    deq = np.asarray(s.dense())
    bound = np.abs(X).max(axis=1, keepdims=True) / 254.0
    assert (np.abs(deq - X) <= bound + 1e-7).all()
    np.testing.assert_array_equal(deq[7], 0.0)
    # codes saturate at the symmetric limit, scale rows are reproducible
    assert np.asarray(s.q).min() >= -127 and np.asarray(s.q).max() <= 127


def test_int8_requantization_is_lossless():
    """Quantizing already-dequantized rows reproduces codes and scales (the
    property `vacuum()` relies on when no fp32 tail is kept)."""
    X = np.random.default_rng(4).normal(size=(50, 24)).astype(np.float32)
    s1 = make_store("int8", X)
    s2 = make_store("int8", s1.dense())
    np.testing.assert_array_equal(np.asarray(s1.q), np.asarray(s2.q))
    np.testing.assert_allclose(np.asarray(s1.scale), np.asarray(s2.scale),
                               rtol=1e-6)


@pytest.mark.parametrize("kind", ALL_STORES)
def test_set_rows_quantizes_on_ingest(kind):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(20, 16)).astype(np.float32)
    Y = rng.normal(size=(4, 16)).astype(np.float32)
    s = make_store(kind, X).set_rows(jnp.asarray([1, 3, 5, 7]), Y)
    want = make_store(kind, Y)  # per-row quantizer: same codes standalone
    got = np.asarray(s.gather(jnp.asarray([[1, 3, 5, 7]])))[0]
    np.testing.assert_allclose(got, np.asarray(want.dense()), rtol=1e-6)
    s2 = s.padded_to(32)
    assert s2.n == 32
    np.testing.assert_array_equal(np.asarray(s2.dense())[20:], 0.0)


# -- pytree + persistence ------------------------------------------------------


@pytest.mark.parametrize("kind", ALL_STORES)
def test_store_is_pytree(kind):
    X = np.random.default_rng(6).normal(size=(40, 8)).astype(np.float32)
    s = make_store(kind, X)
    leaves, treedef = jax.tree_util.tree_flatten(s)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(rebuilt) is type(s)
    np.testing.assert_array_equal(np.asarray(rebuilt.dense()),
                                  np.asarray(s.dense()))
    moved = jax.device_put(s)
    assert isinstance(moved, type(s))


@pytest.mark.parametrize("kind", ALL_STORES)
def test_index_save_load_roundtrip_per_store(tmp_path, kind):
    X, Q = _clustered(n=500, d=16)
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=5,
                          store=kind)
    params = SearchParams(k=5, lam=50)
    ids0, d0 = idx.search(Q, params)
    p = tmp_path / f"index_{kind}.pkl"
    idx.save(p)
    idx2 = LCCSIndex.load(p)
    assert idx2.store.kind == kind
    ids1, d1 = idx2.search(Q, params)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)


@pytest.mark.parametrize("kind", ALL_STORES)
def test_index_pytree_roundtrip_per_store(kind):
    X, Q = _clustered(n=400, d=16)
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=2,
                          store=kind)
    params = SearchParams(k=5, lam=40)
    ids0, _ = jit_search(idx, jnp.asarray(Q), params)
    ids1, _ = jit_search(jax.device_put(idx), jnp.asarray(Q), params)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))


# -- two-stage verify behaviour ------------------------------------------------


def test_params_store_mismatch_raises():
    X, Q = _clustered(n=300, d=16)
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, store="int8")
    with pytest.raises(ValueError, match="does not match"):
        idx.search(Q, SearchParams(k=5, lam=40, store="fp32"))
    with pytest.raises(ValueError, match="rerank_mult"):
        SearchParams(rerank_mult=0)


def test_two_stage_returns_exact_fp32_distances():
    """Stage 2 reranks against the fp32 tail: returned distances must equal
    the fp32 index's, not the dequantized geometry's."""
    X, Q = _clustered(n=800, d=32)
    p = SearchParams(k=10, lam=150)
    ids32, d32 = LCCSIndex.build(X, m=16, w=4.0, seed=1).search(Q, p)
    ids8, d8 = LCCSIndex.build(X, m=16, w=4.0, seed=1, store="int8").search(Q, p)
    np.testing.assert_array_equal(np.asarray(ids8), np.asarray(ids32))
    np.testing.assert_allclose(np.asarray(d8), np.asarray(d32), rtol=1e-6)


def test_disk_lazy_tail_matches_in_memory(tmp_path):
    X, Q = _clustered(n=600, d=24)
    p = SearchParams(k=8, lam=100)
    mem = LCCSIndex.build(X, m=16, w=4.0, seed=3, store="int8")
    disk = LCCSIndex.build(X, m=16, w=4.0, seed=3, store="int8",
                           tail_path=tmp_path / "tail.npy")
    ids_m, d_m = mem.search(Q, p)
    ids_d, d_d = disk.search(Q, p)
    np.testing.assert_array_equal(np.asarray(ids_m), np.asarray(ids_d))
    np.testing.assert_allclose(np.asarray(d_m), np.asarray(d_d), rtol=1e-6)
    # no resident fp32: only the quantized representation counts
    assert disk.store_bytes() == disk.store.nbytes()
    # the pure traced pipeline cannot gather from disk and says so...
    from repro.core.index import search as pure_search

    with pytest.raises(ValueError, match="disk-lazy"):
        pure_search(disk, jnp.asarray(Q), p)
    # ...while jit_search's compiled plan orchestrates the split pipeline
    ids_j, d_j = jit_search(disk, jnp.asarray(Q), p)
    np.testing.assert_array_equal(np.asarray(ids_m), np.asarray(ids_j))
    np.testing.assert_allclose(np.asarray(d_m), np.asarray(d_j), rtol=1e-6)


def test_params_store_mismatch_raises_on_disk_tail(tmp_path):
    """The `store` pin must be enforced on the disk-lazy split pipeline too,
    not just the single-jit path."""
    X, Q = _clustered(n=300, d=16)
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, store="int8",
                          tail_path=tmp_path / "tail.npy")
    with pytest.raises(ValueError, match="does not match"):
        idx.search(Q, SearchParams(k=5, lam=40, store="fp32"))


def test_disk_tail_save_load_is_self_contained(tmp_path):
    """Saving a disk-tail index embeds the tail: loading after the .npy is
    deleted must re-materialise it and search identically."""
    X, Q = _clustered(n=400, d=16)
    tail = tmp_path / "tail.npy"
    idx = LCCSIndex.build(X, m=16, w=4.0, seed=2, store="int8",
                          tail_path=tail)
    p = SearchParams(k=5, lam=50)
    ids0, d0 = idx.search(Q, p)
    pkl = tmp_path / "idx.pkl"
    idx.save(pkl)
    tail.unlink()  # simulate moving the pickle without the sidecar
    idx2 = LCCSIndex.load(pkl)
    assert idx2.tail is None and Path(idx2.tail_path).exists()
    ids1, d1 = idx2.search(Q, p)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)


def test_kernel_matches_reference_on_zero_vectors_angular():
    """A zero corpus row must rank identically on the kernel and reference
    paths (both 1.0 under the clamped-norm angular semantics)."""
    rng = np.random.default_rng(20)
    X = rng.normal(size=(200, 16)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    X[5] = 0.0
    Q = np.concatenate([X[:2], np.zeros((1, 16), np.float32)])
    ids = jnp.broadcast_to(jnp.arange(200, dtype=jnp.int32), (3, 200))
    for kind in ("fp32", "int8"):
        s = make_store(kind, X)
        d_ref = np.asarray(s.gather_dist(ids, jnp.asarray(Q),
                                         metric="angular", use_kernel=False))
        d_ker = np.asarray(s.gather_dist(ids, jnp.asarray(Q),
                                         metric="angular", use_kernel=True))
        assert np.isfinite(d_ref).all() and np.isfinite(d_ker).all()
        np.testing.assert_allclose(d_ker, d_ref, rtol=1e-5, atol=1e-5)


def test_int8_store_memory_reduction(tmp_path):
    X, _ = _clustered(n=1000, d=128)
    fp32 = LCCSIndex.build(X, m=8, w=4.0, store="fp32")
    int8 = LCCSIndex.build(X, m=8, w=4.0, store="int8",
                           tail_path=tmp_path / "tail.npy")
    assert fp32.store_bytes() / int8.store_bytes() >= 3.5


# -- kernel dispatch toggle (satellite: wire gather_l2 into the verify path) ---


@pytest.mark.parametrize("kind", ["fp32", "int8"])
def test_use_gather_kernel_matches_reference(kind):
    """use_gather_kernel=True routes verification through the Pallas gather
    kernels (interpret mode on CPU); ids must match the jnp path exactly and
    distances to float tolerance."""
    X, Q = _clustered(n=500, d=32)
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=4,
                          store=kind)
    base = SearchParams(k=5, lam=64)
    ids0, d0 = idx.search(Q, base)
    ids1, d1 = idx.search(Q, base.replace(use_gather_kernel=True))
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1),
                               rtol=1e-5, atol=1e-5)


def test_hamming_metric_bypasses_kernel():
    """The gather kernels only implement euclidean/angular; a hamming index
    with use_gather_kernel=True must fall back to the reference scorer, not
    silently return angular distances."""
    rng = np.random.default_rng(21)
    X = (rng.random((300, 24)) > 0.5).astype(np.float32)
    idx = LCCSIndex.build(X, m=16, family="hamming", seed=0)
    base = SearchParams(k=5, lam=40)
    ids0, d0 = idx.search(X[:4], base)
    ids1, d1 = idx.search(X[:4], base.replace(use_gather_kernel=True))
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    # self-distance is a true Hamming count: exactly 0, found at rank 0
    assert (np.asarray(d1)[:, 0] == 0).all()


def test_gather_kernel_env_toggle(monkeypatch):
    from repro.core.verify import resolve_use_kernel

    assert resolve_use_kernel(True) is True
    assert resolve_use_kernel(False) is False
    monkeypatch.setenv("REPRO_GATHER_KERNEL", "1")
    assert resolve_use_kernel(None) is True
    monkeypatch.setenv("REPRO_GATHER_KERNEL", "0")
    assert resolve_use_kernel(None) is False
    monkeypatch.delenv("REPRO_GATHER_KERNEL")
    # default off on every backend: the gather kernels do not lower for TPU
    assert resolve_use_kernel(None) is False


# -- recall parity property ----------------------------------------------------


@pytest.mark.parametrize("source", ["bruteforce", "lccs", "multiprobe-full",
                                    "multiprobe-skip"])
def test_int8_two_stage_recall_parity_monolithic(source):
    """Acceptance: int8 + rerank_mult>=2 within 1% recall@10 of fp32 for
    every candidate source on clustered data."""
    X, Q = _clustered(n=1500, d=48, seed=8)
    gt = np.argsort(((Q[:, None, :] - X[None]) ** 2).sum(-1), axis=1)[:, :10]
    p = SearchParams(k=10, lam=150, source=source, probes=9, rerank_mult=2)
    r32 = _recall(LCCSIndex.build(X, m=16, w=4.0, seed=9).search(Q, p)[0], gt)
    r8 = _recall(
        LCCSIndex.build(X, m=16, w=4.0, seed=9, store="int8").search(Q, p)[0],
        gt,
    )
    assert r8 >= r32 - 0.01, (r8, r32)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_quantized_recall_parity_segmented(kind):
    """Same parity through the segmented (dynamic) index: bulk load +
    insert/delete churn, quantize-on-ingest, then search."""
    X, Q = _clustered(n=1200, d=48, seed=10)
    gt = np.argsort(((Q[:, None, :] - X[None]) ** 2).sum(-1), axis=1)[:, :10]
    p = SearchParams(k=10, lam=150)

    def churn(store):
        idx = SegmentedLCCSIndex.build(X[:800], m=16, w=4.0, seed=11,
                                       store=store)
        gids = idx.insert(X[800:])
        idx.delete(gids[-50:])  # delete rows outside the ground-truth set
        return idx

    r32 = _recall(churn("fp32").search(Q, p)[0], gt)
    rq = _recall(churn(kind).search(Q, p)[0], gt)
    assert rq >= r32 - 0.01, (rq, r32)


def test_segmented_quantized_compact_and_vacuum():
    """compact() and vacuum() keep a quantized dynamic index consistent."""
    X, Q = _clustered(n=600, d=24, seed=12)
    idx = SegmentedLCCSIndex.build(X[:400], m=16, w=4.0, seed=13, store="int8")
    gids = idx.insert(X[400:])
    idx.delete(gids[:20])
    idx.compact()
    ids0, d0 = idx.search(Q, SearchParams(k=5, lam=80))
    remap = idx.vacuum()
    assert idx.n_live == 580 and (remap >= -1).all()
    ids1, d1 = idx.search(Q, SearchParams(k=5, lam=80))
    # same vectors, renumbered ids: distances must be preserved
    np.testing.assert_allclose(np.sort(np.asarray(d0), axis=1),
                               np.sort(np.asarray(d1), axis=1), rtol=1e-5)
