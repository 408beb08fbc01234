"""ShardedLCCSIndex semantics: sharded == monolithic exactness, uneven-split
global ids, registry/pytree integration.

Multi-device tests spawn a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N so this process keeps its
own device view (launch contract); single-shard API tests run in-process
(a 1-device mesh exercises the whole shard_map pipeline).
"""
import numpy as np
import pytest

from conftest import run_multidevice


def _run(script: str, n_dev: int = 4) -> str:
    return run_multidevice(script, n_dev)


# ---------------------------------------------------------------------------
# The acceptance property: sharded == monolithic for every source x store x
# shard count, in a complete-coverage configuration (lam and the window width
# cover every row, and rerank_mult covers every survivor) where the candidate
# sets provably coincide -- any deviation is a merge/offset/store-slicing bug
# rather than tie noise.
# ---------------------------------------------------------------------------


def test_sharded_matches_monolithic_all_sources_stores_shards():
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import LCCSIndex, SearchParams, jit_search
        from repro.shard import make_shard_mesh

        rng = np.random.default_rng(0)
        n, d, B, k = 96, 16, 4, 8
        X = rng.normal(size=(n, d)).astype(np.float32)
        Q = rng.normal(size=(B, d)).astype(np.float32)
        base = SearchParams(k=k, lam=128, width=128, rerank_mult=16,
                            use_gather_kernel=False)
        meshes = {S: make_shard_mesh(S) for S in (1, 2, 4)}
        for store in ("fp32", "bf16", "int8"):
            mono = LCCSIndex.build(X, m=16, family="euclidean", w=4.0,
                                   seed=0, store=store)
            sharded = {S: mono.shard(mesh) for S, mesh in meshes.items()}
            for source in ("bruteforce", "lccs", "multiprobe-full",
                           "multiprobe-skip"):
                p = base.replace(
                    source=source,
                    probes=3 if "multiprobe" in source else 1)
                ids_m, d_m = map(np.asarray, jit_search(mono, Q, p))
                for S, sidx in sharded.items():
                    ids_s, d_s = map(np.asarray, sidx.search(Q, p))
                    tag = f"{store}/{source}/S={S}"
                    np.testing.assert_allclose(
                        np.sort(d_s, axis=1), np.sort(d_m, axis=1),
                        rtol=1e-6, atol=0.0, err_msg=tag)
                    for row_s, row_m, dr_s, dr_m in zip(ids_s, ids_m, d_s, d_m):
                        # id sets must agree wherever distances are untied
                        if len(set(np.round(dr_m, 5))) == len(dr_m):
                            assert set(row_s.tolist()) == set(row_m.tolist()), tag
        print("PROPERTY-OK")
        """,
        n_dev=4,
    )
    assert "PROPERTY-OK" in out


def test_uneven_split_global_ids_regression():
    """n=1001 over 4 shards: the seed `core.distributed` sketch computed
    global ids as shard_id * (n // n_shards), silently wrong on uneven
    splits; the sharded layout must pad + mask and stay exact."""
    out = _run(
        """
        import numpy as np, jax
        from repro.core import LCCSIndex, SearchParams, jit_search
        from repro.shard import make_shard_mesh

        rng = np.random.default_rng(1)
        n, d, B, k = 1001, 16, 6, 10
        X = rng.normal(size=(n, d)).astype(np.float32)
        Q = rng.normal(size=(B, d)).astype(np.float32)
        p = SearchParams(k=k, lam=1024, source="bruteforce",
                         use_gather_kernel=False)
        mono = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=0)
        ids_m, d_m = map(np.asarray, jit_search(mono, Q, p))
        sidx = mono.shard(make_shard_mesh(4))
        assert sidx.shards == 4 and sidx.n == n
        assert sidx.rows_per_shard * 4 >= n  # padded, not truncated
        ids_s, d_s = map(np.asarray, sidx.search(Q, p))
        assert ((ids_s >= 0) & (ids_s < n)).all(), ids_s  # never aliased
        np.testing.assert_allclose(np.sort(d_s, axis=1), np.sort(d_m, axis=1),
                                   rtol=1e-6, atol=0.0)
        for a, b in zip(ids_s, ids_m):
            assert set(a.tolist()) == set(b.tolist())
        print("UNEVEN-OK")
        """,
        n_dev=4,
    )
    assert "UNEVEN-OK" in out


def test_shard_csas_built_on_their_own_devices():
    """Each shard's CSA comes out of the build on the device that holds the
    shard's rows (one (1, ...) slice per device), equal to `build_csa` over
    that shard's hash strings alone."""
    out = _run(
        """
        import numpy as np, jax
        from repro.core import LCCSIndex, build_csa
        from repro.shard import make_shard_mesh

        X = np.random.default_rng(2).normal(size=(203, 16)).astype(np.float32)
        mesh = make_shard_mesh(4)
        sidx = LCCSIndex.build(X, m=8, family="euclidean", w=4.0,
                               seed=0).shard(mesh)
        devices = list(mesh.devices.flat)
        for leaf in jax.tree.leaves(sidx.csa):
            shards = sorted(leaf.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            assert [s.device for s in shards] == devices
            assert all(s.data.shape[0] == 1 for s in shards)
        h = np.asarray(sidx.h)
        for s in range(4):
            want = build_csa(jax.numpy.asarray(h[s]))
            for got, ref in zip(jax.tree.leaves(sidx.csa),
                                jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(got)[s],
                                              np.asarray(ref))
        print("PLACED-OK")
        """,
        n_dev=4,
    )
    assert "PLACED-OK" in out


def test_distributed_query_shim_uneven_n():
    """The deprecated `core.distributed.distributed_query` shim now routes
    through repro.shard and must be exact at n % n_shards != 0."""
    out = _run(
        """
        import warnings
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import make_family, distance
        from repro.core.distributed import distributed_query
        from repro.launch.mesh import make_debug_mesh

        rng = np.random.default_rng(2)
        n, d, B, k = 1001, 16, 4, 10
        X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        Q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
        fam = make_family("euclidean", jax.random.key(0), d, 16, w=4.0)
        mesh = make_debug_mesh(4, 1)
        h = fam.hash(X)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            ids, dists = distributed_query(fam, X, h, Q, mesh, k=k, lam=1024)
        ids, dists = np.asarray(ids), np.asarray(dists)
        assert ((ids >= 0) & (ids < n)).all()
        # lam >= n: candidates are complete, so this is exact k-NN
        d2 = np.asarray(distance(X[None, :, :], Q[:, None, :], "euclidean"))
        want = np.sort(d2, axis=1)[:, :k]
        np.testing.assert_allclose(np.sort(dists, axis=1), want, rtol=1e-5)
        print("SHIM-OK")
        """,
        n_dev=4,
    )
    assert "SHIM-OK" in out


# ---------------------------------------------------------------------------
# In-process API tests (1-device mesh still runs the full shard_map pipeline)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small():
    from repro.core import LCCSIndex

    rng = np.random.default_rng(3)
    X = rng.normal(size=(50, 8)).astype(np.float32)
    Q = rng.normal(size=(3, 8)).astype(np.float32)
    return X, Q, LCCSIndex.build(X, m=8, family="euclidean", w=4.0, seed=0)


def test_single_shard_mesh_roundtrip(small):
    from repro.core import SearchParams, jit_search
    from repro.shard import make_shard_mesh

    X, Q, mono = small
    p = SearchParams(k=5, lam=64, width=64, use_gather_kernel=False)
    sidx = mono.shard(make_shard_mesh(1))
    assert sidx.shards == 1 and sidx.n == 50 and sidx.m == 8
    ids_s, d_s = map(np.asarray, sidx.search(Q, p))
    ids_m, d_m = map(np.asarray, jit_search(mono, Q, p))
    np.testing.assert_allclose(np.sort(d_s, axis=1), np.sort(d_m, axis=1),
                               rtol=1e-6)
    assert sidx.index_bytes() > 0 and sidx.store_bytes() > 0


def test_sharded_is_pytree(small):
    import jax

    from repro.shard import ShardedLCCSIndex, make_shard_mesh

    _, Q, mono = small
    sidx = mono.shard(make_shard_mesh(1))
    leaves, treedef = jax.tree.flatten(sidx)
    again = jax.tree.unflatten(treedef, leaves)
    assert isinstance(again, ShardedLCCSIndex)
    assert again.mesh == sidx.mesh and again.n_rows == sidx.n_rows
    from repro.core import SearchParams

    ids, _ = again.search(Q, SearchParams(k=3, lam=16, use_gather_kernel=False))
    assert np.asarray(ids).shape == (3, 3)


def test_sharded_source_registered_and_guards(small):
    import jax.numpy as jnp

    from repro.core import SearchParams, available_sources, jit_search
    from repro.core.index import candidates
    from repro.shard import make_shard_mesh

    X, Q, mono = small
    assert "sharded" in available_sources()
    sidx = mono.shard(make_shard_mesh(1))
    # candidate generation through the registry returns global ids
    p = SearchParams(lam=64, width=64, source="sharded", inner="lccs")
    ids, lcps = candidates(sidx, jnp.asarray(Q), p)
    ids = np.asarray(ids)
    assert ids.shape == (3, 64)
    assert ids.max() < 50 and (ids[ids >= 0] >= 0).all()
    # the pure monolithic pipeline body still refuses a sharded index
    # (stacked store); jit_search itself now routes through the sharded
    # topology plan instead of raising
    from repro.core.index import search as pure_search

    with pytest.raises(TypeError, match="ShardedLCCSIndex"):
        pure_search(sidx, jnp.asarray(Q), SearchParams(k=3, lam=16))
    ids_j, _ = jit_search(sidx, jnp.asarray(Q),
                          SearchParams(k=3, lam=16, use_gather_kernel=False))
    assert np.asarray(ids_j).shape == (3, 3)
    # the "sharded" source refuses a monolithic index
    with pytest.raises(TypeError, match="ShardedLCCSIndex"):
        candidates(mono, jnp.asarray(Q), p)


def test_params_shards_validation(small):
    from repro.core import SearchParams
    from repro.shard import make_shard_mesh

    _, Q, mono = small
    sidx = mono.shard(make_shard_mesh(1))
    with pytest.raises(ValueError, match="shards"):
        sidx.search(Q, SearchParams(k=3, lam=16, shards=4))
    ids, _ = sidx.search(Q, SearchParams(k=3, lam=16, shards=1,
                                         use_gather_kernel=False))
    assert np.asarray(ids).shape == (3, 3)
    with pytest.raises(ValueError, match="recurse"):
        SearchParams(inner="sharded")
    with pytest.raises(ValueError, match="shards must be"):
        SearchParams(shards=0)


def test_disk_tail_rejected(small, tmp_path):
    from repro.core import LCCSIndex
    from repro.shard import make_shard_mesh

    X, _, _ = small
    idx = LCCSIndex.build(X, m=8, family="euclidean", w=4.0, seed=0,
                          store="int8", tail_path=tmp_path / "tail.npy")
    with pytest.raises(ValueError, match="disk-lazy"):
        idx.shard(make_shard_mesh(1))
