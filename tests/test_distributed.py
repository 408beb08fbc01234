"""Multi-device semantics tests.  Each test spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N (shared recipe in
conftest.run_multidevice) so the main test process keeps the invoking
environment's device view (launch contract)."""
import pytest

from conftest import run_multidevice


def _run(script: str, n_dev: int = 8) -> str:
    return run_multidevice(script, n_dev)


@pytest.mark.slow
def test_moe_sharded_matches_local():
    """GShard-style shard_map dispatch == single-program dispatch (no drops)."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.models.moe import MoEConfig, init_moe, _moe_local, _moe_sharded
        from repro.launch.mesh import make_debug_mesh
        from repro.sharding.specs import shard_ctx

        cfg = MoEConfig(d_model=32, d_ff=64, n_experts=8, top_k=2,
                        capacity_factor=64.0)  # no drops
        p = init_moe(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 16, 32), jnp.float32)
        ref, aux_ref = _moe_local(p, x, cfg)
        mesh = make_debug_mesh(2, 4)
        with shard_ctx(mesh):
            got, aux = jax.jit(lambda p, x: _moe_sharded(p, x, cfg, mesh))(p, x)
        err = float(jnp.max(jnp.abs(got - ref)))
        aux_err = abs(float(aux) - float(aux_ref))
        print("ERR", err, aux_err)
        assert err < 1e-4, err
        assert aux_err < 1e-4, (float(aux), float(aux_ref))
        """,
        n_dev=8,
    )
    assert "ERR" in out


def test_distributed_lccs_index_matches_single():
    """Sharded brute-force LCCS query == single-device query (exact merge)."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import make_family, distance
        from repro.core.distributed import (
            build_sharded_hashes, distributed_query, shard_database)
        from repro.core.bruteforce import circ_run_lengths
        from repro.launch.mesh import make_debug_mesh

        rng = np.random.default_rng(0)
        n, d, B = 512, 16, 4
        X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        Q = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
        fam = make_family("euclidean", jax.random.key(0), d, 16, w=4.0)
        mesh = make_debug_mesh(8, 1)
        Xs = shard_database(X, mesh)
        h = build_sharded_hashes(fam, Xs, mesh)
        ids, dists = distributed_query(fam, Xs, h, Q, mesh, k=5, lam=32)
        # single-device reference: same scoring, same verification
        h1 = fam.hash(X)
        for b in range(B):
            lens = circ_run_lengths(h1, fam.hash(Q[b:b+1])[0])
            # reference: per-shard top-32 then global top-5 (same schedule)
            parts = []
            for s in range(8):
                lo, hi = s*64, (s+1)*64
                idx = jnp.argsort(-lens[lo:hi], stable=True)[:32] + lo
                parts.append(idx)
            cand = jnp.concatenate(parts)
            dd = distance(X[cand], Q[b][None, :], "euclidean")
            best = cand[jnp.argsort(dd, stable=True)[:5]]
            got_d = np.sort(np.asarray(dists[b]))
            want_d = np.sort(np.asarray(distance(X[best], Q[b][None,:], "euclidean")))
            np.testing.assert_allclose(got_d, want_d, rtol=1e-5)
        print("DIST-OK")
        """,
        n_dev=8,
    )
    assert "DIST-OK" in out


def test_grad_compress_int8_psum():
    """Int8-compressed psum ~= exact mean; error feedback shrinks bias."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.optim import compress_psum_int8

        mesh = make_debug_mesh(8, 1)
        g = jax.random.normal(jax.random.key(0), (8, 64))  # row per device
        grads = {"w": g}
        err0 = {"w": jnp.zeros((8, 64))}

        def step(grads, err):
            return compress_psum_int8(grads, err, ("data",))

        fn = jax.shard_map(step, mesh=mesh,
                           in_specs=({"w": P("data", None)}, {"w": P("data", None)}),
                           out_specs=({"w": P("data", None)}, {"w": P("data", None)}),
                           check_vma=False)
        red, err = fn(grads, err0)
        exact = jnp.mean(g, axis=0)
        # every device row holds the same reduced mean
        approx = red["w"][0]
        rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
        print("REL", rel)
        assert rel < 0.02, rel
        # error feedback: residuals are bounded by one quantisation step
        s = float(jnp.max(jnp.abs(g)) / 127.0)
        assert float(jnp.max(jnp.abs(err["w"]))) <= s + 1e-6
        """,
        n_dev=8,
    )
    assert "REL" in out


@pytest.mark.slow
def test_dryrun_single_cell_multipod():
    """The multi-pod mesh (2x16x16=512 fake devices) lowers+compiles one cell."""
    out = _run(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import lower_cell
        res = lower_cell("whisper-tiny", "train_4k", multi_pod=True)
        assert res["status"] == "ok", res
        assert res["n_chips"] == 512
        print("MP-OK", res["roofline"]["bottleneck"])
        """,
        n_dev=512,
    )
    assert "MP-OK" in out


def test_elastic_checkpoint_restore_onto_mesh():
    """Fault tolerance at scale: a checkpoint written host-side restores onto
    a (different) device mesh with the caller's shardings (elastic restart)."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_debug_mesh

        tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((4,))}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, keep=2)
        mgr.save(5, tree, extra={"data": {"step": 5}})

        mesh = make_debug_mesh(4, 2)
        shardings = {
            "w": NamedSharding(mesh, P("data", "model")),
            "b": NamedSharding(mesh, P(None)),
        }
        restored, meta = mgr.restore(tree, shardings=shardings)
        assert restored["w"].sharding.spec == P("data", "model")
        np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8))
        assert meta["extra"]["data"]["step"] == 5
        print("ELASTIC-OK")
        """,
        n_dev=8,
    )
    assert "ELASTIC-OK" in out
