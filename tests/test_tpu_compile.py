"""Compile the TPU query path for a described TPU v5e, with no chip attached.

The TPU compiler ships with jaxlib's TPU plug-in and compiles for a
`v5e:2x2` topology that is described, not present.  These tests therefore
catch, at no chip time, what interpret-mode Pallas and the CPU backend
cannot: block shapes the TPU lowering refuses, programs that overflow the
16 GB of HBM, and sharding rules the mesh cannot partition.  Shapes are the
SIFT1M cell the chip smoke test runs: n=10^6, d=128, m=64, batches of 32.

Code that asks `jax.default_backend()` sees the CPU here, so the tests that
need the TPU's choices patch it while the params are resolved (and only
then); nothing is compiled for, or run on, the host CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import LCCSIndex, SearchParams
from repro.core.bruteforce import bruteforce_topk
from repro.core.csa import CSA
from repro.core.lsh import RandomProjectionLSH
from repro.exec.plan import get_topology, topology_of
from repro.store.stores import Fp32Store, Int8Store

N, D, M, B = 1_000_000, 128, 64, 32
HBM = 16 * 2**30  # one v5e chip
SOURCES = {
    "lccs": dict(source="lccs", lam=256, width=64),
    "multiprobe-skip": dict(source="multiprobe-skip", lam=256, width=64,
                            probes=17),
    "bruteforce": dict(source="bruteforce", lam=256),
}
i32, f32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mono_index(store: str, sh) -> LCCSIndex:
    s = lambda *shape, dtype=i32: _shape(shape, dtype, sh)
    family = RandomProjectionLSH(a=s(D, M, dtype=f32), b=s(M, dtype=f32),
                                 w=60.0)
    if store == "fp32":
        vecs, tail = Fp32Store(rows=s(N, D, dtype=f32)), None
    else:
        vecs = Int8Store(q=s(N, D, dtype=jnp.int8), scale=s(N, dtype=f32))
        tail = s(N, D, dtype=f32)
    csa = CSA(I=s(M, N), P=s(M, N), Hd=s(N, 2 * M), L=s(M, N))
    return LCCSIndex(family=family, store=vecs, h=s(N, M), csa=csa,
                     metric="euclidean", tail=tail)


def _tpu_plan(index, params: SearchParams, monkeypatch):
    """(resolved params, jitted plan) as `repro.exec` builds them on a TPU
    backend -- outside the process plan cache."""
    adapter = get_topology(topology_of(index))
    with monkeypatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        p = adapter.resolve(index, params)
    return p, adapter.build(index, p)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < HBM, f"{used / 2**30:.2f} GiB does not fit one v5e"
    return used


# one plan per store (bruteforce's scorer has its own test below): the
# single-probe and the widest multiprobe worklist
@pytest.mark.parametrize("store,source", [("fp32", "lccs"),
                                          ("int8", "multiprobe-skip")])
def test_search_plan_compiles_for_one_chip(store, source, one_chip,
                                           monkeypatch):
    index = _mono_index(store, one_chip)
    p, plan = _tpu_plan(index, SearchParams(k=10, **SOURCES[source]),
                        monkeypatch)
    # the TPU path: the fused probe in its XLA form, the XLA verify gather
    assert p.use_probe_kernel is True and p.use_gather_kernel is False
    compiled = plan.lower(index, _shape((B, D), f32, one_chip)).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # the probe dedupes each query's pool by sorting it: no (B, n) buffer
    assert f"s32[{B},{N}]" not in text


def test_bruteforce_topk_fits_one_chip(one_chip):
    # a whole-batch vmap of the scorer needed ~17 GB here
    compiled = bruteforce_topk.lower(
        _shape((N, M), i32, one_chip), _shape((B, M), i32, one_chip), lam=256
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    _fits(compiled)


def _refused_kernels():
    from repro.kernels.csa_probe.csa_probe import csa_probe_pallas
    from repro.kernels.gather_l2.gather_l2 import gather_dist_pallas
    from repro.kernels.gather_q.gather_q import gather_dist_q_pallas

    L = 256  # candidates per query
    R = B * M  # (query, shift) worklist rows of one lccs batch
    return {
        "gather_l2": lambda s: gather_dist_pallas.lower(
            s((N, D), f32), s((B, L), i32), s((B, D), f32), interpret=False),
        "gather_q": lambda s: gather_dist_q_pallas.lower(
            s((N, D), jnp.int8), s((N,), f32), s((B, L), i32), s((B, D), f32),
            interpret=False),
        "csa_probe": lambda s: csa_probe_pallas.lower(
            s((M, N), i32), s((M, N), i32), s((N, 2 * M), i32),
            s((B, 2 * M), i32), s((R,), i32), s((R,), i32), width=64,
            interpret=False),
    }


@pytest.mark.parametrize("kernel", ["csa_probe", "gather_l2", "gather_q"])
def test_pallas_kernel_refused_by_tpu_lowering(kernel, one_chip):
    """Why the TPU path selects no Pallas kernel: each one's one-row blocks
    break the TPU's (8, 128) block tiling rule.  A kernel that starts to
    lower here is a candidate to put back on the TPU path."""
    lower = _refused_kernels()[kernel]
    s = lambda shape, dtype: _shape(shape, dtype, one_chip)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        lower(s).compile()


def test_sharded_search_plan_compiles_on_mesh(topo, monkeypatch):
    from repro.shard.index import ShardedLCCSIndex, _build_shard_csas

    S = len(topo.devices)
    mesh = Mesh(np.asarray(topo.devices).reshape(S), ("data",))
    rep = NamedSharding(mesh, P())
    row = lambda nd: NamedSharding(mesh, P("data", *([None] * (nd - 1))))
    s = lambda *shape, dtype=i32: _shape((S,) + shape, dtype, row(len(shape) + 1))

    # each shard's CSA is built on the chip holding the shard's rows
    build = jax.jit(lambda h: _build_shard_csas(h, mesh, "data"))
    built = build.lower(s(N, M)).compile()
    _fits(built)
    for leaf in jax.tree.leaves(built.output_shardings):
        assert leaf.spec[0] == "data"

    index = ShardedLCCSIndex(
        family=RandomProjectionLSH(a=_shape((D, M), f32, rep),
                                   b=_shape((M,), f32, rep), w=60.0),
        store=Fp32Store(rows=s(N, D, dtype=f32)), h=s(N, M),
        csa=CSA(I=s(M, N), P=s(M, N), Hd=s(N, 2 * M), L=s(M, N)),
        gid=s(N), metric="euclidean", mesh=mesh, axis="data", n_rows=S * N,
    )
    p, plan = _tpu_plan(index, SearchParams(k=10, **SOURCES["lccs"]),
                        monkeypatch)
    assert p.source == "sharded" and p.inner == "lccs"
    compiled = plan.lower(index, _shape((B, D), f32, rep)).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "all-gather" in text and "tpu_custom_call" not in text
