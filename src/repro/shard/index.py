"""ShardedLCCSIndex -- the monolithic LCCS-LSH index partitioned over a mesh.

The paper's query phase (Algorithm 2) is pointwise per object: candidates are
proposed per database string and verified by a per-row distance.  Shard-local
search plus a global top-k merge is therefore *exact* with respect to the
union of the per-shard candidate sets -- the property that makes FAISS-style
index sharding (Johnson et al., billion-scale GPU search) the right scaling
axis, rather than replicating a brute-force scan.

Layout: corpus rows are partitioned contiguously over the mesh's `axis`
(default "data") into S equal blocks (the last block is padded with sentinel
hash strings and gid = -1, so n does NOT have to divide S).  Every pytree
leaf gains a leading shard axis:

  h     (S, rows, m)   per-shard hash strings, sentinel-padded
  csa   CSA with leaves (S, m, rows) / (S, rows, 2m) -- one CSA per shard,
        built by `build_csa` on the shard's own device (a shard_map over the
        row-partitioned hashes), NOT a split of the global CSA
  gid   (S, rows)      global row ids, -1 on padding
  store VectorStore with leaves (S, rows, ...) -- per-shard vector slices
  tail  (S, rows, d)   per-shard fp32 rerank rows (inexact stores)

The LSH family is ONE shared pytree (replicated): hash strings are comparable
across shards, and queries are hashed once.  `search` runs the whole
hash -> candidate-source -> two-stage-verify pipeline under `shard_map`
(see `repro.shard.search`) and finishes with an `all_gather` + exact global
top-k merge.  Any registered candidate source runs per shard via
`SearchParams.inner` -- the "sharded" registry entry mirrors how "segmented"
wraps an inner source.

Construction::

    from repro.shard import ShardedLCCSIndex, make_shard_mesh

    mesh = make_shard_mesh(4)                     # first 4 devices, axis "data"
    index = ShardedLCCSIndex.build(X, mesh=mesh, m=64, family="euclidean")
    ids, dists = index.search(Q, SearchParams(k=10, lam=200))

    # or partition an existing monolithic index (per-shard CSAs are rebuilt):
    index = LCCSIndex.build(X, m=64).shard(mesh)

On CPU, fake multi-device platforms come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
initialises; see tests/test_shard.py and benchmarks/fig13_sharded.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.csa import CSA, build_csa
from repro.core.index import LCCSIndex
from repro.core.params import SearchParams

_PAD_HASH = np.iinfo(np.int32).max  # sentinel hash value for padded rows


def make_shard_mesh(n_shards: int, *, axis: str = "data") -> Mesh:
    """A 1-axis mesh over the first `n_shards` devices.  On CPU, grow the
    device count with XLA_FLAGS=--xla_force_host_platform_device_count=N
    (must be set before jax initialises its backends)."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"need {n_shards} devices for {n_shards} shards, have "
            f"{len(devices)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} before jax "
            "initialises"
        )
    return Mesh(np.asarray(devices[:n_shards]), (axis,))


def _row_spec(x: jax.Array, axis: str) -> P:
    """Leading-axis sharding spec for a leaf: P(axis, None, ...)."""
    return P(axis, *([None] * (x.ndim - 1)))


def _stack_rows(tree, S: int, rows: int, fill=0):
    """Pad every leaf's leading (row) axis to S*rows and fold it into a
    leading shard axis: (n, ...) -> (S, rows, ...)."""

    def f(x):
        n = x.shape[0]
        if n < S * rows:
            pad = jnp.full((S * rows - n,) + x.shape[1:], fill, x.dtype)
            x = jnp.concatenate([x, pad])
        return x.reshape((S, rows) + x.shape[1:])

    return jax.tree.map(f, tree)


@dataclass
class ShardedLCCSIndex:
    """LCCS-LSH index with rows partitioned over `mesh`'s `axis` (see module
    docstring for the layout).  A registered pytree: arrays (store / h / csa /
    gid / tail and the shared family) are leaves; the metric, mesh, axis name
    and true row count are static aux data, so `jit` caches per mesh."""

    family: Any  # shared LSH family (replicated pytree)
    store: Any  # VectorStore with leading shard axis on every leaf
    h: jax.Array  # (S, rows, m) int32, sentinel-padded
    csa: CSA | None  # per-shard CSAs, stacked; None for bruteforce-only
    gid: jax.Array  # (S, rows) int32 global ids, -1 on padding
    metric: str
    mesh: Mesh
    axis: str
    n_rows: int  # true (unpadded) corpus size
    tail: jax.Array | None = None  # (S, rows, d) fp32 rerank rows

    # class marker so repro.core can guard without importing this package
    sharded = True
    tail_path = None  # disk-lazy tails are a monolithic-index feature
    # topology marker consumed by the repro.exec plan dispatch (the adapter
    # itself is registered by repro.shard.search)
    topology = "sharded"

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(
        data,
        *,
        mesh: Mesh,
        axis: str = "data",
        m: int = 64,
        family: str = "euclidean",
        seed: int = 0,
        build_csa_structure: bool = True,
        store: str = "fp32",
        **family_kw,
    ) -> "ShardedLCCSIndex":
        """Hash + per-shard CSA build over `data`, rows partitioned over
        `mesh`'s `axis`.  Same family construction (and therefore the same
        hash functions) as `LCCSIndex.build`, so a sharded index is search-
        equivalent to the monolithic one built from the same arguments."""
        mono = LCCSIndex.build(
            data, m=m, family=family, seed=seed, build_csa_structure=False,
            store=store, **family_kw,
        )
        return shard_index(
            mono, mesh, axis=axis, build_csa_structure=build_csa_structure
        )

    # -- introspection ------------------------------------------------------

    @property
    def shards(self) -> int:
        return self.h.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.h.shape[1]

    @property
    def n(self) -> int:
        return self.n_rows

    @property
    def m(self) -> int:
        return self.h.shape[2]

    def index_bytes(self) -> int:
        """CSA + hash strings footprint, summed over shards (incl. padding)."""
        tot = self.h.size * 4
        if self.csa is not None:
            tot += (self.csa.I.size + self.csa.P.size + self.csa.Hd.size) * 4
            if self.csa.L is not None:
                tot += self.csa.L.size * 4
        return tot

    def store_bytes(self) -> int:
        tot = self.store.nbytes()
        if self.tail is not None:
            tot += self.tail.size * 4
        return tot

    def total_bytes(self) -> int:
        return self.index_bytes() + self.store_bytes()

    # -- search -------------------------------------------------------------

    def search(self, queries, params: SearchParams | None = None):
        """c-k-ANNS over all shards, jitted end to end via the plan cache
        (`repro.exec`).  `params.source` names the per-shard candidate
        source; the "sharded" topology adapter (`repro.shard.search`)
        rewrites it onto the "sharded" registry entry (source="sharded",
        inner=<source>), pins the kernel toggle, and validates the
        `params.shards` topology pin."""
        from repro.exec import execute

        return execute(self, queries, params)


jax.tree_util.register_dataclass(
    ShardedLCCSIndex,
    data_fields=["family", "store", "h", "csa", "gid", "tail"],
    meta_fields=["metric", "mesh", "axis", "n_rows"],
)


def shard_index(
    index: LCCSIndex,
    mesh: Mesh,
    *,
    axis: str = "data",
    build_csa_structure: bool | None = None,
) -> ShardedLCCSIndex:
    """Partition a monolithic `LCCSIndex` over `mesh`'s `axis`.

    Rows are split contiguously into mesh.shape[axis] equal blocks (the last
    padded with sentinel strings / gid=-1 when n does not divide evenly --
    padded rows are masked out of every candidate set, so uneven corpora are
    handled exactly).  Per-shard CSAs are rebuilt from the shard's rows
    (`build_csa_structure=None` keeps a CSA iff the source index had one);
    the family, store contents and tail are reused as-is.
    """
    if index.tail_path:
        raise ValueError(
            "disk-lazy rerank tails (tail_path=) are not supported by the "
            "sharded index; rebuild with an in-memory tail"
        )
    S = mesh.shape[axis]
    n, m = index.h.shape
    if S < 1:
        raise ValueError(f"mesh axis {axis!r} has size {S}")
    rows = -(-n // S)  # ceil: every shard gets an equal, padded block
    h = np.full((S * rows, m), _PAD_HASH, np.int32)
    h[:n] = np.asarray(index.h)
    gid = np.full((S * rows,), -1, np.int32)
    gid[:n] = np.arange(n, dtype=np.int32)
    hj = jax.device_put(h.reshape(S, rows, m),
                        NamedSharding(mesh, P(axis, None, None)))
    if build_csa_structure is None:
        build_csa_structure = index.csa is not None
    csa = _build_shard_csas(hj, mesh, axis) if build_csa_structure else None
    sharded = ShardedLCCSIndex(
        family=index.family,
        store=_stack_rows(index.store, S, rows),
        h=hj,
        csa=csa,
        gid=jnp.asarray(gid.reshape(S, rows)),
        metric=index.metric,
        mesh=mesh,
        axis=axis,
        n_rows=n,
        tail=None if index.tail is None else _stack_rows(index.tail, S, rows),
    )
    return _device_put_sharded(sharded)


def _build_shard_csas(hj: jax.Array, mesh: Mesh, axis: str) -> CSA:
    """One `build_csa` per shard, each on the device that holds the shard's
    rows: a shard_map over the row-partitioned (S, rows, m) hashes, so the
    build transients never gather on one chip."""
    local = lambda h: jax.tree.map(lambda x: x[None], build_csa(h[0]))
    build = jax.shard_map(local, mesh=mesh, in_specs=P(axis, None, None),
                          out_specs=P(axis), check_vma=False)
    return jax.jit(build)(hj)


def _device_put_sharded(index: ShardedLCCSIndex) -> ShardedLCCSIndex:
    """Place leaves on the mesh: row-partitioned fields over `axis` (leading
    shard dim), the shared family replicated."""
    mesh, axis = index.mesh, index.axis
    rep = NamedSharding(mesh, P())

    def put_rows(t):
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, _row_spec(x, axis))),
            t,
        )

    return ShardedLCCSIndex(
        family=jax.tree.map(lambda x: jax.device_put(x, rep), index.family),
        store=put_rows(index.store),
        h=put_rows(index.h),
        csa=put_rows(index.csa),
        gid=put_rows(index.gid),
        metric=index.metric,
        mesh=mesh,
        axis=index.axis,
        n_rows=index.n_rows,
        tail=put_rows(index.tail),
    )
