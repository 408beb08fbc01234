"""LSH function families.

LCCS-LSH is LSH-family-independent (paper §2.2/§4): the scheme only consumes
the (n, m) int32 matrix of hash values.  Each family here provides:

  hash(X: (n, d) float) -> (n, m) int32           batched hashing (jit-able)
  alternatives(X: (B, d)) -> (vals, scores)       batched multi-probe
      vals:   (B, m, n_alt) int32  -- alternative hash values per position,
      scores: (B, m, n_alt) float  -- ascending penalty per alternative
                                      (consumed by MP-LCCS-LSH, Algorithm 3).
      Pure JAX: traced into the jitted multiprobe candidate sources.
  query_alternatives(q: (d,)) -> (vals, scores)    single-query numpy wrapper
                                                   around `alternatives`.

All families are registered as JAX pytrees (arrays are children; scalar
hyper-parameters are static aux data), so a family -- and any `LCCSIndex`
holding one -- can be passed straight through `jax.jit`, `device_put`, and
sharding APIs.

Families implemented:
  * RandomProjectionLSH  -- Datar et al. 2004, Euclidean distance (Eq. 1).
  * CrossPolytopeLSH     -- Andoni et al. 2015, Angular distance (Eq. 3).
       rotation="gaussian" is the paper's exact definition (dense random
       rotation); rotation="pseudo" is the FALCONN HD3HD2HD1 pseudo-rotation
       (O(d log d), used by default for speed -- same LSH guarantees).
  * BitSamplingLSH       -- Indyk & Motwani 1998, Hamming distance.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from . import theory


def _next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length()


# ---------------------------------------------------------------------------
# Random projection family (Euclidean)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RandomProjectionLSH:
    """h(o) = floor((a . o + b) / w)   (paper Eq. 1)."""

    a: jax.Array  # (d, m)
    b: jax.Array  # (m,)
    w: float
    metric: str = field(default="euclidean")

    @staticmethod
    def create(key: jax.Array, d: int, m: int, w: float) -> "RandomProjectionLSH":
        ka, kb = jax.random.split(key)
        a = jax.random.normal(ka, (d, m), dtype=jnp.float32)
        b = jax.random.uniform(kb, (m,), dtype=jnp.float32, minval=0.0, maxval=w)
        return RandomProjectionLSH(a=a, b=b, w=float(w))

    @property
    def m(self) -> int:
        return self.a.shape[1]

    @property
    def d(self) -> int:
        return self.a.shape[0]

    def projections(self, x: jax.Array) -> jax.Array:
        # HIGHEST: the TPU's default matmul precision rounds the operands to
        # bf16, which moves bucket-edge hashes away from an fp32 (CPU) build
        # of the same rows
        return jnp.matmul(x.astype(jnp.float32), self.a,
                          precision=jax.lax.Precision.HIGHEST) + self.b

    def hash(self, x: jax.Array) -> jax.Array:
        proj = self.projections(x)
        return jnp.floor(proj / self.w).astype(jnp.int32)

    def collision_prob(self, tau: float) -> float:
        return theory.rp_collision_prob(tau, self.w)

    def alternatives(self, x: jax.Array, n_alt: int = 4):
        """Multi-Probe LSH (Lv et al. 2007) alternatives, batched: h +- j,
        scored by the squared distance of the projection to the boundary.
        x: (B, d) -> vals (B, m, n_alt) int32, scores (B, m, n_alt) ascending."""
        n_alt = max(2, n_alt)
        proj = self.projections(jnp.asarray(x, dtype=jnp.float32))  # (B, m)
        h = jnp.floor(proj / self.w)
        f = proj - h * self.w  # in-bucket offset, [0, w)
        js = jnp.arange(1, n_alt // 2 + 1, dtype=jnp.float32)  # (J,)
        up = ((js - 1.0) * self.w + (self.w - f[..., None])) ** 2  # (B, m, J)
        dn = ((js - 1.0) * self.w + f[..., None]) ** 2
        vals = jnp.stack([h[..., None] + js, h[..., None] - js], axis=-1)
        scores = jnp.stack([up, dn], axis=-1)
        vals = vals.reshape(*proj.shape, -1)  # (B, m, 2J): [h+1, h-1, h+2, ...]
        scores = scores.reshape(*proj.shape, -1)
        order = jnp.argsort(scores, axis=-1, stable=True)
        return (
            jnp.take_along_axis(vals, order, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(scores, order, axis=-1),
        )

    def query_alternatives(self, q: np.ndarray, n_alt: int = 4):
        vals, scores = self.alternatives(jnp.asarray(q)[None, :], n_alt)
        return np.asarray(vals[0]), np.asarray(scores[0])


# ---------------------------------------------------------------------------
# Cross-polytope family (Angular)
# ---------------------------------------------------------------------------


def _hadamard_transform(x: jax.Array) -> jax.Array:
    """Fast Walsh-Hadamard transform over the last axis (length = power of 2)."""
    d = x.shape[-1]
    h = 1
    while h < d:
        x = x.reshape(x.shape[:-1] + (d // (2 * h), 2, h))
        a = x[..., 0, :]
        b = x[..., 1, :]
        x = jnp.concatenate([a + b, a - b], axis=-1).reshape(x.shape[:-3] + (d,))
        h *= 2
    return x


@dataclass(frozen=True)
class CrossPolytopeLSH:
    """h(o) = index of the closest signed basis vector of the rotated o (Eq. 3).

    Hash value in [0, 2*dr): index i for +e_i, dr + i for -e_i.
    """

    signs: jax.Array  # pseudo: (m, 3, dr) +-1; gaussian: unused
    rot: jax.Array | None  # gaussian: (m, d, dr); pseudo: None
    d: int
    dr: int  # rotated dimension (power of two for pseudo)
    rotation: str = field(default="pseudo")
    metric: str = field(default="angular")

    @staticmethod
    def create(key: jax.Array, d: int, m: int, rotation: str = "pseudo") -> "CrossPolytopeLSH":
        if rotation == "pseudo":
            dr = _next_pow2(d)
            signs = jax.random.rademacher(key, (m, 3, dr), dtype=jnp.float32)
            return CrossPolytopeLSH(signs=signs, rot=None, d=d, dr=dr, rotation=rotation)
        elif rotation == "gaussian":
            rot = jax.random.normal(key, (m, d, d), dtype=jnp.float32) / math.sqrt(d)
            return CrossPolytopeLSH(
                signs=jnp.zeros((m, 0, 0)), rot=rot, d=d, dr=d, rotation=rotation
            )
        raise ValueError(f"unknown rotation {rotation!r}")

    @property
    def m(self) -> int:
        return self.signs.shape[0] if self.rotation == "pseudo" else self.rot.shape[0]

    def _rotate(self, x: jax.Array) -> jax.Array:
        """(n, d) -> (n, m, dr) rotated copies."""
        if self.rotation == "gaussian":
            return jnp.einsum("nd,mde->nme", x, self.rot,
                              precision=jax.lax.Precision.HIGHEST)
        n = x.shape[0]
        xp = jnp.pad(x, ((0, 0), (0, self.dr - self.d)))
        y = xp[:, None, :] * self.signs[None, :, 0, :]  # (n, m, dr)
        y = _hadamard_transform(y)
        y = y * self.signs[None, :, 1, :]
        y = _hadamard_transform(y)
        y = y * self.signs[None, :, 2, :]
        y = _hadamard_transform(y)
        return y / jnp.sqrt(jnp.float32(self.dr))

    def rotations(self, x: jax.Array) -> jax.Array:
        return self._rotate(x.astype(jnp.float32))

    def hash(self, x: jax.Array) -> jax.Array:
        y = self.rotations(x)  # (n, m, dr)
        idx = jnp.argmax(jnp.abs(y), axis=-1)  # (n, m)
        sgn = jnp.take_along_axis(y, idx[..., None], axis=-1)[..., 0] < 0
        return (idx + jnp.where(sgn, self.dr, 0)).astype(jnp.int32)

    def collision_prob(self, tau: float) -> float:
        return theory.xp_collision_prob(tau, self.dr)

    def alternatives(self, x: jax.Array, n_alt: int = 4):
        """FALCONN-style alternatives, batched: other cross-polytope vertices
        ranked by margin (|y_top| - |y_j|)^2.
        x: (B, d) -> vals (B, m, n_alt) int32, scores (B, m, n_alt) ascending."""
        n_alt = min(n_alt, self.dr - 1)
        y = self.rotations(jnp.asarray(x, dtype=jnp.float32))  # (B, m, dr)
        ay = jnp.abs(y)
        top_vals, top_idx = jax.lax.top_k(ay, n_alt + 1)  # best first
        idx = top_idx[..., 1:]  # (B, m, n_alt)
        sgn = jnp.take_along_axis(y, idx, axis=-1) < 0
        vals = (idx + jnp.where(sgn, self.dr, 0)).astype(jnp.int32)
        scores = (top_vals[..., :1] - top_vals[..., 1:]) ** 2
        return vals, scores

    def query_alternatives(self, q: np.ndarray, n_alt: int = 4):
        vals, scores = self.alternatives(jnp.asarray(q)[None, :], n_alt)
        return np.asarray(vals[0]), np.asarray(scores[0])


# ---------------------------------------------------------------------------
# Bit sampling family (Hamming)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BitSamplingLSH:
    """h_i(o) = o[idx_i] for binary vectors (Indyk & Motwani 1998)."""

    idx: jax.Array  # (m,)
    d: int
    metric: str = field(default="hamming")

    @staticmethod
    def create(key: jax.Array, d: int, m: int) -> "BitSamplingLSH":
        idx = jax.random.randint(key, (m,), 0, d)
        return BitSamplingLSH(idx=idx, d=d)

    @property
    def m(self) -> int:
        return self.idx.shape[0]

    def hash(self, x: jax.Array) -> jax.Array:
        return x[:, self.idx].astype(jnp.int32)

    def collision_prob(self, tau: float) -> float:
        # tau = Hamming distance; p = 1 - tau/d
        return max(0.0, 1.0 - tau / self.d)

    def alternatives(self, x: jax.Array, n_alt: int = 1):
        """Only one alternative per bit: flip it.  x: (B, d) binary."""
        qv = jnp.asarray(x)[:, self.idx].astype(jnp.int32)  # (B, m)
        vals = (1 - qv)[..., None]
        scores = jnp.ones(vals.shape, dtype=jnp.float32)
        return vals, scores

    def query_alternatives(self, q: np.ndarray, n_alt: int = 1):
        vals, scores = self.alternatives(jnp.asarray(q)[None, :], n_alt)
        return np.asarray(vals[0]), np.asarray(scores[0])


def make_family(kind: str, key: jax.Array, d: int, m: int, **kw):
    if kind in ("rp", "euclidean", "random_projection"):
        return RandomProjectionLSH.create(key, d, m, w=kw.get("w", 4.0))
    if kind in ("xp", "angular", "cross_polytope"):
        return CrossPolytopeLSH.create(key, d, m, rotation=kw.get("rotation", "pseudo"))
    if kind in ("bits", "hamming", "bit_sampling"):
        return BitSamplingLSH.create(key, d, m)
    raise ValueError(f"unknown LSH family {kind!r}")


# data rows are hashed in blocks of this many rows, all by the one program
# compiled for that block shape
HASH_BLOCK_ROWS = 4096

_hash_block = jax.jit(lambda family, x: family.hash(x))


def hash_rows(family, x) -> jax.Array:
    """(n, d) data rows -> (n, m) int32 hash strings, computed one
    fixed-shape block at a time.  Every path that hashes corpus rows (the
    monolithic and streaming builds, inserts, chunked ingest) goes through
    here, so a row's hash string never depends on the rows hashed with it:
    on a TPU the fp32 projection can round differently for a different
    batch shape, and `floor` turns one ulp at a bucket edge into another
    hash value -- enough for a segmented index to answer differently from a
    rebuild over the same rows."""
    n = x.shape[0]
    if n == 0:
        return family.hash(jnp.asarray(x, jnp.float32))
    blocks = []
    for lo in range(0, n, HASH_BLOCK_ROWS):
        xb = jnp.asarray(x[lo:lo + HASH_BLOCK_ROWS], jnp.float32)
        if xb.shape[0] < HASH_BLOCK_ROWS:
            xb = jnp.pad(xb, ((0, HASH_BLOCK_ROWS - xb.shape[0]), (0, 0)))
        blocks.append(_hash_block(family, xb))
    return jnp.concatenate(blocks)[:n]


def distance(x: jax.Array, y: jax.Array, metric: str) -> jax.Array:
    """Pairwise-free distance between matching rows of x and y (broadcasting ok)."""
    if metric == "euclidean":
        return jnp.sqrt(jnp.maximum(jnp.sum((x - y) ** 2, axis=-1), 0.0))
    if metric == "angular":
        # clamp norms: a zero vector must yield a finite (maximal) distance,
        # not NaN-poisoned verification
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
        return 1.0 - jnp.sum(xn * yn, axis=-1)  # monotone in angle
    if metric == "hamming":
        return jnp.sum(x != y, axis=-1).astype(jnp.float32)
    raise ValueError(f"unknown metric {metric!r}")


# ---------------------------------------------------------------------------
# Pytree registration: arrays are children, hyper-parameters are static aux.
# This is what lets jax.jit trace a whole LCCSIndex (which holds a family)
# and lets indexes be device_put / sharded / donated as first-class values.
# ---------------------------------------------------------------------------

for _cls, _data, _meta in (
    (RandomProjectionLSH, ("a", "b"), ("w", "metric")),
    (CrossPolytopeLSH, ("signs", "rot"), ("d", "dr", "rotation", "metric")),
    (BitSamplingLSH, ("idx",), ("d", "metric")),
):
    jax.tree_util.register_dataclass(
        _cls, data_fields=list(_data), meta_fields=list(_meta)
    )
