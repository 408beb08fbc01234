"""The plain reference that decides `correct`.  It imports nothing of the
system under test and takes nothing the system made: only the corpus and
the query vectors, regenerated from the seed.

`exact_knn` is `chip_smoke.py`'s exact search: squared L2 at HIGHEST matmul
precision over query blocks, then `top_k`.  `distances_of` recomputes, for
each returned id, the distance between the query and that corpus row.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("k",))
def _knn_blocks(X, Qb, *, k: int):
    hi = jax.lax.Precision.HIGHEST
    xx = jnp.sum(X * X, axis=1)

    def one(q):
        d2 = (xx[None, :] - 2.0 * jnp.einsum("qd,nd->qn", q, X, precision=hi)
              + jnp.sum(q * q, axis=1)[:, None])
        neg, idx = jax.lax.top_k(-d2, k)
        return idx, jnp.sqrt(jnp.maximum(-neg, 0.0))

    return jax.lax.map(one, Qb)


def exact_knn(X, Q, k: int, qblock: int = 32):
    """(ids, dists) of the exact k nearest rows of X to each query: fp32
    squared L2 at HIGHEST matmul precision over query blocks, then top_k.
    Q's rows are padded to a multiple of `qblock`."""
    Q = np.asarray(Q, np.float32)
    nq, d = Q.shape
    pad = -nq % qblock
    Qp = np.concatenate([Q, np.zeros((pad, d), np.float32)]) if pad else Q
    ids, dists = _knn_blocks(X, jnp.asarray(Qp).reshape(-1, qblock, d), k=k)
    return (np.asarray(ids).reshape(-1, k)[:nq],
            np.asarray(dists).reshape(-1, k)[:nq])


@jax.jit
def _row_dists(X, q, ids):
    rows = X[jnp.clip(ids, 0, X.shape[0] - 1)]
    return jnp.sqrt(jnp.sum((rows - q[:, None, :]) ** 2, axis=-1))


def distances_of(X, Q, ids, block: int = 1024) -> np.ndarray:
    """(A, k) float32 L2 distances between query row a of Q and corpus rows
    ids[a] (ids clipped into range; callers treat negative ids apart)."""
    Q = np.asarray(Q, np.float32)
    ids = np.asarray(ids, np.int32)
    out = []
    for lo in range(0, ids.shape[0], block):
        q, i = Q[lo:lo + block], ids[lo:lo + block]
        pad = block - q.shape[0]  # one block shape: one compile
        if pad:
            q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
            i = np.concatenate([i, np.zeros((pad, i.shape[1]), np.int32)])
        out.append(np.asarray(_row_dists(X, jnp.asarray(q),
                                         jnp.asarray(i)))[:block - pad])
    return np.concatenate(out) if out else np.zeros(ids.shape, np.float32)


def compare(ids, dists, truth_ids, ref_dists) -> dict:
    """The numbers `correct` is decided on, for A answers of k ids each:

    recall_loss  1 - mean recall@k against the exact top-k ids
    dist_gap     the widest relative gap between a returned distance and
                 the reference's distance for the same (query, id); an id
                 outside the corpus, or a non-finite distance, reads inf
    """
    ids = np.asarray(ids)
    k = truth_ids.shape[1]
    hits = [len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(ids, truth_ids)]
    recall = float(np.sum(hits)) / (k * max(len(hits), 1))
    dists = np.asarray(dists, np.float64)
    ref = np.asarray(ref_dists, np.float64)
    gap = np.abs(dists - ref) / np.maximum(ref, 1e-6)
    gap = np.where((ids >= 0) & np.isfinite(dists), gap, np.inf)
    return {"recall_loss": 1.0 - recall,
            "dist_gap": float(gap.max()) if gap.size else 0.0}
