"""Brute-force LCCS scoring: longest circular run of matches per row.

|LCCS(T, Q)| equals the longest circular run of 1s in the element-wise match
vector (T == Q) -- the observation that turns the paper's string search into
a dense O(nm) VPU sweep.  Used (a) as the oracle for the `circrun` Pallas
kernel, (b) as a shard-local beyond-paper search path for moderate n, and
(c) for re-ranking in tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def circ_run_lengths(h: jax.Array, q: jax.Array) -> jax.Array:
    """h: (n, m) int32, q: (m,) int32 -> (n,) int32 LCCS lengths."""
    n, m = h.shape
    e = h == q[None, :]
    ee = jnp.concatenate([e, e], axis=1)  # (n, 2m)
    j = jnp.arange(1, 2 * m + 1, dtype=jnp.int32)
    # position of most recent mismatch (1-based); run length ending at j is
    # j - cummax(mismatch positions)
    blockers = jnp.where(ee, 0, j[None, :])
    last_block = lax.cummax(blockers, axis=1)
    runs = j[None, :] - last_block
    return jnp.minimum(jnp.max(runs, axis=1), m).astype(jnp.int32)


# elements of the (q, n, 2m) scoring slabs one bruteforce step may hold:
# 2^25 int32s is a 128 MB slab (a few live at once), so n=10^6, m=64 scores
# one query per step where a whole-batch vmap would need ~17 GB at B=32
_SLAB_ELEMS = 1 << 25


def top_lengths(lengths: jax.Array, k: int, m: int):
    """top_k of (n,) LCCS lengths in [-1, m] (-1: masked), ties to the
    lower row id.

    The tie-break is packed into the key, (length, n-1-id) in one int32, so
    no two keys are equal and the result does not depend on how a backend's
    top_k orders equal values.  Rows beyond 2^31 / (m+1) fall back to
    `lax.top_k`'s own lower-index-first order."""
    bits = max(1, (lengths.shape[0] - 1).bit_length())
    if (m + 1) << bits > 1 << 31:
        return lax.top_k(lengths, k)
    low = (1 << bits) - 1
    key = (lengths << bits) | (low - lax.iota(jnp.int32, lengths.shape[0]))
    keys, idx = lax.top_k(key, k)
    return keys >> bits, idx


@partial(jax.jit, static_argnames=("lam",))
def bruteforce_topk(h: jax.Array, q_hash: jax.Array, lam: int):
    """Score every database string against each query; return top-lam ids/lcps.

    h: (n, m) int32; q_hash: (B, m) int32 -> ids (B, lam), lcps (B, lam).
    Queries are scored in blocks sized so each step's scoring slabs stay
    near `_SLAB_ELEMS` elements -- per-query results are independent of the
    blocking.
    """

    def one(q):
        lengths = circ_run_lengths(h, q)
        vals, idx = top_lengths(lengths, min(lam, h.shape[0]), h.shape[1])
        if lam > h.shape[0]:
            idx = jnp.pad(idx, (0, lam - h.shape[0]), constant_values=-1)
            vals = jnp.pad(vals, (0, lam - h.shape[0]), constant_values=-1)
        return idx.astype(jnp.int32), vals.astype(jnp.int32)

    n, m = h.shape
    block = max(1, min(q_hash.shape[0], _SLAB_ELEMS // (2 * n * m)))
    return lax.map(one, q_hash, batch_size=block)
