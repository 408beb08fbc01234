"""Jit'd public wrappers for the fused CSA probe kernel.

Drop-in fused counterparts of the three `repro.core.search` probe entry
points, selected by `SearchParams.use_probe_kernel` / REPRO_PROBE_KERNEL
(resolved in `repro.exec.stages.resolve_use_probe_kernel`):

  csa_probe_search            == klccs_search           (mode="parallel")
  csa_probe_search_with_lens  == klccs_search_with_lens
  csa_probe_pairs             == klccs_search_pairs

`use_pallas` picks the Pallas kernel (interpret mode, tests only) vs the
fused pure-jnp reference -- both bit-identical to the legacy path.  The
reference form is the route every platform runs (the legacy window gathers
~W x more HBM words and dedupes with two stable argsorts, see ref.py): the
Pallas kernel's (1, n) / (n, 2m) blocks are refused by the TPU lowering,
and its VMEM-resident Hd bounds it at n <= ~32k for m=64 besides, so no
size selects it.  Requires a CSA built with the adjacent-LCP table
(`csa.L`); `supports(csa)` gates that.

Both search wrappers dedupe each query's (m * 2W)-slot pool with
`ref.dedupe_topk_pool`: one two-key sort over the pool, never a pass over
all n ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import default_interpret
from .csa_probe import csa_probe_pallas
from .ref import dedupe_topk_pool, probe_pairs_ref, search_windows_ref


def supports(csa) -> bool:
    """True when `csa` carries the adjacent-LCP table the fused path needs
    (absent only on artifacts saved before the table existed)."""
    return csa is not None and csa.L is not None


def _windows(csa, qd, shifts, qidx, width: int, use_pallas: bool):
    if use_pallas:
        return csa_probe_pallas(
            csa.I, csa.L, csa.Hd, qd, shifts, qidx, width=width,
            interpret=default_interpret(),
        )
    return probe_pairs_ref(csa, qd[qidx], shifts, width)


@functools.partial(jax.jit, static_argnames=("width", "use_pallas"))
def csa_probe_windows(csa, q_hash, width: int = 16, use_pallas: bool = False):
    """Raw fused windows of every (query, shift) pair -- the undeduped pool
    the multiprobe sources merge in one `dedupe_topk_pool` pass.
    q_hash: (B, m) int32.  Returns (ids (B, m, 2W), lcps (B, m, 2W))."""
    B, m = q_hash.shape
    qd = jnp.concatenate([q_hash, q_hash], axis=1).astype(jnp.int32)
    if use_pallas:
        shifts = jnp.tile(jnp.arange(m, dtype=jnp.int32), B)
        qidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), m)
        ids, lcps = _windows(csa, qd, shifts, qidx, width, True)
        return ids.reshape(B, m, -1), lcps.reshape(B, m, -1)
    return search_windows_ref(csa, qd, width)


@functools.partial(jax.jit, static_argnames=("lam", "width", "use_pallas"))
def csa_probe_search(csa, q_hash, lam: int, width: int = 16,
                     use_pallas: bool = False):
    """Fused batched k-LCCS search: == `klccs_search(mode="parallel")`.
    q_hash: (B, m) int32.  Returns (ids (B, lam), lcps (B, lam))."""
    B, m = q_hash.shape
    qd = jnp.concatenate([q_hash, q_hash], axis=1).astype(jnp.int32)
    if use_pallas:
        shifts = jnp.tile(jnp.arange(m, dtype=jnp.int32), B)
        qidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), m)
        ids, lcps = _windows(csa, qd, shifts, qidx, width, True)
    else:
        ids, lcps = search_windows_ref(csa, qd, width)
    return dedupe_topk_pool(ids.reshape(B, -1), lcps.reshape(B, -1), lam)


@functools.partial(jax.jit, static_argnames=("lam", "width", "use_pallas"))
def csa_probe_search_with_lens(csa, q_hash, lam: int, width: int = 16,
                               use_pallas: bool = False):
    """Fused batched search + per-shift best LCP (the §4.2 len bound):
    == `klccs_search_with_lens`.  Returns (ids, lcps, maxlen (B, m))."""
    B, m = q_hash.shape
    qd = jnp.concatenate([q_hash, q_hash], axis=1).astype(jnp.int32)
    if use_pallas:
        shifts = jnp.tile(jnp.arange(m, dtype=jnp.int32), B)
        qidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), m)
        ids, lcps = _windows(csa, qd, shifts, qidx, width, True)
        ids, lcps = ids.reshape(B, m, -1), lcps.reshape(B, m, -1)
    else:
        ids, lcps = search_windows_ref(csa, qd, width)
    maxlen = jnp.max(lcps, axis=2)
    out_ids, out_lcps = dedupe_topk_pool(
        ids.reshape(B, -1), lcps.reshape(B, -1), lam
    )
    return out_ids, out_lcps, maxlen


@functools.partial(jax.jit, static_argnames=("width", "use_pallas"))
def csa_probe_pairs(csa, probe_hashes, shifts, valid, width: int = 16,
                    use_pallas: bool = False):
    """Fused worklist probe: == `klccs_search_pairs`.
    probe_hashes: (R, m); shifts/valid: (R,).  Returns (ids, lcps) (R, 2W),
    invalid rows masked to -1."""
    R = probe_hashes.shape[0]
    qd = jnp.concatenate([probe_hashes, probe_hashes], axis=1).astype(jnp.int32)
    qidx = jnp.arange(R, dtype=jnp.int32)
    ids, lcps = _windows(csa, qd, shifts.astype(jnp.int32), qidx, width,
                         use_pallas)
    ids = jnp.where(valid[:, None], ids, -1)
    lcps = jnp.where(valid[:, None], lcps, -1)
    return ids, lcps
