"""Pure-jnp oracle (and CPU fast path) for the fused CSA probe kernel.

The reference window path (`repro.core.search._window`) gathers 2W full
doubled hash rows per (query, shift) and recomputes every candidate's LCP
from scratch: O(W * m) HBM words per pair.  The fused form replaces the
per-slot recompute with the classic sorted-order identity

    lcp(a, c) = min(lcp(a, b), lcp(b, c))      for a <= b <= c,

using the CSA's adjacent-LCP table ``L`` (built once per index): only the two
*boundary* candidates at the lower-bound insertion position are compared
against the query; every other window slot's LCP is a running min of ``L``
entries walking away from the boundary (Fact 3.2 monotonicity is exactly this
chain).  Per (query, shift) the traffic drops to two m-word rows + 2W small
ints -- a ~W-fold cut -- and the output is bit-identical to `_window`.

Deduplication drops the two stable argsorts of `core.search.dedupe_topk` for
a scatter-max into an (n,)-slot buffer followed by one `top_k`:
`buf[id] = max(lcp)` then top-lam over the buffer.  Ties break toward the
smaller id in both forms (top_k prefers lower indices, and the buffer is
indexed by id), so the result -- ids, values, *and* order -- matches
`dedupe_topk` exactly; see tests/test_probe_kernel.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def _exclusive_running_min(x: jax.Array, fill: int) -> jax.Array:
    """out[j] = min(fill, x[0], .., x[j-1]) by log2(len) shift-and-min steps.
    Not `lax.associative_scan` / `cummin`: vmapped over a multiprobe
    worklist (~10^4 rows) those take the TPU compiler minutes."""
    x = jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])
    s = 1
    while s < x.shape[0]:
        x = jnp.minimum(
            x, jnp.concatenate([jnp.full((s,), fill, x.dtype), x[:-s]])
        )
        s *= 2
    return x


def window_from_adjacent(csa, qd_r: jax.Array, i: jax.Array, pos: jax.Array,
                         width: int):
    """LCPs of the 2W-slot window around insertion position `pos` in I[i],
    from the adjacent-LCP table.  qd_r: (2m,) doubled probe string.
    Returns (ids (2W,), lcps (2W,)) == `core.search._window(csa, qd_r, i,
    pos, width)`."""
    from repro.core.search import _lcp_and_less

    n, m = csa.n, csa.m
    offs = jnp.arange(-width, width, dtype=jnp.int32)
    ps = jnp.clip(pos + offs, 0, n - 1)  # (2W,) window sorted positions
    ids = csa.I[i, ps]

    # boundary LCPs: the only two full string comparisons of the window.
    # pos == 0 (no lower neighbour) / pos == n (no upper) read a clipped row;
    # the chain select below never uses the meaningless side.
    t_l = csa.I[i, jnp.clip(pos - 1, 0, n - 1)]
    t_u = csa.I[i, jnp.clip(pos, 0, n - 1)]
    lcp_l, _ = _lcp_and_less(csa.Hd[t_l], qd_r, i, m)
    lcp_u, _ = _lcp_and_less(csa.Hd[t_u], qd_r, i, m)

    jj = jnp.arange(width, dtype=jnp.int32)
    # down chain: lcp(q, sorted[pos-1-j]) = min(lcp_l, L[pos-2], ..,
    # L[pos-1-j]); out-of-range L slots (p < 0, clipped away) read m = the
    # min-neutral value
    adj_down = jnp.where(
        pos - 2 - jj >= 0, csa.L[i, jnp.clip(pos - 2 - jj, 0, n - 1)], m
    )
    down = jnp.minimum(lcp_l, _exclusive_running_min(adj_down, m))
    # up chain: lcp(q, sorted[pos+j]) = min(lcp_u, L[pos], .., L[pos+j-1])
    adj_up = jnp.where(
        pos + jj <= n - 2, csa.L[i, jnp.clip(pos + jj, 0, n - 1)], m
    )
    up = jnp.minimum(lcp_u, _exclusive_running_min(adj_up, m))
    # slot pos+o reads up[o] (o >= 0) or down[-o-1] (o < 0): a fixed layout.
    # Slots clipped at either end of the sorted order agree with the clipped
    # position's own slot, because both chains stay constant past the ends
    # (out-of-range L reads m, and at pos == 0 / pos == n the two boundary
    # rows coincide), so no per-row gather is needed.
    lcps = jnp.concatenate([down[::-1], up]).astype(jnp.int32)
    return ids, lcps


def probe_pairs_ref(csa, qd: jax.Array, shifts: jax.Array, width: int):
    """Worklist form: one (probe string, shift) pair per row.
    qd: (R, 2m) doubled probe strings; shifts: (R,).
    Returns (ids (R, 2W), lcps (R, 2W))."""
    from repro.core.search import _insertion_pos

    n = csa.n

    def one(qd_r, i):
        pos = _insertion_pos(csa, qd_r, i, jnp.int32(0), jnp.int32(n))
        return window_from_adjacent(csa, qd_r, i, pos, width)

    return jax.vmap(one)(qd, shifts.astype(jnp.int32))


def search_windows_ref(csa, qd: jax.Array, width: int):
    """Full-shift form: all m shifts of every query.
    qd: (B, 2m).  Returns (ids (B, m, 2W), lcps (B, m, 2W))."""
    from repro.core.search import _insertion_pos

    n, m = csa.n, csa.m

    def oneq(qd_r):
        def per_shift(i):
            pos = _insertion_pos(csa, qd_r, i, jnp.int32(0), jnp.int32(n))
            return window_from_adjacent(csa, qd_r, i, pos, width)

        return jax.vmap(per_shift)(jnp.arange(m, dtype=jnp.int32))

    return jax.vmap(oneq)(qd)


@partial(jax.jit, static_argnames=("n", "lam"))
def dedupe_topk_scatter(ids: jax.Array, lcps: jax.Array, n: int, lam: int):
    """Max-LCP per id + global top-lam via scatter-max into an (n,) buffer.
    Bit-identical to `core.search.dedupe_topk` (set, values, and order) but
    O(pool + n log lam) instead of two O(pool log pool) stable argsorts.
    ids/lcps: (B, pool); -1-padded slots are dropped."""
    safe = jnp.where(ids >= 0, ids, n)  # -1 padding -> OOB slot n -> dropped
    buf = jnp.full((ids.shape[0], n), -1, jnp.int32)
    buf = buf.at[jnp.arange(ids.shape[0])[:, None], safe].max(
        lcps.astype(jnp.int32), mode="drop"
    )
    k = min(lam, n)
    vals, idx = lax.top_k(buf, k)  # ties -> lower id first, as dedupe_topk
    out_ids = jnp.where(vals >= 0, idx.astype(jnp.int32), -1)
    if k < lam:  # pad to static lam
        out_ids = jnp.pad(out_ids, ((0, 0), (0, lam - k)), constant_values=-1)
        vals = jnp.pad(vals, ((0, 0), (0, lam - k)), constant_values=-1)
    return out_ids, vals
