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

Deduplication (max LCP per id, then the top lam ids) of a (B, P) pool of
(id, lcp) pairs works over the pool itself: one two-key sort of each row (id
ascending, lcp descending), keep the first slot of each id run, one `top_k`
over the P slots -- O(P log P) per row, whatever the index's n.  (The sort
is over the pool, not over an (n,)-slot buffer: at n = 10^6 and P = m * 2W
= 8192 such a buffer is ~122x wider than the pool it dedupes.)  Ties break
toward the smaller id (top_k prefers lower slots, and the pool is sorted by
id), so the result -- ids, values, *and* order -- matches
`core.search.dedupe_topk` exactly; see tests/test_probe_kernel.py.
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


@partial(jax.jit, static_argnames=("lam",))
def dedupe_topk_pool(ids: jax.Array, lcps: jax.Array, lam: int):
    """Max-LCP per id + top-lam of each row of a (B, pool) pool: one two-key
    sort per row (id ascending, lcp descending), the first slot of each id
    run holds that id's max LCP, then `top_k` over the pool's slots.  No
    packed key, so no overflow at any n.  Bit-identical to
    `core.search.dedupe_topk` per row.
    ids/lcps: (B, pool); -1-padded slots are dropped.
    Returns (ids (B, lam), lcps (B, lam)), -1-padded."""
    sids, neg = lax.sort(
        (ids.astype(jnp.int32), -lcps.astype(jnp.int32)), dimension=1,
        num_keys=2,
    )
    first = jnp.concatenate(
        [jnp.ones_like(sids[:, :1], bool), sids[:, 1:] != sids[:, :-1]], axis=1
    )
    score = jnp.where(first & (sids >= 0), -neg, -1)
    k = min(lam, ids.shape[1])
    vals, idx = lax.top_k(score, k)  # ties -> lower slot, i.e. lower id
    out_ids = jnp.where(vals >= 0, jnp.take_along_axis(sids, idx, axis=1), -1)
    if k < lam:  # pad to static lam
        out_ids = jnp.pad(out_ids, ((0, 0), (0, lam - k)), constant_values=-1)
        vals = jnp.pad(vals, ((0, 0), (0, lam - k)), constant_values=-1)
    return out_ids, vals
