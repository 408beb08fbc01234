"""Fused CSA probe kernel (`repro.kernels.csa_probe`): oracle parity against
the legacy `repro.core.search` probe, toggle-on == toggle-off end-to-end
through `exec.execute`, interpret-mode Pallas execution on CPU, the §4.2
skip_budget >= m exactness claim, and the probe-0 dead-worklist regression.

Everything here asserts BIT-IDENTICAL outputs: the fused path is a pure
performance dispatch (`SearchParams.use_probe_kernel` / REPRO_PROBE_KERNEL),
never an approximation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LCCSIndex, SearchParams, SegmentedLCCSIndex
from repro.core.search import (
    dedupe_topk,
    klccs_search,
    klccs_search_pairs,
    klccs_search_with_lens,
)
from repro.exec import execute, stages
from repro.kernels.csa_probe import (
    csa_probe_pairs,
    csa_probe_search,
    csa_probe_search_with_lens,
    dedupe_topk_pool,
    supports,
)
from repro.kernels.csa_probe.csa_probe import csa_probe_pallas
from repro.kernels.csa_probe.ref import probe_pairs_ref

RNG = np.random.default_rng(7)


def _index(n, m, d=12, seed=0):
    X = np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)
    return X, LCCSIndex.build(X, m=m, family="euclidean", w=4.0, seed=seed)


def _assert_pairs_equal(ids_a, lcps_a, ids_b, lcps_b, tag=""):
    """Order-independent (id, lcp) multiset equality per query row."""
    for r, (ia, la, ib, lb) in enumerate(
        zip(np.asarray(ids_a), np.asarray(lcps_a),
            np.asarray(ids_b), np.asarray(lcps_b))
    ):
        assert sorted(zip(ia.tolist(), la.tolist())) == sorted(
            zip(ib.tolist(), lb.tolist())
        ), f"{tag} row {r}"


# ---------------------------------------------------------------------------
# ref.py oracle parity vs the legacy probe (non-pow2 m, odd n, lam > n)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,m,width,lam",
    [
        (97, 8, 4, 10),    # odd n
        (256, 16, 16, 50),
        (75, 7, 8, 100),   # non-pow2 m, lam > n (padded output)
        (129, 12, 32, 24), # width > typical window occupancy
    ],
)
def test_fused_search_matches_legacy(n, m, width, lam):
    _, idx = _index(n, m, seed=n + m)
    qh = jnp.asarray(idx.h[RNG.integers(0, n, 5)])  # realistic hash strings
    want = klccs_search(idx.csa, qh, lam, width=width, mode="parallel")
    got = csa_probe_search(idx.csa, qh, lam, width=width, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    want3 = klccs_search_with_lens(idx.csa, qh, lam, width=width)
    got3 = csa_probe_search_with_lens(idx.csa, qh, lam, width=width,
                                      use_pallas=False)
    for g, w in zip(got3, want3):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("n,m,width", [(97, 8, 4), (200, 12, 16)])
def test_fused_pairs_matches_legacy(n, m, width):
    _, idx = _index(n, m, seed=n)
    R = 17
    rows = jnp.asarray(idx.h[RNG.integers(0, n, R)])
    shifts = jnp.asarray(RNG.integers(0, m, R).astype(np.int32))
    valid = jnp.asarray(RNG.random(R) > 0.3)
    want = klccs_search_pairs(idx.csa, rows, shifts, valid, width=width)
    got = csa_probe_pairs(idx.csa, rows, shifts, valid, width=width,
                          use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def _pool(seed, B, P, n, n_ids=None, max_lcp=9, dead_rows=()):
    """(B, P) pool of ids in [-1, n_ids) (-1 = padding, lcp -1), rows in
    `dead_rows` all padding."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, n if n_ids is None else n_ids, (B, P))
    ids[list(dead_rows)] = -1
    lcps = np.where(ids >= 0, rng.integers(0, max_lcp, (B, P)), -1)
    return ids.astype(np.int32), lcps.astype(np.int32)


@pytest.mark.parametrize(
    "seed,B,P,n,lam,pool_kw",
    [
        *[(t, 4, 40, 53, 12, {}) for t in range(5)],  # P < n, as before
        (5, 3, 64, 20, 12, {}),                 # P >= n
        (6, 2, 30, 5, 8, {}),                   # lam > n
        (7, 3, 8, 500, 16, {}),                 # P < lam (padded output)
        (8, 4, 50, 300, 10, dict(dead_rows=(0, 2))),  # all -1 rows
        # heavy duplicate ids with tied LCPs
        (9, 4, 200, 1000, 10, dict(n_ids=6, max_lcp=3)),
        (10, 2, 600, 3000, 300, dict(n_ids=40, max_lcp=2)),
        # ids near the int32 limit: no packed key, so nothing overflows
        (11, 3, 100, 2**31 - 1, 20, dict(max_lcp=4)),
        (12, 1, 1, 1, 3, {}),                   # one slot, one id
    ],
)
def test_dedupe_scatter_matches_dedupe_topk(seed, B, P, n, lam, pool_kw):
    """The fused probe's pool dedupe == the legacy sort-based dedupe: same
    id set, same values, same tie order (smaller id first on equal LCP), at
    every pool width, P < n and P >= n alike."""
    ids, lcps = map(jnp.asarray, _pool(seed, B, P, n, **pool_kw))
    want = jax.vmap(lambda i, l: dedupe_topk(i, l, lam))(ids, lcps)
    got = dedupe_topk_pool(ids, lcps, lam)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---------------------------------------------------------------------------
# Pallas kernel, interpret mode (tier-1 on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,m,width", [(97, 8, 4), (75, 12, 8)])
def test_pallas_interpret_matches_ref(n, m, width):
    _, idx = _index(n, m, seed=m)
    B = 3
    qh = jnp.asarray(idx.h[RNG.integers(0, n, B)])
    qd = jnp.concatenate([qh, qh], axis=1).astype(jnp.int32)
    shifts = jnp.tile(jnp.arange(m, dtype=jnp.int32), B)
    qidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), m)
    got = csa_probe_pallas(idx.csa.I, idx.csa.L, idx.csa.Hd, qd, shifts,
                           qidx, width=width, interpret=True)
    want = probe_pairs_ref(idx.csa, qd[qidx], shifts, width)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---------------------------------------------------------------------------
# Toggle-on == toggle-off through exec.execute (every topology)
# ---------------------------------------------------------------------------

_SOURCES = ("lccs", "multiprobe-full", "multiprobe-skip")


def _toggle_params(source, lam=32, width=16, **kw):
    return SearchParams(
        k=5, lam=lam, width=width, source=source,
        probes=4 if source.startswith("multiprobe") else 1,
        use_gather_kernel=False, **kw,
    )


@pytest.mark.parametrize("source", _SOURCES)
def test_toggle_parity_monolithic(source):
    # every source's fused pool (>= 16 * 2 * 16 = 512 slots) is wider than
    # n = 150
    X, idx = _index(150, 16, seed=1)
    Q = np.random.default_rng(2).normal(size=(6, 12)).astype(np.float32)
    off = execute(idx, Q, _toggle_params(source, use_probe_kernel=False))
    on = execute(idx, Q, _toggle_params(source, use_probe_kernel=True))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(off[1]))


@pytest.mark.parametrize("source", _SOURCES)
def test_toggle_parity_monolithic_pool_form(source):
    # every source's fused pool (at most 4 * 8 * 2 * 4 = 256 slots) is
    # narrower than n = 2000
    X, idx = _index(2000, 8, seed=1)
    Q = np.random.default_rng(2).normal(size=(6, 12)).astype(np.float32)
    off = execute(idx, Q, _toggle_params(source, width=4,
                                         use_probe_kernel=False))
    on = execute(idx, Q, _toggle_params(source, width=4,
                                        use_probe_kernel=True))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(off[1]))


@pytest.mark.parametrize("source", _SOURCES)
def test_toggle_parity_segmented(source):
    rng = np.random.default_rng(3)
    idx = SegmentedLCCSIndex.create(12, m=16, family="euclidean", w=4.0,
                                    seed=3)
    idx.insert(rng.normal(size=(90, 12)).astype(np.float32))
    idx.insert(rng.normal(size=(40, 12)).astype(np.float32))
    Q = rng.normal(size=(4, 12)).astype(np.float32)
    off = execute(idx, Q, _toggle_params(source, use_probe_kernel=False))
    on = execute(idx, Q, _toggle_params(source, use_probe_kernel=True))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(off[1]))


@pytest.mark.parametrize("source", _SOURCES)
def test_toggle_parity_sharded(source):
    from repro.shard import make_shard_mesh

    X, idx = _index(120, 16, seed=4)
    sidx = idx.shard(make_shard_mesh(1))  # 1-device mesh: full shard_map path
    Q = np.random.default_rng(5).normal(size=(4, 12)).astype(np.float32)
    off = sidx.search(Q, _toggle_params(source, use_probe_kernel=False))
    on = sidx.search(Q, _toggle_params(source, use_probe_kernel=True))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(off[1]))


def test_narrowed_mode_falls_back():
    """mode="narrowed" has no fused form: toggle-on must fall back to the
    legacy walk and still equal toggle-off exactly."""
    X, idx = _index(150, 16, seed=6)
    Q = np.random.default_rng(6).normal(size=(4, 12)).astype(np.float32)
    off = execute(idx, Q, _toggle_params("lccs", mode="narrowed",
                                         use_probe_kernel=False))
    on = execute(idx, Q, _toggle_params("lccs", mode="narrowed",
                                        use_probe_kernel=True))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(off[1]))


def test_missing_L_falls_back():
    """Artifacts saved before the adjacent-LCP table existed load with
    csa.L=None; the toggle must quietly use the legacy path, not crash."""
    X, idx = _index(100, 8, seed=7)
    bare = LCCSIndex(family=idx.family, store=idx.store, h=idx.h,
                     csa=idx.csa._replace(L=None), metric=idx.metric,
                     tail=idx.tail)
    assert supports(idx.csa) and not supports(bare.csa)
    Q = np.random.default_rng(8).normal(size=(3, 12)).astype(np.float32)
    on = execute(bare, Q, _toggle_params("lccs", use_probe_kernel=True))
    off = execute(idx, Q, _toggle_params("lccs", use_probe_kernel=False))
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(off[0]))


def test_env_toggle_resolution(monkeypatch):
    monkeypatch.delenv(stages.ENV_PROBE_KERNEL, raising=False)
    assert stages.resolve_use_probe_kernel(True) is True
    assert stages.resolve_use_probe_kernel(False) is False
    monkeypatch.setenv(stages.ENV_PROBE_KERNEL, "1")
    assert stages.resolve_use_probe_kernel(None) is True
    assert stages.resolve_use_probe_kernel(False) is False  # explicit wins
    monkeypatch.setenv(stages.ENV_PROBE_KERNEL, "0")
    assert stages.resolve_use_probe_kernel(None) is False


# ---------------------------------------------------------------------------
# §4.2 skip_budget >= m exactness (satellite: docstring claim, now tested)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,m,width,probes",
    [(97, 8, 8, 3), (128, 7, None, 4), (75, 16, 12, 6)],
)
@pytest.mark.parametrize("mode", ["parallel", "narrowed"])
@pytest.mark.parametrize("kern", [False, True])
def test_skip_budget_m_is_exact(n, m, width, probes, mode, kern):
    """skip_budget >= m == multiprobe-full, per (id, lcp) pair -- the
    "exact §4.2 semantics" claim, across modes, widths, non-pow2 m and both
    kernel branches."""
    X, idx = _index(n, m, seed=n * m)
    Q = np.random.default_rng(9).normal(size=(5, 12)).astype(np.float32)
    qh = stages.hash_queries(idx.family, jnp.asarray(Q))
    from repro.core.sources import get_source

    base = SearchParams(k=5, lam=24, width=width, probes=probes, mode=mode,
                        use_gather_kernel=False, use_probe_kernel=kern)
    full = base.replace(source="multiprobe-full")
    skip = base.replace(source="multiprobe-skip", skip_budget=m)
    fi, fl = get_source("multiprobe-full")(idx, jnp.asarray(Q), qh, full)
    si, sl = get_source("multiprobe-skip")(idx, jnp.asarray(Q), qh, skip)
    _assert_pairs_equal(fi, fl, si, sl, tag=f"{mode}/kern={kern}")


# ---------------------------------------------------------------------------
# Probe-0 dead-worklist regression (satellite: output parity vs old form)
# ---------------------------------------------------------------------------


def test_probe0_worklist_parity_with_old_form():
    """The old multiprobe-skip built its worklist over all P probes and
    masked probe 0's rows invalid (pure waste: probe 0 IS the base query the
    full base search already covered).  Rebuild that form inline and assert
    the trimmed worklist changes nothing."""
    from repro.core import multiprobe
    from repro.core.sources import get_source

    n, m, probes, lam, width, budget = 130, 12, 5, 24, 8, 12
    X, idx = _index(n, m, seed=10)
    Q = np.random.default_rng(11).normal(size=(5, 12)).astype(np.float32)
    qh = stages.hash_queries(idx.family, jnp.asarray(Q))
    p = SearchParams(k=5, lam=lam, width=width, probes=probes,
                     source="multiprobe-skip", skip_budget=budget,
                     use_gather_kernel=False, use_probe_kernel=False)
    got = get_source("multiprobe-skip")(idx, jnp.asarray(Q), qh, p)

    # --- old form, inline: P-row worklist with probe 0 masked invalid ---
    base_ids, base_lcps, maxlen = klccs_search_with_lens(
        idx.csa, qh, lam, width=width
    )
    alt_vals, alt_scores = idx.family.alternatives(jnp.asarray(Q), p.n_alt)
    slots, ranks, mask = multiprobe.probe_schedule(
        m, probes, alt_vals.shape[-1], p.max_gap
    )
    order = jnp.argsort(alt_scores[..., 0], axis=-1)
    strings, pos = multiprobe.probe_strings_batch(
        qh, order, alt_vals, slots, ranks, mask
    )
    B, P, _ = strings.shape
    shifts_all = jnp.arange(m, dtype=jnp.int32)
    dist = (pos[:, :, :, None] - shifts_all[None, None, None, :]) % m
    window = jnp.minimum(maxlen + 1, m - 1)
    affected = (
        (dist <= window[:, None, None, :])
        & jnp.asarray(mask)[None, :, :, None]
    ).any(axis=2)
    affected = affected.at[:, 0, :].set(False)  # the old dead mask
    score = jnp.where(affected, window[:, None, :] + 1, 0)
    hit, shifts = jax.lax.top_k(score, budget)
    valid = hit > 0
    rows = jnp.broadcast_to(
        strings[:, :, None, :], (B, P, budget, m)
    ).reshape(-1, m)
    p_ids, p_lcps = klccs_search_pairs(
        idx.csa, rows, shifts.reshape(-1), valid.reshape(-1), width=width
    )
    ids = jnp.concatenate([base_ids, p_ids.reshape(B, -1)], axis=1)
    lcps = jnp.concatenate([base_lcps, p_lcps.reshape(B, -1)], axis=1)
    want = jax.vmap(lambda i, l: dedupe_topk(i, l, lam))(ids, lcps)

    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---------------------------------------------------------------------------
# Sharded budget apportioning (the fig13 regression fix)
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore::repro.core.params.WindowWidthWarning")
def test_local_params_apportioning():
    from repro.shard.search import _local_params

    p = SearchParams(k=10, lam=200, use_gather_kernel=False)
    assert _local_params(p, 1) is p
    p4 = _local_params(p, 4)
    assert p4.lam == 50 and p4.width == 16  # ceil(200/4), ceil(64/4)
    # k floor: a shard must always be able to fill the merge's k slots
    assert _local_params(p, 64).lam == 10
    # explicit width is a user contract -- never scaled
    pw = _local_params(p.replace(width=128), 4)
    assert pw.width == 128 and pw.lam == 50
    # complete coverage survives: lam >= n  =>  lam_local >= ceil(n/S)
    pc = _local_params(p.replace(lam=1024), 4)
    assert pc.lam == 256
