"""LSH family + theory invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="dev dependency (pip install -e .[dev]); "
    "property tests are skipped on minimal environments"
)
from hypothesis import given, settings, strategies as st

from repro.core import make_family, theory
from repro.core.lsh import _hadamard_transform


def test_rp_collision_prob_monotone_decreasing():
    ps = [theory.rp_collision_prob(t, w=4.0) for t in (0.5, 1.0, 2.0, 4.0, 8.0)]
    assert all(a > b for a, b in zip(ps, ps[1:]))
    assert 0.0 < ps[-1] < ps[0] <= 1.0


def test_xp_collision_prob_monotone_decreasing():
    ps = [theory.xp_collision_prob(t, d=128) for t in (0.1, 0.5, 1.0, 1.5, 1.9)]
    assert all(a > b for a, b in zip(ps, ps[1:]))


def test_empirical_rp_collision_matches_eq2():
    """Empirical per-function collision rate ~= Eq. (2) at controlled distance."""
    rng = np.random.default_rng(0)
    d, m, w, tau = 32, 512, 4.0, 2.0
    fam = make_family("euclidean", jax.random.key(0), d, m, w=w)
    o = rng.normal(size=(200, d))
    delta = rng.normal(size=(200, d))
    delta = delta / np.linalg.norm(delta, axis=1, keepdims=True) * tau
    q = o + delta
    ho = np.asarray(fam.hash(jnp.asarray(o)))
    hq = np.asarray(fam.hash(jnp.asarray(q)))
    emp = (ho == hq).mean()
    want = theory.rp_collision_prob(tau, w)
    assert abs(emp - want) < 0.02, (emp, want)


def test_empirical_collision_rate_orders_by_distance_angular():
    rng = np.random.default_rng(1)
    d, m = 64, 256
    fam = make_family("angular", jax.random.key(1), d, m)
    base = rng.normal(size=(100, d))
    base /= np.linalg.norm(base, axis=1, keepdims=True)
    rates = []
    for eps in (0.05, 0.3, 1.0):
        q = base + rng.normal(size=base.shape) * eps
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        hb = np.asarray(fam.hash(jnp.asarray(base)))
        hq = np.asarray(fam.hash(jnp.asarray(q)))
        rates.append((hb == hq).mean())
    assert rates[0] > rates[1] > rates[2], rates


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([8, 16, 64]))
def test_hadamard_is_orthogonal(seed, d):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(3, d)).astype(np.float32)
    y = np.asarray(_hadamard_transform(jnp.asarray(x))) / math.sqrt(d)
    np.testing.assert_allclose(
        np.linalg.norm(y, axis=1), np.linalg.norm(x, axis=1), rtol=1e-5
    )


def test_hash_values_deterministic_and_int32():
    fam = make_family("euclidean", jax.random.key(0), 16, 8, w=4.0)
    x = jnp.ones((4, 16))
    h1, h2 = fam.hash(x), fam.hash(x)
    assert h1.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


@pytest.mark.parametrize("family", ["euclidean", "angular"])
def test_hash_rows_blocks_and_batches_change_no_hash(family, monkeypatch):
    """`hash_rows` pads the last block and trims it away, and a row's hash
    string does not depend on the rows hashed alongside it."""
    from repro.core import lsh

    monkeypatch.setattr(lsh, "HASH_BLOCK_ROWS", 64)
    fam = make_family(family, jax.random.key(1), 24, 8, w=4.0)
    x = np.random.default_rng(0).normal(size=(150, 24)).astype(np.float32)
    whole = np.asarray(lsh.hash_rows(fam, x))
    assert whole.shape == (150, 8) and whole.dtype == np.int32
    np.testing.assert_array_equal(whole, np.asarray(fam.hash(jnp.asarray(x))))
    parts = [np.asarray(lsh.hash_rows(fam, x[lo:lo + 37]))
             for lo in range(0, 150, 37)]
    np.testing.assert_array_equal(np.concatenate(parts), whole)


def test_theorem51_lambda_sublinear_in_n():
    """lambda/n must shrink as m grows (Theorem 5.1: lambda = O(m^{1-1/rho} n))."""
    p1, p2 = 0.9, 0.5
    lam_small = theory.theorem51_lambda(16, 100_000, p1, p2)
    lam_big = theory.theorem51_lambda(256, 100_000, p1, p2)
    assert lam_big < lam_small
    r = theory.rho(p1, p2)
    assert 0 < r < 1


def test_lccs_cdf_properties():
    xs = np.arange(0, 64)
    cdf = theory.lccs_cdf(xs, m=64, p=0.7)
    assert (np.diff(cdf) >= -1e-12).all()  # monotone
    assert cdf[-1] > 0.99
    med = theory.lccs_median(64, 0.7)
    assert abs(float(theory.lccs_cdf(med, 64, 0.7)) - 0.5) < 1e-6


def test_empirical_lccs_matches_evt_cdf():
    """Lemma 5.2: LCCS length of iid-matching strings follows the EVT CDF."""
    rng = np.random.default_rng(2)
    m, p, trials = 128, 0.5, 2000
    from repro.core import circ_run_lengths

    h = (rng.random(size=(trials, m)) > p).astype(np.int32)  # match prob p vs zeros
    q = np.zeros((m,), dtype=np.int32)
    lens = np.asarray(circ_run_lengths(jnp.asarray(h), jnp.asarray(q)))
    med_emp = np.median(lens)
    med_thy = theory.lccs_median(m, p)
    assert abs(med_emp - med_thy) <= 2.0, (med_emp, med_thy)


def test_multiprobe_generation_invariants():
    from repro.core import multiprobe

    rng = np.random.default_rng(0)
    scores = np.sort(rng.random((16, 4)), axis=1)
    probes = multiprobe.generate_perturbations(scores, n_probes=33, max_gap=2)
    assert probes[0] == ()
    assert len(probes) == 33
    totals = [sum(scores[i, j] for i, j in d) for d in probes[1:]]
    assert all(a <= b + 1e-12 for a, b in zip(totals, totals[1:])), "ascending scores"
    for d in probes:
        pos = [i for i, _ in d]
        assert pos == sorted(pos)
        assert all(b - a <= 2 for a, b in zip(pos, pos[1:])), "MAX_GAP respected"
        assert len(set(pos)) == len(pos)


def test_multiprobe_apply():
    from repro.core import multiprobe

    q = np.arange(8, dtype=np.int32)
    alts = np.full((8, 3), 99, dtype=np.int32)
    probes = [(), ((2, 0),), ((1, 1), (3, 0))]
    out = multiprobe.apply_perturbations(q, alts, probes)
    np.testing.assert_array_equal(out[0], q)
    assert out[1][2] == 99 and (np.delete(out[1], 2) == np.delete(q, 2)).all()
    assert out[2][1] == 99 and out[2][3] == 99
