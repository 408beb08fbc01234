"""The copied exact search and the comparison that decides `correct`."""
import numpy as np

from bench.reference import compare, distances_of, exact_knn


def _brute(X, Q, k):
    d = np.sqrt(((Q[:, None, :].astype(np.float64) - X[None]) ** 2).sum(-1))
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


def test_exact_knn_matches_numpy_brute_force():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(3000, 24)).astype(np.float32)
    Q = rng.normal(size=(70, 24)).astype(np.float32)  # not a multiple of 32
    ids, dists = exact_knn(X, Q, 10)
    ref_ids, ref_d = _brute(X, Q, 10)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_allclose(dists, ref_d, rtol=1e-4)


def test_distances_of_is_the_l2_distance_of_each_returned_id():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(500, 16)).astype(np.float32)
    Q = rng.normal(size=(1100, 16)).astype(np.float32)  # two blocks
    ids = rng.integers(0, 500, size=(1100, 5))
    got = distances_of(X, Q, ids)
    want = np.linalg.norm(X[ids] - Q[:, None, :], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_compare_reads_recall_and_distance_gap():
    truth = np.array([[0, 1, 2, 3], [4, 5, 6, 7]])
    ids = np.array([[0, 1, 2, 9], [4, 5, 6, 7]])
    ref = np.ones((2, 4))
    exact = compare(ids, ref, truth, ref)
    assert exact["recall_loss"] == 1.0 - 7 / 8
    assert exact["dist_gap"] == 0.0
    off = compare(ids, ref * 1.001, truth, ref)
    assert abs(off["dist_gap"] - 1e-3) < 1e-9
    bad = compare(np.array([[0, 1, 2, -1], [4, 5, 6, 7]]), ref, truth, ref)
    assert bad["dist_gap"] == float("inf")
