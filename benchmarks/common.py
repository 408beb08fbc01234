"""Shared benchmark helpers: datasets, ground truth, metrics, timing."""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.data.synthetic import clustered_vectors, queries_from  # noqa: E402


def dataset(name: str = "sift-like", n: int = 20_000, seed: int = 0):
    """CPU-scaled analogues of the paper's datasets (Table 2 shapes)."""
    dims = {"msong-like": 420, "sift-like": 128, "gist-like": 960,
            "glove-like": 100, "deep-like": 256}
    d = dims[name]
    angular = name in ("glove-like", "deep-like")
    X = clustered_vectors(n, d, n_clusters=max(20, n // 500), seed=seed,
                          normalize=angular)
    Q = queries_from(X, 50, jitter=0.02 if angular else 0.3, seed=seed + 1)
    if angular:
        Q /= np.linalg.norm(Q, axis=1, keepdims=True)
    return X, Q, angular


def ground_truth(X, Q, k, angular=False):
    if angular:
        Xn = X / np.linalg.norm(X, axis=1, keepdims=True)
        Qn = Q / np.linalg.norm(Q, axis=1, keepdims=True)
        d = 1.0 - Qn @ Xn.T
    else:
        d = np.sqrt(np.maximum(((Q[:, None, :] - X[None, :, :]) ** 2).sum(-1), 0))
    idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(d, idx, axis=1)


def recall(ids, gt) -> float:
    ids = np.asarray(ids)
    return float(
        np.mean([
            len(set(ids[i].tolist()) & set(gt[i].tolist())) / gt.shape[1]
            for i in range(gt.shape[0])
        ])
    )


def overall_ratio(dists, gt_d, angular=False) -> float:
    """Paper's ratio metric: mean over i of Dist(o_i,q)/Dist(o_i*,q).
    Both inputs are true distances (Euclidean) or 1-cos (angular)."""
    d = np.asarray(dists, dtype=np.float64)
    g = np.asarray(gt_d, dtype=np.float64)
    ok = np.isfinite(d) & (g > 1e-12)
    return float(np.mean(np.where(ok, d / np.maximum(g, 1e-12), 1.0)))


def timed(fn, *args, repeats: int = 3, **kw):
    """Median wall time (one warmup call for jit; device work blocked on --
    jnp calls return asynchronously, so un-blocked timings would measure
    dispatch only)."""
    import jax

    jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return out, float(np.median(ts))


def cpu_worker_env(device_count: int | None = None) -> dict:
    """Environment for a benchmark's CPU measurement subprocess (fake host
    devices via XLA_FLAGS, `repro` and `benchmarks` importable).  Refuses on
    an accelerator host: the worker would time the CPU in the chip's place,
    or contend with this process for the chip."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"this benchmark spawns CPU-only workers and would not measure "
            f"the {backend!r} device this process sees; run chip_smoke.py "
            f"on an accelerator host instead"
        )
    env = dict(os.environ)
    if device_count is not None:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={device_count}"
        ).strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    return env


class CsvRows:
    """Collects ``name,us_per_call,derived`` rows for run.py."""

    def __init__(self):
        self.rows: list[tuple[str, float, str]] = []

    def add(self, name: str, seconds: float, derived: str = ""):
        self.rows.append((name, seconds * 1e6, derived))

    def dump(self):
        for name, us, derived in self.rows:
            print(f"{name},{us:.1f},{derived}")
