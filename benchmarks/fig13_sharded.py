"""Beyond-paper Figure 13: sharded multi-device serving.

Two measurements over `repro.shard.ShardedLCCSIndex` on a fake multi-device
CPU host platform (XLA_FLAGS=--xla_force_host_platform_device_count=N):

  parity   sharded top-k must be *exact* w.r.t. the monolithic index: same
           sorted distances, same id set.  Run at an uneven row count
           (n % shards != 0, exercising the gid padding) with a
           complete-coverage configuration (lam >= n), where monolithic and
           sharded candidate sets provably coincide, so any deviation is a
           merge/offset bug rather than tie noise.
  qps      end-to-end query throughput per shard count for two serving
           configurations: "bruteforce" (the dense O(n*m) scan) and "lccs"
           (CSA window probing).  Sharding apportions the per-shard
           candidate budget and window width by the row share
           (`repro.shard.search._local_params`), so the divisible terms
           (top-k cuts, window bandwidth, exact verification) shrink with S
           while only the per-shift binary searches duplicate.  The fused
           probe kernel ("lccs-kernel") is reported as a monolithic
           reference point only: its probe is already compute-bound on
           those duplicated binary searches, so on fake same-core devices a
           sharded sweep of it measures collective overhead, not scaling
           (distinct accelerators are the real target).  Host CPU devices
           share physical cores and XLA already multi-threads the dense
           scan, so the CPU curve understates what distinct accelerators
           give; it documents the trend and the overhead, not the ceiling.

Device counts must be fixed before jax initialises, so `run` re-invokes this
module as a subprocess with the XLA flag set and parses one JSON line back;
the records land in BENCH_search.json under "sharded" (see run.py).
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

from .common import CsvRows, cpu_worker_env

_MARK = "FIG13-JSON:"


def run(csv: CsvRows, n: int = 4000, shard_counts=(1, 2, 4, 8),
        queries: int = 32):
    """Spawn the measurement subprocess (max(shard_counts) fake devices) and
    fold its records into csv + the returned BENCH payload."""
    env = cpu_worker_env(max(shard_counts))
    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig13_sharded", "--worker",
         "--n", str(n), "--queries", str(queries),
         "--shard-counts", ",".join(map(str, shard_counts))],
        capture_output=True, text=True, timeout=1800, env=env, cwd=root,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fig13 worker failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    line = next(l for l in proc.stdout.splitlines() if l.startswith(_MARK))
    payload = json.loads(line[len(_MARK):])
    for rec in payload["configs"]:
        csv.add(
            f"fig13/{rec['source']}/shards{rec['shards']}",
            1.0 / rec["qps"] if rec["qps"] else 0.0,
            f"qps={rec['qps']};recall={rec['recall_at_10']};"
            f"parity={rec['parity']}",
        )
    scan = [r for r in payload["configs"] if r["source"] == "bruteforce"]
    csv.add("fig13/scaling", 0.0,
            f"scan_speedup={max(r['qps'] for r in scan) / scan[0]['qps']:.2f}x;"
            f"parity_exact={payload['parity_exact']}")
    return payload


def _worker(n: int, shard_counts, n_queries: int) -> dict:
    import numpy as np

    from repro.core import LCCSIndex, SearchParams, jit_search
    from repro.shard import make_shard_mesh

    from benchmarks.common import dataset, ground_truth, recall, timed

    X, Q, _ = dataset("sift-like", n=n)
    Q = Q[:n_queries]
    k = 10
    gt, _ = ground_truth(X, Q, k, angular=False)
    serve_cfgs = {
        "bruteforce": SearchParams(k=k, lam=200, source="bruteforce",
                                   use_gather_kernel=False),
        "lccs": SearchParams(k=k, lam=200, source="lccs",
                             use_gather_kernel=False,
                             use_probe_kernel=False),
    }
    # monolithic-only reference: the fused probe kernel (see module docstring
    # for why it is not swept across shard counts here)
    mono_cfgs = dict(serve_cfgs)
    mono_cfgs["lccs-kernel"] = serve_cfgs["lccs"].replace(
        use_probe_kernel=True
    )
    mono = LCCSIndex.build(X, m=32, family="euclidean", w=16.0, seed=0)
    mono_stats = {}
    for name, sp in mono_cfgs.items():
        (ids_m, _), t_m = timed(lambda: jit_search(mono, Q, sp))
        mono_stats[name] = {
            "qps": round(Q.shape[0] / t_m, 1),
            "recall_at_10": round(recall(np.asarray(ids_m), gt), 4),
        }

    # parity corpus: uneven split for every shard count > 1, complete
    # candidate coverage (lam >= n) so monolithic == sharded is exact
    n_par = 1001
    Xp = X[:n_par]
    par_params = SearchParams(k=k, lam=1024, source="bruteforce",
                              use_gather_kernel=False)
    mono_p = LCCSIndex.build(Xp, m=32, family="euclidean", w=16.0, seed=0)
    ids_p, d_p = jit_search(mono_p, Q, par_params)
    ids_p, d_p = np.asarray(ids_p), np.asarray(d_p)

    records, parity_all = [], True
    for S in shard_counts:
        mesh = make_shard_mesh(S)
        sidx = mono.shard(mesh)

        sp = mono_p.shard(mesh)
        ids_sp, d_sp = sp.search(Q, par_params)
        ids_sp, d_sp = np.asarray(ids_sp), np.asarray(d_sp)
        parity = bool(
            np.allclose(np.sort(d_sp, axis=1), np.sort(d_p, axis=1),
                        rtol=1e-6, atol=0.0)
            and all(set(a.tolist()) == set(b.tolist())
                    for a, b in zip(ids_sp, ids_p))
        )
        parity_all &= parity

        for name, spar in serve_cfgs.items():
            (ids_s, _), t_s = timed(lambda: sidx.search(Q, spar))
            records.append({
                "source": name,
                "shards": S,
                "qps": round(Q.shape[0] / t_s, 1),
                "recall_at_10": round(recall(np.asarray(ids_s), gt), 4),
                "parity": parity,
            })
    base_shards = min(shard_counts)
    for rec in records:
        base = next(r for r in records
                    if r["source"] == rec["source"]
                    and r["shards"] == base_shards)
        rec["speedup_vs_base"] = round(rec["qps"] / base["qps"], 2)
    return {
        "n": int(n), "d": int(X.shape[1]), "k": k,
        "queries": int(Q.shape[0]),
        "base_shards": base_shards,
        "parity_n": n_par,
        "parity_exact": parity_all,
        "monolithic": mono_stats,
        "configs": records,
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--shard-counts", default="1,2,4,8")
    args = ap.parse_args()
    counts = tuple(int(s) for s in args.shard_counts.split(","))
    if args.worker:
        payload = _worker(args.n, counts, args.queries)
        assert payload["parity_exact"], (
            "sharded != monolithic on the parity corpus: "
            + json.dumps(payload["configs"])
        )
        print(_MARK + json.dumps(payload))
        return
    csv = CsvRows()
    payload = run(csv, n=args.n, shard_counts=counts, queries=args.queries)
    csv.dump()
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main()
