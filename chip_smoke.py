#!/usr/bin/env python3
"""On-chip smoke test of the LCCS-LSH query path at SIFT1M scale.

    python3 chip_smoke.py              # one TPU chip: static, dynamic, served
    python3 chip_smoke.py --chips 4    # four chips: the sharded index only

Every phase runs through the entry points a user calls (`LCCSIndex`,
`SegmentedLCCSIndex`, `ShardedLCCSIndex`, `RetrievalEngine` + `Router`) in
this one process, and checks what comes out:

  static    n=10^6, d=128, Euclidean, m=64 -- the SIFT1M shape of the
            paper's §6 and ann-benchmarks (`configs/lccs_ann.py`), data
            from `clustered_vectors` and `--seed`.  fp32, int8 (resident
            tail) and int8 with a disk tail each search 1024 held-out
            queries in batches of 32 with lccs, multiprobe-skip and
            bruteforce; recall@10 against an exact reference that shares no
            code with the system (blocked fp32 L2 at HIGHEST precision, then
            top_k) must meet the floors below, int8 must stay within 0.01 of
            fp32, the disk tail must return the resident tail's ids, the
            fused probe the legacy probe's candidates, and the chip's hashes
            a CPU hash of the same rows.
  dynamic   a SegmentedLCCSIndex of >= 10^5 rows (`ingest_chunks`, then
            insert, delete, compact) must answer exactly as `LCCSIndex.build`
            over its live rows.
  served    `RetrievalEngine` + `Router` as `launch/serve.py --async
            --replicas 2` runs them: >= 90% self-retrieval, no plan compiles
            after warm().
  sharded   (--chips 4 only) 4 x 10^6 rows, 10^6 per chip, each shard's CSA
            built on its own chip; recall@10 against the exact reference and
            exact parity with the monolithic index in the complete-coverage
            regime on a sub-corpus.

The last line of standard output is one JSON object naming the device, and
it is printed only when every check held.  Without a TPU the script exits
non-zero and prints no result; `--cpu-rehearsal` runs the same phases on the
CPU at a reduced `--n` to check the control flow, and prints no result
either.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parent

D = 128  # SIFT1M
M = 64  # configs/lccs_ann.py DATASETS["sift"].m
K = 10
N_QUERIES = 1024
BATCH = 32  # the serving engine's max_batch
CLUSTER_ROWS = 100  # mean rows per mixture component
W_FACTOR = 4.0  # w = W_FACTOR x median exact 10th-neighbour distance
HASH_ROWS = 65536  # rows hashed on the CPU for the precision check
DYN_ROWS = 131072  # live rows ingested into the dynamic index
DYN_CHUNK = 32768
SERVE_DOCS = 4096
SERVE_REQUESTS = 384
SHARD_SUB = 512  # complete-coverage parity sub-corpus (sharded phase)

# One SearchParams per source.  lam=256 over a 64-wide k-LCCS window; the
# multiprobe source searches 17 probe strings (the paper's §6 setting).
SOURCES = {
    "lccs": dict(source="lccs", lam=256, width=64),
    "multiprobe-skip": dict(source="multiprobe-skip", lam=256, width=64,
                            probes=17),
    "bruteforce": dict(source="bruteforce", lam=256),
}
SHARD_SOURCES = ("lccs", "multiprobe-skip")

# recall@10 floors at the default --n and --seed, per source: the CPU
# backend's recall on the first 256 queries of the same data and params
# (lccs 0.877, multiprobe-skip 0.944, bruteforce 0.876) less 0.03 for the
# other 768 queries.  The sharded floors are looser guards (no CPU run at
# 4 x 10^6 rows).  A rehearsal at another --n checks only recall > 0.
FLOORS = {"lccs": 0.84, "multiprobe-skip": 0.91, "bruteforce": 0.84}
SHARD_FLOORS = {"lccs": 0.7, "multiprobe-skip": 0.8}
INT8_GAP = 0.01


class Smoke:
    """Phase timer and check ledger: every failed check is kept, and the
    run fails at the end if any did.  Each phase's wall time is printed with
    the XLA compile time inside it (set-up, not search)."""

    def __init__(self):
        import jax

        self.failures: list[str] = []
        self.compile_s = 0.0

        def on_duration(event: str, secs: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    @contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        print(f"[chip_smoke] {name} ...", flush=True)
        yield
        print(f"[chip_smoke] {name}: {time.perf_counter() - t0:.2f} s "
              f"(compile {self.compile_s - c0:.2f} s)", flush=True)

    def check(self, ok: bool, what: str) -> None:
        print(f"[chip_smoke]   {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)


def _die(msg: str, code: int = 1):
    print(f"[chip_smoke] error: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _import_repro():
    """Import the package from this checkout's `src/` and nowhere else."""
    src = ROOT / "src"
    if not (src / "repro" / "core" / "__init__.py").is_file():
        _die(f"no repro package under {src}; run from a full checkout")
    sys.path.insert(0, str(src))
    import repro  # a namespace package: check where its path points

    paths = [Path(p).resolve() for p in repro.__path__]
    if paths != [src / "repro"]:
        _die(f"imported repro from {paths}, not {src / 'repro'}")


# ---------------------------------------------------------------------------
# The exact reference: shares no code with the system under test
# ---------------------------------------------------------------------------


def exact_knn(X, Q, k: int, qblock: int = 32):
    """(ids, dists) of the exact k nearest rows of X to each query: fp32
    squared L2 at HIGHEST matmul precision over query blocks, then top_k."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def run(X, Qb):
        xx = jnp.sum(X * X, axis=1)

        def one(q):
            d2 = (xx[None, :] - 2.0 * jnp.einsum("qd,nd->qn", q, X,
                                                  precision=hi)
                  + jnp.sum(q * q, axis=1)[:, None])
            neg, idx = jax.lax.top_k(-d2, k)
            return idx, jnp.sqrt(jnp.maximum(-neg, 0.0))

        return jax.lax.map(one, Qb)

    nq, d = Q.shape
    ids, dists = run(X, jnp.asarray(Q).reshape(nq // qblock, qblock, d))
    return (np.asarray(ids).reshape(nq, k),
            np.asarray(dists).reshape(nq, k))


def recall_at_k(ids, truth) -> float:
    import numpy as np

    k = truth.shape[1]
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                          for a, b in zip(np.asarray(ids), truth)]))


def _params(name: str, **kw):
    from repro.core import SearchParams

    return SearchParams(k=K, **{**SOURCES[name], **kw})


def search_batches(index, Q, params):
    """All of Q through `index.search` in BATCH-row batches -> host ids."""
    import numpy as np

    return np.concatenate([np.asarray(index.search(Q[i:i + BATCH], params)[0])
                           for i in range(0, Q.shape[0], BATCH)])


def make_data(n: int, seed: int):
    """(corpus (n, D), held-out queries (N_QUERIES, D)) from one mixture."""
    from repro.data.synthetic import clustered_vectors

    X = clustered_vectors(n + N_QUERIES, D, n_clusters=max(1, n // CLUSTER_ROWS),
                          seed=seed)
    return X[:n], X[n:]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def check_hashes(sm: Smoke, index, X) -> None:
    """The device's hash strings against a float64 NumPy hash of the same
    rows on the host: they may differ only where a projection sits on a
    bucket edge, within fp32 rounding of it."""
    import jax.numpy as jnp
    import numpy as np

    rows = X[:HASH_ROWS]
    fam = index.family
    h_dev = np.asarray(index.h[:HASH_ROWS])
    a, b = np.asarray(fam.a, np.float64), np.asarray(fam.b, np.float64)
    u = (rows.astype(np.float64) @ a + b) / fam.w
    h_cpu = np.floor(u).astype(np.int32)
    edge = np.abs(u - np.round(u)) < 1e-4
    miss = h_dev != h_cpu
    # the same projection at the backend's default matmul precision, for
    # the record: what the HIGHEST pin in core/lsh.py protects against
    h_def = np.asarray(jnp.floor(
        (jnp.matmul(jnp.asarray(rows), fam.a) + fam.b) / fam.w
    ).astype(jnp.int32))
    print(f"[chip_smoke]   hashes: {int(miss.sum())} of {miss.size} differ "
          f"from the CPU ({int((miss & ~edge).sum())} off a bucket edge); "
          f"at default matmul precision {int((h_def != h_cpu).sum())} would")
    sm.check(not (miss & ~edge).any(),
             "device hashes equal the CPU's away from bucket edges")


def phase_static(sm: Smoke, n: int, seed: int, full: bool, tmp: Path):
    import jax
    import numpy as np

    from repro.core import LCCSIndex
    from repro.core.index import jit_candidates

    with sm.phase("static.data"):
        X, Q = make_data(n, seed)
        truth, tdist = exact_knn(jax.device_put(X), Q, K)
        w = float(W_FACTOR * np.median(tdist[:, K - 1]))
        print(f"[chip_smoke]   n={n} d={D} m={M} queries={N_QUERIES} "
              f"clusters={max(1, n // CLUSTER_ROWS)} w={w!r}")

    recalls: dict[str, dict[str, float]] = {}
    ids_of: dict[str, dict[str, np.ndarray]] = {}
    layouts = (("fp32", dict(store="fp32")),
               ("int8", dict(store="int8")),
               ("int8-disk", dict(store="int8",
                                  tail_path=str(tmp / "tail.npy"))))
    for layout, kw in layouts:
        with sm.phase(f"static.build[{layout}]"):
            index = LCCSIndex.build(X, m=M, family="euclidean", w=w,
                                    seed=seed, **kw)
            jax.block_until_ready((index.h, index.csa))
        if layout == "fp32":
            with sm.phase("static.hash_check"):
                check_hashes(sm, index, X)
            with sm.phase("static.fused_vs_legacy_probe"):
                for name in ("lccs", "multiprobe-skip"):
                    for lo in (0, BATCH):
                        qb = Q[lo:lo + BATCH]
                        fused = jit_candidates(
                            index, qb, _params(name, use_probe_kernel=True))
                        legacy = jit_candidates(
                            index, qb, _params(name, use_probe_kernel=False))
                        same = all(np.array_equal(np.asarray(f), np.asarray(g))
                                   for f, g in zip(fused, legacy))
                        sm.check(same, f"{name} batch@{lo}: fused probe "
                                       f"candidates == legacy probe's")
        recalls[layout], ids_of[layout] = {}, {}
        for name in SOURCES:
            with sm.phase(f"static.search[{layout}/{name}]"):
                ids = search_batches(index, Q, _params(name))
            r = recall_at_k(ids, truth)
            recalls[layout][name], ids_of[layout][name] = r, ids
            print(f"[chip_smoke]   recall@{K} {layout}/{name} = {r!r}")
            floor = FLOORS[name] if full else 0.0
            sm.check(r > floor, f"{layout}/{name} recall {r:.4f} > {floor}")
        del index
    for name in SOURCES:
        gap = abs(recalls["int8"][name] - recalls["fp32"][name])
        sm.check(gap <= INT8_GAP,
                 f"{name}: int8 recall within {INT8_GAP} of fp32 ({gap:.4f})")
        sm.check(np.array_equal(ids_of["int8-disk"][name],
                                ids_of["int8"][name]),
                 f"{name}: disk-tail ids == resident-tail ids")
    return w, X, Q


def phase_dynamic(sm: Smoke, X, Q, w: float, seed: int, rows: int) -> None:
    import numpy as np

    from repro.core import LCCSIndex, SegmentedLCCSIndex
    from repro.core.index import iter_row_blocks

    rng = np.random.default_rng(seed)
    extra = min(4096, X.shape[0] - rows)
    with sm.phase(f"dynamic.churn[{rows} ingested + {extra} inserted]"):
        seg = SegmentedLCCSIndex.create(D, m=M, family="euclidean", w=w,
                                        seed=seed)
        chunk = min(DYN_CHUNK, rows)
        seg.ingest_chunks(iter_row_blocks(X[:rows], chunk), chunk_rows=chunk)
        seg.insert(X[rows:rows + extra])
        seg.delete(rng.choice(rows + extra, (rows + extra) // 20,
                              replace=False))
        seg.compact()
        seg.delete(rng.choice(rows + extra, (rows + extra) // 50,
                              replace=False))
        seg.compact(full=True)
        # one segment now holds every live row; the rebuild takes them in
        # the segment's row order, so CSA tie-breaks by row match too
        gid = np.asarray(seg.segments[0].gid)
        live = gid[gid >= 0]
        alive = np.flatnonzero(np.asarray(seg.alive)[: rows + extra])
        print(f"[chip_smoke]   live rows {seg.n_live}, segments "
              f"{seg.segment_sizes()}, buffer {seg.buffer_count}")
        sm.check(seg.n_live == live.size >= min(rows, 100_000)
                 and np.array_equal(np.sort(live), alive),
                 f"{seg.n_live} live rows, all in one segment")
    with sm.phase("dynamic.rebuild_over_live_rows"):
        mono = LCCSIndex.build(X[live], m=M, family="euclidean", w=w,
                               seed=seed)
    qs = Q[:256]
    for name in SOURCES:
        with sm.phase(f"dynamic.search[{name}]"):
            p = _params(name)
            got = [seg.search(qs[i:i + BATCH], p) for i in range(0, 256, BATCH)]
            ref = [mono.search(qs[i:i + BATCH], p) for i in range(0, 256, BATCH)]
            ids_s = np.concatenate([np.asarray(g[0]) for g in got])
            d_s = np.concatenate([np.asarray(g[1]) for g in got])
            ids_m = np.concatenate([np.asarray(r[0]) for r in ref])
            d_m = np.concatenate([np.asarray(r[1]) for r in ref])
            mapped = np.where(ids_m >= 0, live[np.maximum(ids_m, 0)], -1)
        rows_off = int((ids_s != mapped).any(axis=1).sum())
        d_off = float(np.max(np.abs(d_s - d_m)))
        sm.check(rows_off == 0 and np.allclose(d_s, d_m, rtol=1e-6, atol=1e-6),
                 f"{name}: segmented == rebuild over live rows ({rows_off} "
                 f"of {qs.shape[0]} queries differ in ids, max |dist diff| "
                 f"{d_off:.3g})")


def phase_served(sm: Smoke) -> None:
    import jax
    import numpy as np

    from repro.configs import ARCHS
    from repro.core import SearchParams
    from repro.data.synthetic import lm_token_batches
    from repro.launch.serve import serve_async
    from repro.models import api
    from repro.serve import RetrievalEngine

    # launch/serve.py's defaults: the gemma-2b smoke backbone, m=32, lccs
    # with k=5, lam=64, max_batch 32, --async --replicas 2
    cfg = ARCHS["gemma-2b"].smoke()
    params = SearchParams.from_legacy(k=5, lam=64, probes=1)
    with sm.phase(f"served.build[{SERVE_DOCS} docs]"):
        engine = RetrievalEngine(cfg, api.init_model(jax.random.key(0), cfg),
                                 m=32, metric="angular", max_batch=BATCH,
                                 search_params=params, store="fp32")
        corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=0)(0, SERVE_DOCS, 32)
        engine.build_index(corpus)
    picks = np.random.default_rng(1).integers(0, SERVE_DOCS, SERVE_REQUESTS)
    with sm.phase(f"served.requests[{SERVE_REQUESTS}]"):
        out = serve_async(engine, corpus, picks, params, replicas=2,
                          slo_ms=500.0, queue_depth=256)
    share = out["hits"] / max(out["requests"], 1)
    sm.check(out["requests"] == SERVE_REQUESTS and share >= 0.9,
             f"self-retrieval {out['hits']}/{out['requests']} >= 90%")
    sm.check(out["plan_misses"] == 0 and out["plan_evictions"] == 0,
             f"no plan compiles after warm() ({out['plan_misses']} compiles, "
             f"{out['plan_evictions']} evictions)")


def phase_sharded(sm: Smoke, n: int, seed: int, full: bool) -> None:
    import jax
    import numpy as np

    from repro.core import LCCSIndex, SearchParams
    from repro.shard import ShardedLCCSIndex, make_shard_mesh

    shards = 4
    with sm.phase("sharded.data"):
        X, Q = make_data(shards * n, seed)
        truth, tdist = exact_knn(jax.device_put(X), Q, K)
        w = float(W_FACTOR * np.median(tdist[:, K - 1]))
        print(f"[chip_smoke]   n={shards * n} ({n} per shard) d={D} m={M} "
              f"w={w!r}")
    mesh = make_shard_mesh(shards)
    with sm.phase("sharded.build"):
        sidx = ShardedLCCSIndex.build(X, mesh=mesh, m=M, family="euclidean",
                                      w=w, seed=seed)
        jax.block_until_ready(sidx.csa)
    devices = list(mesh.devices.flat)
    placed = True
    for leaf in jax.tree.leaves((sidx.csa, sidx.store, sidx.h)):
        for sh in leaf.addressable_shards:
            s = sh.index[0].start or 0
            placed &= sh.data.shape[0] == 1 and sh.device == devices[s]
    csa_devs = sorted({str(sh.device) for leaf in jax.tree.leaves(sidx.csa)
                       for sh in leaf.addressable_shards})
    print(f"[chip_smoke]   CSA shards on {csa_devs}")
    sm.check(placed and len(csa_devs) == shards,
             "each shard's CSA, store and hashes live on their own device")
    for name in SHARD_SOURCES:
        with sm.phase(f"sharded.search[{name}]"):
            ids = search_batches(sidx, Q, _params(name))
        r = recall_at_k(ids, truth)
        print(f"[chip_smoke]   recall@{K} sharded/{name} = {r!r}")
        floor = SHARD_FLOORS[name] if full else 0.0
        sm.check(r > floor, f"sharded/{name} recall {r:.4f} > {floor}")
    del sidx
    with sm.phase(f"sharded.parity[{SHARD_SUB} rows]"):
        sub = X[:SHARD_SUB]
        mono = LCCSIndex.build(sub, m=M, family="euclidean", w=w, seed=seed)
        small = mono.shard(mesh)
        qs = Q[:BATCH]
        for name in ("bruteforce",) + SHARD_SOURCES:
            # the fused probe, as on a TPU: the legacy window path would
            # materialise (rows, 2W, 2m) at W=SHARD_SUB
            p = _params(name, use_probe_kernel=True).replace(
                lam=SHARD_SUB, width=SHARD_SUB)
            ids_m, d_m = map(np.asarray, mono.search(qs, p))
            ids_s, d_s = map(np.asarray, small.search(qs, p))
            same_d = np.allclose(np.sort(d_s, 1), np.sort(d_m, 1), rtol=1e-6,
                                 atol=0.0)
            same_ids = all(set(a.tolist()) == set(b.tolist())
                           for a, b, dm in zip(ids_s, ids_m, d_m)
                           if len(set(np.round(dm, 5))) == len(dm))
            sm.check(same_d and same_ids,
                     f"{name}: sharded == monolithic at complete coverage")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: static, dynamic and served phases on one chip; "
                         "4: only the sharded phase, 10^6 rows per chip")
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="corpus rows (per chip with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU backend to check the control flow "
                         "(use a small --n); prints no result")
    args = ap.parse_args()
    if args.cpu_rehearsal and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}").strip()

    _import_repro()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        _die(f"no TPU: JAX sees {platform!r} devices; chip_smoke.py runs "
             f"only on a TPU (--cpu-rehearsal checks the control flow)", 2)
    if len(devices) < args.chips:
        _die(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}", 2)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"[chip_smoke] devices: {len(devices)} x {devices[0].device_kind} "
          f"({platform}); compile cache {enable_compile_cache()}", flush=True)
    full = args.n == 1_000_000
    sm = Smoke()
    tmp = ROOT / ".smoke_tmp"
    t0 = time.perf_counter()
    try:
        tmp.mkdir(exist_ok=True)
        if args.chips == 4:
            phase_sharded(sm, args.n, args.seed, full)
        else:
            w, X, Q = phase_static(sm, args.n, args.seed, full, tmp)
            phase_dynamic(sm, X, Q, w, args.seed, min(DYN_ROWS, args.n // 2))
            del X
            phase_served(sm)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"[chip_smoke] total {time.perf_counter() - t0:.2f} s "
          f"(compile {sm.compile_s:.2f} s)", flush=True)
    if sm.failures:
        _die(f"{len(sm.failures)} check(s) failed: " + "; ".join(sm.failures))
    if args.cpu_rehearsal:
        print("[chip_smoke] rehearsal passed (CPU; no device result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
