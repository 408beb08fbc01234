"""Per-stage breakdown of the staged query pipeline (repro.obs + repro.exec).

Runs the *instrumented* plan variant (`execute(..., instrument=True)`) over a
small corpus for the monolithic and sharded topologies and reports, per
stage, wall milliseconds summed across repeats -- the numbers a flame chart
would show, but machine-readable so successive PRs can compare where query
time actually goes (probe-bound vs rerank-bound is the axis every paper
tuning knob moves).

Timings come off the registry histogram
`repro_exec_stage_seconds{topology,stage}` via snapshot/delta -- the exact
series a Prometheus scrape of a production server exports -- and the run
also collects the span stream with tracing enabled, writing it as
``BENCH_trace.json`` (Chrome Trace Event Format: load at ui.perfetto.dev or
chrome://tracing).

Sharding needs fake host devices fixed before jax initialises, so `run`
re-invokes this module as a subprocess with XLA_FLAGS set and parses one
JSON line back; run.py folds the payload into BENCH_search.json under
"stage_breakdown".
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

from .common import CsvRows, cpu_worker_env

_MARK = "TRACE-JSON:"


def run(csv: CsvRows, n: int = 1500, queries: int = 32, repeats: int = 5,
        trace_path: str = "BENCH_trace.json") -> dict:
    """Spawn the measurement subprocess (2 fake devices for the sharded
    topology) and fold per-stage means into csv + the returned payload."""
    env = cpu_worker_env(2)
    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.stage_breakdown", "--worker",
         "--n", str(n), "--queries", str(queries),
         "--repeats", str(repeats), "--trace-path", trace_path],
        capture_output=True, text=True, timeout=1800, env=env, cwd=root,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"stage_breakdown worker failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    line = next(l for l in proc.stdout.splitlines() if l.startswith(_MARK))
    payload = json.loads(line[len(_MARK):])
    for topo, stages in payload["topologies"].items():
        for stage, rec in stages.items():
            csv.add(f"trace/{topo}/{stage}", rec["mean_ms"] / 1e3,
                    f"total_ms={rec['total_ms']};count={rec['count']}")
    return payload


def _worker(n: int, n_queries: int, repeats: int, trace_path: str) -> dict:
    import numpy as np

    from repro.core import LCCSIndex, SearchParams
    from repro.exec import execute
    from repro.obs.registry import registry
    from repro.obs.trace import enable_tracing, export_chrome_trace
    from repro.shard import make_shard_mesh

    from benchmarks.common import dataset

    X, Q, _ = dataset("sift-like", n=n)
    Q = Q[:n_queries]
    sp = SearchParams(k=10, lam=min(200, n), use_gather_kernel=False,
                      use_probe_kernel=False)
    mono = LCCSIndex.build(X, m=32, family="euclidean", w=16.0, seed=0)
    indexes = {
        "monolithic": mono,
        "sharded": mono.shard(make_shard_mesh(2)),
    }

    enable_tracing()  # span stream -> BENCH_trace.json alongside the stats
    topologies: dict[str, dict] = {}
    for topo, idx in indexes.items():
        execute(idx, Q, sp, instrument=True)  # compile outside the window
        snap = registry().snapshot()
        for _ in range(repeats):
            ids, dists = execute(idx, Q, sp, instrument=True)
            np.asarray(ids), np.asarray(dists)
        d = registry().since(snap)
        hist = registry().get("repro_exec_stage_seconds")
        stages: dict[str, dict] = {}
        for ls in hist.labelsets():
            if ls["topology"] != topo:
                continue
            vals = d.samples("repro_exec_stage_seconds", **ls)
            if not vals:
                continue
            stages[ls["stage"]] = {
                "count": len(vals),
                "total_ms": round(sum(vals) * 1e3, 3),
                "mean_ms": round(sum(vals) / len(vals) * 1e3, 3),
                "max_ms": round(max(vals) * 1e3, 3),
            }
        topologies[topo] = stages

    doc = export_chrome_trace(trace_path)
    return {
        "n": int(n), "queries": int(n_queries), "repeats": int(repeats),
        "topologies": topologies,
        "trace_file": trace_path,
        "trace_events": len(doc["traceEvents"]),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--n", type=int, default=1500)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--trace-path", default="BENCH_trace.json")
    args = ap.parse_args()
    if args.worker:
        print(_MARK + json.dumps(
            _worker(args.n, args.queries, args.repeats, args.trace_path)))
        return
    csv = CsvRows()
    payload = run(csv, n=args.n, queries=args.queries, repeats=args.repeats,
                  trace_path=args.trace_path)
    csv.dump()
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main()
