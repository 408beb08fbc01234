"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows and writes the search-perf
trajectory (QPS / recall / index bytes per store x source, plus the sharded
QPS-scaling curve) to ``BENCH_search.json`` so successive PRs are comparable
machine-readably.

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--smoke]
  --quick  halve the dataset sizes
  --smoke  fig12 (store sweep) + fig13 (sharded scaling) + fig14 (serving
           front) + stage breakdown (instrumented plans)
           only, tiny n -- the CI gate; still emits BENCH_search.json
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from .common import CsvRows


def _write_bench_json(payload: dict, path: str | Path = "BENCH_search.json"):
    import os

    from repro.exec import plan_cache

    payload = dict(payload, wall_s=round(payload.get("wall_s", 0.0), 1))
    # staged-pipeline compile count for the whole bench run (repro.exec):
    # a jump in misses between PRs means a code path started retracing
    payload["plan_cache"] = plan_cache().stats()
    # absolute QPS on small shared-CPU runners swings +-50% run to run;
    # record the environment so PR-over-PR comparisons weigh deltas sanely
    payload["env"] = {
        "cpu_count": os.cpu_count(),
        "platform": sys.platform,
        "devices": os.environ.get("XLA_FLAGS", ""),
    }
    # benchmarks/scale.py owns the "scale" block and merges it in with a
    # read-modify-write; keep an existing block alive across run.py's
    # wholesale rewrite so the two emitters compose in either order
    path = Path(path)
    if "scale" not in payload and path.exists():
        try:
            prev = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            prev = {}
        if "scale" in prev:
            payload["scale"] = prev["scale"]
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path}")


def main() -> None:
    quick = "--quick" in sys.argv
    smoke = "--smoke" in sys.argv
    n = 4000 if quick else 8000
    csv = CsvRows()
    t0 = time.time()
    from . import fig12_memory, fig13_sharded, fig14_serving, stage_breakdown

    if smoke:
        print("# fig12 (smoke): recall vs store bytes / QPS per store", flush=True)
        search_perf = fig12_memory.run(csv, n=1500)
        print("# fig13 (smoke): sharded QPS scaling + exact parity", flush=True)
        search_perf["sharded"] = fig13_sharded.run(
            csv, n=1200, shard_counts=(1, 2, 4), queries=32
        )
        print("# fig14 (smoke): serving front -- bursty p99 + replica SLO sweep",
              flush=True)
        search_perf["serving"] = fig14_serving.run(
            csv, corpus_docs=128, max_batch=8,
            n_bursts=4, burst=20, period_s=0.7, sweep_cap=800
        )
        print("# trace (smoke): per-stage breakdown", flush=True)
        search_perf["stage_breakdown"] = stage_breakdown.run(
            csv, n=1000, queries=32, repeats=3
        )
        search_perf["wall_s"] = time.time() - t0
        search_perf["mode"] = "smoke"
        _write_bench_json(search_perf)
        print("name,us_per_call,derived")
        csv.dump()
        return

    from . import fig4_5_recall, fig6_7_indexing, fig8_k, fig9_m, fig10_probes
    from . import fig11_dynamic, kernel_bench, table1_scaling

    print("# fig4/5: query time vs recall (Euclidean + Angular)", flush=True)
    fig4_5_recall.run(csv, n=n)
    print("# fig6/7: query time vs index size / build time", flush=True)
    fig6_7_indexing.run(csv, n=n)
    print("# fig8: sensitivity to k", flush=True)
    fig8_k.run(csv, n=n)
    print("# fig9: impact of m", flush=True)
    fig9_m.run(csv, n=n)
    print("# fig10: impact of #probes", flush=True)
    fig10_probes.run(csv, n=n)
    print("# fig11: dynamic churn (segmented vs full rebuild)", flush=True)
    fig11_dynamic.run(csv, n=n // 2)
    print("# fig12: recall vs store bytes / QPS per store", flush=True)
    search_perf = fig12_memory.run(csv, n=n)
    print("# fig13: sharded QPS scaling + exact parity", flush=True)
    search_perf["sharded"] = fig13_sharded.run(
        csv, n=n, shard_counts=(1, 2, 4, 8), queries=32
    )
    print("# fig14: serving front -- bursty p99 + replica SLO sweep", flush=True)
    search_perf["serving"] = fig14_serving.run(csv)
    print("# trace: per-stage breakdown", flush=True)
    search_perf["stage_breakdown"] = stage_breakdown.run(csv)
    print("# table1: complexity scaling in n", flush=True)
    table1_scaling.run(csv)
    print("# kernels", flush=True)
    kernel_bench.run(csv)

    search_perf["wall_s"] = time.time() - t0
    search_perf["mode"] = "quick" if quick else "full"
    _write_bench_json(search_perf)
    print(f"# total bench wall time: {time.time()-t0:.1f}s")
    print("name,us_per_call,derived")
    csv.dump()


if __name__ == "__main__":
    main()
