"""Serving launcher: backbone + LCCS-LSH retrieval over a corpus.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --corpus 512 --requests 128 [--ckpt-dir /tmp/run1] [--shards 4] \
        [--async --replicas 2 --slo-ms 50]
Loads trained weights from --ckpt-dir when present (the train launcher's
output), otherwise serves from random init (layout/perf testing).

--shards N partitions the index over N devices (repro.shard): shard-local
search + exact global top-k merge.  On a CPU host with fewer visible devices
the launcher re-execs itself once with
XLA_FLAGS=--xla_force_host_platform_device_count=N (the CI trick).

--async serves the request stream through the deadline-aware serving front
(repro.router): --replicas N replicated engines (sharing one index + one
jitted backbone, so plans compile once) behind one submit(), --slo-ms the
per-request deadline.  The launcher warms every plan, polls the router's
readiness probe (k8s-style: live workers + warm plan cache), then reports
the SLO window: p50/p95/p99 end-to-end latency, deadline misses, queue
depth, and the per-replica retrace audit.

Observability (repro.obs): --metrics-port N serves Prometheus text format on
:N/metrics and logs a periodic one-line stats summary; --trace PATH collects
the request span tree (queue wait, embed, search, exec stages) and writes
Chrome-trace JSON loadable at https://ui.perfetto.dev; --instrument serves
through the staged per-stage-timed plans (bit-identical results);
--drift-probe N replays N pinned queries against brute-force ground truth
after serving and reports achieved recall (the recall-drift gauge).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS
from repro.core import SearchParams, available_sources, available_stores
from repro.data.synthetic import lm_token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve import RetrievalEngine
from repro.train.step import init_train_state


def _ensure_devices(n_shards: int) -> None:
    """Guarantee >= n_shards visible devices.  On CPU, re-exec once with the
    host-platform device-count flag (it must be set before jax initialises
    its backends, so a plain env mutation inside this process is too late)."""
    if n_shards <= 1 or len(jax.devices()) >= n_shards:
        return
    if jax.default_backend() != "cpu" or os.environ.get("_REPRO_SERVE_REEXEC"):
        raise RuntimeError(
            f"--shards {n_shards} needs {n_shards} devices, have "
            f"{len(jax.devices())} on backend {jax.default_backend()!r}"
        )
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_shards}"
    ).strip()
    env["_REPRO_SERVE_REEXEC"] = "1"
    os.execve(sys.executable,
              [sys.executable, "-m", "repro.launch.serve"] + sys.argv[1:], env)


def _wait_ready(router, timeout_s: float = 120.0, poll_s: float = 0.1) -> float:
    """Readiness probe: poll the router until every replica has a live
    worker and a warm plan cache (the k8s-style gate a deployment recipe
    points its readinessProbe at).  Returns the time-to-ready in seconds."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        if router.ready():
            return time.perf_counter() - t0
        time.sleep(poll_s)
    st = router.stats()
    raise TimeoutError(
        f"router not ready after {timeout_s:.0f}s: "
        + ", ".join(f"{r.name}: batches={r.serve['batches']}"
                    for r in st.replicas)
    )


def serve_async(engine, corpus, picks, search_params, *, replicas: int,
                slo_ms: float, queue_depth: int) -> dict:
    """The --async serving path: replicate the engine, warm + probe
    readiness, push the request stream through the deadline-aware front,
    and report the SLO window + per-replica retrace audit.  Returns the
    window's summary: requests, self-retrieval hits, rejections, SLO misses
    and the plan compiles/evictions counted after warm()."""
    from repro.router import QueueFull, Router

    router = Router.replicate(engine, replicas, params=search_params,
                              default_slo_ms=slo_ms, max_depth=queue_depth)
    try:
        router.warm(corpus[: engine.max_batch])
        ready_s = _wait_ready(router)
        print(f"[launch.serve] router ready in {ready_s*1e3:.0f} ms "
              f"({replicas} replicas, slo {slo_ms:.0f} ms, "
              f"queue depth {queue_depth})")
        t0 = time.perf_counter()
        tickets, rejected = [], 0
        for i in picks:
            try:
                tickets.append((i, router.submit(corpus[i])))
            except QueueFull as e:
                rejected += 1
                time.sleep(e.retry_after_s)
        outs = [(i, t.result(timeout=300)) for i, t in tickets]
        router.drain(timeout_s=60)
        wall = time.perf_counter() - t0
        hits = sum(int(i in ids) for i, (ids, _) in outs)
        st = router.stats()
        lat = st.latency
        print(
            f"[launch.serve] async: {st.completed} completed / "
            f"{st.rejected} rejected / {st.deadline_misses} SLO misses "
            f"in {wall:.2f}s ({st.completed / wall:.1f} QPS); "
            f"p50/p95/p99 = {lat['p50_ms']}/{lat['p95_ms']}/{lat['p99_ms']} ms; "
            f"self-retrieval {hits}/{len(tickets)}"
        )
        # retrace audit, now per replica: misses must be flat after warm(),
        # and evictions flat always (an evicted plan is a future recompile)
        for r in st.replicas:
            print(
                f"[launch.serve]   {r.name}: {r.serve['batches']} batches, "
                f"sizes {r.batch_size_hist}, plan "
                f"{r.serve['plan_misses']} compiles / "
                f"{r.serve['plan_hits']} reuses / "
                f"{r.serve['plan_evictions']} evictions"
            )
        return {
            "requests": len(tickets),
            "hits": hits,
            "rejected": st.rejected,
            "deadline_misses": st.deadline_misses,
            "plan_misses": sum(r.serve["plan_misses"] for r in st.replicas),
            "plan_evictions": sum(r.serve["plan_evictions"]
                                  for r in st.replicas),
        }
    finally:
        router.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--corpus", type=int, default=512)
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--lam", type=int, default=64)
    ap.add_argument("--probes", type=int, default=1)
    ap.add_argument("--source", default=None, choices=sorted(available_sources()),
                    help="candidate source; default: lccs, or multiprobe-skip "
                         "when --probes > 1")
    ap.add_argument("--m", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--dynamic", action="store_true",
                    help="serve a SegmentedLCCSIndex and interleave "
                         "insert/delete/compact updates into the stream")
    ap.add_argument("--store", default="fp32",
                    choices=sorted(available_stores()),
                    help="corpus-vector layout: fp32 = exact single-stage "
                         "verify; bf16/int8 = quantized two-stage rerank")
    ap.add_argument("--build-chunk-rows", type=int, default=None,
                    metavar="ROWS",
                    help="build the static index out of core: stream the "
                         "embedded corpus through the chunked CSA merge in "
                         "ROWS-row blocks (bit-identical to the monolithic "
                         "build; bounds build transients to O(ROWS) fp32)")
    ap.add_argument("--rerank-mult", type=int, default=4,
                    help="two-stage over-fetch factor (quantized stores "
                         "rerank the best k*rerank_mult survivors in fp32)")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the index over this many devices "
                         "(shard-local search + exact global top-k merge); "
                         "on CPU the launcher re-execs with a fake "
                         "multi-device host platform when needed")
    ap.add_argument("--async", dest="async_serve", action="store_true",
                    help="serve through the deadline-aware async front "
                         "(repro.router): EDF micro-batching, bounded-queue "
                         "backpressure, SLO latency stats")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica engines behind the router (--async); "
                         "replicas share one index and one compiled "
                         "backbone, so plans compile once")
    ap.add_argument("--slo-ms", type=float, default=500.0,
                    help="per-request deadline for --async submissions; "
                         "late answers are served but counted as SLO misses "
                         "(the default budgets the launcher's all-at-once "
                         "request burst, where queue wait dominates)")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="per-replica admission bound (--async); beyond it "
                         "submit() rejects with a retry-after hint")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text format on this port "
                         "(/metrics) and log a periodic one-line stats "
                         "summary (repro.obs)")
    ap.add_argument("--stats-interval", type=float, default=5.0,
                    help="seconds between periodic stats log lines "
                         "(with --metrics-port)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="collect the request span tree and write "
                         "Chrome-trace JSON here (load at ui.perfetto.dev)")
    ap.add_argument("--instrument", action="store_true",
                    help="serve through the staged per-stage-timed plan "
                         "variants: bit-identical results, every exec stage "
                         "timed into repro_exec_stage_seconds and the trace")
    ap.add_argument("--drift-probe", type=int, default=0, metavar="N",
                    help="after serving, replay N pinned corpus queries "
                         "against brute-force ground truth and report "
                         "achieved recall (the repro_recall_drift gauge)")
    args = ap.parse_args()

    if args.shards > 1 and args.dynamic:
        ap.error("--shards and --dynamic are mutually exclusive "
                 "(the sharded layout is static)")
    if args.async_serve and args.dynamic:
        ap.error("--async serves query traffic; corpus updates (--dynamic) "
                 "stay on the synchronous stream path")
    if args.build_chunk_rows is not None and args.dynamic:
        ap.error("--build-chunk-rows streams the *static* build; dynamic "
                 "corpora ingest out of core via "
                 "SegmentedLCCSIndex.ingest_chunks")
    _ensure_devices(args.shards)
    enable_compile_cache()

    # any width-vs-lam warning fires once, on the from_legacy construction;
    # the chained field replaces below derive from the same user choice
    from repro.core.params import _suppress_width_warning

    search_params = SearchParams.from_legacy(
        k=args.k, lam=args.lam, probes=args.probes
    )
    with _suppress_width_warning():
        search_params = search_params.replace(store=args.store,
                                              rerank_mult=args.rerank_mult)
        if args.shards > 1:
            search_params = search_params.replace(shards=args.shards)
        if args.source:
            search_params = search_params.replace(source=args.source)

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.smoke()
    params = api.init_model(jax.random.key(0), cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        if mgr.latest_step() is not None:
            like = init_train_state(jax.random.key(0), cfg)
            state, meta = mgr.restore(like)
            params = state.params
            print(f"[launch.serve] restored step {meta['step']} from {args.ckpt_dir}")

    # observability front: metrics endpoint + periodic log line + tracing
    metrics_srv, stats_log = None, None
    if args.metrics_port is not None:
        from repro.obs import StatsLogger, start_metrics_server

        metrics_srv = start_metrics_server(args.metrics_port)
        stats_log = StatsLogger(interval_s=args.stats_interval).start()
        print(f"[launch.serve] Prometheus metrics on "
              f":{metrics_srv.port}/metrics "
              f"(stats line every {args.stats_interval:.0f}s)")
    if args.trace:
        from repro.obs import enable_tracing

        enable_tracing()

    engine = RetrievalEngine(cfg, params, m=args.m, metric="angular",
                             max_batch=args.max_batch,
                             search_params=search_params,
                             store=args.store,
                             shards=args.shards if args.shards > 1 else None,
                             instrument=args.instrument)
    gen = lm_token_batches(vocab=cfg.vocab, seed=0)
    corpus, _ = gen(0, args.corpus, 32)
    # perf_counter, not time.time: the wall clock can step (NTP) mid-build,
    # and every other serve-path timer is already monotonic
    t0 = time.perf_counter()
    engine.build_index(corpus, dynamic=args.dynamic,
                       chunk_rows=args.build_chunk_rows)
    layout = ("dynamic" if args.dynamic
              else f"{args.shards} shards" if args.shards > 1 else "static")
    print(f"[launch.serve] indexed {args.corpus} docs in "
          f"{time.perf_counter()-t0:.1f}s "
          f"(index {engine.index.index_bytes()/1e6:.2f} MB + "
          f"{args.store} store {engine.index.store_bytes()/1e6:.2f} MB, "
          f"{layout})")

    rng = np.random.default_rng(1)
    picks = rng.integers(0, args.corpus, args.requests)
    if args.async_serve:
        serve_async(engine, corpus, picks, search_params,
                    replicas=args.replicas, slo_ms=args.slo_ms,
                    queue_depth=args.queue_depth)
        _obs_epilogue(engine, corpus, args, search_params, metrics_srv,
                      stats_log)
        return
    stream: list = [corpus[i] for i in picks]
    if args.dynamic:
        # interleave a churn burst mid-stream: new docs in, a few docs out,
        # then a compaction, with query micro-batches around each update
        extra, _ = gen(1, args.max_batch, 32)
        mid = len(stream) // 2
        stream[mid:mid] = [
            ("insert", extra),
            ("delete", np.arange(0, args.corpus, max(args.corpus // 8, 1))),
            ("compact",),
        ]
    results = engine.serve_stream(stream)
    qres = [r for r in results if not (isinstance(r, tuple)
                                       and isinstance(r[0], str))]
    hits = sum(int(picks[i] in ids) for i, (ids, _) in enumerate(qres))
    s = engine.stats
    print(
        f"[launch.serve] {s.requests} requests / {s.batches} batches; "
        f"embed {s.embed_s:.2f}s search {s.search_s:.2f}s; "
        f"self-retrieval {hits}/{args.requests}"
    )
    # retrace audit: plan misses are staged-pipeline compiles (repro.exec);
    # a steady-state serving loop must show a flat miss count, and zero
    # evictions (an evicted plan is a future recompile)
    print(
        f"[launch.serve] plan cache: {s.plan_misses} compiles / "
        f"{s.plan_hits} reuses / {s.plan_evictions} evictions "
        f"across {s.batches} batches"
    )
    if args.dynamic:
        idx = engine.index
        print(
            f"[launch.serve] churn: +{s.inserts} -{s.deletes} docs, "
            f"{s.compactions} compactions; live={idx.n_live} "
            f"segments={idx.segment_sizes()} buffer={idx.buffer_count}"
        )
    _obs_epilogue(engine, corpus, args, search_params, metrics_srv, stats_log)


def _obs_epilogue(engine, corpus, args, search_params, metrics_srv,
                  stats_log) -> None:
    """Post-serve observability: drift probe, Chrome-trace export, metrics
    teardown."""
    if args.drift_probe:
        from repro.obs import RecallDriftProbe

        n = min(args.drift_probe, len(corpus))
        sample = np.asarray(engine.embed(corpus[:n]))
        probe = RecallDriftProbe(lambda: engine.index, sample,
                                 search_params, label="launch.serve")
        recall = probe.measure()
        print(f"[launch.serve] recall-drift probe: "
              f"recall@{search_params.k} = {recall:.3f} over {n} pinned "
              f"queries (gauge repro_recall_drift)")
    if args.trace:
        from repro.obs import export_chrome_trace

        doc = export_chrome_trace(args.trace)
        print(f"[launch.serve] wrote {len(doc['traceEvents'])} trace events "
              f"to {args.trace} (load at ui.perfetto.dev)")
    if stats_log is not None:
        stats_log.stop()
    if metrics_srv is not None:
        metrics_srv.close()


if __name__ == "__main__":
    main()
