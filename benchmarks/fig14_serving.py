"""Beyond-paper Figure 14: the serving front under open-loop load.

Two experiments over one smoke-scale LM-in-the-loop engine
(`RetrievalEngine`, gemma-2b reduced), both *open-loop*: arrivals follow a
schedule regardless of completions -- the regime where queueing delay is
visible (closed-loop drivers self-throttle and hide it):

  bursty    equal offered load, mixed token lengths (16/32 interleaved
            inside each burst), one replica each side.  The sync baseline
            replays `serve_stream` semantics faithfully against the
            arrival clock: FIFO order, flush on token-length change,
            blocking serve_batch -- so alternating lengths truncate its
            micro-batches to ~1 request.  The router's EDF queue groups by
            shape and keeps batches full.  Both sides pad dispatches to
            `max_batch` (one compile per token length -- without the
            courtesy the sync side would pay multi-second mid-measurement
            XLA compiles and the comparison would measure compiles, not
            queueing).  Async p99 must come out lower at equal load.
  replicas  max sustained QPS at a fixed p99 SLO for 1 vs 2 replicas:
            sweep offered Poisson load as fractions of the *measured
            saturated 1-replica router throughput* (R1, min of 3 -- a
            sustained-QPS claim deserves a conservative denominator),
            levels approaching and crossing R1
            (0.7/0.85/0.95/1.05/1.15).
            A level is *sustained* only when EVERY trial window meets
            the SLO with zero admission rejections -- an SLO is a
            guarantee, not a median -- and max sustained QPS is the top
            of the *contiguous* sustained prefix: capacity at an SLO
            means every lower load is also safe (open-loop load
            fluctuates), so a lucky pass above a failed level is
            measurement noise, not capacity.  This is where the second
            replica earns its keep: a single worker pipeline has
            serialization points (one wakeup path, one Python thread),
            so a scheduling stall lands straight on the lone queue's
            tail, while a 2-replica front keeps serving through one
            worker's bad window and its worst-trial p99 stays put.
            Each cell lingers rate-matched (time to fill max_batch at
            the replica's traffic share, capped at 0.2*SLO): bucketed
            padding makes a half-empty batch cost full-batch compute,
            so a fixed short linger would silently halve 2-replica
            capacity at moderate load.  (On a multi-core host the
            second replica also raises raw throughput; this container
            pins one CPU, so worst-window stability is the measured
            effect.)  Replicas share one index + one jitted backbone,
            and batches are bucketed, so the per-replica `plan_misses`
            delta must be flat (0) over every measured window -- the
            no-silent-retrace guarantee under concurrent serving.

Latency measurements are only as quiet as the process they run in: after
the fig12/fig13 sweeps the harness process carries enough allocator/cache
state that open-loop timings degrade badly.  Like fig13, `run` therefore
re-invokes this module as a fresh subprocess and parses one JSON line back;
the records land in BENCH_search.json under "serving" (see run.py).

Per-window numbers (latency percentiles, deadline misses, batch-size
histogram) are read off the `repro.obs` registry via snapshot/delta -- the
same series a Prometheus scrape exports -- rather than hand-rolled dict
plumbing.  `router.stats()` remains the source for per-replica plan-miss
attribution (the no-silent-retrace check needs per-engine deltas).
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from .common import CsvRows, cpu_worker_env

_MARK = "FIG14-JSON:"


def _build_engine(corpus_docs: int, max_batch: int):
    import jax

    from repro.configs import ARCHS
    from repro.core import SearchParams
    from repro.models import api
    from repro.serve import RetrievalEngine

    cfg = ARCHS["gemma-2b"].smoke()
    params = api.init_model(jax.random.key(0), cfg)
    # m=32 + a small max_batch puts per-batch Python (queue pop, CSA probe
    # orchestration, dispatch) on par with XLA compute -- the regime real
    # small-batch serving lives in, and the one where a second worker
    # thread actually overlaps useful work
    engine = RetrievalEngine(
        cfg, params, m=32, metric="angular", max_batch=max_batch,
        search_params=SearchParams(k=5, lam=32),
    )
    from repro.data.synthetic import lm_token_batches

    corpus, _ = lm_token_batches(vocab=cfg.vocab, seed=0)(0, corpus_docs, 32)
    engine.build_index(corpus)
    return engine, corpus


def _bursty_schedule(n_bursts, burst, period_s, pools, rng):
    """`burst` arrivals at each period boundary, alternating token lengths
    request by request (the pattern serve_stream's flush-on-change rule
    handles worst)."""
    sched = []
    for b in range(n_bursts):
        for i in range(burst):
            pool = pools[i % len(pools)]
            sched.append((b * period_s + 1e-4 * i,
                          pool[rng.integers(len(pool))]))
    return sched


def _poisson_schedule(rate_qps, n, pool, rng):
    ts = np.cumsum(rng.exponential(1.0 / rate_qps, size=n))
    return [(float(t), pool[rng.integers(len(pool))]) for t in ts]


def _run_sync(engine, schedule, params, pad_to):
    """Replay `serve_stream` semantics against the arrival clock: FIFO,
    coalesce only already-arrived same-shape requests, flush on shape
    change, blocking serve_batch per dispatch.  Returns per-request
    end-to-end latencies (seconds)."""
    from repro.router.router import _pad_rows

    lat = []
    n = len(schedule)
    i = 0
    t_start = time.perf_counter()
    while i < n:
        now = time.perf_counter() - t_start
        if now < schedule[i][0]:
            time.sleep(schedule[i][0] - now)
            now = time.perf_counter() - t_start
        shape = schedule[i][1].shape
        j = i + 1
        while (j < n and j - i < pad_to and schedule[j][0] <= now
               and schedule[j][1].shape == shape):
            j += 1
        rows = np.stack([schedule[b][1] for b in range(i, j)])
        engine.serve_batch(_pad_rows(rows, pad_to), params)
        t_done = time.perf_counter() - t_start
        lat.extend(t_done - schedule[b][0] for b in range(i, j))
        i = j
    return lat


def _run_async(router, schedule, slo_ms):
    """Submit the schedule open-loop through the router.  Returns
    (rejections, wall seconds from first submit to drain).  Latencies land
    in the router's window."""
    from repro.router import QueueFull

    tickets, rejected = [], 0
    t_start = time.perf_counter()
    for t_arr, toks in schedule:
        now = time.perf_counter() - t_start
        if now < t_arr:
            time.sleep(t_arr - now)
        try:
            tickets.append(router.submit(toks, deadline_ms=slo_ms))
        except QueueFull:
            rejected += 1
    for t in tickets:
        t.result(timeout=600)
    router.drain(timeout_s=120)
    return rejected, time.perf_counter() - t_start


def run(csv: CsvRows, *, corpus_docs: int = 160, max_batch: int = 8,
        n_bursts: int = 5, burst: int = 20, period_s: float = 0.7,
        levels=(0.7, 0.85, 0.95, 1.05, 1.15), sweep_cap: int = 960) -> dict:
    """Spawn the measurement subprocess (fresh jax runtime, quiet heap) and
    fold its payload into csv + the returned BENCH block."""
    env = cpu_worker_env()
    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig14_serving", "--worker",
         "--corpus-docs", str(corpus_docs), "--max-batch", str(max_batch),
         "--n-bursts", str(n_bursts), "--burst", str(burst),
         "--period-s", str(period_s),
         "--levels", ",".join(map(str, levels)),
         "--sweep-cap", str(sweep_cap)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=root,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fig14 worker failed:\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}"
        )
    line = next(l for l in proc.stdout.splitlines() if l.startswith(_MARK))
    payload = json.loads(line[len(_MARK):])
    b = payload["bursty"]
    csv.add("fig14/bursty/sync", b["sync"]["p99_ms"] / 1e3,
            f"p99_ms={b['sync']['p99_ms']};batches={b['sync']['batches']}")
    csv.add("fig14/bursty/async", b["async"]["p99_ms"] / 1e3,
            f"p99_ms={b['async']['p99_ms']};batches={b['async']['batches']}")
    for n_rep, qps in payload["replica_sweep"]["max_qps_at_slo"].items():
        csv.add(f"fig14/replicas{n_rep}", 1.0 / qps if qps else 0.0,
                f"max_qps_at_slo={qps};slo_ms={payload['slo_ms']}")
    return payload


def _worker(*, corpus_docs: int, max_batch: int, n_bursts: int, burst: int,
            period_s: float, levels, sweep_cap: int) -> dict:
    from collections import Counter as _TallyCounter

    from repro.obs.registry import registry
    from repro.router import Router, percentiles_ms
    from repro.router.router import _pad_rows

    from benchmarks.common import timed

    engine, corpus = _build_engine(corpus_docs, max_batch)
    params = engine.search_params
    pool32 = corpus
    pool16 = np.ascontiguousarray(corpus[:, :16])
    rng = np.random.default_rng(42)

    # warm every (batch, length) shape both paths will dispatch, so the
    # measurement windows contain zero XLA compiles
    for pool in (pool16, pool32):
        engine.serve_batch(_pad_rows(pool[:max_batch], max_batch), params)

    # closed-loop single-engine batch capacity (device-bound reference)
    _, t_batch = timed(
        lambda: engine.serve_batch(pool32[:max_batch], params), repeats=3)
    capacity_qps = max_batch / t_batch

    # saturated 1-replica *router* throughput R1: dump a deep backlog so
    # every batch is full, and measure the completion rate (min of 3 --
    # a single sample swings ~15% on a shared core, and a sustained-QPS
    # claim deserves a conservative denominator).  R1 < the closed-loop
    # number because it pays queue pop + ticket fulfilment per batch; it
    # is the denominator for offered load.
    router = Router.replicate(engine, 1, params=params,
                              default_slo_ms=10_000.0, max_depth=1024)
    try:
        router.warm([pool32[0]])
        samples = []
        dump = [(0.0, pool32[i % len(pool32)]) for i in range(256)]
        for _ in range(3):
            router.reset_window()
            _, wall = _run_async(router, dump, 10_000.0)
            samples.append(len(dump) / wall)
        r1_qps = float(min(samples))
    finally:
        router.shutdown()
    # tail budget: ~10 full-batch service times.  Sub-saturation queueing
    # (a few batches of wait) fits inside it; the linear backlog of a
    # saturated single queue does not.
    slo_ms = max(10.0 * max_batch * 1e3 / r1_qps, 100.0)

    # -- bursty: equal offered load, 1 replica each side --------------------
    sched = _bursty_schedule(n_bursts, burst, period_s, (pool16, pool32), rng)
    offered_qps = len(sched) / (n_bursts * period_s)

    before = engine.stats.snapshot()
    sync_lat = _run_sync(engine, sched, params, max_batch)
    sync_batches = engine.stats.delta(before).batches
    sync_pct = percentiles_ms(sync_lat)

    router = Router.replicate(engine, 1, params=params,
                              default_slo_ms=slo_ms, max_depth=1024)
    try:
        router.warm([pool16[0], pool32[0]])
        # measurement window = one registry snapshot/delta: the same series
        # a Prometheus scrape would export, no hand-rolled dict plumbing
        snap = registry().snapshot()
        rej, _ = _run_async(router, sched, slo_ms)
        d = registry().since(snap)
    finally:
        router.shutdown()
    async_pct = percentiles_ms(d.samples("repro_router_latency_seconds"))
    batch_hist = dict(sorted(_TallyCounter(
        int(b) for b in d.samples("repro_router_batch_size")).items()))
    bursty = {
        "offered_qps": round(offered_qps, 1),
        "bursts": n_bursts, "burst": burst, "period_s": period_s,
        "sync": {"p50_ms": sync_pct["p50_ms"], "p99_ms": sync_pct["p99_ms"],
                 "batches": int(sync_batches)},
        "async": {"p50_ms": async_pct["p50_ms"],
                  "p99_ms": async_pct["p99_ms"],
                  "batches": sum(batch_hist.values()),
                  "batch_size_hist": batch_hist,
                  "deadline_misses": int(
                      d.value("repro_router_deadline_misses_total")),
                  "rejected": rej},
        "async_beats_sync_p99": async_pct["p99_ms"] < sync_pct["p99_ms"],
    }

    # -- replica sweep: max QPS at the p99 SLO, 1 vs 2 replicas -------------
    # "Sustains" means *every* trial window meets the SLO -- an SLO is a
    # guarantee, so one bad window at a level fails it -- and the reported
    # max is the top of the contiguous sustained prefix: a pass above a
    # failed level is noise, not capacity.  Each cell gets a
    # rate-matched linger (time to collect max_batch at the replica's
    # traffic share, capped well under the SLO): lingering a fixed 2 ms at
    # moderate load would dispatch half-empty bucketed batches, and padding
    # turns those into pure capacity waste.
    trials = 3
    records = []
    misses_flat = True
    max_qps: dict[str, float] = {}
    for n_rep in (1, 2):
        best = 0.0
        prefix_ok = True
        for level in levels:
            rate = level * r1_qps
            linger_ms = min(1e3 * max_batch * n_rep / rate, 0.2 * slo_ms)
            n_req = int(min(max(rate * 2.5, 200), sweep_cap))
            router = Router.replicate(engine, n_rep, params=params,
                                      default_slo_ms=slo_ms,
                                      linger_ms=linger_ms, max_depth=1024)
            p99s, p50s, rejs, misses, rep_misses = [], [], 0, 0, []
            try:
                router.warm([pool32[0]])
                for _ in range(trials):
                    sched = _poisson_schedule(rate, n_req, pool32, rng)
                    # reset_window still re-baselines the per-replica
                    # ServeStats (plan-miss attribution below); the SLO
                    # numbers themselves come off the registry delta
                    router.reset_window()
                    snap = registry().snapshot()
                    rej, wall = _run_async(router, sched, slo_ms)
                    d = registry().since(snap)
                    st = router.stats()
                    rep_misses = [r.serve["plan_misses"]
                                  for r in st.replicas]
                    misses_flat &= all(m == 0 for m in rep_misses)
                    pct = percentiles_ms(
                        d.samples("repro_router_latency_seconds"))
                    p99s.append(pct["p99_ms"])
                    p50s.append(pct["p50_ms"])
                    rejs += rej
                    misses += int(
                        d.value("repro_router_deadline_misses_total"))
            finally:
                router.shutdown()
            sustained = (all(p is not None and p <= slo_ms for p in p99s)
                         and rejs == 0)
            if sustained and prefix_ok:
                best = rate
            else:
                prefix_ok = False
            records.append({
                "replicas": n_rep,
                "offered_level": level,
                "offered_qps": round(rate, 1),
                "requests_per_trial": n_req,
                "trials": trials,
                "linger_ms": round(linger_ms, 1),
                "p50_ms": p50s[-1],
                "p99_ms": max(p99s),            # worst window decides
                "p99_trials": p99s,
                "rejected": rejs,
                "deadline_misses": misses,
                "sustained": sustained,
                "plan_misses": rep_misses,
            })
        max_qps[str(n_rep)] = round(best, 1)

    payload = {
        "corpus": corpus_docs, "max_batch": max_batch,
        "capacity_qps": round(capacity_qps, 1),
        "saturated_qps_1r": round(r1_qps, 1),
        "slo_ms": round(slo_ms, 1),
        "bursty": bursty,
        "replica_sweep": {"levels": list(levels), "records": records,
                          "max_qps_at_slo": max_qps},
        "replica_scaling": (round(max_qps["2"] / max_qps["1"], 2)
                            if max_qps.get("1") else None),
        "plan_misses_flat": misses_flat,
    }
    return payload


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--corpus-docs", type=int, default=160)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--n-bursts", type=int, default=5)
    ap.add_argument("--burst", type=int, default=20)
    ap.add_argument("--period-s", type=float, default=0.7)
    ap.add_argument("--levels", default="0.7,0.85,0.95,1.05,1.15")
    ap.add_argument("--sweep-cap", type=int, default=960)
    args = ap.parse_args()
    kw = dict(
        corpus_docs=args.corpus_docs, max_batch=args.max_batch,
        n_bursts=args.n_bursts, burst=args.burst, period_s=args.period_s,
        levels=tuple(float(x) for x in args.levels.split(",")),
        sweep_cap=args.sweep_cap,
    )
    if args.worker:
        print(_MARK + json.dumps(_worker(**kw)))
        return
    csv = CsvRows()
    payload = run(csv, **kw)
    csv.dump()
    print(json.dumps(payload, indent=2))


if __name__ == "__main__":
    main()
