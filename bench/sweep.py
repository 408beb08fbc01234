#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep up.  One process stands the cell up once and offers each
rate for one window:

    python3 bench/sweep.py --workload sift-1m.lccs.open --seed 1 \\
        --seconds 10 --rates 150,200,250,300,350,400

A rate keeps up when, over the second half of the window, answers come
at no less than 98% of the rate requests fall due, no request fails, and
the backlog (submitted, not yet answered) at the close is no larger than
at the middle plus one batch.  One JSON line per rate goes to standard
output; the table to standard error.  Runs on a TPU only, as
`bench/run.py` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates (queries/s)")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.harness import drive, log, serve, stand_up
    from bench.loadgen import arrival_offsets, percentiles_ms
    from bench.spec import load_cell

    if args.cpu_rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        if jax.devices()[0].platform != "tpu":
            log("error: no TPU")
            return 2
    cell = load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        log(f"error: {cell.name} is not an open-loop cell")
        return 2
    stand, X = stand_up(cell, args.seed, args.n)
    with stand:  # removes a disk tail when the sweep ends
        router = serve(stand, X)
        del X
        b = int(cell.traffic["max_batch"])
        try:
            for rate in (float(r) for r in args.rates.split(",")):
                offsets = arrival_offsets({**cell.traffic, "rate_qps": rate},
                                          args.seconds, args.seed)
                win = drive(router, stand, args.seconds, args.seed,
                            offsets=offsets)
                ans = win.answers
                mid = win.t0 + args.seconds / 2

                def backlog(t):
                    return sum(1 for a in ans if a.t_submit <= t < a.t_done)

                due = sum(1 for a in ans if mid <= a.t_due < win.t_end)
                done = sum(1 for a in ans
                           if a.error is None and mid <= a.t_done < win.t_end)
                pct = percentiles_ms(a.t_done - a.t_due for a in ans
                                     if a.error is None)
                row = {"rate_qps": rate, "offered": len(ans),
                       "answered_share": done / max(due, 1),
                       "backlog_mid": backlog(mid),
                       "backlog_close": backlog(win.t_end),
                       "failed": sum(1 for a in ans if a.error is not None),
                       "p50_ms": pct["p50_ms"], "p95_ms": pct["p95_ms"]}
                row["keeps_up"] = (row["answered_share"] >= 0.98
                                   and row["failed"] == 0
                                   and row["backlog_close"]
                                   <= row["backlog_mid"] + b)
                log(" ".join(f"{k}={v}" for k, v in row.items()))
                print(json.dumps(row), flush=True)
        finally:
            router.shutdown(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
