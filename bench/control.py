#!/usr/bin/env python3
"""Read the numbers `correct` compares, for sound runs, the precision
control and the planted faults, over many seeds in one process:

    python3 bench/control.py --workload sift-1m.mp17.closed \\
        --seeds 11,12,13 --seconds 10 --variants sound,control,misroute

Per seed the cell is stood up once (data and index), and each variant
serves its own window of the cell's traffic through a router of its own
(`bench/faults.py`).  Once the index is freed, every answer is compared
with the exact reference.  One JSON line per (seed, variant) goes to
standard output.  These readings set the limits in `bench/cells/`; a
benchmark run never runs a variant.  Runs on a TPU only, as
`bench/run.py` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--variants", default="sound,control")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.faults import VARIANTS
    from bench.harness import check, drive, log, serve, stand_up
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
    variants = args.variants.split(",")
    unknown = set(variants) - set(VARIANTS)
    if unknown:
        log(f"error: unknown variants {sorted(unknown)}; have {VARIANTS}")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        stand, X = stand_up(cell, seed, args.n)
        with stand:  # removes a disk tail once the answers are compared
            answers = {}
            for variant in variants:
                router = serve(stand, X, variant)
                try:
                    answers[variant] = drive(router, stand, args.seconds,
                                             seed).answers
                finally:
                    router.shutdown(drain=False)
                del router
                gc.collect()
            del X
            stand.index = None
            gc.collect()
            checked = check(stand, answers)
        for variant, nums in checked.items():
            row = {"cell": cell.name, "seed": seed, "variant": variant,
                   "answers": len(answers[variant]), **nums}
            log(" ".join(f"{k}={v!r}" for k, v in row.items()))
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
