#!/usr/bin/env python3
"""Benchmark of the LCCS-LSH serving path on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` in this process and prints, as the last
line of standard output, one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer ones), `device`, with `--trace 1` a `breakdown`, and last the
numbers `correct` was decided on beside their limits (`checks`, also the
last lines of standard error).

Without a TPU, or with fewer chips than the cell asks for, or outside a
full checkout, it exits non-zero and prints no result.  `--cpu-rehearsal`
runs the same code on the CPU at a small `--n` and prints no result either.

JAX's persistent compilation cache is kept in `.jax_cache/` at the root of
the checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402 -- after the clock starts
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def _die(msg: str, code: int = 2):
    print(f"[bench] error: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at a small --n; prints no result")
    ap.add_argument("--n", type=int, default=None,
                    help="corpus rows (rehearsals only)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        _die("--seed must be non-negative")
    if args.n is not None and not args.cpu_rehearsal:
        _die("--n is for --cpu-rehearsal only")

    src = ROOT / "src"
    if not (src / "repro" / "core" / "__init__.py").is_file():
        _die(f"no repro package under {src}: run from a full checkout")
    sys.path[:0] = [str(src), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    import jax

    if args.cpu_rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench.harness import CompileWatch, dumps, log, run_cell
    from bench.spec import load_cell

    try:
        cell = load_cell(args.workload, trace=bool(args.trace))
    except (KeyError, FileNotFoundError) as e:
        _die(str(e))
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        _die(f"no TPU: JAX sees {platform!r} devices; the benchmark runs "
             f"only on a TPU (--cpu-rehearsal checks the control flow)")
    if len(devices) < cell.chips:
        _die(f"{cell.name} needs {cell.chips} chips; JAX sees "
             f"{len(devices)}")
    watch = CompileWatch()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, watch=watch, n=args.n)
    if args.cpu_rehearsal:
        log("rehearsal (CPU, no result): " + dumps(out))
        return 0
    print(dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
