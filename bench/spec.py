"""What a cell is, read from data: `BENCHMARK.json` names the cells, and
each cell's configuration, traffic mix, correctness limits and metric
readers are files of their own, found by name:

    bench/configs/<config>.json    deployment: sizes, store, data, source
    bench/traffic/<traffic>.json   load: loop kind, rate or clients, router,
                                   search parameters, query pool
    bench/cells/<cell>.json        the limits `correct` is held to, with the
                                   readings they were set from
    bench/metrics/<metric>.py      one reader per metric: `read(run)`

A configuration's `tail` key says where an inexact store's fp32 rerank
rows live: `"device"`, the default and what a configuration without the
key gets, keeps them as a device array; `"disk"` writes them to an `.npy`
file that every batch gathers its survivors' rows from on the host, as
the program's split plan serves it.  `"disk"` with an fp32 store, or any
other value, is an error.  The benchmark writes the file into a fresh
directory in the system's temp directory (never under the checkout),
with 1.1 x its size free or it exits non-zero, and removes the directory
once the answers are compared or a step fails.  The file is read through
the host's page cache as it was written, as a host of that size serves
it: nothing drops the cache.

Adding a configuration, a mix, a cell or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: tuple  # BENCHMARK.json metric entries this cell reports


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def reported_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` prints: the end-to-end ones with
    `--trace 0`, the per-layer ones with `--trace 1`.  A metric with a
    `workloads` key is reported in the cells it lists; a per-layer metric
    without one in every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in moved)]


def load_cell(name: str, trace: bool = False) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(ROOT / configs[w["config"]]["file"])
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = _json(BENCH / "cells" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                metrics=tuple(reported_metrics(bench, name, trace)))


def metric_reader(name: str):
    """The `read(run) -> float | None` function of `bench/metrics/<name>.py`
    (names may hold dots, so the file is loaded by path)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
