"""Every cell of BENCHMARK.json resolves by name to its configuration,
traffic, limits and metric readers, and the file keeps the contract's
shape."""
import json
import re

import numpy as np
import pytest

from bench.harness import RunRecord
from bench.spec import ROOT, load_benchmark, load_cell, metric_reader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = load_cell(cell)
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert c.chips in (1, 4) and len(w["why"]) <= 200
    for key in ("n", "d", "k", "m", "w", "store", "metric", "data"):
        assert key in c.config
    for key in ("source", "reduced", "assumed"):
        assert key in c.config
    assert len(c.config["source"]) <= 200
    for key in ("loop", "replicas", "max_batch", "search", "pool"):
        assert key in c.traffic
    assert ("rate_qps" if c.traffic["loop"] == "open" else "clients") \
        in c.traffic
    assert set(c.limits["limits"]) >= {"recall_loss", "dist_gap", "failed"}
    names = {m["name"] for m in c.metrics}
    assert "setup_s" in names and len(names) >= 2
    layer = load_cell(cell, trace=True).metrics
    assert layer
    for m in c.metrics + layer:
        assert callable(metric_reader(m["name"]))


def test_config_entries_point_at_their_files():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]


def _run(done_s=(), trace=None, seconds=30.0):
    class NoReg:
        def value(self, name):
            return 0.0

        def samples(self, name):
            return []

    return RunRecord(max_batch=32, seconds=seconds, setup_s=50.0,
                     build_s=30.0, compile_setup_s=1.0, latencies_s=[],
                     done_s=np.sort(np.asarray(done_s, np.float64)),
                     reg=NoReg(), trace=trace)


def test_readers_return_nothing_on_an_empty_run():
    run = _run()
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in ("setup_s", "build_s", "compile_s"):
            continue
        v = metric_reader(m["name"])(run)
        assert v is None or (m["name"] == "qps" and v == 0.0), m["name"]


def _batches(period=1.2, count=9, size=32):
    """Answer times of `count` full batches served back to back, each
    batch's answers resolving within a millisecond."""
    return np.concatenate([t + np.linspace(0.0, 1e-3, size)
                           for t in period * np.arange(1, count + 1)])


def test_qps_counts_the_batch_in_service_by_its_elapsed_share():
    qps = metric_reader("qps")
    run = _run(_batches(), seconds=10.0)
    # 8 batches by 9.6 s, and a third of the ninth by 10 s
    assert qps(run) == pytest.approx((8 + 1 / 3) * 32 / 10.0, rel=1e-3)
    # a later close counts a larger share, not a step of a whole batch
    later = _run(_batches(), seconds=10.3)
    assert qps(later) == pytest.approx((8 + 0.7 / 1.2) * 32 / 10.3,
                                       rel=1e-3)
    # the first batch's work counts from the window's start
    assert run.answered(0.6) == pytest.approx(16.0, rel=1e-3)
    assert run.answered(1.201) == pytest.approx(32.0)


def test_device_idle_reads_only_a_complete_trace():
    idle = metric_reader("device_idle.open")
    trace = {"busy_s": 1.8, "window_s": 2.4, "complete": True}
    assert idle(_run(_batches(), trace=trace)) == pytest.approx(25.0)
    lost = {**trace, "complete": False}
    assert idle(_run(_batches(), trace=lost)) is None
