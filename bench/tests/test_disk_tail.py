"""A GIST-shaped deployment whose fp32 rerank rows live on disk, run on the
CPU with the chip check skipped: the served index gathers its tail on the
host, `correct` holds only for the program as it is, the precision control
fails on `dist_gap`, and the tail's directory lies outside the checkout and
is gone once the run ends, however it ends."""
import json
import shutil
import tempfile
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, serving
from bench.faults import VARIANTS, control_index
from bench.harness import CompileWatch, run_cell, stand_up
from bench.spec import ROOT, Cell
from repro.exec.topology import has_disk_tail

N = 4096  # corpus rows: small enough for the CPU
SIFT = json.loads((ROOT / "bench" / "configs" / "sift-1m.json").read_text())
GIST = {**SIFT, "name": "gist-1m", "d": 960, "store": "int8", "tail": "disk",
        "w": 170.9}
CLOSED = {"loop": "closed", "clients": 128, "replicas": 2, "max_batch": 32,
          "slo_ms": 500.0, "linger_ms": 2.0, "max_depth": 256, "pool": 4096,
          "search": {"source": "lccs", "lam": 256, "width": 64,
                     "rerank_mult": 4}}
LIMITS = {"limits": {"recall_loss": 0.42, "dist_gap": 0.0001, "failed": 0}}
SEED = 2**33 + 7


def gist_cell(**config) -> Cell:
    return Cell(name="gist-1m.lccs.closed", chips=1,
                config={**GIST, **config}, traffic=CLOSED, limits=LIMITS,
                metrics=())


@pytest.fixture(scope="module")
def watch():
    return CompileWatch()


@pytest.fixture
def tail_dirs(monkeypatch):
    """Every directory `tempfile.mkdtemp` makes during the test."""
    made = []
    mkdtemp = tempfile.mkdtemp

    def recording(*a, **kw):
        made.append(mkdtemp(*a, **kw))
        return made[-1]

    monkeypatch.setattr(tempfile, "mkdtemp", recording)
    return made


@pytest.fixture
def served(monkeypatch):
    """What the window saw: the served index and the stand's tail files."""
    seen = {}
    drive = harness.drive

    def watching(router, stand, *a, **kw):
        seen["index"] = router.replicas[0].engine.index
        seen["tail_dir"] = stand.tail_dir
        seen["files"] = sorted(p.name for p in Path(stand.tail_dir).iterdir())
        return drive(router, stand, *a, **kw)

    monkeypatch.setattr(harness, "drive", watching)
    return seen


@pytest.mark.parametrize("variant", VARIANTS)
def test_disk_tail_correct_only_for_the_sound_program(variant, watch,
                                                      tail_dirs, served):
    out = run_cell(gist_cell(), seed=SEED, seconds=2.0, trace=False,
                   t_start=time.perf_counter(), watch=watch, n=N,
                   variant=variant)
    assert out["attempted"] > 0
    assert out["correct"] is (variant == "sound"), out["checks"]
    if variant == "control":
        gap = out["checks"]["dist_gap"]
        assert gap["value"] > gap["limit"], out["checks"]
    assert has_disk_tail(served["index"])
    assert served["files"] == (["control-bf16.npy", "tail.npy"]
                               if variant == "control" else ["tail.npy"])
    assert tail_dirs == [served["tail_dir"]]
    assert not Path(served["tail_dir"]).resolve().is_relative_to(ROOT)
    assert not Path(served["tail_dir"]).exists()


def test_tail_directory_goes_when_a_step_raises(monkeypatch, watch,
                                                tail_dirs):
    def broken(router, stand, *a, **kw):
        assert (Path(stand.tail_dir) / "tail.npy").is_file()
        raise RuntimeError("window failed")

    monkeypatch.setattr(harness, "drive", broken)
    with pytest.raises(RuntimeError, match="window failed"):
        run_cell(gist_cell(), seed=SEED, seconds=1.0, trace=False,
                 t_start=time.perf_counter(), watch=watch, n=N)
    assert len(tail_dirs) == 1 and not Path(tail_dirs[0]).exists()


def test_no_room_exits_with_the_bytes_needed(monkeypatch, tail_dirs):
    real = shutil.disk_usage

    def full(path):
        return real(path)._replace(free=1000)

    monkeypatch.setattr(shutil, "disk_usage", full)
    with pytest.raises(SystemExit, match=str(int(1.1 * N * 960 * 4))):
        stand_up(gist_cell(), SEED, N)
    assert len(tail_dirs) == 1 and not Path(tail_dirs[0]).exists()


@pytest.mark.parametrize("tail", ["device", "disk"])
def test_control_rounds_the_rerank_rows_to_bf16(tail):
    stand, X = stand_up(gist_cell(tail=tail), SEED, 512)
    with stand:
        index, cfg = control_index(stand.index, X, stand.cfg)
        assert index.store is stand.index.store and cfg == stand.cfg
        want = np.asarray(X.astype(jnp.bfloat16).astype(jnp.float32))
        if tail == "disk":
            assert Path(index.tail_path).parent == Path(stand.tail_dir)
            rows = np.load(index.tail_path)
        else:
            rows = np.asarray(index.tail)
        np.testing.assert_array_equal(rows, want)
        assert not np.array_equal(rows, np.asarray(X))


@pytest.mark.parametrize("config, tail", [
    ({"store": "fp32"}, "device"),
    ({"store": "int8"}, "device"),
    ({"store": "int8", "tail": "device"}, "device"),
    ({"store": "bf16", "tail": "disk"}, "disk"),
])
def test_tail_of_a_config(config, tail):
    assert serving.tail_of(config) == tail


@pytest.mark.parametrize("config", [
    {"store": "fp32", "tail": "disk"},
    {"store": "int8", "tail": "host"},
    {"store": "int8", "tail": None},
])
def test_tail_of_refuses(config):
    with pytest.raises(ValueError):
        serving.tail_of(config)
