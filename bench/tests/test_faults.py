"""A run on the CPU, with the chip check skipped: `correct` holds for the
program as it is, and comes out false with the precision control or any
planted fault in its place."""
import time

import pytest

from bench.faults import VARIANTS
from bench.harness import CompileWatch, run_cell
from bench.spec import load_benchmark, load_cell

N = 4096  # corpus rows: small enough for the CPU


@pytest.fixture(scope="module")
def watch():
    return CompileWatch()


CELLS = [w["name"] for w in load_benchmark()["workloads"]]


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("cell", CELLS)
def test_correct_only_for_the_sound_program(cell, variant, watch):
    out = run_cell(load_cell(cell), seed=2**33 + 5, seconds=3.0, trace=False,
                   t_start=time.perf_counter(), watch=watch, n=N,
                   variant=variant)
    assert out["attempted"] > 0
    assert out["correct"] is (variant == "sound"), out["checks"]
    assert list(out)[-1] == "checks"
