"""The trace reduction: busy time as a union of device intervals, idle
gaps named by host events, operations summed by program."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.tracing import (gaps, merge, reduce_file, reduce_planes,
                           short_name)

RECORDED = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_merge_and_gaps():
    busy = merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert gaps([], 0, 4) == [(0, 4)]


def test_short_names():
    assert short_name("%fusion.21 = s32[8]{0} fusion(s32[8] %x)") == "fusion.21"
    assert short_name("jit_search_pipeline(1234567)") == "jit_search_pipeline"


def test_reduce_synthetic_trace():
    host = plane("/host:CPU",
                 python=[ev("bench.traced", 100, 1000),
                         ev("PJRT_Execute", 400, 300),
                         ev("outer", 0, 2000)])
    # a second thread of the same name must not hide the first
    host.lines.append(NS(name="python", events=[ev("other", 0, 50)]))
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_a(1)", 50, 250),
                             ev("jit_b(2)", 700, 500)],
                XLA_Ops=[ev("%op.1 = f32[] add()", 120, 100),
                         ev("%op.2 = f32[] mul()", 800, 50)])
    r = reduce_planes([host, dev, plane("/host:metadata")])
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(150e-9)  # 120..220 and 800..850
    assert r["device_ops"] == [["jit_a/op.1", pytest.approx(100e-9)],
                               ["jit_b/op.2", pytest.approx(50e-9)]]
    # the longest gap, 220..800, has its middle in PJRT_Execute (400..700),
    # the narrower of the two host events that cover it
    assert r["idle_gaps"][0] == ["PJRT_Execute", pytest.approx(580e-9)]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [580e-9, 250e-9, 20e-9])


def test_reduce_without_window_or_device_reads_nothing():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("%a = f32[] add()", 0, 5)])
    assert reduce_planes([dev]) is None
    assert reduce_planes([plane("/host:CPU",
                                python=[ev("bench.traced", 0, 9)])]) is None


def test_reduce_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite: two small jitted programs run
    three times inside `bench.traced`, 20 ms of host sleep after each."""
    r = reduce_file(RECORDED)
    assert 0 < r["busy_s"] < 0.02 * r["window_s"]
    assert r["window_s"] > 0.06  # three sleeps of 20 ms
    assert r["device_ops"][0][0] == "jit__lambda/sort.6"
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"]
    # the sleeps are the longest gaps, and the trace names them
    assert [g[0] for g in r["idle_gaps"][:3]] == ["$time sleep"] * 3
    assert all(g[1] > 0.015 for g in r["idle_gaps"][:3])
