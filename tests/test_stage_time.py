"""The benchmark's device time by query stage (`bench/stage_time.py`): the
stage of an operation from its `tf_op` scope path, self time under nesting,
search-program executions in the window, and the per-layer metrics that
read them (`bench/metrics/*_device_ms.open.py`,
`bench/metrics/queue_wait_p95_ms.open.py`)."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import stage_time  # noqa: E402
from bench.harness import RunRecord  # noqa: E402
from bench.spec import metric_reader  # noqa: E402
from bench.xplane_pb2 import XSpace  # noqa: E402

RECORDED = ROOT / "bench" / "tests" / "data" / "tiny_tpu.xplane.pb"


def _plane(space, name, lines):
    """Add a plane of `lines` {line: [(name, tf_op, start_ns, dur_ns)]}."""
    pl = space.planes.add(name=name)
    pl.stat_metadata[1].id = 1
    pl.stat_metadata[1].name = "tf_op"
    names: dict[str, int] = {}
    for line_name, events in lines.items():
        ln = pl.lines.add(name=line_name, timestamp_ns=1000)
        for ev_name, tf_op, start, dur in events:
            key = f"{ev_name}|{tf_op}"
            if key not in names:
                mid = names[key] = len(names) + 1
                md = pl.event_metadata[mid]
                md.id, md.name = mid, ev_name
                if tf_op:
                    md.stats.add(metadata_id=1, str_value=tf_op)
            ln.events.add(metadata_id=names[key],
                          offset_ps=(start - 1000) * 1000,
                          duration_ps=dur * 1000)
    return pl


def _space():
    """A window of 1000 ns (1000..2000) on the host.  The device runs
    `jit_search_pipeline` three times: the first run straddles the window's
    opening, the second is whole, the third straddles its close."""
    space = XSpace()
    _plane(space, "/host:CPU", {"python": [("bench.traced", "", 1000, 1000),
                                           ("router.serve", "", 900, 300)]})
    prog = "jit(search_pipeline)"
    _plane(space, "/device:TPU:0", {
        "XLA Modules": [("jit_search_pipeline(7)", "", 900, 150),
                        ("jit_search_pipeline(7)", "", 1100, 400),
                        ("jit_search_pipeline(7)", "", 1800, 400)],
        "XLA Ops": [
            ("%fusion.8 = s32[] fusion()", f"{prog}/probe/x:", 500, 100),
            ("%fusion.0 = s32[] fusion()", f"{prog}/probe/x:", 950, 90),
            ("%fusion.1 = f32[] fusion()", f"{prog}/hash/dot_general:",
             1100, 50),
            # a while loop (no tf_op, as on the TPU) of 200 ns whose body
            # ops take 150 of it
            ("%while.2 = s32[] while()", "", 1150, 200),
            ("%sort.3 = s32[] sort()", f"{prog}/probe/while/body/sort:",
             1160, 100),
            ("%fusion.4 = s32[] fusion()", f"{prog}/probe/while/body/add:",
             1270, 50),
            # an expansion with no metadata, then the op that consumes it
            ("%while.9 = s32[] while()", "", 1350, 30),
            ("%dynamic-update-slice.10 = s32[] dynamic-update-slice()", "",
             1355, 20),
            ("%sort.11 = s32[] sort()", f"{prog}/probe/top_k:", 1380, 20),
            ("%fusion.5 = f32[] fusion()",
             f"{prog}/verify/jit(exact_topk)/top_k:", 1400, 80),
            ("%copy.6 = f32[] copy()", "", 1480, 10),
            ("%fusion.7 = f32[] fusion()", f"{prog}/hash/dot_general:",
             1800, 100),
        ],
    })
    return space


def test_stage_of_reads_the_first_stage_scope():
    assert stage_time.stage_of("jit(search_pipeline)/probe/while/sort:") \
        == "probe"
    assert stage_time.stage_of(
        "jit(survivor_pipeline)/survivors/jit(top_k)/x") == "survivors"
    assert stage_time.stage_of("jit(f)/verify/hash/x") == "verify"
    assert stage_time.stage_of("jit(<lambda>)/jit(sort)/sort") == "other"
    assert stage_time.stage_of("") == "other"


def test_self_times_count_nested_ops_once():
    t = stage_time.self_times([("probe", 0, 100), ("probe", 10, 40),
                               ("verify", 50, 150), ("hash", 200, 210)])
    # the nested probe op counts its 30 ns, its parent the other 20 ns it
    # ran alone; the verify op outlasts its parent and is cut at 100
    assert t["probe"] == pytest.approx(50e-9)
    assert t["verify"] == pytest.approx(50e-9)
    assert t["hash"] == pytest.approx(10e-9)
    assert sum(t.values()) == pytest.approx(110e-9)  # the busy union


def test_split_space_counts_whole_runs_on_a_synthetic_trace():
    split = stage_time.split_space(_space())
    s = split["stage_s"]
    assert set(s) == {"hash", "probe", "verify"}
    assert s["hash"] == pytest.approx(50e-9)
    # the loop's own 50 ns, its body's 150, the expansion's 30 and the
    # consumer's 20
    assert s["probe"] == pytest.approx(250e-9)
    assert s["verify"] == pytest.approx(90e-9)  # with the trailing copy
    assert split["unscoped_s"] == pytest.approx((50 + 30 + 10) * 1e-9)
    assert split["executions"] == 1
    assert split["window_s"] == pytest.approx(1000e-9)
    assert stage_time.split_space(XSpace()) is None


def test_fill_stages_keeps_other_outside_any_run():
    ops = [("other", 0, 10), ("probe", 10, 20), ("other", 30, 40)]
    assert stage_time.fill_stages(ops, [(0, 25)]) == [
        "probe", "probe", "other"]


def test_split_reads_tf_op_from_a_recorded_tpu_trace():
    """The trace `bench/tests/test_tracing.py` reads: two small jitted
    programs (both `jit__lambda`) run three times each on one TPU v5 lite,
    under no stage scope."""
    space = stage_time.load_space(RECORDED)
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    names = {k: v.name for k, v in dev.stat_metadata.items()}
    tf_ops = {st.str_value for md in dev.event_metadata.values()
              for st in md.stats if names[st.metadata_id] == "tf_op"}
    assert "jit(<lambda>)/jit(sort)/sort:" in tf_ops
    split = stage_time.split_space(space, program="jit__lambda")
    assert set(split["stage_s"]) == {"other"}
    assert 0 < split["stage_s"]["other"] < 0.02 * split["window_s"]
    # of the six runs one ends before the window opens, one straddles its
    # opening, and the last ends after the last recorded op
    assert split["executions"] == 3
    assert stage_time.split_space(space)["executions"] == 0


def _run(trace=None, waits=None):
    class Reg:
        def samples(self, name):
            if waits is None:
                raise KeyError(name)
            return waits

    return RunRecord(max_batch=32, seconds=10.0, setup_s=50.0, build_s=30.0,
                     compile_setup_s=1.0, latencies_s=[],
                     done_s=np.zeros(0), reg=Reg(), trace=trace)


def test_stage_readers_need_a_complete_trace_with_executions(
        tmp_path, monkeypatch):
    from bench import harness

    log = tmp_path / "plugins" / "profile" / "1"
    log.mkdir(parents=True)
    (log / "x.xplane.pb").write_bytes(_space().SerializeToString())
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    complete = {"busy_s": 1e-6, "window_s": 1e-6, "complete": True}
    probe = metric_reader("probe_device_ms.open")
    assert probe(_run(complete)) == pytest.approx(250e-9 * 1e3)
    assert metric_reader("hash_device_ms.open")(_run(complete)) == \
        pytest.approx(50e-9 * 1e3)
    assert metric_reader("verify_device_ms.open")(_run(complete)) == \
        pytest.approx(90e-9 * 1e3)
    assert probe(_run({**complete, "complete": False})) is None
    assert probe(_run(None)) is None


def test_queue_wait_reader_reads_the_histogram_or_nothing():
    read = metric_reader("queue_wait_p95_ms.open")
    waits = list(np.linspace(0.0, 0.2, 101))
    assert read(_run(waits=waits)) == pytest.approx(190.0)
    assert read(_run(waits=[])) is None
    assert read(_run()) is None  # a program without the histogram
