"""repro.obs: the unified metrics registry, tracing, exposition, and the
instrumented exec-plan variants.

The load-bearing contracts:
  - registry counters/gauges/histograms are label-aware, thread-safe, and
    window cleanly via snapshot/delta;
  - `LatencyWindow` survives concurrent record/percentiles (the replica
    worker thread vs stats callers race -- regression for the unlocked deque);
  - the plan cache attributes evictions to the scope that built the evicted
    plan, and `stats()` exposes the per-scope tallies;
  - instrumented plans return BIT-IDENTICAL (ids, dists) to the fused plans
    for every topology x store x probe-kernel toggle, live under distinct
    cache keys, and leave the fast path's miss audit untouched;
  - the span stream exports as valid Chrome-trace JSON;
  - the /metrics endpoint serves parseable Prometheus text format;
  - the recall-drift probe gauges achieved recall against brute force.
"""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from repro.core import LCCSIndex, SearchParams, SegmentedLCCSIndex
from repro.exec import compile_plan, execute, plan_cache
from repro.obs.registry import registry
from repro.obs import trace as _trace_mod  # noqa: F401 -- see import test
from repro.obs.trace import (
    add_span,
    clear_trace,
    disable_tracing,
    enable_tracing,
    events,
    export_chrome_trace,
    span,
    stage,
    tracing_enabled,
)

N, D, B = 160, 16, 4
# complete-coverage regime (cf. tests/test_exec.py): candidate sets provably
# coincide, so instrumented-vs-fused comparisons are exact, not tie-lucky
BASE = SearchParams(k=6, lam=N + 12, width=N + 12, rerank_mult=64,
                    use_gather_kernel=False)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(N, D)).astype(np.float32)
    Q = rng.normal(size=(B, D)).astype(np.float32)
    return X, Q


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    disable_tracing()
    clear_trace()


# ---------------------------------------------------------------------------
# Registry: metric semantics + snapshot/delta windowing
# ---------------------------------------------------------------------------


def test_counter_labels_and_partial_sum():
    c = registry().counter("obs_test_counter_total", "t", labelnames=("a",))
    c.inc(a="x")
    c.inc(2.0, a="y")
    assert c.value(a="x") == 1.0
    assert c.value() == 3.0  # no filter: sum across series
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1.0, a="x")
    with pytest.raises(ValueError, match="takes labels"):
        c.inc(b="nope")
    with pytest.raises(ValueError, match="no labels"):
        c.value(b="nope")


def test_gauge_last_write_wins():
    g = registry().gauge("obs_test_gauge", "t", labelnames=("a",))
    g.set(5.0, a="x")
    g.set(2.0, a="x")
    g.inc(1.0, a="x")
    assert g.value(a="x") == 3.0


def test_histogram_buckets_sum_count_and_reservoir():
    h = registry().histogram("obs_test_hist_seconds", "t",
                             buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum_value() == pytest.approx(55.55)
    assert sorted(h.samples()) == [0.05, 0.5, 5.0, 50.0]
    (_, rec), = h.collect().items()
    assert rec["buckets"] == [1, 1, 1, 1]  # one obs per bucket incl +Inf


def test_registry_get_or_create_and_kind_mismatch():
    a = registry().counter("obs_test_redeclare_total", "t", labelnames=("a",))
    assert registry().counter("obs_test_redeclare_total",
                              labelnames=("a",)) is a
    with pytest.raises(ValueError, match="already registered"):
        registry().gauge("obs_test_redeclare_total", labelnames=("a",))
    with pytest.raises(ValueError, match="already registered"):
        registry().counter("obs_test_redeclare_total", labelnames=("b",))
    with pytest.raises(KeyError, match="no metric"):
        registry().get("obs_test_never_declared")


def test_snapshot_delta_window():
    c = registry().counter("obs_test_window_total", "t", labelnames=("a",))
    h = registry().histogram("obs_test_window_seconds", "t")
    c.inc(10.0, a="x")
    h.observe(1.0)
    snap = registry().snapshot()
    c.inc(2.0, a="x")
    c.inc(1.0, a="z")  # a series born inside the window counts from 0
    h.observe(2.0)
    h.observe(3.0)
    d = registry().since(snap)
    assert d.value("obs_test_window_total") == 3.0
    assert d.value("obs_test_window_total", a="x") == 2.0
    assert sorted(d.samples("obs_test_window_seconds")) == [2.0, 3.0]
    assert d.count("obs_test_window_seconds") == 2
    with pytest.raises(TypeError, match="not a histogram"):
        d.samples("obs_test_window_total")


# ---------------------------------------------------------------------------
# Satellite 1: LatencyWindow under concurrent record/read
# ---------------------------------------------------------------------------


def test_latency_window_concurrent_record_and_percentiles():
    """Replica worker threads record while stats callers snapshot: the
    unlocked-deque version raised RuntimeError('deque mutated during
    iteration') under this load; the locked one must return consistent
    views and lose nothing."""
    from repro.router.metrics import LatencyWindow

    win = LatencyWindow(maxlen=100_000, label="obs-test-window")
    n_writers, per_writer = 8, 2000
    stop = threading.Event()
    errors: list[BaseException] = []

    def write():
        try:
            for i in range(per_writer):
                win.record(i * 1e-6)
        except BaseException as e:  # pragma: no cover -- the regression
            errors.append(e)

    def read():
        try:
            while not stop.is_set():
                win.percentiles()
                win.values()
        except BaseException as e:  # pragma: no cover -- the regression
            errors.append(e)

    readers = [threading.Thread(target=read) for _ in range(2)]
    writers = [threading.Thread(target=write) for _ in range(n_writers)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(timeout=60)
    stop.set()
    for t in readers:
        t.join(timeout=60)
    assert not errors, errors
    vals = win.values()
    assert len(vals) == n_writers * per_writer
    # every recorded value also landed in the registry histogram series
    hist = registry().get("repro_router_latency_seconds")
    assert hist.count(replica="obs-test-window") == n_writers * per_writer


# ---------------------------------------------------------------------------
# Satellite 2: plan-cache eviction attribution
# ---------------------------------------------------------------------------


def test_plan_cache_attributes_evictions_to_builder_scope():
    from repro.exec.plan import PlanCache

    cache = PlanCache(maxsize=2)  # shares the global registry counters;
    # unique scope labels keep this test's tallies isolated
    build = lambda: object()  # the cache never introspects the plan
    cache.get_or_build(("k1",), build, scope="obs-evict-a")
    cache.get_or_build(("k2",), build, scope="obs-evict-b")
    assert cache.scope_evictions("obs-evict-a") == 0
    # k1 is LRU; inserting k3 under scope b must charge the eviction to a
    cache.get_or_build(("k3",), build, scope="obs-evict-b")
    assert len(cache) == 2
    assert cache.scope_evictions("obs-evict-a") == 1
    assert cache.scope_evictions("obs-evict-b") == 0
    assert cache.scope_evictions(None) == 0
    scopes = cache.stats()["scopes"]
    assert scopes["obs-evict-a"] == {"hits": 0, "misses": 1, "evictions": 1}
    assert scopes["obs-evict-b"]["misses"] == 2
    # a hit refreshes recency: touching k2 then inserting k4 evicts k3 (b)
    cache.get_or_build(("k2",), build, scope="obs-evict-a")
    cache.get_or_build(("k4",), build, scope="obs-evict-a")
    assert cache.scope_evictions("obs-evict-b") == 1
    assert cache.stats()["scopes"]["obs-evict-a"]["hits"] == 1


def test_serve_stats_carries_plan_evictions_field():
    from repro.serve.engine import ServeStats

    s = ServeStats()
    assert s.plan_evictions == 0
    assert "plan_evictions" in vars(s)


# ---------------------------------------------------------------------------
# Satellite 3 + tentpole: instrumented plans are bit-identical and
# cache-disjoint from the fused fast path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["probe-py", "probe-kernel"])
@pytest.mark.parametrize("store", ["fp32", "int8"])
def test_instrumented_parity_all_topologies(data, store, kernel):
    """instrument=True must change WHERE time is measured, never WHAT is
    computed: ids and dists bit-identical to the fused plan for monolithic,
    segmented, and sharded, with the CSA probe kernel both off and on."""
    from repro.shard import make_shard_mesh

    X, Q = data
    p = BASE.replace(source="lccs", use_probe_kernel=kernel)
    mono = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=1,
                           store=store)
    seg = SegmentedLCCSIndex.build(X, m=16, family="euclidean", w=4.0,
                                   seed=1, store=store)
    sharded = mono.shard(make_shard_mesh(1))
    for tag, idx in (("monolithic", mono), ("segmented", seg),
                     ("sharded", sharded)):
        ids_f, d_f = map(np.asarray, execute(idx, Q, p))
        ids_i, d_i = map(np.asarray, execute(idx, Q, p, instrument=True))
        np.testing.assert_array_equal(ids_f, ids_i,
                                      err_msg=f"{tag}/{store}")
        np.testing.assert_array_equal(d_f, d_i, err_msg=f"{tag}/{store}")


def test_instrumented_parity_disk_tail(data, tmp_path):
    X, Q = data
    p = BASE.replace(source="lccs")
    disk = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=1,
                           store="int8", tail_path=tmp_path / "tail.npy")
    ids_f, d_f = map(np.asarray, execute(disk, Q, p))
    ids_i, d_i = map(np.asarray, execute(disk, Q, p, instrument=True))
    np.testing.assert_array_equal(ids_f, ids_i)
    np.testing.assert_array_equal(d_f, d_i)


def test_instrumented_parity_sharded_multidevice(data):
    """Real shard_map staging (4 fake devices): the staged probe/verify/merge
    plan must match the fused all_gather pipeline exactly."""
    from conftest import run_multidevice

    out = run_multidevice(
        """
        import numpy as np
        from repro.core import LCCSIndex, SearchParams
        from repro.exec import execute
        from repro.shard import make_shard_mesh

        N, D, B = 160, 16, 4
        rng = np.random.default_rng(11)
        X = rng.normal(size=(N, D)).astype(np.float32)
        Q = rng.normal(size=(B, D)).astype(np.float32)
        p = SearchParams(k=6, lam=N + 12, width=N + 12, rerank_mult=64,
                         use_gather_kernel=False, source="lccs")
        idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=1,
                              store="int8").shard(make_shard_mesh(4))
        ids_f, d_f = map(np.asarray, execute(idx, Q, p))
        ids_i, d_i = map(np.asarray, execute(idx, Q, p, instrument=True))
        np.testing.assert_array_equal(ids_f, ids_i)
        np.testing.assert_array_equal(d_f, d_i)
        print("SHARDED-INSTRUMENTED-PARITY-OK")
        """,
        4,
    )
    assert "SHARDED-INSTRUMENTED-PARITY-OK" in out


def test_instrumented_plans_key_separately_no_off_path_retrace(data):
    """Flipping instrumentation is two cache entries, not an invalidation:
    the fused plan compiles exactly once per (params, shape) whether or not
    an instrumented twin exists, so turning observability on in one replica
    cannot poison another replica's no-retrace audit."""
    X, Q = data
    idx = LCCSIndex.build(X[: N - 3], m=16, family="euclidean", w=4.0, seed=2)
    p = SearchParams(k=3, lam=32, use_gather_kernel=False)
    cache = plan_cache()

    h0, m0 = cache.hits, cache.misses
    execute(idx, Q, p)                       # fused compile
    assert (cache.hits, cache.misses) == (h0, m0 + 1)
    execute(idx, Q, p, instrument=True)      # staged twin: its own compile
    assert (cache.hits, cache.misses) == (h0, m0 + 2)
    execute(idx, Q + 1.0, p)                 # fused path: pure reuse
    execute(idx, Q + 2.0, p, instrument=True)
    assert (cache.hits, cache.misses) == (h0 + 2, m0 + 2)
    plan_f = compile_plan(idx, Q, p)
    plan_i = compile_plan(idx, Q, p, instrument=True)
    assert plan_f is not plan_i
    assert not plan_f.instrumented and plan_i.instrumented
    assert cache.misses == m0 + 2  # compile_plan lookups above were hits


def test_instrumented_execute_feeds_stage_histogram(data):
    X, Q = data
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=3,
                          store="int8")
    p = BASE.replace(source="lccs")
    snap = registry().snapshot()
    execute(idx, Q, p, instrument=True)
    d = registry().since(snap)
    seen = {
        ls["stage"]
        for ls in registry().get("repro_exec_stage_seconds").labelsets()
        if ls["topology"] == "monolithic"
        and d.samples("repro_exec_stage_seconds", **ls)
    }
    assert {"hash_queries", "probe"} <= seen, seen
    # the fused path records nothing
    snap = registry().snapshot()
    execute(idx, Q, p)
    assert registry().since(snap).count("repro_exec_stage_seconds") == 0


# ---------------------------------------------------------------------------
# Tracing: span tree -> Chrome-trace JSON
# ---------------------------------------------------------------------------


def test_span_noop_when_disabled():
    clear_trace()
    assert not tracing_enabled()
    with span("invisible"):
        pass
    add_span("also-invisible", 0.0, 1.0)
    assert events() == []


def test_span_tree_exports_valid_chrome_trace(tmp_path):
    enable_tracing()
    with span("outer", layer="test"):
        with span("inner"):
            pass
    disable_tracing()
    evs = events()
    names = [e["name"] for e in evs]
    assert names == ["inner", "outer"]  # completion order; viewer nests by ts
    inner, outer = evs
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"layer": "test"}
    assert inner["tid"] == outer["tid"]  # same-thread: containment == nesting

    path = tmp_path / "trace.json"
    doc = export_chrome_trace(path)
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(doc))
    assert loaded["displayTimeUnit"] == "ms"
    for e in loaded["traceEvents"]:
        assert e["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e)


def test_stage_times_histogram_even_without_tracing():
    assert not tracing_enabled()
    before = registry().get("repro_exec_stage_seconds").count(
        topology="obs-test", stage="probe")
    with stage("obs-test", "probe"):
        pass
    hist = registry().get("repro_exec_stage_seconds")
    assert hist.count(topology="obs-test", stage="probe") == before + 1
    assert events() == []  # ...but no trace event while disabled


def test_trace_context_manager_exports_and_restores(tmp_path):
    from repro.obs.trace import trace

    path = tmp_path / "ctx_trace.json"
    assert not tracing_enabled()
    with trace(path):
        with span("inside"):
            pass
    assert not tracing_enabled()
    evs = json.loads(path.read_text())["traceEvents"]
    assert [e["name"] for e in evs] == ["inside"]


def test_device_profile_raises_when_the_profiler_cannot_start(
        tmp_path, monkeypatch):
    """A run that asked for a device trace never silently records none."""
    import jax

    from repro.obs.trace import device_profile

    def refuse(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        device_profile(tmp_path)


def test_obs_package_does_not_shadow_submodules():
    """`repro.obs.trace` the submodule vs `repro.obs.trace` the re-exported
    contextmanager: attribute access on the package must yield the callable
    (API), while `import repro.obs.trace` yields the module -- consumers
    import through the submodule path.  Pin both so a refactor cannot
    silently swap them."""
    import importlib

    import repro.obs as obs

    assert callable(obs.trace)  # the contextmanager re-export wins on attr
    mod = importlib.import_module("repro.obs.trace")
    assert hasattr(mod, "span") and hasattr(mod, "add_span")


# ---------------------------------------------------------------------------
# Satellite 5 (tier-1 half): Prometheus endpoint scrape + parse
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$'
)


def test_metrics_endpoint_scrapes_and_parses():
    from repro.obs import MetricsServer

    c = registry().counter("obs_test_scrape_total", "scrape me",
                           labelnames=("who",))
    c.inc(3.0, who='qu"oted\nname')  # exercises label escaping
    registry().histogram("obs_test_scrape_seconds", "h").observe(0.3)
    with MetricsServer(port=0) as srv:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)
    lines = [l for l in body.splitlines() if l]
    assert any(l == "# TYPE obs_test_scrape_total counter" for l in lines)
    assert any(l.startswith("# HELP obs_test_scrape_total") for l in lines)
    for l in lines:
        if not l.startswith("#"):
            assert _SAMPLE_RE.match(l), l
    sample = next(l for l in lines
                  if l.startswith("obs_test_scrape_total{"))
    assert sample.endswith(" 3.0") and r'qu\"oted\nname' in sample
    # histogram exposition: cumulative buckets capped by +Inf == count
    assert any(l.startswith('obs_test_scrape_seconds_bucket{le="+Inf"} 1')
               for l in lines)
    assert any(l.startswith("obs_test_scrape_seconds_count 1")
               for l in lines)


def test_stats_logger_line_shapes():
    from repro.obs import StatsLogger

    reg = registry()
    snap = reg.snapshot()
    line = StatsLogger().line(reg.since(snap), 2.0)
    assert line.startswith("[obs] 0 req in 2.0s")
    assert "QPS" in line and "plan compiles" in line


# ---------------------------------------------------------------------------
# Recall-drift probe
# ---------------------------------------------------------------------------


def test_recall_drift_probe_gauges_recall(data):
    from repro.obs import RecallDriftProbe

    X, Q = data
    idx = LCCSIndex.build(X, m=16, family="euclidean", w=4.0, seed=5)
    # complete coverage: the serving route provably equals brute force
    probe = RecallDriftProbe(idx, Q, BASE.replace(source="lccs"),
                             label="obs-test-drift")
    r = probe.measure()
    assert r == 1.0
    assert probe.last() == 1.0
    assert len(probe.history) == 1
    assert registry().get("repro_recall_drift").value(
        probe="obs-test-drift") == 1.0
    assert registry().get("repro_recall_drift_measurements_total").value(
        probe="obs-test-drift") == 1.0
    # a deliberately starved budget must read as sub-1.0 recall, not crash
    lean = SearchParams(k=6, lam=8, width=8, rerank_mult=1,
                        use_gather_kernel=False, source="lccs")
    starved = RecallDriftProbe(lambda: idx, Q, lean, label="obs-test-lean")
    assert 0.0 <= starved.measure() <= 1.0
