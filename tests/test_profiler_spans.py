"""The program's spans on the JAX profiler's clock, its named programs and
stage scopes, and the router's queue-wait histogram.

  - a profiler trace of a served run holds the router's and the engine's
    spans, nested per worker thread, and `router.serve` names the requests
    `router.submit` admitted;
  - with no profile recording a span is a shared no-op: its argument
    callables are never called;
  - `repro_router_queue_wait_seconds` gets one sample per request, the time
    from submit to the dispatch of its batch;
  - the served plans compile as `jit_search_pipeline`, with `hash`, `probe`
    and `verify` scopes in their op metadata, and to the same operations as
    the pipeline without scopes.
"""
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LCCSIndex, SearchParams
from repro.exec import compile_plan, named_jit, stages
from repro.obs.registry import registry
from repro.obs.trace import device_profile, span
from repro.router import Router
from repro.serve import RetrievalEngine
from repro.serve.engine import ServeStats

pytestmark = pytest.mark.timeout(300)

N, D, M, B = 512, 16, 16, 8
PARAMS = SearchParams(k=4, lam=32, width=16)


class VectorEngine(RetrievalEngine):
    """An engine whose queries arrive as vectors (no backbone)."""

    def __init__(self, **kw):
        super().__init__(None, None, **kw)

    def embed(self, vectors):
        return jnp.asarray(vectors, jnp.float32)


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(N, D)).astype(np.float32)
    idx = LCCSIndex.build(X, m=M, family="euclidean", w=4.0, seed=0)
    return X, idx


def _host_lines(logdir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                lines.append([(ev.name, ev.start_ns,
                               ev.start_ns + ev.duration_ns, dict(ev.stats))
                              for ev in ln.events])
    return lines


def test_served_trace_holds_router_and_engine_spans(served, tmp_path):
    X, idx = served
    engines = []
    for i in range(2):
        e = VectorEngine(m=M, metric="euclidean", max_batch=B,
                         search_params=PARAMS, name=f"replica-{i}")
        e.index = idx
        engines.append(e)
    router = Router(engines, params=PARAMS, default_slo_ms=5000.0)
    try:
        router.warm(X[:B])
        with device_profile(tmp_path):
            tickets = [router.submit(X[i]) for i in range(3 * B)]
            for t in tickets:
                t.result(timeout=60)
            # a span still open when the profile stops is not recorded:
            # join the workers first, so every router.serve has closed
            router.shutdown()
    finally:
        router.shutdown()
    assert glob.glob(f"{tmp_path}/plugins/profile/*/perfetto_trace.json.gz")

    lines = _host_lines(tmp_path)
    names = {n for line in lines for n, *_ in line}
    assert {"router.submit", "router.next_batch", "router.serve",
            "engine.dispatch", "engine.wait", "engine.to_host"} <= names
    served_reqs, submitted = set(), set()
    for line in lines:
        serves = [ev for ev in line if ev[0] == "router.serve"]
        for name, s, e, stats in line:
            if name == "router.submit":
                submitted.add(int(stats["req"]))
            if name in ("engine.dispatch", "engine.wait", "engine.to_host"):
                # inside a router.serve of the same worker thread
                assert any(ss <= s and e <= se for _, ss, se, _ in serves)
            if name == "router.next_batch":
                # waiting for a batch never overlaps serving one
                assert all(e <= ss or se <= s for _, ss, se, _ in serves)
        for _, _, _, stats in serves:
            assert stats["replica"] in ("replica-0", "replica-1")
            assert 1 <= int(stats["n_live"]) <= B
            assert int(stats["batch"]) >= 0
            served_reqs |= {int(r) for r in str(stats["reqs"]).split()}
    assert submitted == set(range(3 * B))
    assert served_reqs == submitted


def test_span_is_a_shared_noop_without_a_profile():
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()

    def never():
        raise AssertionError("argument built with no profile recording")

    a = span("router.serve", replica="r", reqs=never)
    b = span("engine.wait")
    assert a is b  # one shared null context: no TraceMe, no strings
    with a:
        pass


class _StampEngine:
    """Engine-protocol stub that stamps the dispatch time of each request
    (its token row holds the request's index)."""

    def __init__(self, max_batch=4):
        self.max_batch = max_batch
        self.name = "stamp"
        self.stats = ServeStats()
        self.search_params = None
        self.index = object()
        self.dispatched: dict[int, float] = {}
        self.lock = threading.Lock()

    def serve_batch_nowait(self, tokens, params=None, *, n_live=None):
        now = time.perf_counter()
        with self.lock:
            for row in tokens[:n_live]:
                self.dispatched[int(row[0])] = now
        time.sleep(0.01)  # let the next requests queue up
        n = tokens.shape[0]

        class Done:
            def result(self):
                return (np.zeros((n, 2), np.int32),
                        np.zeros((n, 2), np.float32))

        return Done()


def test_queue_wait_histogram_is_dispatch_minus_submit():
    eng = _StampEngine()
    snap = registry().snapshot()
    with Router([eng], default_slo_ms=5000.0, names=["qw-test"]) as router:
        tickets = []
        for i in range(10):
            tickets.append(router.submit(np.array([i], np.int32)))
            time.sleep(0.003)
        for t in tickets:
            t.result(timeout=30)
    waits = registry().since(snap).samples(
        "repro_router_queue_wait_seconds", replica="qw-test")
    assert len(waits) == 10
    # one SLO: EDF serves in submission order, so the samples are in it too
    dispatch = [w + t.t_submit for w, t in zip(waits, tickets)]
    for i in range(10):
        # the requests of one batch share its dispatch time...
        batch = [j for j in range(10)
                 if eng.dispatched[j] == eng.dispatched[i]]
        assert np.ptp([dispatch[j] for j in batch]) < 1e-6
        # ...which the worker takes just before it calls the engine
        assert 0 <= eng.dispatched[i] - dispatch[i] < 0.05
    assert all(w >= 0 for w in waits)


def _plan_text(idx, Q, params):
    plan = compile_plan(idx, Q, params)
    return plan.run.lower(idx, Q).compile().as_text()


@pytest.mark.parametrize("source", ["lccs", "multiprobe-skip"])
def test_search_plan_is_named_and_scoped_by_stage(served, source):
    _, idx = served
    Q = jnp.asarray(np.random.default_rng(1).normal(size=(B, D)), jnp.float32)
    p = PARAMS.replace(source=source, probes=5 if "multi" in source else 1)
    hlo = _plan_text(idx, Q, p)
    assert re.search(r"^HloModule jit_search_pipeline\b", hlo, re.M)
    for scope in ("hash", "probe", "verify"):
        assert re.search(rf'op_name="jit\(search_pipeline\)/{scope}/', hlo)


def _ops(hlo: str) -> str:
    """Optimised HLO without metadata: the module name, op metadata and the
    stack-frame tables."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"^HloModule \S+", "HloModule", hlo, flags=re.M)
    return "\n".join(
        ln for ln in hlo.splitlines()
        if not re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations"
                        r"|StackFrames)", ln))


@pytest.mark.parametrize("source", ["lccs", "multiprobe-skip"])
def test_stage_scopes_leave_the_compiled_ops_unchanged(served, source):
    _, idx = served
    Q = jnp.asarray(np.random.default_rng(2).normal(size=(B, D)), jnp.float32)
    p = compile_plan(idx, Q, PARAMS.replace(
        source=source, probes=5 if "multi" in source else 1)).params

    def unscoped(index, queries, params):
        qh = stages.hash_queries(index.family, queries)
        cand_ids, _ = stages.probe(index, queries, qh, params)
        return stages.verify(index.store, index.tail, queries, cand_ids,
                             params, params.metric or index.metric)

    plain = jax.jit(unscoped, static_argnames="params").lower(
        idx, Q, params=p).compile().as_text()
    assert _ops(_plan_text(idx, Q, p)) == _ops(plain)


def test_named_jit_names_the_program():
    def f(x, *, k):
        return x * k

    hlo = named_jit(f, "probe", k=3).lower(jnp.ones(4)).as_text()
    assert hlo.splitlines()[0].startswith("module @jit_probe ")
