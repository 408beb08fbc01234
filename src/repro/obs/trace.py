"""End-to-end tracing: `span()` context managers through the whole query
path, exported as Chrome-trace JSON (perfetto-loadable).

The span tree for one served request covers every layer the query crosses:

    router.submit            (submitter thread)
    queue_wait               (worker thread, recorded retroactively per batch)
    serve_batch
      embed
      search
        exec.hash_queries    (instrumented plans only -- see repro.exec)
        exec.probe
        exec.gather / exec.survivors / exec.rerank
        exec.merge

Tracing is OFF by default and `span()` is a guarded no-op when disabled: one
module-global bool check, no allocation, no lock -- the serve fast path pays
nothing.  Enable with `enable_tracing()` (or the `trace()` context manager,
which also exports on exit), then load the JSON at https://ui.perfetto.dev
or chrome://tracing.

Stage *timing* is separate from tracing: instrumented exec plans always
record per-stage seconds into the registry histogram
`repro_exec_stage_seconds{topology,stage}` (that is what they are for), and
additionally emit trace events when tracing is on.  `device_profile()` wraps
`jax.profiler.trace` for real-TPU runs where host-side walls are not enough.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

from .registry import registry

_enabled = False
_lock = threading.Lock()
_events: list[dict] = []
_t0 = time.perf_counter()  # trace epoch: ts fields are µs since this

_STAGE_HIST = None  # lazily-declared registry histogram (import-order safe)


def _stage_hist():
    global _STAGE_HIST
    if _STAGE_HIST is None:
        _STAGE_HIST = registry().histogram(
            "repro_exec_stage_seconds",
            "per-stage device-inclusive wall seconds of instrumented "
            "search plans (repro.exec)",
            labelnames=("topology", "stage"),
        )
    return _STAGE_HIST


def tracing_enabled() -> bool:
    return _enabled


def enable_tracing(*, clear: bool = True) -> None:
    """Start collecting span events (process-wide, all threads)."""
    global _enabled, _t0
    with _lock:
        if clear:
            _events.clear()
            _t0 = time.perf_counter()
        _enabled = True


def disable_tracing() -> None:
    global _enabled
    with _lock:
        _enabled = False


def clear_trace() -> None:
    with _lock:
        _events.clear()


def add_span(name: str, t_start: float, t_end: float, **args) -> None:
    """Record a completed span from perf_counter timestamps -- the
    retroactive form, used where the interval is only known after the fact
    (queue wait: submit happened on another thread)."""
    if not _enabled:
        return
    ev = {
        "name": name,
        "ph": "X",
        "ts": (t_start - _t0) * 1e6,
        "dur": max(t_end - t_start, 0.0) * 1e6,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if args:
        ev["args"] = {k: v for k, v in args.items()}
    with _lock:
        _events.append(ev)


@contextmanager
def span(name: str, **args):
    """Trace one interval on the current thread.  Near-zero cost when
    tracing is off; nested spans become a tree in the Chrome trace viewer
    (same-tid containment)."""
    if not _enabled:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add_span(name, t0, time.perf_counter(), **args)


@contextmanager
def stage(topology: str, name: str):
    """One instrumented exec stage: records wall seconds into the
    `repro_exec_stage_seconds` histogram ALWAYS (instrumented plans exist to
    measure), and a `exec.<name>` trace span when tracing is on.  The caller
    must `block_until_ready` its stage output inside the `with` so the
    interval includes the device work."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        _stage_hist().observe(t1 - t0, topology=topology, stage=name)
        if _enabled:
            add_span(f"exec.{name}", t0, t1, topology=topology)


def events() -> list[dict]:
    with _lock:
        return list(_events)


def to_chrome_trace() -> dict:
    """The collected spans as a Chrome-trace ("Trace Event Format") object:
    `json.dump` it and load at ui.perfetto.dev / chrome://tracing."""
    with _lock:
        evs = list(_events)
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


def export_chrome_trace(path) -> dict:
    doc = to_chrome_trace()
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc


@contextmanager
def trace(path=None, *, clear: bool = True):
    """Collect spans for the body and (optionally) export them:

        with obs.trace("serve_trace.json"):
            router.submit(...); ...

    Leaves tracing in its previous state on exit."""
    was = _enabled
    enable_tracing(clear=clear)
    try:
        yield
    finally:
        if not was:
            disable_tracing()
        if path is not None:
            export_chrome_trace(path)


def device_profile(logdir):
    """The real-accelerator hook: a context manager wrapping
    `jax.profiler.trace(logdir)` so a TPU run captures XLA device timelines
    (TensorBoard / xprof) alongside the host-side span tree.  A profiler
    that cannot start raises: a run that asked for a device trace never
    silently records none."""
    import jax

    return jax.profiler.trace(str(logdir))
