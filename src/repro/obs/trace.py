"""End-to-end tracing on the JAX profiler's clock: `span()` context managers
through the whole query path, recorded as TraceMe events in the profiler's
own trace, next to the device's `XLA Ops`.

The spans of one served request, by thread:

    router.submit      submitter thread; `req` = the request id
    router.next_batch  replica worker: waiting for and lingering over requests
    router.serve       replica worker: stack, pad, dispatch, result, fulfil;
                       `replica`, `n_live`, `batch` (per-replica sequence),
                       `reqs` (the request ids served)
      engine.dispatch  the embed cast, the plan lookup and the enqueue
      engine.wait      blocking on the device
      engine.to_host   copying the answers to the host
    exec.<stage>       instrumented plans only (see repro.exec)

The synchronous `RetrievalEngine.serve_batch` records `serve_batch` with
`embed` and `search` inside it.  The device side names its programs
(`jit_search_pipeline`, ...) and their stages (`hash`, `probe`, `verify`
scopes), so a trace attributes device time by stage and names the host
work behind every idle gap.

A span costs one TraceMe check while no profile is recording: no event, no
argument string.  Record with `device_profile(logdir)` (or any
`jax.profiler` trace); it writes the `.xplane.pb` for TensorBoard / xprof
and a `perfetto_trace.json.gz` for https://ui.perfetto.dev.

Stage *timing* is separate from tracing: instrumented exec plans always
record per-stage seconds into the registry histogram
`repro_exec_stage_seconds{topology,stage}` (that is what they are for), and
emit an `exec.<stage>` span as well.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

from jax.profiler import TraceAnnotation

from .registry import registry

_recording = TraceAnnotation.is_enabled
_OFF = nullcontext()

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


def span(name: str, **args):
    """Trace one interval on the current thread, as a profiler TraceMe
    event.  Nested spans nest in the trace viewer.  An argument may be a
    zero-argument callable: it is called, and its value recorded, only
    while a profile is recording."""
    if not _recording():
        return _OFF
    return TraceAnnotation(name, **{k: v() if callable(v) else v
                                    for k, v in args.items()})


@contextmanager
def stage(topology: str, name: str):
    """One instrumented exec stage: records wall seconds into the
    `repro_exec_stage_seconds` histogram ALWAYS (instrumented plans exist to
    measure), inside an `exec.<name>` span.  The caller must
    `block_until_ready` its stage output inside the `with` so the interval
    includes the device work."""
    with span(f"exec.{name}", topology=topology):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            _stage_hist().observe(time.perf_counter() - t0,
                                  topology=topology, stage=name)


def device_profile(logdir):
    """Record a profiler trace of the body into `logdir`:

        with device_profile("serve_trace"):
            router.submit(...); ...

    The trace holds the program's spans and the device's operations on one
    clock (`plugins/profile/<time>/*.xplane.pb`), plus a
    `perfetto_trace.json.gz` loadable at ui.perfetto.dev.  A profiler that
    cannot start raises: a run that asked for a trace never silently
    records none."""
    import jax

    return jax.profiler.trace(str(logdir), create_perfetto_trace=True)
