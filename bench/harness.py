"""One run of one cell: set up, drive the window, check, report.

    set-up   the corpus and query pool from the seed, on the device; the
             index build; the router's replicas; `Router.warm` of the
             cell's one batch shape.  `setup_s` runs from process start to
             the window's start.
    window   open- or closed-loop requests of single query vectors through
             `Router.submit`, for `seconds`.  With `trace`, the middle half
             (or the traffic's shorter `trace_s`) is traced by the profiler.
    check    after the window has closed and every answer is in, the
             program's state is freed, the corpus is made again from the
             seed, and every answer is compared with the exact reference
             (`bench/reference.py`) under the cell's limits.

A configuration whose fp32 rerank rows live on disk (`tail: "disk"`,
`bench/serving.py`) has its tail written into a fresh directory in the
system's temp directory, never under the checkout.  The `Stand` owns that
directory and removes it once the answers are compared, or when a step
raises; an `atexit` hook removes it at the latest.

`bench/sweep.py` and `bench/control.py` drive the same pieces.
"""
from __future__ import annotations

import atexit
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .spec import ROOT, Cell, metric_reader

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = ROOT / ".bench_trace"
_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T0:8.2f}] {msg}", file=sys.stderr,
          flush=True)


class CompileWatch:
    """Backend compiles per phase ("setup", "window", "check")."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.count: dict[str, int] = {}
        self.seconds: dict[str, float] = {}

        def on_event(event: str, secs: float, **_):
            if event == COMPILE_EVENT:
                self.count[self.phase] = self.count.get(self.phase, 0) + 1
                self.seconds[self.phase] = (self.seconds.get(self.phase, 0.0)
                                            + secs)

        jax.monitoring.register_event_duration_secs_listener(on_event)


BURST_GAP_S = 0.005  # the answers of one served batch resolve closer


def bursts(done_s) -> tuple[np.ndarray, np.ndarray]:
    """The bursts in which answers came: the time of each burst's last
    answer, and the answers counted by then.  Answers less than
    `BURST_GAP_S` apart belong to one burst (one served batch)."""
    d = np.sort(np.asarray(done_s, np.float64))
    last = np.r_[np.diff(d) > BURST_GAP_S, True] if d.size else d > 0
    return d[last], (np.flatnonzero(last) + 1).astype(np.float64)


@dataclass
class RunRecord:
    """What the metric readers in `bench/metrics/` read.  Times are seconds
    from the window's start."""

    max_batch: int
    seconds: float
    setup_s: float
    build_s: float
    compile_setup_s: float
    latencies_s: list
    done_s: np.ndarray               # when each answer came
    reg: object                      # repro.obs registry Delta of the window
    trace: dict | None = None        # bench/tracing.py reduction

    def answered(self, t: float) -> float:
        """Answers completed by `t`, with the work between two bursts
        counted as its time elapses: linear between the ends of consecutive
        bursts, from 0 at the window's start.  Where batches are served back
        to back, that is whole batches served plus the elapsed share of the
        batch in service."""
        ends, counts = bursts(self.done_s)
        return float(np.interp(t, np.r_[0.0, ends], np.r_[0.0, counts]))


@dataclass
class Stand:
    """A cell's deployment, stood up from one seed.  As a context manager
    it removes its tail directory on leaving."""

    cell: Cell
    seed: int
    n: int
    cfg: dict
    queries: np.ndarray              # the pool, on the host
    index: object
    build_s: float
    tail_dir: str | None = None      # holds a disk tail's files

    def close(self) -> None:
        if self.tail_dir is not None:
            shutil.rmtree(self.tail_dir, ignore_errors=True)
            log(f"tail directory {self.tail_dir} removed")
            self.tail_dir = None

    def __enter__(self) -> "Stand":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclass
class Window:
    answers: list
    t0: float
    t_end: float
    reg: object
    peak_bytes: int


def family_seed(seed: int) -> int:
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def tail_directory() -> str:
    """A fresh directory for a disk tail in the system's temp directory,
    removed at exit at the latest."""
    path = tempfile.mkdtemp(prefix="bench-tail-")
    atexit.register(shutil.rmtree, path, ignore_errors=True)
    if Path(path).resolve().is_relative_to(ROOT):
        shutil.rmtree(path)
        raise SystemExit(f"[bench] error: the temp directory {path} lies "
                         f"under the checkout {ROOT}; a tail is never "
                         f"written there")
    return path


def stand_up(cell: Cell, seed: int,
             n: int | None = None) -> tuple[Stand, object]:
    """Data and index for `cell` from `seed`.  Returns the stand and the
    device corpus (the caller drops it once it has built what it needs).
    The caller closes the stand (`with stand:`) once it is done."""
    import jax

    from . import serving
    from .data import make_data

    cfg = dict(cell.config)
    n = int(n or cfg["n"])
    pool = int(cell.traffic["pool"])
    tail_dir = tail_directory() if serving.tail_of(cfg) == "disk" else None
    try:
        X, Q = make_data(cfg, n, pool, seed)
        log(f"data made: {n} x {cfg['d']} corpus, {pool} pool queries")
        t_b = time.perf_counter()
        index = serving.build_index(X, cfg, float(cfg["w"]),
                                    family_seed(seed), tail_dir)
        jax.block_until_ready(index)
        build_s = time.perf_counter() - t_b
    except BaseException:
        if tail_dir is not None:
            shutil.rmtree(tail_dir, ignore_errors=True)
        raise
    log(f"{cell.name} seed={seed} n={n} d={cfg['d']} w={cfg['w']!r}: "
        f"index built in {build_s:.3f} s"
        + (f", fp32 tail in {tail_dir}" if tail_dir else ""))
    return Stand(cell, seed, n, cfg, np.asarray(Q), index, build_s,
                 tail_dir), X


def serve(stand: Stand, X, variant: str = "sound"):
    """The router over the stand's index; `variant` puts the precision
    control or a planted fault (`bench/faults.py`) in the program's place."""
    from . import faults, serving

    index, cfg, engine_cls = stand.index, stand.cfg, serving.VectorEngine
    if variant == "control":
        index, cfg = faults.control_index(index, X, cfg)
    elif variant != "sound":
        engine_cls = faults.faulty_engine(variant)
    router = serving.make_router(index, cfg, stand.cell.traffic,
                                 engine_cls=engine_cls)
    router.warm(stand.queries[: int(stand.cell.traffic["max_batch"])])
    return router


def _tracer(log_dir: Path, start: float, length: float):
    """Trace `length` seconds from `start`, from a thread of its own."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a Python tracer slows every thread

    def run():
        time.sleep(max(start - time.perf_counter(), 0.0))
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced"):
            time.sleep(max(start + length - time.perf_counter(), 0.0))
        jax.profiler.stop_trace()

    t = threading.Thread(target=run, name="bench-trace", daemon=True)
    t.start()
    return t


def drive(router, stand: Stand, seconds: float, seed: int, *,
          trace: bool = False, offsets: np.ndarray | None = None) -> Window:
    """One window of the cell's traffic through `router`; returns once every
    answer is in (or has waited a minute past the close).  `offsets`
    overrides the open loop's due times (the knee sweep)."""
    import jax

    from repro.obs.registry import registry

    from .loadgen import Client, arrival_offsets, query_picks

    tr = stand.cell.traffic
    pool = len(stand.queries)
    if tr["loop"] == "open":
        if offsets is None:
            offsets = arrival_offsets(tr, seconds, seed)
        picks = query_picks(pool, len(offsets), seed)
    else:
        picks = query_picks(pool, 1 << 20, seed)
    client = Client(router, stand.queries, picks)
    gc.collect()
    snap = registry().snapshot()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    tracer = None
    if trace:
        # the middle of the window: its half, or the traffic's `trace_s`
        # where a shorter slice keeps the trace small
        length = min(seconds / 2, float(tr.get("trace_s", seconds)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = _tracer(TRACE_DIR, t0 + (seconds - length) / 2, length)
    with jax.profiler.TraceAnnotation("bench.window"):
        if tr["loop"] == "open":
            client.run_open(t0, offsets)
        else:
            client.run_closed(t0, t_end, int(tr["clients"]))
        time.sleep(max(t_end - time.perf_counter(), 0.0))
    client.finish()
    if tracer is not None:
        tracer.join()
    devices = jax.devices()[: stand.cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return Window(client.answers, t0, t_end, registry().since(snap),
                  int(peak))


def check(stand: Stand, answers_by_variant: dict) -> dict:
    """The numbers `correct` is decided on, per variant: the corpus is made
    again from the seed and each answer compared with the exact reference.
    Call once the program's index is freed."""
    from .data import make_data
    from .reference import compare, distances_of, exact_knn

    cfg, k = stand.cfg, int(stand.cfg["k"])
    X, _ = make_data(cfg, stand.n, len(stand.queries), stand.seed)
    truth = exact_knn(X, stand.queries, k)[0]  # the whole pool: one shape
    out = {}
    for variant, answers in answers_by_variant.items():
        ok = [a for a in answers if a.error is None]
        if ok:
            ids = np.stack([a.ids for a in ok])
            dists = np.stack([a.dists for a in ok])
            qi = np.array([a.qi for a in ok])
            nums = compare(ids, dists, truth[qi],
                           distances_of(X, stand.queries[qi], ids))
        else:
            nums = {"recall_loss": 1.0, "dist_gap": float("inf")}
        nums["failed"] = len(answers) - len(ok)
        out[variant] = nums
    del X
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, watch: CompileWatch, n: int | None = None,
             variant: str = "sound") -> dict:
    """Run `cell` once and return the result line's object.  `n` overrides
    the corpus size (CPU rehearsals and tests)."""
    import jax

    from .tracing import latest_xplane, reduce_file

    tr = cell.traffic
    stand, X = stand_up(cell, seed, n)
    with stand:  # removes a disk tail once the answers are compared
        router = serve(stand, X, variant)
        del X  # an fp32 store keeps the rows it serves
        log("router warm")
        try:
            compile_setup_s = watch.seconds.get("setup", 0.0)
            watch.phase = "window"
            win = drive(router, stand, seconds, seed, trace=trace)
            watch.phase = "check"
            log("every answer in")
        finally:
            router.shutdown(drain=False)
        stand.index = None
        del router
        gc.collect()
        nums = check(stand, {variant: win.answers})[variant]
        log("reference compared")

    answers = win.answers
    ok = [a for a in answers if a.error is None]
    in_window = sum(1 for a in ok if a.t_done <= win.t_end)
    log(f"window {seconds} s: {len(answers)} requests, "
        f"{len(answers) - len(ok)} failed, {in_window} answered in the "
        f"window; backend compiles in the window: "
        f"{watch.count.get('window', 0)}")
    done_s = np.sort([a.t_done - win.t0 for a in ok])
    ends = bursts(done_s)[0]
    pauses = np.diff(ends) if len(ends) > 1 else np.array([np.inf])
    if tr["loop"] == "closed" and ok:
        log(f"answers came in {len(ends)} bursts, the first at "
            f"{float(ends[0])!r} s, then every "
            f"{float(np.median(pauses)) * 1e3!r} ms (median)")
    if tr["loop"] == "open":
        late = [a.t_submit - a.t_due for a in answers]
        log(f"generator lateness: median {float(np.median(late)) * 1e3!r} "
            f"ms, max {float(np.max(late)) * 1e3!r} ms")
    reduced = None
    if trace:
        reduced = reduce_file(latest_xplane(TRACE_DIR))
        if reduced is not None:
            # the device cannot idle longer than answers pause, unless the
            # profiler dropped operation events
            longest = max((g[1] for g in reduced["idle_gaps"]), default=0.0)
            reduced["complete"] = longest <= float(np.max(pauses))
            log(f"trace reduced: busy {reduced['busy_s']!r} s of "
                f"{reduced['window_s']!r} s; longest idle gap {longest!r} s, "
                f"longest pause between answer bursts "
                f"{float(np.max(pauses))!r} s: "
                + ("complete" if reduced["complete"] else "events lost"))
    checks = {name: {"value": nums[name], "limit": lim}
              for name, lim in cell.limits["limits"].items()}
    correct = bool(ok) and all(c["value"] <= c["limit"]
                               for c in checks.values())

    run = RunRecord(max_batch=int(tr["max_batch"]), seconds=seconds,
                    setup_s=win.t0 - t_start, build_s=stand.build_s,
                    compile_setup_s=compile_setup_s,
                    latencies_s=[a.t_done - a.t_due for a in ok],
                    done_s=done_s, reg=win.reg, trace=reduced)
    metrics = {}
    for m in cell.metrics:
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": win.peak_bytes}
    out = {"correct": correct, "attempted": len(answers),
           "failed": nums["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = {name: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for name, c in checks.items()}
    return out


def _finite(v):
    return v if np.isfinite(v) else str(v)


def dumps(out: dict) -> str:
    return json.dumps(out)
