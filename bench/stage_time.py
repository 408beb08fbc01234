"""Device time by query stage, read from a profiler trace (`.xplane.pb`).

The program runs each stage of its search program under a
`jax.named_scope` (`hash`, `probe`, `verify`; `survivors` in the disk-tail
plan), and XLA keeps the scope path in each operation's `tf_op` metadata,
for example `jit(search_pipeline)/probe/while/body/sort`.  An operation
counts for the first stage named in its path, else for `other`.
`jax.profiler.ProfileData` does not expose event metadata, so the file is
parsed with the profiler's own schema (`bench/xplane_pb2.py`, generated
protocol-buffer code that imports only `google.protobuf`).

Some operations carry no scope: XLA expands a few operations into new
ones without metadata (on the TPU the probe's scatter-max becomes a sort, a
fusion and a `while` loop, none of which has an `op_name` in the compiled
HLO), and the trace gives no `tf_op` for `while` events.  Such an operation
counts for the first stage among the operations nested in it, else for the
next scoped operation of the same program run (an expansion runs just
before the operation that consumes it), else for the one before it; one
nested in another takes its outermost operation's stage.  `unscoped_s` is
the time so counted.

Only whole runs of the search program count: its module events
(`jit_search_pipeline` on `XLA Modules`) that lie inside the window (the
host's `bench.traced` annotation, as `bench/tracing.py` takes it) and
inside the span of the recorded operations.  The device tracer records an
operation only if it starts and ends while the tracer runs, so the run in
progress at either edge of a trace lacks operations (on the TPU, a 53 ms
sort that straddles an edge is missing whole) while its module event,
cut to the tracer's span, remains.  An operation counts by its self time:
its interval less the operations nested in it, so a `while` loop and the
operations of its body count once.  With several devices the times and
the count of runs (`executions`) are their means.
"""
from __future__ import annotations

import bisect
import functools
import os
from collections import defaultdict
from pathlib import Path

from .tracing import MODULES_LINE, OPS_LINE, WINDOW, short_name

STAGES = ("hash", "probe", "verify", "survivors")
PROGRAM = "jit_search_pipeline"


def load_space(path: str | Path):
    from .xplane_pb2 import XSpace

    space = XSpace()
    space.ParseFromString(Path(path).read_bytes())
    return space


def stage_of(tf_op: str) -> str:
    """The first stage scope in a `tf_op` path, else `other`."""
    for part in tf_op.rstrip(":").split("/"):
        if part in STAGES:
            return part
    return "other"


def self_times(events) -> dict[str, float]:
    """Seconds by stage of (stage, start_ns, end_ns) events on one line,
    each counted by its self time: an event nested in another takes its
    time out of its parent's; one that outlasts its parent is cut at the
    parent's end."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [end, stage, start, time of children]

    def close(top):
        out[top[1]] += (top[0] - top[2] - top[3]) * 1e-9

    for stage, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            e = min(e, stack[-1][0])
            stack[-1][3] += e - s
        stack.append([e, stage, s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def fill_stages(ops, runs) -> list[str]:
    """The stage of each (stage, start, end) op, an unscoped one's counted
    as the module's docstring sets out; `runs` are the (start, end)
    intervals of program runs."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    outer: dict[int, int] = {}      # op -> its outermost op
    inner: dict[int, str | None] = {}  # outermost op -> its first stage
    cur, end = -1, float("-inf")
    for i in order:
        stage, s, e = ops[i]
        if s >= end:
            cur, end = i, e
            inner[cur] = None
        outer[i] = cur
        if inner[cur] is None and stage != "other":
            inner[cur] = stage
    runs = sorted(runs)
    starts = [r[0] for r in runs]
    by_run: dict[int, list[int]] = defaultdict(list)
    for t in inner:  # outermost ops, in time order
        r = bisect.bisect_right(starts, ops[t][1]) - 1
        if r >= 0 and ops[t][1] < runs[r][1]:
            by_run[r].append(t)
    filled = dict(inner)
    for tops in by_run.values():
        for seq in (reversed(tops), tops):  # the next scoped op, else the last
            carry = None
            for t in seq:
                if filled[t] is None:
                    filled[t] = carry
                else:
                    carry = filled[t]
    return [stage if stage != "other" else filled[outer[i]] or "other"
            for i, (stage, _, _) in enumerate(ops)]


def _line_events(plane, line, stat_names):
    """(name, tf_op, start_ns, end_ns) of each event on `line`."""
    out = []
    for ev in line.events:
        md = plane.event_metadata[ev.metadata_id]
        tf_op = next((st.str_value for st in md.stats
                      if stat_names.get(st.metadata_id) == "tf_op"), "")
        s = line.timestamp_ns + ev.offset_ps * 1e-3
        out.append((md.name, tf_op, s, s + ev.duration_ps * 1e-3))
    return out


def split_space(space, program: str = PROGRAM) -> dict | None:
    """`{"stage_s": {stage: seconds}, "unscoped_s": seconds,
    "executions": whole runs, "window_s": seconds}`, or None when the trace
    holds no window or no device."""
    window, devices = None, []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/host:") and window is None:
            window = next(((s, e) for ln in plane.lines
                           for n, _, s, e in _line_events(plane, ln, {})
                           if n == WINDOW), None)
        elif plane.name.startswith("/device:") and OPS_LINE in lines:
            devices.append((plane, lines, stat_names))
    if window is None or not devices:
        return None
    lo, hi = window
    stage_s: dict[str, float] = defaultdict(float)
    n_runs, unscoped = 0.0, 0.0
    for plane, lines, stat_names in devices:
        mods = (_line_events(plane, lines[MODULES_LINE], stat_names)
                if MODULES_LINE in lines else [])
        ops = [(stage_of(tf_op), s, e) for _, tf_op, s, e
               in _line_events(plane, lines[OPS_LINE], stat_names)]
        if not ops:
            continue
        first, last = min(s for _, s, _ in ops), max(e for _, _, e in ops)
        whole = sorted((s, e) for n, _, s, e in mods
                       if short_name(n) == program
                       and max(lo, first) <= s and e <= min(hi, last))
        starts = [s for s, _ in whole]

        def in_run(s):
            r = bisect.bisect_right(starts, s) - 1
            return r >= 0 and s < whole[r][1]

        stages = fill_stages(ops, [(s, e) for _, _, s, e in mods])
        kept = [(stage, raw, s, e) for stage, (raw, s, e) in zip(stages, ops)
                if in_run(s)]
        filled = self_times([(st, s, e) for st, _, s, e in kept])
        raw = self_times([(r, s, e) for _, r, s, e in kept])
        for stage, t in filled.items():
            stage_s[stage] += t / len(devices)
        unscoped += (raw.get("other", 0.0)
                     - filled.get("other", 0.0)) / len(devices)
        n_runs += len(whole) / len(devices)
    return {"stage_s": dict(stage_s), "unscoped_s": unscoped,
            "executions": n_runs, "window_s": (hi - lo) * 1e-9}


@functools.lru_cache(maxsize=1)
def _split_file(path: str, mtime_ns: int) -> dict | None:
    from .harness import log

    split = split_space(load_space(path))
    if split is not None:
        log("stage split: " + ", ".join(
            f"{k} {v!r} s" for k, v in sorted(split["stage_s"].items()))
            + f" ({split['unscoped_s']!r} s of ops with no scope, counted "
              f"by position), over {split['executions']!r} whole runs of "
              f"{PROGRAM} in {split['window_s']!r} s")
    return split


def split_file(path: str | Path) -> dict | None:
    """`split_space` of the trace at `path`, parsed once per file."""
    return _split_file(str(path), os.stat(path).st_mtime_ns)


def stage_ms_per_execution(run, stage: str) -> float | None:
    """Device milliseconds of `stage` per whole search-program run in the
    run's traced slice; None unless the run was traced, its trace is
    complete, and the search program ran whole and named the stage."""
    from .harness import TRACE_DIR
    from .tracing import latest_xplane

    if not run.trace or not run.trace.get("complete"):
        return None
    split = split_file(latest_xplane(TRACE_DIR))
    if not split or split["executions"] <= 0:
        return None
    t = split["stage_s"].get(stage, 0.0)
    return t * 1e3 / split["executions"] if t > 0 else None
