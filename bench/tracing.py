"""Reduce a profiler trace (`.xplane.pb`) to the device's busy time, its
idle gaps, and the operations that took the time.

The window is the span of the benchmark's own `bench.traced` annotation on
the host.  A device is busy while an operation runs on it: the union of the
intervals on the `XLA Ops` line of its `/device:` plane.  With several
devices, busy time is their mean.  Time between operations inside one
program (the sequencing of a `while` loop's small operations) is idle by
this count, as time between programs is.

`device_ops` sums the operation intervals by program (`XLA Modules`) and
operation.  Each of the longest idle gaps is named by the narrowest host
event that covers its middle, on any host thread, or `unattributed`.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
_SKIP_HOST = {WINDOW, "bench.window"}


def latest_xplane(log_dir: str | Path) -> str:
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of disjoint sorted `busy` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def clip(events, lo: float, hi: float):
    """(name, start, end) events cut to [lo, hi]; those outside dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def short_name(name: str) -> str:
    """`%fusion.21 = s32[...] fusion(...)` -> `fusion.21`;
    `jit_search_pipeline(1234)` -> `jit_search_pipeline`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", head)


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reduce_planes(planes) -> dict | None:
    """The reduction over `ProfileData.planes` (or any objects with `name`,
    `lines[].name` and `lines[].events[]` of `name`, `start_ns`,
    `duration_ns`).  None when the trace holds no window or no device."""
    host, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):  # thread names repeat: a list
            host.extend(_events(ln) for ln in plane.lines)
        elif plane.name.startswith("/device:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append(lines)
    spans = [(s, e) for line in host for n, s, e in line if n == WINDOW]
    if not spans or not devices:
        return None
    lo, hi = spans[0]
    busy_ns, union, ops = [], [], defaultdict(float)
    for lines in devices:
        mods = sorted(clip(lines.get(MODULES_LINE) or [], lo, hi),
                      key=lambda ev: ev[1])
        ops_ev = clip(lines[OPS_LINE], lo, hi)
        busy = merge((s, e) for _, s, e in ops_ev)
        busy_ns.append(sum(e - s for s, e in busy))
        union.extend(busy)
        starts = [s for _, s, _ in mods]
        for name, s, e in ops_ev:
            i = bisect.bisect_right(starts, s) - 1
            prog = (short_name(mods[i][0]) + "/"
                    if i >= 0 and mods[i][2] >= e else "")
            ops[prog + short_name(name)] += (e - s) / len(devices)
    idle = sorted(gaps(merge(union), lo, hi), key=lambda g: g[0] - g[1])
    host_evs = [(n, s, e) for line in host for n, s, e in line
                if n not in _SKIP_HOST and e > s]
    named = []
    for s, e in idle[:TOP]:
        mid = (s + e) / 2
        cover = [(ce - cs, n) for n, cs, ce in host_evs if cs <= mid < ce]
        named.append([min(cover)[1] if cover else "unattributed",
                      (e - s) * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "device_ops": [[n, t * 1e-9] for n, t in top],
            "idle_gaps": named}


def reduce_file(path: str | Path) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)
