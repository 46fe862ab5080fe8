"""Reduce a profiler trace to the device metrics of one traced window.

The JAX profiler writes an ``.xplane.pb``; :func:`load_events` flattens it
to :class:`Event` rows, and :func:`reduce_window` computes, over the
window spanned by the host spans named ``window_span`` (the benchmark's
jobs):

* ``busy_s`` — the union of the intervals in which an operation ran on a
  device, averaged over the cell's chips (given by device id; a chip that
  ran no operation counts as idle), or over the devices traced where no
  ids are given;
* ``window_s`` — from the first job span's start to the last one's end;
* ``device_ops`` — the device operations that took most time, by name,
  each counted by its self time (a loop op without its body's ops);
* ``idle_gaps`` — the longest gaps in the busy union, each named by the
  innermost host span on the jobs' own thread that covers the gap's
  midpoint: what the host was doing while the device waited.

Everything below ``load_events`` works on plain rows, so the tests can
check it on a small recorded trace without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

#: the device planes' line that holds one event per executed operation
DEVICE_OPS_LINE = "XLA Ops"
#: an operation's name in the breakdown is cut to this many characters
#: (XLA names an op by its whole HLO instruction)
NAME_CHARS = 160


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [Event(plane.name, line.name, ev.name, ev.start_ns,
                  ev.start_ns + ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def is_device_op(ev: Event) -> bool:
    return ev.plane.startswith("/device:") and ev.line == DEVICE_OPS_LINE


def device_id(plane: str) -> int | None:
    """The device id in a device plane's name (``/device:TPU:3`` -> 3)."""
    m = re.fullmatch(r"/device:[^:]+:(\d+)", plane)
    return int(m.group(1)) if m else None


def window_ops(events: list[Event], window_span: str, devices=None):
    """The window the ``window_span`` host spans cover and the device ops
    inside it -> (job spans, lo, hi, {device: [op]}), or None where the
    trace holds no such span or no op inside it.  With ``devices`` (ids)
    the keys are those ids, each chip present even where it ran no op, and
    a plane of any other device is left out; without, the keys are the
    planes that ran an op."""
    jobs = [ev for ev in events if ev.name == window_span
            and not ev.plane.startswith("/device:")]
    if not jobs:
        return None
    lo = min(ev.start_ns for ev in jobs)
    hi = max(ev.end_ns for ev in jobs)
    ops: dict = defaultdict(list)
    for ev in events:
        if is_device_op(ev) and ev.end_ns > lo and ev.start_ns < hi:
            key = ev.plane if devices is None else device_id(ev.plane)
            if devices is None or key in devices:
                ops[key].append(ev)
    if not ops:
        return None
    if devices is not None:
        ops = {d: ops.get(d, []) for d in devices}
    return jobs, lo, hi, ops


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals, clipped to ``[lo, hi]``."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(evs: list[Event], lo: float, hi: float):
    """(name, self ns) of each event of one line, clipped to ``[lo, hi]``:
    its time less that of the events nested in it (a loop's body ops
    inside the loop op), so that the times add up to the busy union."""
    out = []
    stack: list[list] = []          # [event, clipped start, end, child ns]
    for ev in sorted(evs, key=lambda e: (e.start_ns, -e.end_ns)):
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0].name, max(0.0, top[2] - top[1] - top[3])))
        if stack:
            stack[-1][3] += max(0.0, min(e, stack[-1][2]) - s)
        stack.append([ev, s, e, 0.0])
    out.extend((top[0].name, max(0.0, top[2] - top[1] - top[3]))
               for top in stack)
    return out


def _host_name(spans: list[Event], t: float) -> str:
    """Name of the shortest span in ``spans`` that covers ``t``."""
    inside = [ev for ev in spans if ev.start_ns <= t <= ev.end_ns]
    if not inside:
        return "no host span"
    return min(inside, key=lambda ev: ev.end_ns - ev.start_ns).name


def reduce_window(events: list[Event], window_span: str, devices=None,
                  top: int = 10) -> dict | None:
    """Device busy time, top operations and idle gaps over the window the
    ``window_span`` host spans cover, on the chips ``devices`` (ids; every
    device that ran an op where None); None when the trace holds no such
    span or no device operation of those chips inside it."""
    found = window_ops(events, window_span, devices)
    if found is None:
        return None
    jobs, lo, hi, ops_by_device = found
    op_ns: dict[str, float] = defaultdict(float)
    for evs in ops_by_device.values():
        for name, ns in self_times(evs, lo, hi):
            op_ns[name] += ns
    busy = {dev: union([(ev.start_ns, ev.end_ns) for ev in evs], lo, hi)
            for dev, evs in ops_by_device.items()}
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values())
    busy_ns /= len(busy)

    # gaps on the first device, attributed on the jobs' own thread
    job_line = (jobs[0].plane, jobs[0].line)
    spans = [ev for ev in events if (ev.plane, ev.line) == job_line
             and ev.end_ns > lo and ev.start_ns < hi]
    merged = busy[min(busy)]
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    idle = [[_host_name(spans, (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps[:top]]
    ops = sorted(op_ns.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {"busy_s": busy_ns / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": len(busy), "jobs": len(jobs),
            "device_ops": [[name[:NAME_CHARS], ns / 1e9] for name, ns in ops],
            "idle_gaps": idle}
