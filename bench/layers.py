"""What the program names itself in a profiler trace, per layer.

    python3 bench/layers.py --workload <name> --seed <n> --seconds <s>

runs one traced window of a cell, as ``bench/run.py --trace 1`` does, and
prints one JSON line: ``window``, :func:`bench.trace.reduce_window`'s
reduction, and ``layers``, :func:`reduce_layers`'s, over the same window:

* ``spans`` — {name: [count, seconds]} of the program's host spans
  named ``engine.*`` (``repro.obs.span``) that start inside the window;
* ``scopes`` — device self seconds of the ops of each phase scope
  (``jax.named_scope``), an op counting in the innermost one it carries
  and in ``UNSCOPED`` where it carries none, so that they add up to
  ``busy_s``;
* ``kernels`` — device self seconds of each named Pallas kernel.

A TPU op event carries no scope stat.  :func:`load_events` reads each op's
scope from its program's HLO, which the trace keeps in its
``/host:metadata`` plane, by the op's instruction name within the
program's ``XLA Modules`` event.  ``bench/run.py --trace 1`` reports the
scopes of the hybrid engine's phases through :func:`scope_ms` (the
readers ``bench/metrics/*_ms.py``); the spans and kernels only this
prints.  Like ``bench/run.py``, this exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import shutil
import sys
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import trace  # noqa: E402

#: the device planes' line that holds one event per executed program
DEVICE_MODULES_LINE = "XLA Modules"
#: the plane that holds each traced program's HLO, as the stat
#: ``HLO_STAT`` of an event metadata named like the program's events
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: the program's phase scopes; an op counts in the innermost one its
#: scope names, else in ``UNSCOPED``
SCOPES = ("exchange", "remote_delivery", "global_phase", "local_phase",
          "bsp_delivery", "bsp_compute", "message_accounting")
UNSCOPED = "unscoped"
#: the program's Pallas kernels, by the name each ``pallas_call`` gives
KERNELS = ("min_step", "pr_step", "ell_spmv")
#: the program's own host spans start with this
ENGINE_SPANS = "engine."


@dataclasses.dataclass(frozen=True)
class Event(trace.Event):
    #: a device op's name scope: the ``op_name`` of its HLO instruction
    #: (``jit(loop)/while/body/local_phase/...``), "" where unknown
    scope: str = ""


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``, as
    :func:`bench.trace.load_events` reads them.  A device op takes its
    scope from the HLO of the program whose ``DEVICE_MODULES_LINE`` event
    holds it."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    with open(path, "rb") as f:
        op_names = hlo_op_names(f.read())
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: [(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events] for line in plane.lines}
        modules = sorted(lines.get(DEVICE_MODULES_LINE, []),
                         key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for line, evs in lines.items():
            for name, start, end in evs:
                scope = ""
                if line == trace.DEVICE_OPS_LINE and modules:
                    k = bisect.bisect_right(starts, start) - 1
                    if k >= 0 and start <= modules[k][2]:
                        scope = op_names.get(modules[k][0], {}).get(
                            instruction(name), "")
                out.append(Event(plane.name, line, name, start, end, scope))
    return out


def instruction(name: str) -> str:
    """The HLO instruction's own name in a device op's event name
    (``%fusion.263 = pred[...] fusion(...)`` -> ``fusion.263``)."""
    return name.split(" = ", 1)[0].lstrip("%")


# -- the .xplane.pb's program HLO, read off the protobuf wire format -------

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(number, value) of each varint and length-delimited field of one
    protobuf message; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _field(buf, number: int, default=b""):
    return next((v for k, v in _fields(buf) if k == number), default)


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """{program: {instruction: op_name}} for each program whose HLO the
    trace's ``METADATA_PLANE`` holds.  Field numbers are those of
    ``XSpace`` (planes 1), ``XPlane`` (name 2, event_metadata 4,
    stat_metadata 5), ``XEventMetadata`` (name 2, stats 5), ``XStat``
    (metadata_id 1, bytes_value 6) and ``XStatMetadata`` (id 1, name 2)
    in ``tsl/profiler/protobuf/xplane.proto``."""
    out = {}
    for number, plane in _fields(memoryview(xspace)):
        if number != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        entries = [(k, _field(e, 2)) for k, e in _fields(plane)
                   if k in (4, 5)]
        hlo = {_field(md, 1, 0) for k, md in entries
               if k == 5 and _text(_field(md, 2)) == HLO_STAT}
        for k, md in entries:
            if k != 4:
                continue
            for number_, stat in _fields(md):
                if number_ == 5 and _field(stat, 1, 0) in hlo:
                    out[_text(_field(md, 2))] = _hlo_op_names(
                        _field(stat, 6))
    return out


def _hlo_op_names(hlo) -> dict[str, str]:
    """{instruction: op_name} of one ``HloProto``: hlo_module 1 ->
    computations 3 -> instructions 2 -> (name 1, metadata 7 -> op_name
    2), as in ``xla/service/hlo.proto`` and ``xla/xla_data.proto``."""
    names = {}
    for k, comp in _fields(_field(hlo, 1)):
        if k != 3:
            continue
        for k2, inst in _fields(comp):
            if k2 != 2:
                continue
            name = op_name = ""
            for k3, value in _fields(inst):
                if k3 == 1:
                    name = _text(value)
                elif k3 == 7:
                    op_name = _text(_field(value, 2))
            names[name] = op_name
    return names


def phase_of(ev: Event) -> str:
    """The innermost of ``SCOPES`` that the op's scope names."""
    for part in reversed(ev.scope.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def kernel_of(ev: trace.Event) -> str | None:
    """The named kernel an op runs, if any: a custom call whose HLO
    instruction is named after the kernel (``%min_step.1 = ...
    custom-call(...)``)."""
    name = instruction(ev.name).split(".")[0]
    if name in KERNELS and "custom-call(" in ev.name:
        return name
    return None


def reduce_layers(events: list[Event], window_span: str, devices=None
                  ) -> dict | None:
    """``spans``, ``scopes`` and ``kernels`` (module docstring) over the
    window that the ``window_span`` host spans cover, the device times
    per chip of ``devices`` like ``busy_s``; None where ``reduce_window``
    reads nothing."""
    found = trace.window_ops(events, window_span, devices)
    if found is None:
        return None
    jobs, lo, hi, ops_by_device = found
    spans: dict[str, list] = {}
    for ev in events:
        if (ev.name.startswith(ENGINE_SPANS) and lo <= ev.start_ns < hi
                and not ev.plane.startswith("/device:")):
            count_s = spans.setdefault(ev.name, [0, 0.0])
            count_s[0] += 1
            count_s[1] += (ev.end_ns - ev.start_ns) / 1e9
    scopes: dict[str, float] = defaultdict(float)
    kernels: dict[str, float] = defaultdict(float)
    per_device = 1e9 * len(ops_by_device)
    for evs in ops_by_device.values():
        # self_times names each op by its event's name: name them by index
        named = [trace.Event(ev.plane, ev.line, i, ev.start_ns, ev.end_ns)
                 for i, ev in enumerate(evs)]
        for i, ns in trace.self_times(named, lo, hi):
            scopes[phase_of(evs[i])] += ns / per_device
            kernel = kernel_of(evs[i])
            if kernel is not None:
                kernels[kernel] += ns / per_device
    return {"jobs": len(jobs), "spans": spans, "scopes": dict(scopes),
            "kernels": dict(kernels)}


def scope_ms(run: dict, scope: str):
    """Device self milliseconds per job and chip in phase scope ``scope``
    from a run's ``layers`` record; None where the trace has no such
    scope."""
    layers = run.get("layers")
    if not layers or not layers["jobs"] or scope not in layers["scopes"]:
        return None
    return 1e3 * layers["scopes"][scope] / layers["jobs"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness

    wl = harness.load_workload(args.workload)
    try:
        devices = harness.chips(wl.chips)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    prep = harness.prepare(wl, args.seed, devices)
    trace_dir = harness.CACHE / "layers_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        with jax.default_device(devices[0]):
            wall, done = harness.window(prep.graph, prep.kind, prep.prog,
                                        args.seconds, traced=True,
                                        place=prep.place)
    events = load_events(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    ids = [d.id for d in devices]
    print(json.dumps({
        "jobs": len(done), "wall_s": wall,
        "window": trace.reduce_window(events, harness.JOB_SPAN, ids),
        "layers": reduce_layers(events, harness.JOB_SPAN, ids)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
