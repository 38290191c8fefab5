"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device planes are the ``/device:TPU:<n>`` planes.  On each, the
``XLA Ops`` line holds one event per operation that ran and the
``XLA Modules`` line one event per program run.  The benchmark brackets
its measured window and its own submit and wait calls with
``jax.profiler.TraceAnnotation`` events whose names start with
``bench:``; they are on a host plane, on the same clock.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench:window"
_MODULE_ID = re.compile(r"\(\d+\)$")


def program_name(module_event_name: str) -> str:
    """``jit_run(12)`` -> ``jit_run``."""
    return _MODULE_ID.sub("", module_event_name).strip()


def union_length(intervals) -> float:
    """Total length covered by (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    """(start, end) pairs merged where they overlap, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """``%fusion.3 = u32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_planes(planes) -> dict:
    """The raw material of every device metric, from ProfileData planes
    (or any objects with the same ``name``/``lines``/``events`` shape).

    Returns ``{"devices": {plane: {"ops": [(name, s, e)], "modules":
    [(name, s, e)]}}, "annotations": [(name, s, e)]}`` with times in
    seconds on the trace's clock; an op is named by its program and its
    HLO instruction, ``jit_run/pad.0``."""
    devices, notes = {}, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {"ops": [], "modules": []}
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if line in lines:
                    dev[key] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in lines[line].events
                    ]
            dev["ops"] = _with_programs(dev["ops"], dev["modules"])
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        notes.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "annotations": notes}


def _with_programs(ops, modules) -> list:
    """Each op renamed ``<program>/<instruction>`` after the program run
    that holds its start."""
    runs = sorted((s, e, program_name(n)) for n, s, e in modules)
    starts = [s for s, _e, _n in runs]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
        out.append((f"{prog}/{op_name(name)}", s, e))
    return out


def reduce_trace(raw: dict, top: int = 10) -> dict | None:
    """Device metrics over the ``bench:window`` annotation.

    Returns None when the trace holds no window or no device plane.
    ``busy_s`` is the union of operation intervals, averaged over the
    devices; ``program_s`` sums device time per program name over the
    devices; ``device_ops`` and ``idle_gaps`` are the breakdown's lists,
    each gap labelled with the ``bench:`` annotation it fell in."""
    wins = [(s, e) for n, s, e in raw["annotations"] if n == WINDOW]
    if not wins or not raw["devices"]:
        return None
    lo, hi = wins[0]
    busy, program_s, op_s, gaps = [], defaultdict(float), defaultdict(float), []
    for dev in raw["devices"].values():
        ops = clip([(s, e) for _n, s, e in dev["ops"]], lo, hi)
        if not ops:  # no op line: fall back to program intervals
            ops = clip([(s, e) for _n, s, e in dev["modules"]], lo, hi)
        busy.append(union_length(ops))
        for name, s, e in dev["modules"]:
            for cs, ce in clip([(s, e)], lo, hi):
                program_s[program_name(name)] += ce - cs
        for name, s, e in dev["ops"]:
            for cs, ce in clip([(s, e)], lo, hi):
                op_s[name] += ce - cs
        prev = lo
        for s, e in merged(ops) + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    notes = [(n, s, e) for n, s, e in raw["annotations"] if n != WINDOW]

    def label(g0, g1):
        mid = 0.5 * (g0 + g1)
        inside = [n for n, s, e in notes if s <= mid <= e]
        return inside[-1] if inside else "bench:other"

    by_label = defaultdict(float)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    for g0, g1 in gaps:
        by_label[label(g0, g1)] += g1 - g0
    n_dev = len(raw["devices"])
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / n_dev,
        "program_s": dict(program_s),
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(g0, g1), g1 - g0] for g0, g1 in longest],
        "idle_by_label": dict(by_label),
    }


def load_xplane(path) -> dict:
    """:func:`read_planes` of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return read_planes(ProfileData.from_file(str(path)).planes)
