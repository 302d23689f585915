"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-op device time and the host's activity in the idle gaps.

* The traced window is the host annotation ``WINDOW`` that the benchmark
  holds open from just after the profiler starts until just before it
  stops; device and host events share the trace's clock.
* Device time comes from each device plane's ``XLA Ops`` line: busy time is
  the union of those op intervals inside the window, averaged over the
  devices, and idle time is the rest of the window. Ops nest (a while
  loop holds its body's ops), so an op's time is its self time: each
  instant goes to the innermost op running then.
* An idle gap is labelled with the host annotation (the benchmark's spans
  around the engine's phases) that overlaps it most, or ``no host span``.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW = "bench_window"
NO_SPAN = "no host span"


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def merge(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


_HLO = re.compile(r"^(%?[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def op_name(name: str) -> str:
    """``%fusion.190 fusion`` from the full HLO text of a trace event."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def self_times(events, lo, hi) -> collections.Counter:
    """``{name: seconds}`` inside [lo, hi], each instant given to the
    innermost of the nested ``(name, start, end)`` events covering it."""
    out = collections.Counter()
    stack, cursor = [], lo

    def give(upto):
        nonlocal cursor
        a, b = max(cursor, lo), min(upto, hi)
        if stack and b > a:
            out[stack[-1][0]] += (b - a) * 1e-9
        cursor = max(cursor, upto)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            give(stack[-1][2])
            stack.pop()
        give(s)
        stack.append((name, s, e))
    while stack:
        give(stack[-1][2])
        stack.pop()
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes, host_labels=()) -> dict:
    """The reduction over already-read planes: each an object with ``name``
    and ``lines`` (each with ``name`` and ``events`` of ``name``,
    ``start_ns``, ``duration_ns``) — :class:`jax.profiler.ProfileData`'s
    shape, or a test's stand-in.

    Returns ``window_s``, ``busy_s`` (mean over devices), ``devices``,
    ``op_s`` (``{op: device self seconds}``, summed over devices),
    ``gaps`` (``[(start_ns, end_ns, label)]`` of the first device) and
    ``idle_by_label`` (``{label: seconds}``)."""
    window = None
    host = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:TPU"):
            ops = [line for line in plane.lines if line.name == "XLA Ops"]
            if ops:
                devices.append([(op_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in ops[0].events])
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in host_labels:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("trace holds no device plane with XLA Ops")
    lo, hi = window
    busy, op_s, gaps = [], collections.Counter(), None
    for d, events in enumerate(devices):
        spans = merge(_clip([(s, e) for _n, s, e in events], lo, hi))
        busy.append(sum(e - s for s, e in spans))
        op_s.update(self_times(events, lo, hi))
        if d == 0:
            edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    labelled = []
    idle_by_label = collections.Counter()
    host.sort(key=lambda h: h[1])
    for gs, ge in gaps:
        overlap = collections.Counter()
        for name, s, e in host:
            if s >= ge:
                break
            if e > gs:
                overlap[name] += min(e, ge) - max(s, gs)
        label = overlap.most_common(1)[0][0] if overlap else NO_SPAN
        labelled.append((gs, ge, label))
        idle_by_label[label] += (ge - gs) * 1e-9
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "devices": len(devices),
            "op_s": dict(op_s),
            "gaps": labelled,
            "idle_by_label": dict(idle_by_label)}


def reduce_file(path: str, host_labels=()) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, host_labels)


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time, and the idle time by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_label"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
