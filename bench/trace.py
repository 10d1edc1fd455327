"""Reduce a JAX profiler trace (``.xplane.pb``) to the run's device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the chips used (the ``/device:TPU:<n>``
  planes; other device planes, such as a custom trace plane, hold no
  operations);
* idle share: 1 - busy / window;
* ``breakdown``: the device operations that took most time, and the
  idle gaps of the first chip summed by what the host was doing then:
  the innermost ``bench.<name>`` span (``jax.profiler.TraceAnnotation``
  written by the harness around each call into a layer) that covers
  the middle of the gap.

The window is the host span ``bench.window``.  Device operations are
the events of a device plane's ``XLA Ops`` line (``XLA Modules`` where a
plane has no op line).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops", "XLA Modules")
WINDOW = "bench.window"
TOP = 10


def _device_planes(profile) -> list:
    """The chips' planes (``/device:TPU:<n>``), in device order."""
    planes = [p for p in profile.planes if DEVICE.match(p.name)]
    return sorted(planes, key=lambda p: int(DEVICE.match(p.name)[1]))


def _events(line) -> list[tuple[int, int, str]]:
    return sorted((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                  for e in line.events)


def _op_events(plane) -> list[tuple[int, int, str]]:
    """(start, end, name) of every operation.  An op is named
    ``<program>/<instruction>`` (``jit_run/%fusion.3``): the enclosing
    ``XLA Modules`` event without its fingerprint, and the HLO
    instruction's name without the text after ``=``."""
    lines = {line.name: line for line in plane.lines}
    modules = _events(lines["XLA Modules"]) if "XLA Modules" in lines else []
    starts = [s for s, _e, _n in modules]
    for name in OP_LINES:
        if name not in lines:
            continue
        out = []
        for s, e, op in _events(lines[name]):
            i = bisect.bisect_right(starts, s) - 1
            prog = modules[i][2].split("(")[0] if i >= 0 and \
                s < modules[i][1] else "?"
            out.append((s, e, f"{prog}/{op.split(' = ')[0]}"
                        if name != "XLA Modules" else prog))
        return out
    return []


def _host_spans(profile) -> list[tuple[int, int, str]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    out.append((int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name))
    return out


def _union(intervals: list[tuple[int, int]], lo: int, hi: int
           ) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _label(mid: int, spans: list[tuple[int, int, str]]) -> str:
    inner = None
    for s, e, name in spans:
        if name != WINDOW and s <= mid < e:
            if inner is None or e - s < inner[1] - inner[0]:
                inner = (s, e, name)
    return inner[2] if inner else "host.other"


def reduce_profile(profile, chips: int) -> dict | None:
    """Busy and window seconds and the breakdown, or None where no
    device operation was recorded."""
    planes = _device_planes(profile)[:chips]
    events = [_op_events(p) for p in planes]
    if not any(events):
        return None
    spans = _host_spans(profile)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for ev in events for s, _e, _n in ev)
        hi = max(e for ev in events for _s, e, _n in ev)
    busy = []
    for ev in events:
        merged = _union([(s, e) for s, e, _n in ev], lo, hi)
        busy.append(sum(e - s for s, e in merged))
    first = _union([(s, e) for s, e, _n in events[0]], lo, hi)

    op_time: dict[str, int] = {}
    for s, e, name in events[0]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            op_time[name] = op_time.get(name, 0) + d
    gap_time: dict[str, int] = {}
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            label = _label((s + e) // 2, spans)
            gap_time[label] = gap_time.get(label, 0) + (e - s)

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": sum(busy) / chips / 1e9,
        "window_s": (hi - lo) / 1e9,
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gap_time)},
    }


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce_file(path: str, chips: int) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), chips)


def reduce_dir(trace_dir: str, chips: int) -> dict | None:
    path = find_xplane(trace_dir)
    return reduce_file(path, chips) if path else None
