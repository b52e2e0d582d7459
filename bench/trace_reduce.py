"""Profiler trace -> device busy time, idle share and where the idle
time went.

``read_xplane(path)`` takes the ``.xplane.pb`` the JAX profiler writes
and returns the two things the reduction needs: every device's
operation intervals and the benchmark's own window span (the host
annotation ``WINDOW_SPAN``, on the profiler's clock). ``reduce`` then
computes, inside the window:

* ``busy_s``: the union of each device's operation intervals, averaged
  over the devices that ran anything in the trace (the chips used);
* ``idle_share``: 1 - busy / window;
* ``device_ops``: the operations that took the most device time;
* ``idle_gaps``: the device's idle time, grouped by the benchmark's
  host span (what the client was doing) over each part of each gap;
  where spans nest or overlap, the one that started last; idle time no
  span covers is ``outside_spans``.

Host spans are recorded by the harness on its own clock
(``time.perf_counter``) and moved onto the profiler's by the offset
between the window annotation's start on both clocks.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench:window"
#: Device lines to read busy time from, in order of preference: one
#: event per executed operation, else one per executed program.
OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10

Interval = Tuple[str, float, float]          # (name, start_s, end_s)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {directory}, found {len(paths)}")
    return paths[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def read_xplane(path: str) -> dict:
    """{"devices": {plane: [(op, start_s, end_s)]}, "window": (start_s,
    end_s) or None, "lines": {plane: [line names]}} from one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    lines: Dict[str, List[str]] = {}
    window = None
    for plane in data.planes:
        names = [ln.name for ln in plane.lines]
        if _is_device_plane(plane.name):
            lines[plane.name] = names
            pick = next((n for n in OP_LINES if n in names), None)
            evs = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ln in plane.lines if ln.name == pick
                   for ev in ln.events]
            if evs:               # a chip the run did not use stays out
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
    return {"devices": devices, "window": window, "lines": lines}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that ``busy`` (a union) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _attribute(gap: Tuple[float, float], spans: Sequence[Interval],
               into: Dict[str, float]) -> None:
    """Split one idle gap at the span boundaries inside it and add each
    piece to the span covering it that started last (the innermost), or
    to ``outside_spans``."""
    lo, hi = gap
    cuts = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                             if lo < t < hi)})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        over = [(s, n) for n, s, e in spans if s <= mid < e]
        into[max(over)[1] if over else "outside_spans"] += b - a


def reduce(devices: Dict[str, Sequence[Interval]],
           window: Tuple[float, float],
           spans: Sequence[Interval] = ()) -> dict:
    """Busy time, idle share, top device ops and labelled idle gaps of
    the window (see module doc). ``spans`` are on the trace's clock."""
    lo, hi = window
    length = hi - lo
    if length <= 0:
        raise ValueError("empty trace window")
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    for evs in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        for n, s, e in inside:
            op_time[n] += e - s
        busy = union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy)
        for g in gaps(busy, lo, hi):
            near = [sp for sp in spans if sp[2] > g[0] and sp[1] < g[1]]
            _attribute(g, near, idle_by)
    n = len(devices)
    busy_s = busy_total / n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": length,
            "idle_share": 1.0 - busy_s / length,
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[k, v / n] for k, v in top_gaps],
            "n_devices": n}


def reduce_file(path: str, spans: Sequence[Interval] = (),
                window_start_host: Optional[float] = None) -> dict:
    """``reduce`` over one ``.xplane.pb``. ``spans`` are on the host's
    clock; ``window_start_host`` is the window annotation's start on that
    clock, which aligns the two."""
    t = read_xplane(path)
    if t["window"] is None:
        raise ValueError(f"no {WINDOW_SPAN!r} annotation in {path}")
    shift = 0.0 if window_start_host is None else \
        t["window"][0] - window_start_host
    moved = [(n, s + shift, e + shift) for n, s, e in spans]
    out = reduce(t["devices"], t["window"], moved)
    out["lines"] = t["lines"]
    return out
