"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

* busy: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices;
* ``device_ops``: device seconds per operation name, largest first;
* ``idle_gaps``: the device's idle intervals inside the window, each
  put to the benchmark's own host span (``bench.*``) that overlaps it
  most, summed per span name, largest first.

The window is the host span ``bench.window`` where the trace has it,
else the extent of the device operations. On a TPU the operations are
the events of each ``/device:TPU:<n>`` plane's ``XLA Ops`` line; the
test of this module passes the names a CPU trace uses instead.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

TPU_PLANE = "/device:TPU:"
TPU_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10

Interval = Tuple[int, int]


def find_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Tuple[str, int, int]]) -> Dict[str, float]:
    """Seconds of idle gap per host span name (the span that overlaps a
    gap most takes all of it; ``unattributed`` where none does)."""
    out: Dict[str, float] = {}
    for a, b in gap_list:
        best, name = 0, "unattributed"
        for span, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, span
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(path: str, *, device_plane: str = TPU_PLANE,
           op_line: str = TPU_OP_LINE, host_plane: str = HOST_PLANE,
           exclude: str = r"$^") -> Dict[str, object]:
    """``{"busy_s", "window_s", "devices", "device_ops", "idle_gaps"}``.
    ``op_line`` matches line names by prefix; ``exclude`` is a regular
    expression of event names that are not operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    skip = re.compile(exclude)
    per_device: List[List[Interval]] = []
    op_time: Dict[str, float] = {}
    spans: List[Tuple[str, int, int]] = []
    window: Optional[Interval] = None
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            ivs = []
            for line in plane.lines:
                if not line.name.startswith(op_line):
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0 or skip.match(ev.name):
                        continue
                    s = int(ev.start_ns)
                    ivs.append((s, s + int(ev.duration_ns)))
                    op_time[ev.name] = (op_time.get(ev.name, 0.0)
                                        + ev.duration_ns * 1e-9)
            if ivs:
                per_device.append(ivs)
        if plane.name == host_plane:
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
                    else:
                        spans.append((ev.name, s, e))
    if not per_device:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": []}
    extent = (min(a for ivs in per_device for a, _ in ivs),
              max(b for ivs in per_device for _, b in ivs))
    if window is None or window[1] <= extent[0] or extent[1] <= window[0]:
        # no host span, or one on a clock the device's events do not share
        window = extent
    lo, hi = window
    busy_total = 0.0
    idle: Dict[str, float] = {}
    for ivs in per_device:
        busy = clip(merge(ivs), lo, hi)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for name, s in attribute(gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + s / len(per_device)
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_total / len(per_device),
            "window_s": (hi - lo) * 1e-9, "devices": len(per_device),
            "device_ops": top(op_time), "idle_gaps": top(idle)}
