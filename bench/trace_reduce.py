"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The window is the host span ``bench.window`` that the harness opens and
closes around the measured window.  For each device plane
(``/device:TPU:<n>``) the operations are the events of its ``XLA Ops``
line, clipped to the window:

  busy_s      union of the operations' intervals;
  top_ops     total seconds per operation name;
  idle_gaps   the intervals with no operation, each named by the innermost
              host span that covers its midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def subtract(xs: list, ys: list) -> list:
    """Merged intervals ``xs`` minus merged intervals ``ys``."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append([cur, ys[k][0]])
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def _clip(a, b, lo, hi):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _device_planes(pd):
    return [p for p in pd.planes
            if re.fullmatch(r"/device:TPU:\d+", p.name)]


def reduce_profile(pd) -> dict:
    """The reduction of a loaded ``jax.profiler.ProfileData``."""
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    spans = []                               # (start, end, name) host spans
    window = None
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    devices = {}
    ops_total: collections.Counter = collections.Counter()
    gaps = []
    for plane in _device_planes(pd):
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                iv = _clip(e.start_ns, e.start_ns + e.duration_ns, lo, hi)
                if iv is None:
                    continue
                name = op_name(e.name)
                ops_total[name] += (iv[1] - iv[0]) / 1e9
                ops.append(list(iv))
        busy = union(ops)
        idle = subtract([[lo, hi]], busy)
        devices[plane.name] = {"busy_s": length(busy) / 1e9}
        gaps += [(b - a, (a + b) / 2) for a, b in idle]
    n = max(len(devices), 1)
    top = [[k, v / n] for k, v in ops_total.most_common(10)]
    named: collections.Counter = collections.Counter()
    spans = [s for s in spans if s[2] != WINDOW_SPAN
             and s[1] > lo and s[0] < hi]
    starts = np.array([s[0] for s in spans], np.float64)
    ends = np.array([s[1] for s in spans], np.float64)
    for dur, mid in gaps:
        cover = np.flatnonzero((starts <= mid) & (ends > mid))
        name = (spans[cover[np.argmin(ends[cover] - starts[cover])]][2]
                if cover.size else "none")
        named[name] += dur / 1e9 / n
    return {"window_s": (hi - lo) / 1e9, "devices": devices,
            "top_ops": top,
            "idle_gaps": [[k, v] for k, v in named.most_common(10)]}


def reduce_trace(trace_dir: str) -> dict:
    """The reduction of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return reduce_profile(ProfileData.from_file(files[0]))
