"""Device time per jitted program and the host-device clock skew, from a
profiler trace (``.xplane.pb``).

  modules        per XLA module (the ``(...)`` fingerprint stripped from
                 the name: ``jit_decode_step``), the device seconds of its
                 runs on the device planes' ``XLA Modules`` line clipped to
                 the window, and the runs that overlap it, averaged over
                 the device planes as ``trace_reduce``'s ``top_ops`` are;
  clock_skew_us  the median, over the runs of every module, of the run's
                 start on the device minus the start of the host event
                 that launched it (``DoEnqueueProgram``), matched by the
                 ``run_id`` stat both carry where the trace has it, else in
                 order.  The device's clock is not corrected by it.  A run
                 enqueued behind others starts late by their time, so
                 where the host launches ahead of the device this median
                 reads the queue; the clocks' offset is at or below the
                 smallest difference.

``trace_reduce.reduce_profile`` returns both, the skew as its median and
its least difference.
"""
from __future__ import annotations

import collections
import re

import numpy as np

MODULES_LINE = "XLA Modules"
LAUNCH_EVENT = "DoEnqueueProgram"
WINDOW_SPAN = "bench.window"


def module_name(text: str) -> str:
    """``jit_decode_step(7211001471201339969)`` -> ``jit_decode_step``."""
    return text.split("(", 1)[0]


def _device_planes(pd):
    return [p for p in pd.planes
            if re.fullmatch(r"/device:TPU:\d+", p.name)]


def _host_events(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def window(pd) -> tuple:
    """(start, end) in ns of the ``bench.window`` host span."""
    for e in _host_events(pd):
        if e.name == WINDOW_SPAN and e.duration_ns > 0:
            return e.start_ns, e.start_ns + e.duration_ns
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def _runs(plane):
    """(start_ns, end_ns, module name, run_id or None) of every run."""
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for e in line.events:
                yield (e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name), dict(e.stats).get("run_id"))


def modules(pd, win: tuple) -> dict:
    """``{module: {"seconds": s, "runs": n}}`` inside ``win`` (ns)."""
    lo, hi = win
    secs: collections.Counter = collections.Counter()
    runs: collections.Counter = collections.Counter()
    planes = _device_planes(pd)
    for plane in planes:
        for a, b, name, _ in _runs(plane):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                secs[name] += (b - a) / 1e9
                runs[name] += 1
    n = max(len(planes), 1)
    return {k: {"seconds": v / n, "runs": runs[k] / n}
            for k, v in secs.most_common()}


def skew_us(device_runs: list, launches: list,
            stat=np.median) -> float | None:
    """``stat`` (the median) of device start minus launch start, in us.
    Both lists hold (start_ns, run_id or None); runs are matched by run_id
    where every entry has one, else in order of their starts."""
    if all(r is not None for _, r in device_runs + launches):
        first: dict = {}
        for t, r in launches:
            first[r] = min(t, first.get(r, t))
        diffs = [t - first[r] for t, r in device_runs if r in first]
    else:
        diffs = [d - h for (d, _), (h, _) in zip(sorted(device_runs),
                                                  sorted(launches))]
    return float(stat(diffs)) / 1e3 if diffs else None


def device_runs(pd) -> list:
    """(start_ns, run_id or None) of every run on the device planes."""
    return [(a, r) for plane in _device_planes(pd)
            for a, _, _, r in _runs(plane)]


def clock_skew_us(pd, stat=np.median) -> float | None:
    """The trace's host-device clock skew (module docstring), by ``stat``
    over the runs; None where it has no device plane or no launch
    event."""
    launches = [(e.start_ns, dict(e.stats).get("run_id"))
                for e in _host_events(pd) if e.name == LAUNCH_EVENT]
    return skew_us(device_runs(pd), launches, stat)
